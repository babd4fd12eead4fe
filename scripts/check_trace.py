#!/usr/bin/env python
"""Chrome-trace JSON validator for the serving stack's trace exports.

``python scripts/check_trace.py trace.json [--require NAME ...]``

Validates the file a ``--trace-out`` run writes (``examples/serve_ann.py``,
``benchmarks/serve_load.py``, or any ``TraceRecorder.write``):

* **Schema** — top level is ``{"traceEvents": [...]}``; every event has
  ``name``/``ph``/``pid``/``tid`` and a numeric ``ts`` (except pure
  metadata), with ``ph`` one of the phases the recorder emits
  (``X i b n e M``); ``X`` events carry a non-negative numeric ``dur``;
  async events (``b``/``n``/``e``) carry an ``id``.
* **Nesting** — per ``tid``, ``X`` (complete) spans form a proper stack:
  any two either nest by containment or are disjoint.  Partial overlap is
  exactly the malformed-trace shape Perfetto renders as garbage, and would
  mean the recorder's span context managers interleaved incorrectly.
* **Async pairing** — every ``(cat, id)`` lifeline opened with ``b`` is
  closed by an ``e`` (and vice versa), with begin <= end timestamps.
* **--require NAME** (repeatable) — at least one event with that name
  exists; the CI smoke requires the span names the serving stack promises
  (``coalescer.form``, ``engine.search``, ``engine.dispatch``...).

Exit code 0 when the trace is well-formed (a per-check summary is
printed); 1 with a report otherwise.  Stdlib only, so CI can run it
without installing anything.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
from typing import Dict, List, Tuple

_PHASES = {"X", "i", "b", "n", "e", "M"}
# a float tolerance for containment checks: perf_counter microsecond
# arithmetic can put a child's end a hair past its parent's
_EPS_US = 0.5


def _check_event_schema(i: int, ev: object, errors: List[str]) -> bool:
    if not isinstance(ev, dict):
        errors.append(f"event[{i}]: not an object: {ev!r}")
        return False
    ok = True
    for key in ("name", "ph", "pid", "tid"):
        if key not in ev:
            errors.append(f"event[{i}] ({ev.get('name', '?')}): "
                          f"missing {key!r}")
            ok = False
    ph = ev.get("ph")
    if ph not in _PHASES:
        errors.append(f"event[{i}] ({ev.get('name', '?')}): "
                      f"unknown phase {ph!r}")
        return False
    if ph != "M":
        if not isinstance(ev.get("ts"), (int, float)):
            errors.append(f"event[{i}] ({ev.get('name', '?')}): "
                          f"non-numeric ts {ev.get('ts')!r}")
            ok = False
    if ph == "X":
        dur = ev.get("dur")
        if not isinstance(dur, (int, float)) or dur < 0:
            errors.append(f"event[{i}] ({ev.get('name', '?')}): X event "
                          f"needs numeric dur >= 0, got {dur!r}")
            ok = False
    if ph in ("b", "n", "e") and "id" not in ev:
        errors.append(f"event[{i}] ({ev.get('name', '?')}): async {ph!r} "
                      f"event missing id")
        ok = False
    return ok


def _check_nesting(events: List[dict], errors: List[str]) -> int:
    """Per-(pid, tid) stack check over X spans; returns spans checked."""
    by_tid: Dict[Tuple, List[dict]] = {}
    for ev in events:
        if ev.get("ph") == "X" and isinstance(ev.get("ts"), (int, float)):
            by_tid.setdefault((ev.get("pid"), ev.get("tid")), []).append(ev)
    n = 0
    for tid, spans in sorted(by_tid.items(), key=lambda kv: str(kv[0])):
        # sort by start asc, then duration desc so a parent precedes the
        # children that start at the same timestamp
        spans.sort(key=lambda e: (e["ts"], -e["dur"]))
        stack: List[dict] = []
        for ev in spans:
            n += 1
            start, end = ev["ts"], ev["ts"] + ev["dur"]
            while stack and start >= stack[-1]["ts"] + stack[-1]["dur"] - _EPS_US:
                stack.pop()
            if stack:
                p_end = stack[-1]["ts"] + stack[-1]["dur"]
                if end > p_end + _EPS_US:
                    errors.append(
                        f"tid {tid}: span {ev['name']!r} "
                        f"[{start:.1f}, {end:.1f}] partially overlaps "
                        f"enclosing {stack[-1]['name']!r} "
                        f"[{stack[-1]['ts']:.1f}, {p_end:.1f}]")
            stack.append(ev)
    return n


def _check_async_pairing(events: List[dict], errors: List[str]) -> int:
    """Every (cat, id) lifeline: b ... e, begin before end."""
    begins: Dict[Tuple, dict] = {}
    ends: Dict[Tuple, dict] = {}
    n = 0
    for ev in events:
        ph = ev.get("ph")
        if ph not in ("b", "e") or "id" not in ev:
            continue
        n += 1
        key = (ev.get("cat"), ev["id"])
        side = begins if ph == "b" else ends
        if key in side:
            errors.append(f"async {('begin' if ph == 'b' else 'end')} "
                          f"duplicated for (cat, id)={key}")
        side[key] = ev
    for key, ev in sorted(begins.items(), key=str):
        if key not in ends:
            errors.append(f"async begin without end: (cat, id)={key} "
                          f"({ev.get('name', '?')!r})")
        elif ends[key]["ts"] < ev["ts"] - _EPS_US:
            errors.append(f"async end before begin: (cat, id)={key}")
    for key in sorted(ends, key=str):
        if key not in begins:
            errors.append(f"async end without begin: (cat, id)={key}")
    return n


def validate(trace: object, require: List[str] = ()) -> List[str]:
    """All findings for one parsed trace object (empty list = valid)."""
    errors: List[str] = []
    if not isinstance(trace, dict) or "traceEvents" not in trace:
        return ["top level must be an object with a 'traceEvents' array "
                "(the Chrome-trace JSON object format)"]
    events = trace["traceEvents"]
    if not isinstance(events, list):
        return ["'traceEvents' must be an array"]
    well_formed = [ev for i, ev in enumerate(events)
                   if _check_event_schema(i, ev, errors)]
    _check_nesting(well_formed, errors)
    _check_async_pairing(well_formed, errors)
    names = {ev.get("name") for ev in well_formed}
    for name in require:
        if name not in names:
            errors.append(f"required event name {name!r} not present "
                          f"(have: {', '.join(sorted(filter(None, names)))})")
    return errors


def main(argv: List[str]) -> int:
    ap = argparse.ArgumentParser(
        description="validate a Chrome-trace JSON file (see docstring)")
    ap.add_argument("trace", help="path to the trace JSON")
    ap.add_argument("--require", action="append", default=[],
                    metavar="NAME",
                    help="require at least one event with this name "
                         "(repeatable)")
    args = ap.parse_args(argv)

    path = pathlib.Path(args.trace)
    try:
        trace = json.loads(path.read_text())
    except (OSError, json.JSONDecodeError) as e:
        print(f"check_trace: cannot read {path}: {e}")
        return 1

    errors = validate(trace, args.require)
    if errors:
        for e in errors:
            print(f"check_trace: {e}")
        print(f"check_trace: FAIL ({len(errors)} finding(s) in {path})")
        return 1
    events = trace["traceEvents"]
    n_spans = sum(1 for e in events if e.get("ph") == "X")
    n_async = sum(1 for e in events if e.get("ph") in ("b", "n", "e"))
    print(f"check_trace: OK — {len(events)} events "
          f"({n_spans} spans, {n_async} async) in {path}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
