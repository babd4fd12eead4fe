"""Program spans and named scopes in a JAX profiler trace.

The serving path opens its spans through ``repro.obs.Observability.span``;
under a profiler session each is a host event named ``coalescer.*`` or
``engine.*`` whose args (``batch``, ``bucket``, ``arrays``, ...) are the
event's stats.  The compiled search tags each HLO instruction's ``op_name``
metadata with the ``ann.*`` scope of the phase it belongs to; a TPU op event
carries no such stat, so an op is mapped to its scope through the compiled
executable's HLO text (:func:`scope_map`).

Reductions, each over the window ``[lo, hi)`` of a trace:

* :func:`host_spans`: per span name, its total seconds, count and self
  seconds (its time less that of the spans nested in it on its thread);
* :func:`op_self_s`: per device op, its self time (nested op events, such
  as a ``while``'s body, subtracted);
* :func:`scope_s`: the self time of the search executables' ops per
  ``ann.*`` scope;
* :func:`clock_offsets`: how far each search run on the device starts after
  its ``engine.dispatch`` span starts, and ends before its ``engine.sync``
  span ends;
* :func:`idle_in_spans`: the share of device idle time that lies inside a
  program span.
"""
from __future__ import annotations

import re
from collections import Counter
from typing import NamedTuple

from bench import devtrace

PROGRAM = ("coalescer.", "engine.")
SEARCH_MODULES = ("jit_jitted",)
UNSCOPED = "unscoped"
# containment slack: the trace rounds event times to the nanosecond
SLACK_NS = 2.0


class Span(NamedTuple):
    name: str
    start_ns: float
    end_ns: float
    thread: str
    args: dict


def load_spans(path: str, prefixes=PROGRAM) -> list[Span]:
    """Every host event whose name starts with one of ``prefixes``."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            # line names repeat (every Python thread may be "python"), so
            # a thread is its line's position in the plane
            thread = f"{plane.name}#{i} {line.name}"
            for e in line.events:
                if e.name.startswith(prefixes):
                    out.append(Span(e.name, e.start_ns, e.end_ns, thread,
                                    dict(e.stats)))
    return sorted(out, key=lambda s: (s.start_ns, -s.end_ns))


def _clip(s: float, e: float, lo: float, hi: float) -> float:
    return max(0.0, min(e, hi) - max(s, lo))


def host_spans(spans: list[Span], lo: float, hi: float) -> dict:
    """{name: {"total_s", "count", "self_s"}} of the spans that overlap
    the window, their times clipped to it; ``count`` counts those that
    start inside it."""
    out: dict[str, dict] = {}
    for thread in {s.thread for s in spans}:
        evs = sorted((s for s in spans if s.thread == thread),
                     key=lambda s: (s.start_ns, -s.end_ns))
        own = [_clip(s.start_ns, s.end_ns, lo, hi) for s in evs]
        stack: list[int] = []
        for i, s in enumerate(evs):
            while stack and evs[stack[-1]].end_ns <= s.start_ns:
                stack.pop()
            if stack and s.end_ns <= evs[stack[-1]].end_ns + SLACK_NS:
                own[stack[-1]] -= _clip(s.start_ns, s.end_ns, lo, hi)
            stack.append(i)
        for s, self_ns in zip(evs, own):
            total = _clip(s.start_ns, s.end_ns, lo, hi)
            if total <= 0:
                continue
            row = out.setdefault(s.name, {"total_s": 0.0, "count": 0,
                                          "self_s": 0.0})
            row["total_s"] += total / 1e9
            row["self_s"] += max(self_ns, 0.0) / 1e9
            row["count"] += int(lo <= s.start_ns < hi)
    return out


def self_times(ops: list) -> list[tuple]:
    """(event, self ns) of one device's op events: each op's duration less
    those of the op events nested in it."""
    evs = sorted(ops, key=lambda e: (e.start_ns, -e.end_ns))
    own = [e.end_ns - e.start_ns for e in evs]
    stack: list[int] = []
    for i, e in enumerate(evs):
        while stack and evs[stack[-1]].end_ns <= e.start_ns:
            stack.pop()
        if stack and e.end_ns <= evs[stack[-1]].end_ns + SLACK_NS:
            own[stack[-1]] -= e.end_ns - e.start_ns
        stack.append(i)
    return [(e, max(t, 0.0)) for e, t in zip(evs, own)]


def op_self_s(trace: devtrace.Trace, lo: float, hi: float) -> dict:
    """{op: self seconds} of the first device's ops that start in the
    window, ops keyed as ``devtrace.op_key`` keys them."""
    out: dict[str, float] = {}
    for e, own in self_times(trace.ops[0]):
        if lo <= e.start_ns < hi:
            k = devtrace.op_key(e.name)
            out[k] = out.get(k, 0.0) + own / 1e9
    return out


_COMPUTATION = re.compile(r"^(?:ENTRY )?(%[\w.\-]+) .*\{$")
_INSTRUCTION = re.compile(r"^\s*(ROOT )?(%[\w.\-]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"calls=(%[\w.\-]+)")
_SCOPE = re.compile(r"(?:^|/)(ann\.[a-z]+)(?=/|$)")
# ops XLA adds or passes through without metadata of their own; each takes
# the scope of what it moves (its first operand)
_MOVES = re.compile(r" (copy|copy-start|copy-done|bitcast|get-tuple-element)"
                    r"\((.*)")
_NAME = re.compile(r"%[\w.\-]+")
_INDEX = re.compile(r"index=(\d+)")


def _scope_of(op_name: str):
    found = _SCOPE.findall(op_name)
    return found[-1] if found else None


def scope_map(hlo_text: str) -> dict:
    """{instruction name: innermost ``ann.*`` scope} of a compiled HLO
    module's text, read from each instruction's ``op_name`` metadata.  An
    instruction without a scope of its own takes one from the HLO: a
    fusion, the most common scope of the computation it calls; a copy,
    bitcast or tuple element XLA inserted, the scope of what it moves (an
    element of a loop body's parameter, the scope of the value the body
    returns in that place)."""
    own: dict[str, str] = {}
    calls: dict[str, str] = {}
    members: dict[str, Counter] = {}
    moves: dict[str, tuple] = {}       # name -> (operand, index, computation)
    params: set = set()
    roots: dict[str, list] = {}        # computation -> ROOT tuple operands
    computation = None
    for line in hlo_text.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            computation = m.group(1)
            members[computation] = Counter()
            continue
        m = _INSTRUCTION.match(line)
        if not m:
            continue
        name = m.group(2)
        on = _OP_NAME.search(line)
        scope = _scope_of(on.group(1)) if on else None
        if scope is not None:
            own[name] = scope
            if computation is not None:
                members[computation][scope] += 1
        c = _CALLS.search(line)
        if c:
            calls[name] = c.group(1)
        if " parameter(" in line:
            params.add(name)
        if m.group(1) and " tuple(" in line:
            roots[computation] = _NAME.findall(line.split(" tuple(", 1)[1])
        mv = _MOVES.search(line)
        if mv and name not in own:
            operand = _NAME.search(mv.group(2))
            index = _INDEX.search(line)
            if operand:
                moves[name] = (operand.group(0),
                               int(index.group(1)) if index else None,
                               computation)
    for name, callee in calls.items():
        if name not in own and members.get(callee):
            own[name] = members[callee].most_common(1)[0][0]

    def resolve(name, depth=0):
        if name in own or depth > 32 or name not in moves:
            return own.get(name)
        operand, index, comp = moves[name]
        if operand in params and index is not None \
                and index < len(roots.get(comp, ())):
            operand = roots[comp][index]
        return resolve(operand, depth + 1)

    for name in moves:
        scope = resolve(name)
        if scope is not None:
            own[name] = scope
    return own


def _search_runs(trace: devtrace.Trace, lo: float, hi: float,
                 modules=SEARCH_MODULES) -> list:
    return sorted((e for e in trace.modules[0]
                   if devtrace.module_key(e.name) in modules
                   and lo <= e.start_ns < hi), key=lambda e: e.start_ns)


def scope_s(trace: devtrace.Trace, lo: float, hi: float, maps: list,
            modules=SEARCH_MODULES) -> dict:
    """{scope: self seconds} of the ops inside the search executables' runs
    that start in the window; ops no map scopes count as ``UNSCOPED``.
    ``maps`` holds one :func:`scope_map` per executable that may have run
    (one per bucket): each run takes the map that knows most of its ops."""
    runs = _search_runs(trace, lo, hi, modules)
    timed = self_times(trace.ops[0])
    out: dict[str, float] = {}
    i = 0
    for run in runs:
        inside = []
        while i < len(timed) and timed[i][0].start_ns < run.start_ns - SLACK_NS:
            i += 1
        j = i
        while j < len(timed) and timed[j][0].start_ns <= run.end_ns:
            if timed[j][0].end_ns <= run.end_ns + SLACK_NS:
                inside.append((devtrace.op_key(timed[j][0].name),
                               timed[j][1]))
            j += 1
        i = j
        if not maps:
            best = {}
        else:
            best = max(maps, key=lambda m: sum(t for k, t in inside if k in m))
        for k, t in inside:
            scope = best.get(k, UNSCOPED)
            out[scope] = out.get(scope, 0.0) + t / 1e9
    return out


def clock_offsets(trace: devtrace.Trace, spans: list[Span], lo: float,
                  hi: float, modules=SEARCH_MODULES) -> dict:
    """Per search run in the window: device start less the start of the
    last ``engine.dispatch`` span begun before it, and the end of the first
    ``engine.sync`` span that ends after the run's end less the run's end.
    Both are >= 0 when the two clocks agree; the result holds their lists
    (ms) and the runs that fall outside their host pair by more than 1 ms."""
    dispatch = sorted(s.start_ns for s in spans if s.name == "engine.dispatch")
    sync_end = sorted(s.end_ns for s in spans if s.name == "engine.sync")
    start_ms, end_ms = [], []
    for run in _search_runs(trace, lo, hi, modules):
        before = [t for t in dispatch if t <= run.start_ns + 1e6]
        after = [t for t in sync_end if t >= run.end_ns - 1e6]
        if not before or not after:
            continue
        start_ms.append((run.start_ns - before[-1]) / 1e6)
        end_ms.append((after[0] - run.end_ns) / 1e6)
    outside = sum(1 for a, b in zip(start_ms, end_ms) if a < -1 or b < -1)
    return {"start_ms": start_ms, "end_ms": end_ms, "outside_1ms": outside}


def idle_in_spans(trace: devtrace.Trace, spans: list[Span], lo: float,
                  hi: float) -> float:
    """Share of the first device's idle time in the window that lies
    inside some program span (on any thread)."""
    busy = devtrace.merge(((e.start_ns, e.end_ns) for e in trace.ops[0]),
                          lo, hi)
    idle = devtrace.gaps(busy, lo, hi)
    covered = devtrace.merge(((s.start_ns, s.end_ns) for s in spans), lo, hi)
    total = sum(e - s for s, e in idle)
    inside = 0.0
    j = 0
    for s, e in idle:
        while j < len(covered) and covered[j][1] <= s:
            j += 1
        k = j
        while k < len(covered) and covered[k][0] < e:
            inside += _clip(covered[k][0], covered[k][1], s, e)
            k += 1
    return inside / total if total else 1.0
