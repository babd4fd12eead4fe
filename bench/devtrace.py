"""Reduction of a JAX profiler trace to the device numbers the benchmark reports.

The run wraps its traced window in a host annotation named ``WINDOW``; its
interval on the trace's clock is the window.  From the device planes
(``/device:TPU:<i>``):

* busy time: the union of the intervals in which an operation (line ``XLA
  Ops``; ``XLA Modules`` where a plane has no op line) runs inside the window,
  averaged over the devices; the idle share is 1 - busy / window;
* executable time: per module name (line ``XLA Modules``), the summed device
  duration of its runs and their count.  Module names are JAX's: ``jit_<f>``
  for a jitted function ``f``, optionally with a ``(<id>)`` suffix;
* the breakdown: the operations that took most device time, and the longest
  idle gaps, each named by the innermost host event that covers its middle
  (what the host was doing while the device waited).
"""
from __future__ import annotations

import glob
import os
import re
from typing import NamedTuple

WINDOW = "bench.traced_window"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
TOP = 10


class Event(NamedTuple):
    name: str
    start_ns: float
    end_ns: float


class Trace(NamedTuple):
    ops: list          # per device: list[Event]
    modules: list      # per device: list[Event]
    host: list         # list[Event], every host plane and line
    window: Event      # the WINDOW annotation


def find_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {log_dir}, "
                           f"found {len(paths)}")
    return paths[0]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ops, modules, host = [], [], []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            lines = {ln.name: [Event(e.name, e.start_ns, e.end_ns)
                               for e in ln.events] for ln in plane.lines}
            mods = lines.get("XLA Modules", [])
            ops.append(lines.get("XLA Ops", mods))
            modules.append(mods)
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                host.extend(Event(e.name, e.start_ns, e.end_ns)
                            for e in ln.events)
    wins = [e for e in host if e.name == WINDOW]
    if len(wins) != 1:
        raise RuntimeError(f"trace holds {len(wins)} {WINDOW!r} annotations")
    if not ops:
        raise RuntimeError("trace holds no TPU device plane")
    return Trace(ops, modules, host, wins[0])


def merge(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """Union of (start, end) intervals clipped to [lo, hi], sorted."""
    out: list[list[float]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def gaps(busy: list[tuple[float, float]], lo: float, hi: float):
    """The idle intervals of [lo, hi] between merged busy intervals."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def host_activity(host: list[Event], t: float) -> str:
    """Name of the innermost host event (other than the window) covering t."""
    best = None
    for e in host:
        if e.name != WINDOW and e.start_ns <= t <= e.end_ns and (
                best is None
                or e.end_ns - e.start_ns < best.end_ns - best.start_ns):
            best = e
    return best.name if best is not None else "no host event"


def module_key(name: str) -> str:
    """``jit_jitted(1234)`` -> ``jit_jitted``."""
    return re.sub(r"\(\d+\)$", "", name)


def op_key(name: str) -> str:
    """An op event is named by its HLO text; keep the instruction name:
    ``%fusion.3 = f32[...] fusion(...)`` -> ``%fusion.3``."""
    return name.split(" = ", 1)[0]


def reduce(trace: Trace) -> dict:
    """busy_s (mean over devices), window_s, per-module device seconds and
    run counts, and the breakdown of device operations and idle gaps."""
    lo, hi = trace.window.start_ns, trace.window.end_ns
    busy_ns, first_busy = [], None
    for dev_ops in trace.ops:
        merged = merge(((e.start_ns, e.end_ns) for e in dev_ops), lo, hi)
        busy_ns.append(sum(e - s for s, e in merged))
        if first_busy is None:
            first_busy = merged
    module_s: dict[str, float] = {}
    module_runs: dict[str, int] = {}
    for dev_mods in trace.modules:
        for e in dev_mods:
            if e.end_ns <= lo or e.start_ns >= hi:
                continue
            k = module_key(e.name)
            module_s[k] = module_s.get(k, 0.0) + (e.end_ns - e.start_ns) / 1e9
            module_runs[k] = module_runs.get(k, 0) + 1
    op_s: dict[str, float] = {}
    for e in trace.ops[0]:
        if lo <= e.start_ns < hi:
            k = op_key(e.name)
            op_s[k] = op_s.get(k, 0.0) + (e.end_ns - e.start_ns) / 1e9
    top_ops = sorted(op_s.items(), key=lambda kv: -kv[1])[:TOP]
    idle = sorted(gaps(first_busy, lo, hi), key=lambda g: g[0] - g[1])[:TOP]
    idle_gaps = [[host_activity(trace.host, (s + e) / 2), (e - s) / 1e9]
                 for s, e in idle]
    return {
        "busy_s": sum(busy_ns) / len(busy_ns) / 1e9,
        "window_s": (hi - lo) / 1e9,
        "module_s": module_s,
        "module_runs": module_runs,
        "breakdown": {"device_ops": [[n, s] for n, s in top_ops],
                      "idle_gaps": idle_gaps},
    }
