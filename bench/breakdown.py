#!/usr/bin/env python3
"""Where one traced run of a cell spends its time, by program span and by
named scope.

    python3 bench/breakdown.py --workload <cell> --seed <n> --seconds <s> \\
        --out <report.json> [--xplane <copy.xplane.pb>]

from the root of a checkout, on a machine with a TPU.  Runs the cell once
as ``bench/run.py --trace 1`` does (same set-up, window, profiler over the
window's last seconds and check), but compiles every executable afresh, and
keeps the profiler trace; writes a
report (JSON) with:

* ``result``: the run's result line;
* ``per_batch``: mean milliseconds per batch searched in the traced window,
  of each ``coalescer.*``/``engine.*`` span (self time for
  ``engine.search``), with ``engine_host_ms`` (``engine.search`` less its
  ``engine.sync``) and ``coalescer_host_ms`` (``coalescer.form`` plus
  ``coalescer.resolve``);
* ``latency_ms_mean``: the mean request latency of the traced window's
  requests, as the client timed them;
* ``readback``: arrays and bytes of each batch's device-to-host read;
* ``scopes``: seconds and share of the search executables' op self time per
  ``ann.*`` scope (``unscoped``: ops no scope names);
* ``lane_occupancy``: 100 x lane steps / (lanes x loop iterations) over the
  traced window's batches, with the engine's own counters beside it;
* ``clock``: offsets between each search run on the device and its
  ``engine.dispatch``/``engine.sync`` spans (``bench.spans.clock_offsets``);
* ``idle_in_spans``: share of device idle time inside a program span;
* ``idle_gaps``: the longest idle gaps, each named by the innermost host
  event over its middle, and whether it touches the window's edge;
* ``top_ops_self``: the ops with most device self time, with their scope,
  and ``top_unscoped_ops``, those no scope names (any executable's);
* ``trace_bytes``: size of the trace file.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def _mean(xs):
    return statistics.fmean(xs) if xs else None


def _per_batch(spans, lo, hi) -> dict:
    """Mean ms per ``engine.search`` span that starts in [lo, hi)."""
    from bench import spans as sp
    inside = [s for s in spans if lo <= s.start_ns < hi]
    searches = [s for s in inside if s.name == "engine.search"]
    n = len(searches)
    if not n:
        return {}
    table = sp.host_spans(spans, lo, hi)
    out = {"batches": n}
    for name, row in sorted(table.items()):
        t = row["self_s"] if name == "engine.search" else row["total_s"]
        out[name + ("_self" if name == "engine.search" else "")] = \
            1e3 * t / n
    search_ms = 1e3 * table["engine.search"]["total_s"] / n
    sync_ms = 1e3 * table.get("engine.sync", {}).get("total_s", 0.0) / n
    out["engine_host_ms"] = search_ms - sync_ms
    out["coalescer_host_ms"] = 1e3 * sum(
        table.get(k, {}).get("total_s", 0.0)
        for k in ("coalescer.form", "coalescer.resolve")) / n
    return out


def breakdown(root: Path, cell, seed: int, seconds: float, device,
              n_devices: int, xplane_out=None) -> dict:
    import numpy as np
    from bench import devtrace, harness, loadgen
    from bench import spans as sp

    kept: dict = {}

    class KeepingTracer(harness.Tracer):
        def reduce(self):
            path = devtrace.find_xplane(self.dir)
            kept["trace_bytes"] = os.path.getsize(path)
            kept["host_span"] = self.host_span
            kept["trace"] = devtrace.load(path)
            kept["spans"] = sp.load_spans(path)
            if xplane_out:
                shutil.copy(path, xplane_out)
            return super().reduce()

    def keep_log(gen):
        def wrapped(*a, **kw):
            kept["log"] = gen(*a, **kw)
            return kept["log"]
        return wrapped

    batches: list = []

    def capture(engine):
        """Keep the engine, and each batch's host span and lane steps."""
        kept["engine"] = engine
        inner = engine.search

        def search(queries, *a, **kw):
            t0 = time.perf_counter()
            res = inner(queries, *a, **kw)
            batches.append((t0, time.perf_counter(),
                            np.asarray(res.stats.steps), res.buckets))
            return res
        engine.search = search

    saved = (harness.Tracer, loadgen.closed_loop, loadgen.open_loop)
    harness.Tracer = KeepingTracer
    loadgen.closed_loop = keep_log(loadgen.closed_loop)
    loadgen.open_loop = keep_log(loadgen.open_loop)
    try:
        result = harness.run_cell(root, cell, seed, seconds, True,
                                  T_START, device, n_devices,
                                  plant=capture)
    finally:
        harness.Tracer, loadgen.closed_loop, loadgen.open_loop = saved

    trace, spans, engine = kept["trace"], kept["spans"], kept["engine"]
    lo, hi = trace.window.start_ns, trace.window.end_ns
    report = {"workload": cell.name, "seed": seed, "seconds": seconds,
              "result": result, "trace_bytes": kept["trace_bytes"],
              "window_s": (hi - lo) / 1e9}
    report["per_batch"] = _per_batch(spans, lo, hi)
    reads = [s.args for s in spans
             if s.name == "engine.readback" and lo <= s.start_ns < hi]
    report["readback"] = {
        "per_batch_arrays": sorted({int(a.get("arrays", -1)) for a in reads}),
        "bytes_mean": _mean([a.get("bytes", 0) for a in reads])}
    report["host_spans"] = sp.host_spans(spans, lo, hi)

    # the requests and searches inside the traced span of the host clock
    t_lo, t_hi = kept["host_span"]
    log = kept["log"]
    report["latency_ms_mean"] = _mean([
        (d - u) * 1e3 for u, d, ok in zip(log.due, log.done, log.ok)
        if ok and t_lo <= u and d <= t_hi])
    traced = [b for b in batches if t_lo <= b[0] and b[1] <= t_hi]
    top = engine.bucket_sizes[-1]
    lane_steps = lane_iters = 0
    for _, _, steps, buckets in traced:
        lane_steps += int(steps.sum())
        for i in range(len(buckets)):
            chunk = steps[i * top:(i + 1) * top]
            lane_iters += len(chunk) * int(chunk.max())
    report["lane_occupancy"] = {
        "window": 100.0 * lane_steps / lane_iters if lane_iters else None,
        "batches": len(traced)}
    st = engine.stats()
    report["lane_occupancy"].update(
        engine_lane_steps_total=st["lane_steps_total"],
        engine_loop_iters_total=st["loop_iters_total"],
        all_batches_lane_steps=sum(int(s.sum()) for _, _, s, _ in batches))

    # instruction -> scope of every bucket executable the window ran
    import jax.numpy as jnp
    search = engine.index.searcher(engine.params)
    dim = engine.graph.dim
    used = sorted({b for _, _, _, bs in traced for b in bs}) or \
        [engine.bucket_sizes[-1]]
    maps = [sp.scope_map(search.lower(jnp.zeros((b, dim), jnp.float32))
                         .compile().as_text()) for b in used]
    seconds_by_scope = sp.scope_s(trace, lo, hi, maps)
    total = sum(seconds_by_scope.values())
    report["scopes"] = {
        "buckets": used,
        "map_sizes": [len(m) for m in maps],
        "seconds": seconds_by_scope,
        "share_pct": {k: 100.0 * v / total
                      for k, v in seconds_by_scope.items()} if total else {},
        "op_self_s_search": total}
    off = sp.clock_offsets(trace, spans, lo, hi)

    def summary(xs):
        return {"median": statistics.median(xs), "min": min(xs),
                "max": max(xs)} if xs else None
    report["clock"] = {"start_ms": summary(off["start_ms"]),
                       "end_ms": summary(off["end_ms"]),
                       "runs": len(off["start_ms"]),
                       "outside_1ms": off["outside_1ms"]}
    report["idle_in_spans"] = sp.idle_in_spans(trace, spans, lo, hi)
    # the longest idle gaps, named as devtrace names them, and whether
    # each touches the window's edge
    busy = devtrace.merge(((e.start_ns, e.end_ns) for e in trace.ops[0]),
                          lo, hi)
    longest = sorted(devtrace.gaps(busy, lo, hi),
                     key=lambda g: g[0] - g[1])[:devtrace.TOP]
    report["idle_gaps"] = [
        [devtrace.host_activity(trace.host, (g0 + g1) / 2),
         (g1 - g0) / 1e9, g0 <= lo or g1 >= hi] for g0, g1 in longest]
    report["no_host_event_gaps"] = sum(
        1 for name, _, edge in report["idle_gaps"]
        if name == "no host event" and not edge)
    scope_of = {k: v for m in maps for k, v in m.items()}
    ranked = sorted(sp.op_self_s(trace, lo, hi).items(),
                    key=lambda kv: -kv[1])
    report["top_ops_self"] = [[op, t, scope_of.get(op, sp.UNSCOPED)]
                              for op, t in ranked[:devtrace.TOP]]
    report["top_unscoped_ops"] = [[op, t] for op, t in ranked
                                  if op not in scope_of][:devtrace.TOP]
    return report


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", required=True)
    ap.add_argument("--xplane")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from bench import harness
    cell = harness.resolve_cell(ROOT, args.workload)
    import jax
    # every executable compiles afresh in this process, before any
    # compile (JAX settles whether to use its persistent cache once): the
    # cache's key ignores metadata, so a cached search may predate its
    # scopes, and another process's compile need not name its
    # instructions as the scope map's compile here does
    jax.config.update("jax_enable_compilation_cache", False)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print("breakdown: needs a TPU", file=sys.stderr)
        return 3
    report = breakdown(ROOT, cell, args.seed, args.seconds, devices[0],
                       len(devices), args.xplane)
    Path(args.out).parent.mkdir(parents=True, exist_ok=True)
    Path(args.out).write_text(json.dumps(report, indent=1))
    print(json.dumps({k: report[k] for k in (
        "workload", "per_batch", "scopes", "clock", "idle_in_spans",
        "lane_occupancy", "readback", "no_host_event_gaps",
        "trace_bytes")}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
