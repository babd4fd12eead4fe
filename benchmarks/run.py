"""Benchmark harness entry point.

``PYTHONPATH=src python -m benchmarks.run [--only NAME]``

Prints ``name,us_per_call,derived`` CSV, one block per paper table/figure
(see benchmarks/paper_figs.py) plus the roofline table from the dry-run
artifacts (benchmarks/roofline_report.py).
"""
from __future__ import annotations

import argparse
import sys
import time
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="substring filter on benchmark names")
    ap.add_argument("--skip-roofline", action="store_true")
    ap.add_argument("--sweep-backends", action="store_true",
                    help="sweep SearchConfig.dist_backend and write "
                         "BENCH_dist_backend.json (skips the figure suite)")
    ap.add_argument("--bench-out", default="BENCH_dist_backend.json",
                    help="output path for --sweep-backends")
    ap.add_argument("--sweep-serve", action="store_true",
                    help="latency-under-load sweep through the async "
                         "coalescing engine; writes BENCH_serve.json "
                         "(skips the figure suite)")
    ap.add_argument("--serve-out", default="BENCH_serve.json",
                    help="output path for --sweep-serve")
    ap.add_argument("--trace-out", default=None,
                    help="with --sweep-serve: dump Chrome-trace/Perfetto "
                         "JSON of the highest-QPS sweep point here "
                         "(open in ui.perfetto.dev)")
    ap.add_argument("--sweep-batch", action="store_true",
                    help="batch-amortization sweep of the batch-major "
                         "engine (B x backend); appends rows to "
                         "BENCH_dist_backend.json (skips the figure suite)")
    ap.add_argument("--sweep-build", action="store_true",
                    help="construction-throughput sweep of the batched "
                         "builder (build_batch x backend vs the serial "
                         "reference); appends rows to BENCH_build.json "
                         "(skips the figure suite)")
    ap.add_argument("--build-out", default="BENCH_build.json",
                    help="output path for --sweep-build")
    args = ap.parse_args()
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()

    if args.sweep_backends:
        from benchmarks import dist_backend
        dist_backend.sweep(args.bench_out)
        return

    if args.sweep_batch:
        from benchmarks import batch_sweep
        batch_sweep.sweep(args.bench_out)
        return

    if args.sweep_build:
        from benchmarks import build_sweep
        build_sweep.sweep(args.build_out)
        return

    if args.sweep_serve:
        from benchmarks import serve_load
        serve_load.sweep(args.serve_out, trace_out=args.trace_out)
        return

    from benchmarks import paper_figs
    from benchmarks import roofline_report

    print("name,us_per_call,derived")
    failures = []
    for fn in paper_figs.ALL:
        if args.only and args.only not in fn.__name__:
            continue
        t0 = time.time()
        try:
            fn()
            print(f"# {fn.__name__} done in {time.time() - t0:.0f}s",
                  file=sys.stderr)
        except Exception as e:  # noqa: BLE001
            failures.append(fn.__name__)
            print(f"{fn.__name__},nan,ERROR:{type(e).__name__}:{e}")
            traceback.print_exc(file=sys.stderr)
    if not args.skip_roofline and not args.only:
        roofline_report.render()
        roofline_report.kernel_rooflines()
    if failures:
        print(f"# FAILED: {failures}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
