#!/usr/bin/env python3
"""Run one cell of the benchmark once, on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout.  Set-up (corpus, the cached index, warm-up of
every shape the cell's traffic uses) is timed as ``setup_s``; a checkout's
first run builds the index, and that build is reported on its own line
(``[setup] index_s=``), not in ``setup_s``.  Then the traffic runs for
``--seconds``; then what the program answered is checked against the plain
reference.  The last line of standard output is the result
as one JSON object; the numbers checked and their limits are also the last
lines of standard error.  With ``--trace 1`` the metrics are the cell's
per-layer ones, read from a profiler trace of the window's last seconds.

Exits non-zero, with no result, without a TPU, with fewer chips than the cell
asks for, or outside a checkout that holds the program (``src/repro``).
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be >= 0")
    if not (ROOT / "src" / "repro").is_dir():
        print(f"bench: no program at {ROOT / 'src' / 'repro'}",
              file=sys.stderr)
        return 2
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    # the TPU runtime logs to a fixed directory under /tmp unless told not to
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from bench import harness

    cell = harness.resolve_cell(ROOT, args.workload)
    harness.use_compile_cache(ROOT)
    import jax

    devices = jax.devices()
    dev = devices[0]
    harness.log("device", platform=dev.platform, kind=dev.device_kind,
                count=len(devices))
    if dev.platform != "tpu":
        print(f"bench: no TPU found (platform {dev.platform}); refusing to "
              "run", file=sys.stderr)
        return 3
    if len(devices) < cell.chips:
        print(f"bench: {args.workload} needs {cell.chips} chips, found "
              f"{len(devices)}", file=sys.stderr)
        return 3
    result = harness.run_cell(ROOT, cell, args.seed, args.seconds,
                              bool(args.trace), T_START, dev, len(devices))
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
