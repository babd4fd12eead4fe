#!/usr/bin/env python3
"""Record the small device trace that ``test_devtrace.py`` reads.

    python3 bench/tests/record_trace.py      # on a machine with a TPU

Three runs each of two jitted functions inside the traced window, with a
20 ms host sleep under its own annotation between them and 50 ms sleeps
at both ends of the window, written to
``bench/tests/data/tiny.xplane.pb``.
"""
import glob
import os
import shutil
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main() -> int:
    import jax
    import jax.numpy as jnp
    from bench import devtrace
    if jax.devices()[0].platform != "tpu":
        print("record_trace: needs a TPU", file=sys.stderr)
        return 1
    mm = jax.jit(lambda x: x @ x)
    ew = jax.jit(lambda x: jnp.tanh(x) * 2.0 + 1.0)
    x = jnp.ones((2048, 2048), jnp.float32)
    mm(x).block_until_ready()
    ew(x).block_until_ready()
    d = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(d, profiler_options=opts)
    with jax.profiler.TraceAnnotation(devtrace.WINDOW):
        # 50 ms of margin at both ends: the device clock may sit a
        # millisecond or two off the host's
        with jax.profiler.TraceAnnotation("bench.test_lead"):
            time.sleep(0.05)
        for _ in range(3):
            mm(x).block_until_ready()
            with jax.profiler.TraceAnnotation("bench.test_sleep"):
                time.sleep(0.02)
            ew(x).block_until_ready()
        with jax.profiler.TraceAnnotation("bench.test_tail"):
            time.sleep(0.05)
    jax.profiler.stop_trace()
    src = glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True)[0]
    dst = os.path.join(ROOT, "bench", "tests", "data", "tiny.xplane.pb")
    shutil.copy(src, dst)
    shutil.rmtree(d)
    print(f"wrote {dst} ({os.path.getsize(dst)} bytes)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
