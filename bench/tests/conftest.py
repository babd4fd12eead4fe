"""Tests of the benchmark's own code, run by hand on the CPU:

    JAX_PLATFORMS=cpu python3 -m pytest bench/tests -q

(the repository's tier-1 run collects ``tests/`` only)."""
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY_N = 1500


class FakeDevice:
    """Stands in for the chip: the harness's look for a TPU is skipped."""
    platform = "cpu"
    device_kind = "TPU v5 lite"

    def memory_stats(self):
        return None


def tiny_cell(config: str, traffic: str, **overrides):
    """Configuration ``bench/configs/<config>.json`` at N = TINY_N under the
    traffic mix ``bench/traffic/<traffic>.json``, its parameters overridden;
    it reports every end-to-end metric its kind gives."""
    import json
    from bench import harness
    cfg = json.loads((ROOT / "bench/configs" / f"{config}.json").read_text())
    mix = json.loads((ROOT / "bench/traffic" / f"{traffic}.json").read_text())
    mix.update(overrides)
    return harness.Cell(
        name=f"{config}.{traffic}",
        config=dict(cfg, n=TINY_N, name="tiny-" + cfg["name"]),
        traffic=mix, chips=1,
        end_to_end=[{"name": n, "unit": "-"} for n in
                    ("qps", "latency_p99_ms", "recall_at_10", "setup_s")],
        per_layer=[])


def run_tiny(cell, seed=3, seconds=1.0, plant=None):
    import time
    from bench import harness
    harness.use_compile_cache(ROOT)
    return harness.run_cell(ROOT, cell, seed, seconds, False,
                            time.perf_counter(), FakeDevice(), 1,
                            plant=plant)
