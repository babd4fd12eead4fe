"""The control — the reference in the program's place, one precision down —
must come out not correct.  On the CPU only the bfloat16 control is lower
precision (``Precision.HIGH`` is float32 there); both run on the chip at the
cells' own size with ``python3 bench/control.py``, readings in PERF.md."""
import pytest

from bench import check, control
from bench.tests.conftest import tiny_cell


@pytest.mark.parametrize("config,traffic", [("sift128", "saturate"),
                                            ("bigann128-int8", "saturate"),
                                            ("sift128", "single")])
def test_control_is_not_correct(config, traffic):
    precision = "bf16"
    cell = tiny_cell(config, traffic)
    values = control.control_values(cell.config, cell.traffic, 7, precision,
                                    2000)
    correct, checks = check.verdict(values, cell.config["recall_target"])
    assert not correct
    assert checks["dist_gap"]["value"] > check.DIST_GAP_LIMIT
