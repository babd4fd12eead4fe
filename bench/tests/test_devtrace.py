from pathlib import Path

import pytest

from bench import devtrace
from bench.devtrace import Event, Trace

FIXTURE = Path(__file__).parent / "data" / "tiny.xplane.pb"


def synthetic():
    """Window 0..100 ns.  Device ops: [10, 30) and [20, 40) overlap, [60, 70);
    one op straddles the window's start ([-5, 5)).  Modules: jit_f twice,
    jit_g once.  Host: a sleep annotation over [40, 60)."""
    ops = [Event("fusion.1", -5, 5), Event("fusion.1", 10, 30),
           Event("dot.2", 20, 40), Event("fusion.1", 60, 70)]
    mods = [Event("jit_f(12)", 10, 40), Event("jit_f(12)", 60, 70),
            Event("jit_g", -5, 5)]
    host = [Event(devtrace.WINDOW, 0, 100), Event("bench.sleep", 40, 60),
            Event("outer", 0, 100)]
    return Trace([ops], [mods], host, host[0])


def test_busy_idle_and_executable_time():
    r = devtrace.reduce(synthetic())
    # union inside the window: [0,5) + [10,40) + [60,70) = 45 ns
    assert r["busy_s"] == pytest.approx(45e-9)
    assert r["window_s"] == pytest.approx(100e-9)
    assert r["module_runs"] == {"jit_f": 2, "jit_g": 1}
    assert r["module_s"]["jit_f"] == pytest.approx(40e-9)
    gaps = r["breakdown"]["idle_gaps"]
    # longest gaps first: [70,100) 30 ns, [40,60) 20 ns, [5,10) 5 ns
    assert [g[1] for g in gaps] == pytest.approx([30e-9, 20e-9, 5e-9])
    assert gaps[1][0] == "bench.sleep"
    assert gaps[0][0] == "outer"
    ops = dict(r["breakdown"]["device_ops"])
    assert ops["fusion.1"] == pytest.approx(30e-9)


def test_merge_and_gaps():
    assert devtrace.merge([(5, 8), (1, 3), (2, 4)], 0, 10) == [(1, 4), (5, 8)]
    assert devtrace.gaps([(1, 4), (5, 8)], 0, 10) == [(0, 1), (4, 5), (8, 10)]
    assert devtrace.module_key("jit_jitted(4217)") == "jit_jitted"
    assert devtrace.op_key("%fusion.3 = f32[8]{0} fusion(f32[8]{0} %x)") \
        == "%fusion.3"


def test_recorded_tpu_trace():
    """The trace ``record_trace.py`` took on a v5e: 50 ms of host sleep, three
    runs each of two jitted functions with a 20 ms sleep after each first
    one, 50 ms of sleep, all inside the window."""
    tr = devtrace.load(str(FIXTURE))
    r = devtrace.reduce(tr)
    assert r["module_runs"] == {"jit__lambda": 6}
    mods = tr.modules[0]
    assert len(mods) == 6
    module_s = sum(e.end_ns - e.start_ns for e in mods) / 1e9
    assert r["module_s"]["jit__lambda"] == pytest.approx(module_s)
    # busy: the union of the ops, all inside the six runs (to the trace's
    # rounding of a nanosecond)
    ops = sorted((e.start_ns, e.end_ns) for e in tr.ops[0])
    assert all(any(m.start_ns - 2 <= s and e <= m.end_ns + 2 for m in mods)
               for s, e in ops)
    union, end = 0.0, -1.0
    for s, e in ops:
        union += max(0.0, e - max(s, end))
        end = max(end, e)
    assert r["busy_s"] == pytest.approx(union / 1e9)
    assert 0.5 * module_s < r["busy_s"] <= module_s
    window = (tr.window.end_ns - tr.window.start_ns) / 1e9
    assert r["window_s"] == pytest.approx(window)
    assert window > 0.1 + 3 * 0.02
    # the longest idle gaps are the sleeps, named by what the host did
    names = [g[0] for g in r["breakdown"]["idle_gaps"][:5]]
    assert sorted(names) == ["bench.test_lead", "bench.test_sleep",
                             "bench.test_sleep", "bench.test_sleep",
                             "bench.test_tail"]
