import gzip
import json
from pathlib import Path

import pytest

from bench import devtrace, spans
from bench.devtrace import Event, Trace
from bench.spans import Span

DATA = Path(__file__).parent / "data"
TRACE = DATA / "serve.xplane.pb"
HLO_TEXTS = DATA / "serve_hlo.json.gz"
recorded = pytest.mark.skipif(not HLO_TEXTS.exists(),
                              reason="record_serve_trace.py not run")


def _span(name, s, e, thread="t0", **args):
    return Span(name, s, e, thread, args)


def test_host_spans_self_time_clip_and_count():
    sp = [_span("engine.search", 10, 50), _span("engine.sync", 20, 40),
          _span("coalescer.form", 0, 10),
          # another thread: overlaps in time, nests in nothing
          _span("coalescer.submit", 15, 25, thread="t1"),
          _span("engine.search", 90, 130)]
    r = spans.host_spans(sp, 0, 100)
    assert r["engine.search"] == {"total_s": pytest.approx(50e-9),
                                  "count": 2,
                                  "self_s": pytest.approx(30e-9)}
    assert r["engine.sync"]["self_s"] == pytest.approx(20e-9)
    assert r["coalescer.submit"]["self_s"] == pytest.approx(10e-9)
    assert r["coalescer.form"]["count"] == 1


def test_op_self_time_subtracts_nested_ops():
    ops = [Event("%while.1 = s32[] while(...)", 0, 100),
           Event("%fusion.2 = f32[8] fusion(...)", 10, 30),
           Event("%sort.3 = s32[8] sort(...)", 40, 70),
           Event("%fusion.2 = f32[8] fusion(...)", 120, 130)]
    tr = Trace([ops], [[]], [Event(devtrace.WINDOW, 0, 200)],
               Event(devtrace.WINDOW, 0, 200))
    r = spans.op_self_s(tr, 0, 200)
    assert r == pytest.approx({"%while.1": 50e-9, "%fusion.2": 30e-9,
                               "%sort.3": 30e-9})


HLO = """HloModule jit_jitted

%fused_computation.1 (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  ROOT %mul.1 = f32[8]{0} multiply(%p, %p), metadata={op_name="jit(jitted)/while/body/ann.distance/mul"}
}

%body.2 (p: (s32[], f32[8])) -> (s32[], f32[8]) {
  %p.1 = (s32[], f32[8]) parameter(0)
  %get-tuple-element.7 = f32[8]{0} get-tuple-element(%p.1), index=1
  %copy.8 = f32[8]{0} copy(f32[8]{0} %get-tuple-element.7)
  %fusion.1 = f32[8]{0} fusion(%x), kind=kLoop, calls=%fused_computation.1
  %sort.3 = s32[8]{0} sort(%y), metadata={op_name="jit(jitted)/while/body/ann.counters/sort"}
  %copy.4 = s32[8]{0} copy(%z)
  %copy.9 = s32[8]{0} copy(s32[8]{0} %sort.3)
  ROOT %tuple.10 = (s32[], f32[8]) tuple(s32[] %c, f32[8]{0} %fusion.1)
}

ENTRY %main.5 (a: f32[8]) -> f32[8] {
  ROOT %while.6 = (s32[], f32[8]) while(%t), condition=%c, body=%body.2, metadata={op_name="jit(jitted)/while"}
}
"""


def test_scope_map_reads_op_name_and_fused_computations():
    m = spans.scope_map(HLO)
    assert m["%fusion.1"] == "ann.distance"    # from its fused computation
    assert m["%sort.3"] == "ann.counters"
    assert "%copy.4" not in m and "%while.6" not in m
    # a copy takes the scope of what it moves: an op's output, or a loop
    # carry element, which is what the body returns in that place
    assert m["%copy.9"] == "ann.counters"
    assert m["%copy.8"] == m["%get-tuple-element.7"] == "ann.distance"


def test_scope_time_clock_offsets_and_idle_in_spans():
    mods = [Event("jit_jitted(7)", 10, 50), Event("jit_pack(8)", 52, 54)]
    ops = [Event("%while.6 = ...", 10, 50), Event("%fusion.1 = ...", 12, 30),
           Event("%sort.3 = ...", 30, 44), Event("%copy.4 = ...", 44, 48),
           Event("%fusion.9 = ...", 52, 54)]
    host = [Event(devtrace.WINDOW, 0, 100)]
    tr = Trace([ops], [mods], host, host[0])
    r = spans.scope_s(tr, 0, 100, [spans.scope_map(HLO)])
    assert r == pytest.approx({"ann.distance": 18e-9, "ann.counters": 14e-9,
                               spans.UNSCOPED: 8e-9})
    sp = [_span("engine.dispatch", 5, 8), _span("engine.sync", 9, 51),
          _span("coalescer.fill", 60, 90)]
    off = spans.clock_offsets(tr, sp, 0, 100)
    assert off["start_ms"] == pytest.approx([5e-6])
    assert off["end_ms"] == pytest.approx([1e-6])
    assert off["outside_1ms"] == 0
    # idle: [0,10) [50,52) [54,100) = 58 ns; in spans: [5,8) [9,10)
    # [50,51) [60,90) = 35 ns
    assert spans.idle_in_spans(tr, sp, 0, 100) == pytest.approx(35 / 58)


@recorded
def test_recorded_serving_trace_spans_in_batch_order():
    """A v5e trace of the serving path (``record_serve_trace.py``): every
    batch's spans carry its number, in the order the dispatcher runs them,
    and each batch comes back in one device-to-host read."""
    sp = spans.load_spans(str(TRACE))
    loop = [s for s in sp if s.name != "coalescer.submit"]
    order = ("coalescer.form", "engine.search", "engine.pad",
             "engine.dispatch", "engine.sync", "engine.readback",
             "engine.postprocess", "coalescer.resolve")
    by_batch: dict = {}
    for s in loop:
        by_batch.setdefault(s.args["batch"], []).append(s.name)
    formed = [b for b, names in by_batch.items() if "coalescer.form" in names]
    assert len(formed) >= 8 + 3
    for b in formed:
        names = [n for n in by_batch[b] if n in order]
        assert names == list(order), (b, by_batch[b])
    reads = [s.args for s in sp if s.name == "engine.readback"]
    assert {r["arrays"] for r in reads} == {1}
    assert sum(1 for s in sp if s.name == "coalescer.submit") >= 32


@recorded
def test_recorded_serving_trace_scopes_and_clock():
    tr = devtrace.load(str(TRACE))
    lo, hi = tr.window.start_ns, tr.window.end_ns
    with gzip.open(HLO_TEXTS, "rt") as f:
        maps = [spans.scope_map(text) for text in json.load(f).values()]
    by_scope = spans.scope_s(tr, lo, hi, maps)
    total = sum(by_scope.values())
    assert total > 0
    assert {"ann.distance", "ann.queue", "ann.visited", "ann.select",
            "ann.counters", "ann.loop"} <= set(by_scope)
    assert by_scope.get(spans.UNSCOPED, 0.0) / total < 0.05
    off = spans.clock_offsets(tr, spans.load_spans(str(TRACE)), lo, hi)
    assert len(off["start_ms"]) >= 11
    assert off["outside_1ms"] == 0
