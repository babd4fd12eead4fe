"""Observability stack: sketch accuracy, exporters, span trees, overhead.

The contracts under test (docs/observability.md):

* ``LogHistogram`` quantiles are within ``rel_err`` of exact NumPy
  quantiles while memory stays bounded; merge is exact on bucket counts
  (associative up to float ``sum`` accumulation order).
* ``MetricsRegistry`` round-trips through JSON, merges across replicas,
  and emits valid Prometheus text (label escaping included).
* ``TraceRecorder`` produces Chrome-trace JSON that the repo's own
  validator (``scripts/check_trace.py``) accepts: spans nest by
  containment, async request lifelines pair up, shed events appear.
* Disabled observability is a true no-op: the engines write nothing into
  the registry and allocate no trace events on the hot path.
"""
import importlib.util
import json
import sys
from pathlib import Path

import numpy as np
import pytest

from repro.ann import AnnIndex, IndexSpec, SearchParams
from repro.data import make_vector_dataset
from repro.obs import (NULL_OBS, NULL_TRACER, LogHistogram, MetricsRegistry,
                       Observability, TraceRecorder)

ROOT = Path(__file__).resolve().parents[1]


def _load_check_trace():
    spec = importlib.util.spec_from_file_location(
        "check_trace", ROOT / "scripts" / "check_trace.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


PARAMS = SearchParams(k=10, queue_len=48, m_max=4, num_walkers=4,
                      max_steps=128, local_steps=4)
BUCKETS = (1, 2, 4, 8)


@pytest.fixture(scope="module")
def ds():
    return make_vector_dataset("deep", n=1200, n_queries=16, k=10, dim=24,
                               n_clusters=12, seed=7)


@pytest.fixture(scope="module")
def index(ds):
    return AnnIndex.build(ds, IndexSpec(degree=12, passes=1))


# -- LogHistogram ------------------------------------------------------------

def test_histogram_quantiles_match_numpy_within_rel_err():
    rng = np.random.RandomState(0)
    # lognormal spans ~4 decades — the shape latency streams actually have
    values = rng.lognormal(mean=1.0, sigma=1.5, size=20_000)
    h = LogHistogram(rel_err=0.01)
    h.observe_many(values)
    for q in (0.01, 0.10, 0.50, 0.90, 0.95, 0.99, 0.999):
        exact = float(np.quantile(values, q, method="lower"))
        got = h.quantile(q)
        assert abs(got - exact) <= 0.02 * exact, (q, got, exact)
    assert h.mean == pytest.approx(values.mean())
    assert h.min == values.min() and h.max == values.max()
    assert h.quantile(0.0) == values.min()
    assert h.quantile(1.0) == values.max()


def test_histogram_memory_bounded_and_collapse_keeps_tail():
    h = LogHistogram(rel_err=0.01, max_buckets=64)
    rng = np.random.RandomState(1)
    # 12 decades of values — far more than 64 buckets can hold exactly
    h.observe_many(10.0 ** rng.uniform(-6, 6, size=5000))
    assert h.n_buckets <= 64
    assert h.count == 5000
    # collapse folds the LOW buckets; the tail keeps full resolution
    assert h.quantile(0.5) <= h.quantile(0.99) <= h.max


def test_histogram_zero_and_nonfinite_values():
    h = LogHistogram()
    h.observe(0.0)
    h.observe(-3.0)          # below min-trackable -> zero bucket
    h.observe(float("nan"))  # dropped
    h.observe(float("inf"))  # dropped
    h.observe(5.0)
    assert h.count == 3
    assert h.zero_count == 2
    assert h.quantile(0.0) == -3.0          # exact min envelope
    assert h.quantile(0.99) == 0.0          # nearest-rank lower of 3 values
    assert h.quantile(1.0) == 5.0           # exact max envelope


def test_histogram_merge_is_associative():
    rng = np.random.RandomState(2)
    parts = [rng.lognormal(size=777) for _ in range(3)]

    def sketch(v):
        h = LogHistogram()
        h.observe_many(v)
        return h

    ab_c = sketch(parts[0]).merge(sketch(parts[1])).merge(sketch(parts[2]))
    bc = sketch(parts[1]).merge(sketch(parts[2]))
    a_bc = sketch(parts[0]).merge(bc)
    da, db = ab_c.to_dict(), a_bc.to_dict()
    # bucket counts/count/min/max are exactly associative; float `sum`
    # differs only by accumulation order
    for key in ("buckets", "count", "min", "max", "zero_count"):
        assert da[key] == db[key]
    assert da["sum"] == pytest.approx(db["sum"], rel=1e-9)
    # and the merged sketch matches a single sketch over the concatenation
    allv = np.concatenate(parts)
    whole = sketch(allv)
    assert ab_c.to_dict()["buckets"] == whole.to_dict()["buckets"]
    assert ab_c.quantile(0.95) == whole.quantile(0.95)


def test_histogram_merge_rejects_mixed_resolution_and_roundtrips():
    a, b = LogHistogram(rel_err=0.01), LogHistogram(rel_err=0.05)
    with pytest.raises(ValueError):
        a.merge(b)
    a.observe_many([1.0, 2.0, 4.0])
    back = LogHistogram.from_dict(json.loads(json.dumps(a.to_dict())))
    assert back.to_dict() == a.to_dict()
    assert back.quantile(0.5) == a.quantile(0.5)


# -- MetricsRegistry ---------------------------------------------------------

def test_registry_types_and_kind_conflict():
    reg = MetricsRegistry()
    reg.counter("req_total").inc(3, outcome="served")
    reg.gauge("queue_depth").set(7)
    reg.histogram("lat_ms").observe(12.5, backend="ref")
    with pytest.raises(TypeError):
        reg.gauge("req_total")
    with pytest.raises(ValueError):
        reg.counter("req_total").labels(outcome="served").inc(-1)


def test_registry_merge_and_json_roundtrip():
    a, b = MetricsRegistry(), MetricsRegistry()
    a.counter("req_total").inc(2, outcome="served")
    b.counter("req_total").inc(3, outcome="served")
    b.counter("req_total").inc(1, outcome="shed")
    for v in (1.0, 2.0, 3.0):
        a.histogram("lat_ms").observe(v)
    for v in (4.0, 5.0):
        b.histogram("lat_ms").observe(v)
    a.merge(b)
    d = a.to_dict()
    served = [s for s in d["req_total"]["series"]
              if s["labels"] == {"outcome": "served"}]
    assert served[0]["value"] == 5.0
    hist = d["lat_ms"]["series"][0]
    assert hist["histogram"]["count"] == 5
    assert set(hist["quantiles"]) == {"p50", "p95", "p99"}
    back = MetricsRegistry.from_json(a.to_json())
    assert back.to_dict() == d


def test_prometheus_exposition_format_and_escaping():
    reg = MetricsRegistry()
    reg.counter("req_total", "requests by outcome").inc(
        2, path='a"b\\c\nd')
    for v in (1.0, 2.0, 2.0, 100.0):
        reg.histogram("lat_ms", "latency").observe(v)
    text = reg.to_prometheus()
    assert "# HELP req_total requests by outcome" in text
    assert "# TYPE req_total counter" in text
    # escaping order: backslash, then quote, then newline
    assert 'path="a\\"b\\\\c\\nd"' in text
    assert "# TYPE lat_ms histogram" in text
    assert 'lat_ms_bucket{le="+Inf"} 4' in text
    assert "lat_ms_sum 105" in text
    assert "lat_ms_count 4" in text
    # cumulative bucket counts are monotone and end at the total
    cums = [int(line.rsplit(" ", 1)[1]) for line in text.splitlines()
            if line.startswith("lat_ms_bucket")]
    assert cums == sorted(cums) and cums[-1] == 4


# -- TraceRecorder -----------------------------------------------------------

def test_span_nesting_and_chrome_trace_schema(tmp_path):
    rec = TraceRecorder()
    rec.name_thread("test-main")
    with rec.span("outer", cat="t", args={"a": 1}) as sp:
        sp.event("marker", {"k": "v"})
        with rec.span("inner", cat="t"):
            pass
        sp.add_args(b=2)
    rec.async_begin("request", 7, args={"deadline_ms": 5})
    rec.async_end("request", 7, args={"outcome": "served"})
    trace = rec.to_chrome_trace()
    ct = _load_check_trace()
    assert ct.validate(trace, require=["outer", "inner", "marker",
                                       "request"]) == []
    byname = {e["name"]: e for e in trace["traceEvents"]}
    outer, inner = byname["outer"], byname["inner"]
    assert outer["ph"] == inner["ph"] == "X"
    assert outer["args"] == {"a": 1, "b": 2}
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 0.5
    names = {e["name"] for e in trace["traceEvents"] if e["ph"] == "M"}
    assert names == {"process_name", "thread_name"}
    # write() output parses back to the same thing
    p = tmp_path / "t.json"
    rec.write(str(p))
    assert ct.validate(json.loads(p.read_text())) == []


def test_trace_ring_buffer_bounds_memory():
    rec = TraceRecorder(max_events=100)
    for i in range(500):
        rec.instant(f"e{i}")
    assert rec.n_events == 100
    assert rec.dropped_events == 400
    kept = [e["name"] for e in rec.events()]
    assert kept[0] == "e400" and kept[-1] == "e499"  # oldest dropped first


def test_check_trace_rejects_malformed_traces():
    ct = _load_check_trace()
    # partial overlap = malformed nesting
    bad = {"traceEvents": [
        {"name": "a", "ph": "X", "pid": 1, "tid": 1, "ts": 0, "dur": 10},
        {"name": "b", "ph": "X", "pid": 1, "tid": 1, "ts": 5, "dur": 10},
    ]}
    assert any("partially overlaps" in e for e in ct.validate(bad))
    # async begin without end
    bad = {"traceEvents": [
        {"name": "r", "ph": "b", "cat": "q", "id": 1, "pid": 1, "tid": 1,
         "ts": 0},
    ]}
    assert any("begin without end" in e for e in ct.validate(bad))
    assert ct.validate({"nope": []})  # wrong top level


def test_null_tracer_is_shared_noop():
    assert NULL_TRACER.enabled is False
    s1 = NULL_TRACER.span("x")
    s2 = NULL_TRACER.span("y")
    assert s1 is s2  # shared singleton, zero allocation
    with s1 as sp:
        sp.add_args(a=1)
        sp.event("e")
    NULL_TRACER.instant("i")
    NULL_TRACER.async_begin("r", 1)
    assert NULL_TRACER.n_events == 0


def test_device_annotation_smoke(tmp_path):
    # the profiler sink: with no profiler session running, a profile-only
    # span is the shared null span; under a session it records
    obs = Observability(tracing=False, metrics=False, profile=True)
    assert obs.span("x") is obs.span("y")
    with obs.span("engine.dispatch", cat="engine", bucket=8) as sp:
        sp.add_args(pad=1)
    events = _profiled(tmp_path, lambda: _nested_spans(obs))
    assert [e.name for e in events] == ["outer.span", "inner.span"]


# -- the one span API: Observability.span -------------------------------------

def _nested_spans(obs):
    with obs.tags(batch=5):
        with obs.span("outer.span", cat="t", size=3) as sp:
            with obs.span("inner.span", cat="t"):
                pass
            sp.add_args(bytes=64)


def _profiled(tmp_path, fn):
    """Run ``fn`` under a jax.profiler session on this host; return the
    ``*.span`` events of the captured host plane, in start order."""
    import jax
    from jax.profiler import ProfileData
    jax.profiler.start_trace(str(tmp_path))
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.rglob("*.xplane.pb")
    pd = ProfileData.from_file(str(path))
    events = [e for plane in pd.planes if plane.name.startswith("/host:")
              for line in plane.lines for e in line.events
              if e.name.endswith(".span")]
    return sorted(events, key=lambda e: e.start_ns)


@pytest.mark.parametrize("tracing,profile", [(False, False), (True, False),
                                             (False, True), (True, True)])
def test_span_sinks(tmp_path, tracing, profile):
    obs = Observability(tracing=tracing, metrics=False, profile=profile)
    if not (tracing or profile):
        # neither sink: the shared null span, no allocation, nothing kept
        assert obs.span("a", x=1) is obs.span("b")
        assert obs.tags(batch=1) is obs.tags(batch=2)
    events = _profiled(tmp_path, lambda: _nested_spans(obs))
    recorded = [e for e in obs.tracer.events() if e["ph"] == "X"]
    # the recorder sink: what tracing records, and only then
    assert [e["name"] for e in recorded] == (
        ["inner.span", "outer.span"] if tracing else [])
    # the profiler sink: what a jax.profiler session sees, and only then
    assert [e.name for e in events] == (
        ["outer.span", "inner.span"] if profile else [])
    if profile:
        outer, inner = events
        assert dict(outer.stats) == {"batch": 5, "size": 3, "bytes": 64}
        assert dict(inner.stats) == {"batch": 5}
        assert outer.start_ns <= inner.start_ns
        assert inner.end_ns <= outer.end_ns


def test_span_args_tags_and_nesting():
    obs = Observability(tracing=True, metrics=False)
    _nested_spans(obs)
    with obs.span("after.span", cat="t"):
        pass
    ev = {e["name"]: e for e in obs.tracer.events()}
    outer, inner = ev["outer.span"], ev["inner.span"]
    assert outer["args"] == {"batch": 5, "size": 3, "bytes": 64}
    assert outer["cat"] == "t"
    assert inner["args"] == {"batch": 5}
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 0.5
    # tags end with their block
    assert ev["after.span"]["args"] == {}
    ct = _load_check_trace()
    assert ct.validate(obs.tracer.to_chrome_trace(),
                       require=["outer.span", "inner.span"]) == []


# -- engine + coalescer integration ------------------------------------------

def test_engine_search_records_spans_metrics_and_telemetry(ds, index):
    obs = Observability(tracing=True, metrics=True)
    engine = index.serve(PARAMS, bucket_sizes=BUCKETS, obs=obs)
    res = engine.search(ds.queries[:3], gt_ids=ds.gt_ids[:3])
    assert res.ids.shape[0] == 3

    names = [e["name"] for e in obs.tracer.events()]
    assert names == ["engine.pad", "engine.dispatch", "engine.sync",
                     "engine.readback", "engine.postprocess",
                     "engine.search"]   # children close first
    ev = {e["name"]: e for e in obs.tracer.events()}
    assert ev["engine.pad"]["args"] == {"bucket": 4, "pad": 1}
    # one chunk: its ids, dists and SearchStats in ONE device->host read
    assert ev["engine.readback"]["args"]["arrays"] == 1
    assert ev["engine.readback"]["args"]["bytes"] == 4 * 4 * (2 * 10 + 8)
    ct = _load_check_trace()
    assert ct.validate(obs.tracer.to_chrome_trace(),
                       require=["engine.search", "engine.dispatch"]) == []

    d = obs.registry.to_dict()
    # convergence telemetry: one per-lane histogram per SearchStats leaf
    for field in ("steps", "crit_rounds", "dist_comps", "uniq_comps",
                  "batch_dup_comps"):
        series = d[f"ann_{field}"]["series"]
        assert series[0]["labels"] == {"backend": "ref", "bucket": "4"}
        assert series[0]["histogram"]["count"] == 3  # one obs per lane
    assert d["serve_request_latency_ms"]["series"][0]["histogram"][
        "count"] == 1


def test_engine_stats_schema_bounded_memory_and_key_order(ds, index):
    engine = index.serve(PARAMS, bucket_sizes=BUCKETS)
    for i in range(4):
        engine.search(ds.queries[:1 + i % 2], gt_ids=ds.gt_ids[:1 + i % 2])
    m = engine.stats()
    keys = list(m)
    head = ["queries_served", "requests_served", "padded_queries",
            "jit_cache_size", "cache_hits", "cache_misses",
            "dist_comps_total", "uniq_comps_total", "batch_dup_comps_total",
            "batch_dup_ratio", "lane_steps_total", "loop_iters_total"]
    assert keys[:len(head)] == head
    lat = ["latency_mean_ms", "latency_p50_ms", "latency_p90_ms",
           "latency_p95_ms", "latency_p99_ms", "latency_max_ms"]
    assert keys[len(head):len(head) + len(lat)] == lat
    # per-bucket blocks ascend, each led by its chunks counter
    bucket_keys = [k for k in keys if k.startswith("bucket")]
    served = sorted(int(k[len("bucket"):-len("_chunks")])
                    for k in bucket_keys if k.endswith("_chunks"))
    assert served == [1, 2]
    assert bucket_keys[0] == "bucket1_chunks"
    assert bucket_keys[7] == "bucket2_chunks"
    assert keys[-1] == "recall_at_k"
    assert m["latency_p99_ms"] <= m["latency_max_ms"]
    # metrics() alias and the live-sketch accessor agree
    assert engine.metrics() == engine.stats()
    hists = engine.latency_histograms()
    assert set(hists) == {"request", "bucket1", "bucket2"}
    assert hists["request"].count == 4
    # bounded memory: the sketch, not a sample list, backs the stats
    assert hists["request"].n_buckets <= hists["request"].max_buckets


def test_disabled_obs_writes_nothing(ds, index):
    # default = NULL_OBS: no trace events, no registry series, ever
    engine = index.serve(PARAMS, bucket_sizes=BUCKETS)
    assert engine.obs is NULL_OBS
    engine.search(ds.queries[:2])
    assert NULL_OBS.tracer.n_events == 0
    assert NULL_OBS.registry.to_dict() == {}
    # explicit all-off bundle on the engine's own registry: also untouched
    obs = Observability(tracing=False, metrics=False)
    engine2 = index.serve(PARAMS, bucket_sizes=BUCKETS, obs=obs)
    engine2.search(ds.queries[:2])
    assert obs.tracer.n_events == 0
    assert obs.registry.to_dict() == {}
    assert obs.enabled is False


def test_coalesced_span_tree_under_manual_flush(ds, index):
    obs = Observability(tracing=True, metrics=True)
    srv = index.serve_async(PARAMS, start=False, bucket_sizes=BUCKETS,
                            obs=obs)
    futs = [srv.submit(q) for q in ds.queries[:3]]
    assert srv.flush() == 3
    ids = np.stack([f.result().ids for f in futs])
    assert ids.shape == (3, 10)
    srv.close()

    trace = obs.tracer.to_chrome_trace()
    ct = _load_check_trace()
    assert ct.validate(trace, require=[
        "coalescer.submit", "coalescer.form", "engine.search",
        "engine.dispatch", "coalescer.resolve", "request"]) == []
    ev = trace["traceEvents"]
    # one coalesced batch: form, then engine.search containing the
    # dispatch, then resolve, all tagged with the batch's number
    form = next(e for e in ev if e["name"] == "coalescer.form")
    srch = next(e for e in ev if e["name"] == "engine.search")
    disp = next(e for e in ev if e["name"] == "engine.dispatch")
    res = next(e for e in ev if e["name"] == "coalescer.resolve")
    assert form["ts"] + form["dur"] <= srch["ts"] + 0.5
    assert srch["ts"] <= disp["ts"]
    assert disp["ts"] + disp["dur"] <= srch["ts"] + srch["dur"] + 0.5
    assert srch["ts"] + srch["dur"] <= res["ts"] + 0.5
    assert form["args"]["size"] == 3
    assert form["args"]["batch"] == disp["args"]["batch"] \
        == res["args"]["batch"] == 0
    assert sorted(form["args"]["edf_order"]) == [0, 1, 2]
    # every submitted request has a paired b/e lifeline ending "served"
    begins = [e for e in ev if e["ph"] == "b" and e["name"] == "request"]
    ends = [e for e in ev if e["ph"] == "e" and e["name"] == "request"]
    assert len(begins) == len(ends) == 3
    assert all(e["args"]["outcome"] == "served" for e in ends)
    # registry: served outcomes + queue-wait sketch
    d = obs.registry.to_dict()
    served = [s for s in d["coalescer_requests_total"]["series"]
              if s["labels"] == {"outcome": "served"}]
    assert served[0]["value"] == 3.0
    assert d["coalescer_queue_wait_ms"]["series"][0]["histogram"][
        "count"] == 3
    # coalescer stats stay sketch-backed with the same key schema
    st = srv.stats()
    for key in ("batch_size_mean", "queue_wait_p50_ms", "queue_wait_p99_ms"):
        assert key in st


def test_deadline_shed_emits_span_event_and_counter(ds, index):
    obs = Observability(tracing=True, metrics=True)
    srv = index.serve_async(PARAMS, start=False, bucket_sizes=BUCKETS,
                            obs=obs)
    fut = srv.submit(ds.queries[0], deadline_ms=0.001)
    import time as _t
    _t.sleep(0.01)
    srv.flush()
    with pytest.raises(Exception):
        fut.result(timeout=5)
    srv.close()
    sheds = [e for e in obs.tracer.events() if e["name"] == "deadline_shed"]
    assert sheds and "late_ms" in sheds[0]["args"]
    ends = [e for e in obs.tracer.events()
            if e["ph"] == "e" and e["args"].get("outcome") == "shed"]
    assert len(ends) == 1
    d = obs.registry.to_dict()
    shed = [s for s in d["coalescer_requests_total"]["series"]
            if s["labels"] == {"outcome": "shed"}]
    assert shed[0]["value"] == 1.0


# -- spans of one batch, and the engine's lane counters ------------------------

def test_one_batch_spans_in_order_under_pump(ds, index):
    import threading
    obs = Observability(tracing=True, metrics=False)
    srv = index.serve_async(PARAMS, start=False, bucket_sizes=BUCKETS,
                            obs=obs, max_batch=8, max_wait_ms=5.0)
    # the dispatcher's wait, driven by hand: idle until a request arrives,
    # then fill until max_wait_ms has passed
    late = threading.Timer(0.02, lambda: srv.submit(ds.queries[0]))
    late.start()
    assert srv.wait_due()
    late.join()
    assert srv.pump() == 1
    srv.close()
    assert srv.wait_due() is False
    ev = sorted((e for e in obs.tracer.events()
                 if e["ph"] == "X" and e["name"] != "coalescer.submit"),
                key=lambda e: e["ts"])
    # the wait after close: an idle span before the next batch, number 1
    assert ev[-1]["name"] == "coalescer.idle"
    assert ev.pop()["args"]["batch"] == 1
    assert [e["name"] for e in ev] == [
        "coalescer.idle", "coalescer.fill", "coalescer.batch",
        "coalescer.form", "engine.search", "engine.pad", "engine.dispatch",
        "engine.sync", "engine.readback", "engine.postprocess",
        "coalescer.resolve"]
    assert {e["args"]["batch"] for e in ev} == {0}
    assert ev[1]["dur"] >= 0.5 * 5e3   # the fill span waits out max_wait
    # the batch span holds the rest of the batch's spans
    assert all(ev[2]["ts"] <= e["ts"] and e["ts"] + e["dur"]
               <= ev[2]["ts"] + ev[2]["dur"] + 0.5 for e in ev[3:])
    ct = _load_check_trace()
    assert ct.validate(obs.tracer.to_chrome_trace(),
                       require=["coalescer.submit"]) == []


def test_engine_lane_counters_are_sums_of_steps(ds, index):
    obs = Observability(tracing=True, metrics=False)
    engine = index.serve(PARAMS, bucket_sizes=BUCKETS, obs=obs)
    top = BUCKETS[-1]
    steps = []
    for size in (3, 8, 11):     # 11 > the top bucket: two chunks, 8 + 3
        res = engine.search(ds.queries[:size])
        assert isinstance(res.stats.steps, np.ndarray)
        steps.append(res.stats.steps)
    st = engine.stats()
    assert st["lane_steps_total"] == sum(int(s.sum()) for s in steps)
    chunks = [c for s in steps for c in np.split(s, range(top, len(s), top))]
    assert len(chunks) == 4
    assert st["loop_iters_total"] == sum(int(c.max()) for c in chunks)
    assert 0 < st["lane_steps_total"] <= top * st["loop_iters_total"]
    # the two-chunk request still reads each chunk back once
    reads = [e["args"]["arrays"] for e in obs.tracer.events()
             if e["name"] == "engine.readback"]
    assert reads == [1, 1, 2]
