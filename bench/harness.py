"""One run of one cell: set-up, the measured window, the check, the result.

Everything here is driven by data.  A cell of ``BENCHMARK.json`` names a
configuration (``bench/configs/<file>``, whose corpus generator is
``bench/generators/<generator>.py``) and a traffic mix
(``bench/traffic/<traffic>.json``, whose ``kind`` is the module
``bench/traffic/<kind>.py`` that drives the window); a per-layer metric
``<name>`` is read by ``bench/metrics/<name>.py``, or by
``bench/metrics/<stem>.py`` for a name ``<stem>.<cell kind>``.
"""
from __future__ import annotations

import gc
import json
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import NamedTuple

import numpy as np

from bench import byname, check, corpus, devtrace, index_cache, reference

COMPILE_CACHE = Path("bench") / ".cache" / "jax"
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
TRACE_MAX_S = 4.0


class Cell(NamedTuple):
    name: str
    config: dict
    traffic: dict
    chips: int
    end_to_end: list      # metric entries of BENCHMARK.json for this cell
    per_layer: list


def log(phase: str, **fields) -> None:
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in fields.items()),
          file=sys.stderr, flush=True)


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def resolve_cell(root: Path, name: str) -> Cell:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; one of {sorted(cells)}")
    w = cells[name]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    cfg = json.loads((root / cfg_entry["file"]).read_text())
    reference.check_metric(cfg["metric"])
    if cfg["index"].get("metric", "l2") != cfg["metric"]:
        raise SystemExit(f"{cfg_entry['file']}: index metric "
                         f"{cfg['index'].get('metric')!r} is not the "
                         f"configuration's {cfg['metric']!r}")
    return Cell(
        name=name, config=cfg,
        traffic=json.loads(
            (root / "bench" / "traffic" / f"{w['traffic']}.json").read_text()),
        chips=int(w["chips"]),
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, name)])


def use_compile_cache(root: Path) -> str:
    """JAX's persistent compilation cache at a fixed path in the checkout,
    for every executable however fast it compiles."""
    path = str(root / COMPILE_CACHE)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    import jax
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    # no eviction: the directory belongs to this checkout, and eviction's
    # bookkeeping files fail when a size limit comes from the environment
    jax.config.update("jax_compilation_cache_max_size", -1)
    return path


class CompileCounter:
    """Executables JAX compiles or fetches from its cache in this process."""

    def __init__(self):
        import jax.monitoring
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, secs: float, **_) -> None:
        if event == BACKEND_COMPILE:
            self.n += 1


def load_peaks(root: Path, kind: str) -> dict:
    peaks = json.loads((root / "bench" / "peaks.json").read_text())
    if kind not in peaks:
        raise SystemExit(f"no peaks for device kind {kind!r} in "
                         f"bench/peaks.json; known: {sorted(peaks)}")
    return peaks[kind]


def reader(name: str):
    """The ``read(view)`` function of a per-layer metric."""
    for stem in (name, name.split(".")[0]):
        if byname.path("metrics", stem).is_file():
            return byname.load("metrics", stem).read
    raise SystemExit(f"no reader bench/metrics/{name}.py")


class Tracer:
    """Starts and stops the profiler around the traced part of the window."""

    def __init__(self, enabled: bool):
        self.enabled = enabled
        self.dir = None
        self.annotation = None
        self.host_span = (np.nan, np.nan)

    def start(self):
        import jax
        self.dir = tempfile.mkdtemp(prefix="bench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.annotation = jax.profiler.TraceAnnotation(devtrace.WINDOW)
        self.annotation.__enter__()
        self.host_span = (time.perf_counter(), np.nan)

    def stop(self):
        import jax
        if self.annotation is None:
            return
        self.host_span = (self.host_span[0], time.perf_counter())
        self.annotation.__exit__(None, None, None)
        self.annotation = None
        jax.profiler.stop_trace()

    def hooks(self, seconds: float, whole: bool = False) -> dict:
        """Window offsets at which to start and stop: the last
        ``min(TRACE_MAX_S, seconds / 2)`` seconds of the window, or all of
        it where device work is sparse."""
        if not self.enabled:
            return {}
        span = seconds if whole else min(TRACE_MAX_S, seconds / 2)
        return {seconds - span: self.start, seconds: self.stop}

    def reduce(self) -> dict:
        try:
            t0 = time.perf_counter()
            reduced = devtrace.reduce(
                devtrace.load(devtrace.find_xplane(self.dir)))
            log("trace", read_s=time.perf_counter() - t0,
                busy_s=reduced["busy_s"], window_s=reduced["window_s"],
                modules=json.dumps(reduced["module_runs"]))
            return reduced
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def search_params(cfg: dict):
    from repro.ann import SearchParams
    return SearchParams(**cfg["search"])


class BatchLog:
    """Per batch the engine searched: (start, end, lanes, sum of the lanes'
    first-toucher distance computations ``SearchStats.uniq_comps``, sum of
    their global steps ``SearchStats.steps``), recorded around the engine's
    ``search`` as the coalescer calls it.  The engine has read
    ``uniq_comps`` to the host already; ``steps`` is one more small read."""

    def __init__(self, engine):
        self.rows: list[tuple[float, float, int, int, int]] = []
        inner = engine.search

        def search(queries, *a, **kw):
            t0 = time.perf_counter()
            res = inner(queries, *a, **kw)
            self.rows.append((t0, time.perf_counter(), len(res.ids),
                              int(np.sum(np.asarray(res.stats.uniq_comps))),
                              int(np.sum(np.asarray(res.stats.steps)))))
            return res
        engine.search = search


class Run(NamedTuple):
    """What a traffic kind's ``window`` gets."""
    cell: Cell
    index: object           # the program's AnnIndex, loaded or built
    data: corpus.Corpus
    seed: int
    seconds: float
    tracer: Tracer
    t_setup: float          # when set-up began, moved on by an index build
    counter: CompileCounter
    device: object
    plant: object           # tests only: breaks the timed path underneath


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------

def run_cell(root: Path, cell: Cell, seed: int, seconds: float, trace: bool,
             t_start: float, device, n_devices: int, plant=None) -> dict:
    """Everything after the look for a chip; returns the result line."""
    counter = CompileCounter()
    peaks = load_peaks(root, device.device_kind)
    cfg = cell.config
    kind = byname.load("traffic", cell.traffic["kind"])
    data = corpus.config_corpus(cfg)
    index, info = index_cache.load_or_build(root, cfg, data.base, log)
    # a checkout builds the index once, in its first run: that build is
    # reported on its own line, and kept out of setup_s
    built_s = info["seconds"] if info["index"] == "built" else 0.0
    tracer = Tracer(trace)
    out = kind.window(Run(cell, index, data, seed, seconds, tracer,
                          t_start + built_s, counter, device, plant))
    del index
    gc.collect()
    log("setup", index=info["index"], index_s=info["seconds"],
        setup_s=out["e2e"]["setup_s"],
        setup_with_build_s=out["e2e"]["setup_s"] + built_s)

    # the check, once the window has closed and the program's state is freed
    t0 = time.perf_counter()
    recalls, gap, bad = [], 0.0, 0
    for ans in out["answers"]:
        if len(ans["queries"]) == 0:
            continue
        r = check.readings(ans["corpus"], ans["queries"], ans["ids"],
                           ans["dists"], cfg["k"], cfg["metric"])
        recalls.append(r["recall_per_query"])
        gap, bad = max(gap, r["dist_gap"]), bad + r["bad_rows"]
    recall = float(np.mean(np.concatenate(recalls))) if recalls else 0.0
    values = {"unanswered": out["failed"], "bad_rows": bad,
              "dist_gap": gap, "recall_at_10": recall}
    extra = out.get("checks", {})
    values.update({name: v for name, (v, _, _) in extra.items()})
    correct, checks = check.verdict(
        values, cfg["recall_target"],
        {name: (op, limit) for name, (_, op, limit) in extra.items()})
    log("check", seconds=time.perf_counter() - t0, correct=correct)

    result = {"correct": correct, "attempted": out["attempted"],
              "failed": out["failed"]}
    dev = {"platform": device.platform, "kind": device.device_kind,
           "count": n_devices, "memory_peak_bytes": out["peak"]}
    metrics = {}
    if trace:
        reduced = tracer.reduce() if tracer.dir else None
        view = {"cell": cell, "layer": out["layer"], "trace": reduced,
                "batches": out.get("batches"), "peaks": peaks,
                "tracer_span": tracer.host_span}
        for m in cell.per_layer:
            v = reader(m["name"])(view)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        if reduced is not None:
            dev.update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
            result["breakdown"] = reduced["breakdown"]
    else:
        e2e = dict(out["e2e"], recall_at_10=recall)
        for m in cell.end_to_end:
            v = e2e.get(m["name"])
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    result["metrics"] = metrics
    result["device"] = dev
    for name, c in checks.items():
        print(f"check {name} {c['value']!r} {c['op']} {c['limit']!r}",
              file=sys.stderr)
    result["checks"] = checks
    # the keys above are the result's; "checks" stays last
    return {k: result[k] for k in ("correct", "attempted", "failed",
                                   "metrics", "device",
                                   *(["breakdown"] if "breakdown" in result
                                     else []), "checks")}
