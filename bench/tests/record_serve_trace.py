#!/usr/bin/env python3
"""Record the small serving trace that ``test_spans.py`` reads.

    python3 bench/tests/record_serve_trace.py      # on a machine with a TPU

A 600-point index served through ``serve_async`` with ``profile=True``:
inside the traced window, 8 single requests one after another, then 24
submitted at once (batches of up to 8), so the trace holds every program
span of the serving path and the search executables of buckets 1 and 8.
Writes ``bench/tests/data/serve.xplane.pb`` and, beside it,
``serve_hlo.json.gz``: per bucket, the compiled HLO text of the search
executable, compiled in the same process as the one that ran, with JAX's
persistent cache off (the text ``bench.spans.scope_map`` reads).
"""
import glob
import gzip
import json
import os
import shutil
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
DATA = os.path.join(ROOT, "bench", "tests", "data")
BUCKETS = (1, 2, 4, 8)


def main() -> int:
    import jax
    import jax.numpy as jnp
    from bench import devtrace, spans
    from repro.ann import AnnIndex, IndexSpec, SearchParams
    from repro.data import make_vector_dataset
    from repro.obs import Observability
    if jax.devices()[0].platform != "tpu":
        print("record_serve_trace: needs a TPU", file=sys.stderr)
        return 1
    # compile afresh, before any compile: a persistent cache would hand
    # back a search compiled before its scopes (its key ignores metadata)
    jax.config.update("jax_enable_compilation_cache", False)
    ds = make_vector_dataset("deep", n=600, n_queries=32, k=10, dim=32,
                             n_clusters=6, seed=0)
    index = AnnIndex.build(ds, IndexSpec(degree=12, passes=1))
    params = SearchParams(k=10, queue_len=32, m_max=4, num_walkers=4,
                          max_steps=64, local_steps=4)
    obs = Observability(tracing=False, metrics=False, profile=True)
    srv = index.serve_async(params, obs=obs, bucket_sizes=BUCKETS,
                            max_wait_ms=2.0)
    srv.engine.warmup()
    srv.submit(ds.queries[0]).result()
    d = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(d, profiler_options=opts)
    with jax.profiler.TraceAnnotation(devtrace.WINDOW):
        for q in ds.queries[:8]:
            srv.submit(q).result()
        for f in [srv.submit(q) for q in ds.queries[8:32]]:
            f.result()
    jax.profiler.stop_trace()
    srv.close()
    src = glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True)[0]
    dst = os.path.join(DATA, "serve.xplane.pb")
    shutil.copy(src, dst)
    shutil.rmtree(d)
    search = index.searcher(params)
    hlo = {str(b): search.lower(
        jnp.zeros((b, ds.queries.shape[1]), jnp.float32)).compile().as_text()
        for b in BUCKETS}
    with gzip.open(os.path.join(DATA, "serve_hlo.json.gz"), "wt") as f:
        json.dump(hlo, f)
    print(f"wrote {dst} ({os.path.getsize(dst)} bytes) and "
          "serve_hlo.json.gz")
    return 0


if __name__ == "__main__":
    sys.exit(main())
