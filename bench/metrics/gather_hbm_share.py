"""Distance layer (``kernels/*``, ``quant/kernels.py``): the least HBM time
the served searches need, as a share of their device time (%).

Least bytes of one dispatched batch: min(sum of the batch's first-toucher
distance computations ``SearchStats.uniq_comps``, N) rows of the table the
traversal reads, plus, with a re-rank, ``rerank_k`` float32 rows per query.
A row is ``dim * 4`` bytes for float32, ``dim * 2`` for bfloat16 and
``dim + 4`` for int8 codes with their per-row float32 scale.
``uniq_comps`` counts the rows a batch must gather whatever backend gathers
them, so the share reads the same work whatever implements it.  Least
time = least bytes / the chip's HBM bandwidth (``bench/peaks.json``).

Device time: the summed device duration of the served search executables in
the trace, the jitted searcher of ``repro.ann.index.AnnIndex.searcher``,
whose module is ``jit_jitted``.  The batches counted are those the engine
dispatched inside the traced window; their mean least time is multiplied by
the number of search-executable runs the trace holds.

Caveat: at N = 24,000 the whole float32 table (12.3 MB) is smaller than one
batch's gathers, so min(., N) caps nearly every batch at the table, and a
search that kept the table on chip across steps would need fewer bytes than
this counts.
"""
SEARCH_MODULES = ("jit_jitted",)


def read(run):
    tr, batches = run["trace"], run["batches"]
    if tr is None or batches is None:
        return None
    lo, hi = run["tracer_span"]
    rows = [r for r in batches if r[0] >= lo and r[1] <= hi]
    runs = sum(tr["module_runs"].get(m, 0) for m in SEARCH_MODULES)
    device_s = sum(tr["module_s"].get(m, 0.0) for m in SEARCH_MODULES)
    if not rows or not runs or device_s <= 0:
        return None
    cfg = run["cell"].config
    n, dim = cfg["n"], cfg["dim"]
    quant = cfg["index"].get("quant", "none")
    dtype = quant if isinstance(quant, str) else quant["dtype"]
    row = {"none": dim * 4, "bf16": dim * 2, "int8": dim + 4}[dtype]
    rerank = cfg["search"].get("rerank_k", 0) * dim * 4
    least = [min(uniq, n) * row + size * rerank
             for _, _, size, uniq, _ in rows]
    least_s = sum(least) / len(least) * runs \
        / run["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / device_s
