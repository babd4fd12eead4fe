#!/usr/bin/env python3
"""The control of the check: the plain reference put in the program's place,
computed in the precision below the configuration's float32.

    python3 bench/control.py --workload <cell> --seeds 1 2 3 \
        --queries Q [--precision high|bf16]

For each seed it answers what a run of the cell would be asked (the first Q
requests of the seed's query plan) with ``reference.knn`` at
``--precision``, and judges those answers with the run's own comparison
(``bench.check``).  Give Q as many as one of the cell's runs answers.  The
control must come out not correct; its readings set the upper end of each
limit.  Benchmark runs never run it.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]


def control_values(cfg: dict, traffic: dict, seed: int, precision: str,
                   n_queries: int) -> dict:
    from bench import check, corpus, reference
    data = corpus.config_corpus(cfg)
    plan = corpus.QueryPlan(traffic.get("queries"), seed)
    q = corpus.Stream(data, seed, corpus.QUERIES).rows(
        plan.indices(range(n_queries)))
    k, metric = cfg["k"], cfg["metric"]
    ids, dists = reference.knn(data.base, q, k, precision, metric)
    r = check.readings(data.base, q, ids, dists, k, metric)
    return {"unanswered": 0, "bad_rows": r["bad_rows"],
            "dist_gap": r["dist_gap"],
            "recall_at_10": float(np.mean(r["recall_per_query"]))}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--precision", default="high", choices=("high", "bf16"))
    ap.add_argument("--queries", type=int, required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import check, harness
    cell = harness.resolve_cell(ROOT, args.workload)
    harness.use_compile_cache(ROOT)
    import jax
    dev = jax.devices()[0]
    for seed in args.seeds:
        values = control_values(cell.config, cell.traffic, seed,
                                args.precision, args.queries)
        correct, checks = check.verdict(values, cell.config["recall_target"])
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "precision": args.precision,
                          "platform": dev.platform, "correct": correct,
                          "checks": checks}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
