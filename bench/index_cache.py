"""The built index of a configuration, cached in the checkout.

Building the index takes minutes (the NSG build prunes on the host), a run
tens of seconds, so the first run in a checkout builds it with
``AnnIndex.build`` and saves it with ``AnnIndex.save``; every later run loads
it.  The key is a hash of the configuration's entries that decide the index
(``INDEX_KEYS``: sizes, corpus, index spec; not the search parameters), of
every source file under ``src/repro`` and of the corpus's bytes, so a change
to any of them builds anew.  A loaded index is used only if its stored
vectors equal the corpus regenerated from the configuration (for cosine, the
corpus's rows normalized, within float32 rounding).
"""
from __future__ import annotations

import hashlib
import json
import os
import time
from pathlib import Path

import numpy as np

CACHE = Path("bench") / ".cache" / "index"
# the entries of a configuration that decide the built index
INDEX_KEYS = ("name", "n", "dim", "metric", "data", "index")


def source_files(root: Path) -> list[Path]:
    src = root / "src" / "repro"
    return sorted(p for p in src.rglob("*")
                  if p.is_file() and "__pycache__" not in p.parts
                  and p.suffix != ".pyc")


def cache_key(root: Path, cfg: dict, corpus: np.ndarray) -> str:
    h = hashlib.sha256()
    built_from = {key: cfg[key] for key in INDEX_KEYS}
    h.update(json.dumps(built_from, sort_keys=True).encode())
    h.update(np.ascontiguousarray(corpus, np.float32).tobytes())
    for p in source_files(root):
        h.update(str(p.relative_to(root)).encode())
        h.update(b"\0")
        h.update(p.read_bytes())
        h.update(b"\0")
    return h.hexdigest()


def index_spec(cfg: dict):
    from repro.ann import IndexSpec
    return IndexSpec(**cfg["index"])


def stored_as_given(stored: np.ndarray, corpus: np.ndarray,
                    metric: str) -> bool:
    """The index holds this corpus (cosine indices hold it normalized)."""
    if metric != "cosine":
        return np.array_equal(stored, corpus)
    unit = corpus / np.maximum(np.linalg.norm(corpus, axis=1, keepdims=True),
                               1e-30)
    return stored.shape == unit.shape and np.allclose(stored, unit, rtol=0,
                                                      atol=1e-6)


def load_or_build(root: Path, cfg: dict, corpus: np.ndarray,
                  log) -> tuple[object, dict]:
    """(AnnIndex, info) where info says whether it was built or loaded."""
    from repro.ann import AnnIndex
    key = cache_key(root, cfg, corpus)
    cache_dir = root / CACHE
    path = cache_dir / f"{cfg['name']}-{key[:20]}.npz"
    t0 = time.perf_counter()
    if path.exists():
        index = AnnIndex.load(str(path))
        same = stored_as_given(np.asarray(index.graph.vectors), corpus,
                               cfg["index"].get("metric", "l2"))
        if same:
            info = {"index": "loaded", "seconds": time.perf_counter() - t0}
            log("index", path=path.relative_to(root), **info)
            return index, info
        log("index", path=path.relative_to(root),
            stale="stored vectors differ from the corpus; building anew")
    index = AnnIndex.build(corpus, index_spec(cfg))
    build_s = time.perf_counter() - t0
    cache_dir.mkdir(parents=True, exist_ok=True)
    for old in cache_dir.glob(f"{cfg['name']}-*.npz"):
        if old.stem.rsplit("-", 1)[0] == cfg["name"]:
            old.unlink()
    tmp = cache_dir / f"{cfg['name']}-{key[:20]}.{os.getpid()}.tmp.npz"
    index.save(str(tmp))
    os.replace(tmp, path)
    info = {"index": "built", "seconds": build_s}
    log("index", path=path.relative_to(root), **info)
    return index, info
