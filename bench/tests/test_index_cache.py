import json
import shutil
from pathlib import Path

import numpy as np
import pytest

from bench import corpus, index_cache

ROOT = Path(__file__).resolve().parents[2]


@pytest.fixture()
def fake_root(tmp_path):
    (tmp_path / "src" / "repro" / "core").mkdir(parents=True)
    (tmp_path / "src" / "repro" / "core" / "a.py").write_text("x = 1\n")
    return tmp_path


def config():
    cfg = json.loads((ROOT / "bench/configs/sift128.json").read_text())
    return dict(cfg, name="test-sift128", n=300)


def test_key_follows_every_program_source_the_config_and_corpus(fake_root):
    cfg = config()
    base = corpus.config_corpus(cfg).base

    def key(c=cfg, b=base):
        return index_cache.cache_key(fake_root, c, b)
    k0 = key()
    assert key() == k0
    (fake_root / "src/repro/core/a.py").write_text("x = 2\n")
    k1 = key()
    assert k1 != k0
    (fake_root / "src/repro/core/b.py").write_text("")
    k2 = key()
    assert k2 != k1
    # byte code is not source
    (fake_root / "src/repro/core/__pycache__").mkdir()
    (fake_root / "src/repro/core/__pycache__/a.cpython.pyc").write_text("z")
    assert key() == k2
    assert key(dict(cfg, n=301)) != k2
    moved = base.copy()
    moved[7, 3] = np.nextafter(moved[7, 3], np.float32(np.inf))
    assert key(b=moved) != k2
    # search parameters do not decide the index
    s = dict(cfg["search"], queue_len=64)
    assert key(dict(cfg, search=s)) == k2


def test_built_once_then_loaded_and_checked(tmp_path):
    root = tmp_path / "co"
    shutil.copytree(ROOT / "src" / "repro", root / "src" / "repro",
                    ignore=shutil.ignore_patterns("__pycache__"))
    cfg = config()
    base = corpus.config_corpus(cfg).base
    logs = []
    log = lambda phase, **kw: logs.append(kw)
    idx, info = index_cache.load_or_build(root, cfg, base, log)
    assert info["index"] == "built"
    idx2, info2 = index_cache.load_or_build(root, cfg, base, log)
    assert info2["index"] == "loaded"
    assert np.array_equal(np.asarray(idx2.graph.nbrs),
                          np.asarray(idx.graph.nbrs))
    # a cached file whose vectors are not the corpus is not served
    (path,) = (root / index_cache.CACHE).glob("*.npz")
    other, _ = index_cache.load_or_build(root, cfg, base + 1.0, log)
    other.save(str(path))
    _, info3 = index_cache.load_or_build(root, cfg, base, log)
    assert info3["index"] == "built"
    assert any("stale" in kw for kw in logs)
    assert len(list((root / index_cache.CACHE).glob("*.npz"))) == 1
