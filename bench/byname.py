"""Pieces of the benchmark found by name: ``bench/<kind>/<name>.py``.

A configuration names its corpus generator (``bench/generators/``), a traffic
mix its kind (``bench/traffic/``), and ``BENCHMARK.json`` each per-layer
metric (``bench/metrics/``).  A later cell adds such a file; none already
there is edited.
"""
from __future__ import annotations

import importlib.util
import re
from pathlib import Path

HERE = Path(__file__).resolve().parent
_NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}")


def path(kind: str, name: str) -> Path:
    return HERE / kind / f"{name}.py"


def load(kind: str, name: str):
    """The module ``bench/<kind>/<name>.py``; exits if there is none."""
    p = path(kind, name)
    if not _NAME.fullmatch(name) or not p.is_file():
        raise SystemExit(f"no bench/{kind}/{name}.py")
    mod_name = "bench_" + re.sub(r"\W", "_", f"{kind}_{name}")
    spec = importlib.util.spec_from_file_location(mod_name, p)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
