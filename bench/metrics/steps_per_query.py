"""Traversal (``core/speedann.py``, ``core/bfis.py``): mean global steps a
searched query took (``SearchStats.steps``), over the lanes of every batch the
engine searched in the window (``harness.BatchLog``)."""


def read(run):
    return run["layer"].get("steps_per_query")
