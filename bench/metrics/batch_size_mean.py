"""Front end (``serve/coalescer.py``): mean true size of the batches the
coalescer dispatched in the window (requests served / batches dispatched,
from ``AsyncAnnEngine.stats()``)."""


def read(run):
    return run["layer"].get("batch_size_mean")
