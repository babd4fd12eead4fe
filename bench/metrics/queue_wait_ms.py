"""Front end (``serve/coalescer.py``): mean time a request waited in the
coalescer's queue before its batch was dispatched, over the window's
requests, from ``AsyncAnnEngine.stats()`` (host clock at the coalescer)."""


def read(run):
    return run["layer"].get("queue_wait_ms")
