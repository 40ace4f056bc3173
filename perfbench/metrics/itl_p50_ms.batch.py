"""Serving engine: median gap between consecutive tokens of one request, over
the requests that completed inside the window, pooled."""
from perfbench.stats import median


def read(run):
    return median(run["itl_ms"]) if run.get("itl_ms") else None
