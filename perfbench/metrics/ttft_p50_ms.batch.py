"""Serving engine: median time from sending a request to its first token, over
the requests that completed inside the window (closed loop: it includes the
wait for a free slot)."""
from perfbench.stats import median


def read(run):
    return median(run["ttft_ms"]) if run.get("ttft_ms") else None
