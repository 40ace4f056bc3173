"""Serving engine: median duration of engine.step() inside the window, from
the harness's own span around the bound method."""
from perfbench.stats import median


def read(run):
    steps = run.get("engine_steps")
    if not steps:
        return None
    return median([(t1 - t0) * 1e3 for t0, t1, *_ in steps])
