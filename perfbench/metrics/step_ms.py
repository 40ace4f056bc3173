"""Training loop: median over the window's log intervals of
(time between two log lines) / steps. Source: the program's own log lines,
timed by the harness (program_span)."""
from perfbench.stats import median


def read(run):
    xs = run.get("step_intervals_ms")
    return median(xs) if xs else None
