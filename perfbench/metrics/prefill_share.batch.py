"""Serving engine: the share of the engine's stepping time spent in
prefill, in percent: ``steps.prefill.total_s / steps.step.total_s`` of the
engine's always-on counters, over the engine's LIFE, not the window: the
warm-up's compiles, the ramp and the drain are in it, so it moves with
set-up state (the runner takes no snapshot at the window's opening yet).
While prefill blocks the step, this is the share of the time in which no
running request gets a token."""
from perfbench import program_spans as ps


def read(run):
    steps = ps.step_counters(run)
    if steps is None:
        return None
    total = steps.get("step", {}).get("total_s", 0.0)
    if not total:
        return 0.0
    return 100.0 * steps.get("prefill", {}).get("total_s", 0.0) / total
