"""Parallel layouts: device time per step of all-reduce, all-gather,
reduce-scatter, all-to-all and collective-permute operations, summed (not
the exposed part), from the profiler trace, averaged over the chips."""
from perfbench import trace_reduce


def read(run):
    summary, steps = run.get("device_summary"), run.get("traced_steps")
    if not summary or not steps:
        return None
    s = trace_reduce.summed_s(run["trace"], summary["window"],
                              trace_reduce.is_collective)
    return None if not s else s / steps * 1e3
