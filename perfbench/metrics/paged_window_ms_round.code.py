"""Kernels: device milliseconds per decode round in the sliding-window
layers' paged decode kernel (``tpu_custom_call`` events whose name holds
``paged_window_decode``: three layers of 64 query heads over 8 key/value
heads, each walking a slot's last 512 rows and not its ~8k), from the
profiler trace. 0 when no such kernel or no round is in the window."""
from perfbench import program_spans as ps


def read(run):
    summary = run.get("device_summary")
    if not summary:
        return None
    s = ps.kernel_s(run, "paged_window_decode")
    if s is None:
        return None
    rounds = ps.rounds_in(ps.program_spans(run), summary["window"])
    return s * 1e3 / rounds if rounds else 0.0
