"""Kernels: device milliseconds per decode round in the state-space
layers' decode kernel (``tpu_custom_call`` events whose name holds
``ssm_update``), from the profiler trace. 0 when no such kernel or no round
is in the window."""
from perfbench import program_spans as ps


def read(run):
    summary = run.get("device_summary")
    if not summary:
        return None
    s = ps.kernel_s(run, "ssm_update")
    if s is None:
        return None
    rounds = ps.rounds_in(ps.program_spans(run), summary["window"])
    return s * 1e3 / rounds if rounds else 0.0
