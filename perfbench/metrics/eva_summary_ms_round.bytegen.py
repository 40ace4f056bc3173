"""Kernels: device milliseconds per decode round in EVA's chunk summariser
(``tpu_custom_call`` events whose name holds ``eva_summary``), from the
profiler trace: every call of it in the window, those of prefill calls (two
chunks a call of 32 bytes) with those of decode rounds (a slot fills a
chunk every 16th round), over the window's decode rounds. 0 when no such
kernel or no round is in the window."""
from perfbench import program_spans as ps


def read(run):
    summary = run.get("device_summary")
    if not summary:
        return None
    s = ps.kernel_s(run, "eva_summary")
    if s is None:
        return None
    rounds = ps.rounds_in(ps.program_spans(run), summary["window"])
    return s * 1e3 / rounds if rounds else 0.0
