"""Kernels: device milliseconds a training step in the sliding-window
layers' flash kernels (``flash_window_fwd``, ``flash_window_bwd_dq``,
``flash_window_bwd_dkv``; the recomputed forward of a rematerialised layer
too). None on a program that has no such kernel: the line leaves it out."""
from perfbench import program_spans as ps


def read(run):
    summary, steps = run.get("device_summary"), run.get("traced_steps")
    if not summary or not steps:
        return None
    s = ps.kernel_s(run, "flash_window_")
    return s / steps * 1e3 if s else None
