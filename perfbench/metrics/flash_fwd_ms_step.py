"""Kernels: device time per training step of the flash attention forward
kernels (``flash_fwd*``, both orientations; the recomputed forward of a
rematerialised layer too), averaged over the chips. 0 where XLA's dense
attention runs in their place."""
from perfbench import program_spans as ps


def read(run):
    summary, steps = run.get("device_summary"), run.get("traced_steps")
    if not summary or not steps:
        return None
    s = ps.kernel_s(run, "flash_fwd")
    return None if s is None else s / steps * 1e3
