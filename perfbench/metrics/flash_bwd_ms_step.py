"""Kernels: device time per training step of the flash attention backward
kernels (``flash_bwd_dq*`` and ``flash_bwd_dkv*``), averaged over the
chips. With ``flash_fwd_ms_step`` it adds up to ``pallas_ms_step``."""
from perfbench import program_spans as ps


def read(run):
    summary, steps = run.get("device_summary"), run.get("traced_steps")
    if not summary or not steps:
        return None
    s = ps.kernel_s(run, "flash_bwd_dq", "flash_bwd_dkv")
    return None if s is None else s / steps * 1e3
