"""Kernels: the Kimi-delta-attention state's decode kernel's share of its
memory roofline in the doc cell, in percent: the bytes it has to move in the
window's decode rounds (``solar_bytes.kda_update_bytes`` of the rounds'
``batch`` span attribute, the running rows: each row's matrix states of each
of the three KDA layers, 64 heads of ``[128, 128]`` float32, read once and
written once, and the row's vectors) over the chip's peak bytes a second,
divided by the device seconds of the kernel (``kda_update*``) in the window.
The kernel holds 16 heads (1 MiB in, 1 MiB out) a grid step, so what a grid
step costs beside its copy shows here. 0 when the program has no such
kernel, span or attribute."""
from perfbench import solar_bytes, xplane_stats
from perfbench import program_spans as ps


def read(run):
    summary = run.get("device_summary")
    if not summary:
        return None
    seconds = ps.kernel_s(run, "kda_update")
    if seconds is None:
        return None
    rows = xplane_stats.round_attrs(run, "batch")
    if not seconds or not rows:
        return 0.0
    least_s = (solar_bytes.kda_update_bytes(run["config"], rows)
               / run["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least_s / seconds
