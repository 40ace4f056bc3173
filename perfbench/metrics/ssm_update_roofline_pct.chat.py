"""Kernels: the ``ssm_update`` kernel's share of its memory roofline, in
percent: the bytes it has to move in the window's decode rounds
(``ssm_bytes.ssm_update_bytes`` of the rounds' ``batch`` span attribute, the
running rows: each row's float32 state of each state-space layer read once
and written once) over the chip's peak bytes a second, divided by the
kernel's device seconds in the window. Memory-bound: a row's update is six
operations an element it reads and writes. 0 when the program has no such
kernel, span or attribute."""
from perfbench import program_spans as ps
from perfbench import ssm_bytes, xplane_stats


def read(run):
    summary = run.get("device_summary")
    if not summary:
        return None
    seconds = ps.kernel_s(run, "ssm_update")
    if seconds is None:
        return None
    rows = xplane_stats.round_attrs(run, "batch")
    if not seconds or not rows:
        return 0.0
    least_s = (ssm_bytes.ssm_update_bytes(run["config"], rows)
               / run["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least_s / seconds
