"""Kernels: the sliding-window layers' paged decode kernel's share of its
memory roofline in the code cell, in percent: the bytes it has to read in
the window's decode rounds (``laguna_bytes.paged_window_read_bytes`` of the
rounds' ``window_blocks`` span attribute: the blocks from the one that holds
a slot's oldest visible key to its last, x 16 rows x 3 window planes x 4,096
B) over the chip's peak bytes a second, divided by the kernel's device
seconds in the window. Its walks are short (33 blocks a slot), so the
steps' fixed costs weigh more than in the full layers' kernel. 0 when the
program has no such kernel, span or attribute."""
from perfbench import laguna_bytes, xplane_stats
from perfbench import program_spans as ps


def read(run):
    summary = run.get("device_summary")
    if not summary:
        return None
    seconds = ps.kernel_s(run, "paged_window_decode")
    if seconds is None:
        return None
    blocks = xplane_stats.round_attrs(run, "window_blocks")
    if not seconds or not blocks:
        return 0.0
    least_s = (laguna_bytes.paged_window_read_bytes(run["config"], blocks)
               / run["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least_s / seconds
