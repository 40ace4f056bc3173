"""Kernels: the sliding-window layers' flash kernels' share of the chip's
bf16 peak, in percent: the least time the band's arithmetic needs (the
(query, key) pairs that the traced steps' masks allow, counted on the host
by the runner from the batches' ``segment_ids``, x
``mellum_flops.window_pair_flops``: q.k and p.v over 32 heads of 128, three
passes, three sliding layers; the recomputed forward is not counted) over
the kernels' device seconds in the traced window. What holds it under 100:
the tiles' masked corners (a 512 x 512 tile at the band's edge and at a
document's start does a whole tile's arithmetic), the recomputed forward,
and the softmax's element-wise work. None on a program that has no such
kernel."""
from perfbench import mellum_flops
from perfbench import program_spans as ps


def read(run):
    summary, pairs = run.get("device_summary"), run.get("window_pairs_traced")
    if not summary or not pairs:
        return None
    seconds = ps.kernel_s(run, "flash_window_")
    if not seconds:
        return None
    least_s = (mellum_flops.window_pair_flops(run["config"], pairs)
               / run["peaks"]["bf16_flops_per_s"])
    return 100.0 * least_s / seconds
