"""Kernels: the dense paged decode kernel's share of its memory roofline in
the reason cell, in percent: the bytes it has to read in the window's decode
rounds (``nemotron_bytes.paged_decode_read_bytes`` of the rounds'
``kv_blocks`` span attribute: blocks x 16 rows x 2 attention planes x (K + V)
x 2 heads x 128 x 2 B = 2,048 B a cached token) over the chip's peak bytes a
second, divided by the kernel's device seconds in the window. Memory-bound:
one query row a slot, 16 query heads a key/value head. 0 when the program
has no such kernel, span or attribute."""
from perfbench import nemotron_bytes, xplane_stats
from perfbench import program_spans as ps


def read(run):
    summary = run.get("device_summary")
    if not summary:
        return None
    seconds = ps.kernel_s(run, "paged_decode")
    if seconds is None:
        return None
    kv_blocks = xplane_stats.round_attrs(run, "kv_blocks")
    if not seconds or not kv_blocks:
        return 0.0
    least_s = (nemotron_bytes.paged_decode_read_bytes(run["config"],
                                                      kv_blocks)
               / run["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least_s / seconds
