"""Kernels: the dense paged decode kernel's share of its memory roofline,
in percent: the bytes it has to read in the window's decode rounds
(``eva_bytes.paged_decode_read_bytes`` of the rounds' ``kv_rows`` span
attribute: the rows the slots' two-region tables hold, R(T) a slot, x
layers x (K + V) x 32 heads x 128 x 2 B) over the chip's peak bytes a
second, divided by the kernel's device seconds in the window. Memory-bound:
at one query row a slot the kernel's operations per byte are far under the
chip's ridge. 0 when the program has no such kernel, span or attribute."""
from perfbench import eva_bytes, xplane_stats
from perfbench import program_spans as ps


def read(run):
    summary = run.get("device_summary")
    if not summary:
        return None
    seconds = ps.kernel_s(run, "paged_decode")
    if seconds is None:
        return None
    kv_rows = xplane_stats.round_attrs(run, "kv_rows")
    if not seconds or not kv_rows:
        return 0.0
    least_s = (eva_bytes.paged_decode_read_bytes(run["config"], kv_rows)
               / run["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least_s / seconds
