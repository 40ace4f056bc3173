"""Kernels: the ``eva_summary`` kernel's share of its memory roofline, in
percent: the bytes it has to move for the chunks pooled in the traced window
(``eva_bytes.eva_summary_bytes`` of the ``summaries`` attribute of the
window's ``mta.engine.decode_round`` and ``mta.engine.prefill_call`` spans:
16 rows read and one written a chunk, layer and K/V) over the chip's peak
bytes a second, divided by the kernel's device seconds in the window. A grid
step moves 17 rows of 8 KB for K and as many for V, so this reads how well
that small transfer hides its latency, not a stream. 0 when the program has
no such kernel, span or attribute."""
from perfbench import eva_bytes
from perfbench import program_spans as ps

SPANS = (ps.ROUND, "mta.engine.prefill_call")


def read(run):
    summary = run.get("device_summary")
    if not summary:
        return None
    seconds = ps.kernel_s(run, "eva_summary")
    if seconds is None:
        return None
    lo, hi = summary["window"]
    chunks = 0.0
    for name, start, dur, attrs in (run.get("xplane_stats") or {}).get(
            "spans", []):
        if name in SPANS and dur > 0 and "summaries" in attrs:
            inside = max(0, min(start + dur, hi) - max(start, lo)) / dur
            chunks += inside * float(attrs["summaries"])
    if not seconds or not chunks:
        return 0.0
    least_s = (eva_bytes.eva_summary_bytes(run["config"], chunks)
               / run["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least_s / seconds
