"""Kernels: the latent paged decode kernel's share of its memory roofline in
the agent cell, in percent: the bytes it has to read in the window's decode
rounds (``longcat_bytes.paged_latent_read_bytes`` of the rounds'
``kv_tokens`` span attribute: cached rows x 2 planes a layer x layers x
(latent + roped key) x 2 B, unpadded) over the chip's peak bytes a second,
divided by the kernel's device seconds in the window. Memory-bound by
construction (one query row a slot); what keeps the kernel off this roof is
the value path's re-expansion of every tile through kv_up's 64 heads. 0
when the program has no such kernel, span or attribute."""
from perfbench import longcat_bytes, xplane_stats
from perfbench import program_spans as ps


def read(run):
    summary = run.get("device_summary")
    if not summary:
        return None
    seconds = ps.kernel_s(run, "paged_decode_latent")
    if seconds is None:
        return None
    kv_tokens = xplane_stats.round_attrs(run, "kv_tokens")
    if not seconds or not kv_tokens:
        return 0.0
    config = run["config"]
    itemsize = {"bfloat16": 2, "float32": 4}[config["serve"]["kv_cache_dtype"]]
    least_s = (longcat_bytes.paged_latent_read_bytes(config, kv_tokens,
                                                     itemsize)
               / run["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least_s / seconds
