"""Kernels: the chunked scans' share of their roofline in the reason cell,
in percent: the least time the window's prefill calls' scans need, the
LARGER of their matmul operations at the chip's peak
(``nemotron_bytes.ssd_chunk_flops``: scores a group) and their operands'
bytes at the HBM's (``nemotron_bytes.ssd_chunk_bytes``), both from shapes
(the calls in the window x the engine's call width), over the device seconds
under the program's ``ssm/ssd_chunk`` scope. None on a program that names no
such scope: the line leaves it out."""
from perfbench import admission_spans, nemotron_bytes


def read(run):
    if not run.get("device_summary"):
        return None
    seconds = nemotron_bytes.sub_s(run, "prefill", "ssm", "ssd_chunk")
    calls = admission_spans.of(run)["prefill_calls_in"]
    width = ((run.get("engine_stats") or {}).get("prefill") or {}).get(
        "width", 0)
    if not seconds or not calls or not width:
        return None
    config, peaks = run["config"], run["peaks"]
    least_s = max(
        nemotron_bytes.ssd_chunk_flops(config, calls * width)
        / peaks["bf16_flops_per_s"],
        nemotron_bytes.ssd_chunk_bytes(config, calls, width)
        / peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / seconds
