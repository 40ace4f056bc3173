"""Model: the expert layers' share of their weight-stream roofline in the
reason cell, in percent: the least time their matrices need
(``nemotron_bytes.moe_stream_bytes``: the share of (layer, held expert) pairs
the engine's rounds touched, ``stats_snapshot()["moe"]``, x 5 layers x 64
experts x 2 x 2688 x 1856 x 2 B a round, and every round the shared expert's
two matrices and the router, at the chip's peak bytes a second) over the
device time a decode round spends in part ``moe`` (router, sort, both
grouped GEMMs, the shared expert, combine: ``moe_ms_round``'s numerator).
Memory-bound: 9 rows an expert are far under the chip's ridge. The touched
share covers the engine's life, the time the traced window. 0 when the
program counts no such thing or registers no decode step."""
from perfbench import manifest, nemotron_bytes, scope_time
from perfbench import program_spans as ps


def read(run):
    summary = run.get("device_summary")
    if not summary:
        return None
    touched = manifest.load_module(
        "metrics", "experts_touched_share.longgen").read(run)
    seconds = scope_time.part_s(run, "decode", ("moe",))
    rounds = ps.rounds_in(ps.program_spans(run), summary["window"])
    if not touched or not seconds or not rounds:
        return 0.0
    least_s = (nemotron_bytes.moe_stream_bytes(run["config"], rounds,
                                               touched / 100.0)
               / run["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least_s / seconds
