"""The per-layer readers of ``serve.deepseek-v2-lite.longgen-closed`` on a
hand-built ``run``: a 10 ms window with two decode rounds over 3000 and
5000 cached tokens and 2 ms in the latent kernel; and the same readers on a
program that names none of it."""
import pytest

from perfbench import kernel_bytes, manifest as mf, trace_reduce, xplane_stats

MS = 1_000_000
KERNEL = {"op": "custom-call", "target": "tpu_custom_call"}
CONFIG = {"num_layers": 9, "kv_lora_rank": 512, "qk_rope_head_dim": 64,
          "serve": {"kv_cache_dtype": "bfloat16"}}
PEAKS = {"hbm_bytes_per_s": 819e9}
NAMES = ["decode_round_ms.longgen", "paged_latent_ms_round.longgen",
         "paged_latent_roofline_pct.longgen",
         "experts_touched_share.longgen"]


def ev(name, start_ms, end_ms, info=None):
    return [name, round(start_ms * MS), round((end_ms - start_ms) * MS),
            dict(info or {})]


def run_of(device, host, stats=None, engine_stats=None):
    trace = {"planes": [
        {"name": "/device:TPU:0",
         "lines": [{"name": "XLA Ops", "events": device}]},
        {"name": "/host:CPU", "lines": [{"name": "stepper", "events": host}]}]}
    return {"trace": trace, "config": CONFIG, "peaks": PEAKS,
            "device_summary": trace_reduce.device_summary(trace),
            "xplane_stats": stats, "engine_stats": engine_stats or {}}


DEVICE = [ev("paged_decode_latent.11", 1, 2, KERNEL),
          ev("fusion.7", 2, 4, {"op": "fusion"}),
          ev("paged_decode_latent.11", 6, 7, KERNEL),
          ev("paged_mq_latent.9", 8, 9, KERNEL)]
HOST = [ev("bench.window", 0, 10),
        ev("mta.engine.decode_round", 1, 5),
        ev("mta.engine.decode_round", 6, 8)]
STATS = {
    "spans": [ev("mta.engine.decode_round", 1, 5, {"kv_tokens": 3000}),
              ev("mta.engine.decode_round", 6, 8, {"kv_tokens": 5000})]}


def read(name, run):
    return mf.load_reader(name)(run)


def test_readers_on_a_run_that_names_everything():
    run = run_of(DEVICE, HOST, STATS, {"moe": {
        "expert_pairs_touched": 450, "expert_pairs_possible": 512}})
    assert read("decode_round_ms.longgen", run) == pytest.approx(3.0)
    assert read("paged_latent_ms_round.longgen", run) == pytest.approx(1.0)
    assert read("experts_touched_share.longgen", run) \
        == pytest.approx(100 * 450 / 512)
    least_s = 8000 * 9 * 576 * 2 / 819e9
    assert kernel_bytes.paged_latent_read_bytes(CONFIG, 8000) \
        == 8000 * 9 * 576 * 2
    assert read("paged_latent_roofline_pct.longgen", run) \
        == pytest.approx(100 * least_s / 2e-3)
    assert read("paged_latent_roofline_pct.longgen", run) < 100


def test_a_round_that_straddles_the_window_counts_by_its_share():
    stats = {"spans": [ev("mta.engine.decode_round", -2, 2,
                          {"kv_tokens": 4000})]}
    run = run_of(DEVICE, HOST, stats)
    assert xplane_stats.round_attrs(run, "kv_tokens") == pytest.approx(2000)


@pytest.mark.parametrize("name", NAMES)
def test_a_program_without_the_names_reads_zero(name):
    """The parent commit of the PR that added them: no span attribute, no
    latent kernel, no `moe` counters, no kept stats."""
    run = run_of([ev("fusion.1", 0, 9, {"op": "fusion"})],
                 [ev("bench.window", 0, 10)])
    assert read(name, run) == 0.0
