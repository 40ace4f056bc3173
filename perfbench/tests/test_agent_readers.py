"""The per-layer readers of ``serve.longcat-flash-chat.agent-closed`` on a
hand-built ``run``: a 10 ms window with two decode rounds over 3000 and 5000
cached tokens and 2 ms in the latent kernel, and the engine's `moe` counters
of a share; and the same readers on a program that names none of it."""
import pytest

from perfbench import longcat_bytes, manifest as mf, trace_reduce

MS = 1_000_000
KERNEL = {"op": "custom-call", "target": "tpu_custom_call"}
CONFIG = {"num_layers": 4, "kv_lora_rank": 512, "qk_rope_head_dim": 64,
          "serve": {"kv_cache_dtype": "bfloat16"}}
PEAKS = {"hbm_bytes_per_s": 819e9}
NAMES = ["decode_round_ms.agent", "decode_wait_ms_round.agent",
         "host_gap_ms_round.agent", "paged_latent_ms_round.agent",
         "paged_latent_roofline_pct.agent", "zero_expert_share.agent",
         "experts_touched_share.agent", "expert_load_max_over_mean.agent",
         "prefill_share.agent"]
MOE = {"decode_rounds": 10, "tokens": 640, "assignments": 30720,
       "assignments_zero": 10240, "assignments_here": 640,
       "assignments_absent": 19840, "experts_here": 16,
       "expert_pairs_touched": 400, "expert_pairs_possible": 640,
       "here_max_rows": 130}


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
            "xplane_stats": stats, "engine_stats": engine_stats or {},
            "engine_steps": [(0.0, 0.01, 48, 48), (0.01, 0.02, 64, 64)],
            "max_batch": 64}


DEVICE = [ev("paged_decode_latent.11", 1, 2, KERNEL),
          ev("fusion.7", 2, 4, {"op": "fusion"}),
          ev("paged_decode_latent.12", 6, 7, KERNEL),
          ev("paged_mq_latent.9", 8, 9, KERNEL)]
HOST = [ev("bench.window", 0, 10),
        ev("mta.engine.decode_round", 1, 5),
        ev("mta.engine.decode.wait", 2, 4.5),
        ev("mta.engine.decode_round", 6, 8),
        ev("mta.engine.decode.wait", 6.5, 7.5)]
STATS = {
    "spans": [ev("mta.engine.decode_round", 1, 5, {"kv_tokens": 3000}),
              ev("mta.engine.decode_round", 6, 8, {"kv_tokens": 5000})]}


def read(name, run):
    return mf.load_reader(name)(run)


def test_readers_on_a_run_that_names_everything():
    run = run_of(DEVICE, HOST, STATS, {
        "moe": MOE, "steps": {"step": {"total_s": 4.0},
                              "prefill": {"total_s": 1.0}}})
    assert read("decode_round_ms.agent", run) == pytest.approx(3.0)
    assert read("decode_wait_ms_round.agent", run) == pytest.approx(1.75)
    assert read("paged_latent_ms_round.agent", run) == pytest.approx(1.0)
    assert read("prefill_share.agent", run) == pytest.approx(25.0)
    assert read("batch_occupancy.agent", run) == pytest.approx(87.5)
    # the rounds' 8,000 cached tokens once a plane, two planes a layer
    assert longcat_bytes.paged_latent_read_bytes(CONFIG, 8000) \
        == 8000 * 2 * 4 * 576 * 2
    least_s = 8000 * 8 * 576 * 2 / 819e9
    assert read("paged_latent_roofline_pct.agent", run) \
        == pytest.approx(100 * least_s / 2e-3)
    assert 0 < read("paged_latent_roofline_pct.agent", run) < 100
    assert read("zero_expert_share.agent", run) \
        == pytest.approx(100 * 10240 / 30720)
    assert read("experts_touched_share.agent", run) \
        == pytest.approx(100 * 400 / 640)
    # 130 rows on the busiest held experts over 640 / 16 = 40 summed means
    assert read("expert_load_max_over_mean.agent", run) \
        == pytest.approx(130 / 40)
    # the idle 10 - 5 ms of the window over 2 rounds
    assert read("host_gap_ms_round.agent", run) == pytest.approx(2.5)


@pytest.mark.parametrize("name", NAMES)
def test_a_program_without_the_names_reads_zero(name):
    """The parent commit of the PR that added them: no span attribute, no
    latent kernel, no `moe` counters of a share, no kept stats."""
    run = run_of([ev("fusion.1", 0, 9, {"op": "fusion"})],
                 [ev("bench.window", 0, 10)],
                 engine_stats={"moe": {"decode_rounds": 3, "assignments": 90,
                                       "expert_pairs_touched": 40,
                                       "expert_pairs_possible": 0}})
    assert read(name, run) == 0.0


def test_readers_without_a_trace_give_none():
    for name in ("decode_round_ms.agent", "paged_latent_ms_round.agent",
                 "paged_latent_roofline_pct.agent",
                 "host_gap_ms_round.agent", "decode_wait_ms_round.agent"):
        assert read(name, {"engine_stats": {}}) is None
    assert read("zero_expert_share.agent", {}) is None
