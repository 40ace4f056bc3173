"""The per-layer readers of ``serve.lfm2-24b-a2b.assist-closed`` on a
hand-built ``run``: a 10 ms window with two decode rounds over 200 and 300
blocks, 2 ms in the paged decode kernel, a decode module whose instructions
lie in parts ``conv`` and ``moe``, and the engine's `moe` counters of a model
that holds every expert; and the same readers on a program that names none
of it."""
import pytest

from megatronapp_tpu.trace.scope_map import ScopeMap, Scoped
from perfbench import lfm2_bytes, manifest as mf, trace_reduce

MS = 1_000_000
KERNEL = {"op": "custom-call", "target": "tpu_custom_call"}
CONFIG = {"hidden_size": 2048, "num_attention_heads": 32,
          "num_key_value_heads": 8, "moe_intermediate_size": 1536,
          "num_experts": 64, "num_hidden_layers": 9, "num_dense_layers": 1,
          "layer_types": ["conv", "full_attention", "conv", "conv", "conv",
                          "full_attention", "conv", "conv", "conv"],
          "serve": {"kv_cache_dtype": "bfloat16",
                    "params_dtype": "bfloat16"}}
PEAKS = {"hbm_bytes_per_s": 819e9}
NAMES = ["decode_round_ms.assist", "decode_wait_ms_round.assist",
         "host_gap_ms_round.assist", "prefill_share.assist",
         "conv_ms_round", "paged_decode_ms_round.assist",
         "paged_decode_roofline_pct.assist", "moe_stream_roofline_pct.assist",
         "experts_touched_share.assist", "expert_load_max_over_mean.assist"]
MOE = {"decode_rounds": 10, "tokens": 1920, "assignments": 61440,
       "assignments_zero": 0, "assignments_here": 61440,
       "assignments_absent": 0, "experts_here": 64,
       "expert_pairs_touched": 4608, "expert_pairs_possible": 5120,
       "here_max_rows": 1600}


def ev(name, start_ms, end_ms, info=None):
    return [name, round(start_ms * MS), round((end_ms - start_ms) * MS),
            dict(info or {})]


def run_of(device, host, stats=None, engine_stats=None, modules=(),
           maps=()):
    trace = {"planes": [
        {"name": "/device:TPU:0",
         "lines": [{"name": "XLA Ops", "events": device},
                   {"name": "XLA Modules", "events": list(modules)}]},
        {"name": "/host:CPU", "lines": [{"name": "stepper", "events": host}]}]}
    return {"trace": trace, "config": CONFIG, "peaks": PEAKS,
            "device_summary": trace_reduce.device_summary(trace),
            "xplane_stats": stats, "engine_stats": engine_stats or {},
            "engine_steps": [(0.0, 0.01, 144, 144), (0.01, 0.02, 192, 192)],
            "max_batch": 192, "scope_maps": list(maps)}


DEVICE = [ev("paged_decode.11", 1, 2, KERNEL),
          ev("fusion.7", 2, 3.5, {"op": "fusion"}),
          ev("fusion.8", 3.5, 4, {"op": "fusion"}),
          ev("paged_decode.11", 6, 7, KERNEL),
          ev("fusion.7", 7, 7.5, {"op": "fusion"}),
          ev("paged_mq.9", 8, 9, KERNEL)]
MODULES = [ev("jit__decode_traced(1)", 1, 4), ev("jit__decode_traced(1)", 6, 8)]
MAPS = [ScopeMap("jit__decode_traced", "decode", {
    "fusion.7": Scoped("moe", "fwd", "fusion", "", ""),
    "fusion.8": Scoped("conv", "fwd", "fusion", "", ""),
    "paged_decode.11": Scoped("attention", "fwd", "custom-call", "", "")},
    {})]
HOST = [ev("bench.window", 0, 10),
        ev("mta.engine.decode_round", 1, 5),
        ev("mta.engine.decode.wait", 2, 4.5),
        ev("mta.engine.decode_round", 6, 8),
        ev("mta.engine.decode.wait", 6.5, 7.5)]
STATS = {
    "spans": [ev("mta.engine.decode_round", 1, 5, {"kv_blocks": 200}),
              ev("mta.engine.decode_round", 6, 8, {"kv_blocks": 300})]}


def read(name, run):
    return mf.load_reader(name)(run)


def test_the_bytes_against_a_count_by_hand():
    # 500 blocks x 16 rows x 2 attention planes x (K + V) x 8 heads x 64 x 2 B
    assert lfm2_bytes.paged_decode_read_bytes(CONFIG, 500) \
        == 500 * 16 * 4096 == 500 * 16 * 2 * 2 * 8 * 64 * 2
    # a round that touches every pair streams 8 layers x 64 experts x 9.437M
    # parameters x 2 B = 9.66 GB; 3 rounds at nine tenths of the pairs
    whole = 8 * 64 * 3 * 2048 * 1536 * 2
    assert whole == 9_663_676_416
    assert lfm2_bytes.moe_stream_bytes(CONFIG, 1, 1.0) == whole
    assert lfm2_bytes.moe_stream_bytes(CONFIG, 3, 0.9) \
        == pytest.approx(3 * 0.9 * whole)


def test_readers_on_a_run_that_names_everything():
    run = run_of(DEVICE, HOST, STATS, {
        "moe": MOE, "steps": {"step": {"total_s": 4.0},
                              "prefill": {"total_s": 1.0}}},
        MODULES, MAPS)
    assert read("decode_round_ms.assist", run) == pytest.approx(3.0)
    assert read("decode_wait_ms_round.assist", run) == pytest.approx(1.75)
    assert read("paged_decode_ms_round.assist", run) == pytest.approx(1.0)
    assert read("prefill_share.assist", run) == pytest.approx(25.0)
    assert read("batch_occupancy.assist", run) == pytest.approx(87.5)
    least_s = 500 * 16 * 4096 / 819e9
    assert read("paged_decode_roofline_pct.assist", run) \
        == pytest.approx(100 * least_s / 2e-3)
    assert 0 < read("paged_decode_roofline_pct.assist", run) < 100
    # parts: fusion.8 in `conv` (0.5 ms), fusion.7 in `moe` (2 ms), 2 rounds
    assert read("conv_ms_round", run) == pytest.approx(0.25)
    assert read("moe_ms_round", run) == pytest.approx(1.0)
    assert read("experts_touched_share.assist", run) == pytest.approx(90.0)
    # two rounds' touched experts over the 2 ms in part `moe`
    least_s = 2 * 0.9 * 9_663_676_416 / 819e9
    assert read("moe_stream_roofline_pct.assist", run) \
        == pytest.approx(100 * least_s / 2e-3)
    # 1600 rows on the busiest experts over 61440 / 64 = 960 summed means
    assert read("expert_load_max_over_mean.assist", run) \
        == pytest.approx(1600 / 960)
    # the idle 10 - 5.5 ms of the window over 2 rounds
    assert read("host_gap_ms_round.assist", run) == pytest.approx(2.25)


@pytest.mark.parametrize("name", NAMES)
def test_a_program_without_the_names_reads_zero(name):
    """The parent commit of the PR that added them: no part `conv`, no
    `kv_blocks` on a kept span, no load counts, no scope map."""
    run = run_of([ev("fusion.1", 0, 9, {"op": "fusion"})],
                 [ev("bench.window", 0, 10)],
                 engine_stats={"moe": {"decode_rounds": 3, "assignments": 90,
                                       "expert_pairs_touched": 40,
                                       "expert_pairs_possible": 0}})
    assert read(name, run) == 0.0


def test_readers_without_a_trace_give_none():
    for name in ("decode_round_ms.assist", "paged_decode_ms_round.assist",
                 "paged_decode_roofline_pct.assist",
                 "moe_stream_roofline_pct.assist",
                 "host_gap_ms_round.assist", "decode_wait_ms_round.assist"):
        assert read(name, {"engine_stats": {}}) is None
    assert read("experts_touched_share.assist", {}) is None
    assert read("expert_load_max_over_mean.assist", {}) is None
