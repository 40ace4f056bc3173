"""The per-layer readers of ``serve.laguna-xs.2.code-closed`` on a hand-built
``run``: a 10 ms window with two decode rounds over 2,000 and 3,000 full-plane
blocks and 330 and 660 window-plane blocks, 2 ms in the full layers' paged
decode kernel and 1 ms in the window layers', a decode module whose
instructions lie in parts ``attention`` and ``moe``, and the engine's
``window`` and ``moe`` counters; and the same readers on a program that names
none of it."""
import pytest

from megatronapp_tpu.trace.scope_map import ScopeMap, Scoped
from perfbench import laguna_bytes, manifest as mf, trace_reduce

MS = 1_000_000
KERNEL = {"op": "custom-call", "target": "tpu_custom_call"}
CONFIG = {"hidden_size": 2048, "head_dim": 128, "num_key_value_heads": 8,
          "moe_intermediate_size": 512,
          "shared_expert_intermediate_size": 512, "num_experts": 256,
          "num_hidden_layers": 5,
          "layer_types": ["full_attention", "sliding_attention",
                          "sliding_attention", "sliding_attention",
                          "full_attention"],
          "mlp_layer_types": ["dense", "sparse", "sparse", "sparse",
                              "sparse"],
          "serve": {"kv_cache_dtype": "bfloat16",
                    "params_dtype": "bfloat16"}}
PEAKS = {"hbm_bytes_per_s": 819e9}
NAMES = ["decode_round_ms.code", "decode_wait_ms_round.code",
         "host_gap_ms_round.code", "paged_decode_ms_round.code",
         "paged_decode_roofline_pct.code", "paged_window_ms_round.code",
         "paged_window_roofline_pct.code", "window_rows_walked_share.code",
         "kv_bytes_held_per_token.code", "experts_touched_share.code",
         "expert_load_max_over_mean.code", "moe_stream_roofline_pct.code"]
MOE = {"decode_rounds": 10, "tokens": 320, "assignments": 10240,
       "assignments_zero": 0, "assignments_here": 10240,
       "assignments_absent": 0, "experts_here": 256,
       "expert_pairs_touched": 6400, "expert_pairs_possible": 10240,
       "here_max_rows": 200}
WINDOW = {"rows_walked": 16_000, "rows_full_walk": 256_000,
          "bytes_held": 9_000 * 256_000, "tokens_in_flight": 256_000}


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
            "engine_steps": [(0.0, 0.01, 24, 24), (0.01, 0.02, 32, 32)],
            "max_batch": 32, "scope_maps": list(maps)}


DEVICE = [ev("paged_decode.11", 1, 2, KERNEL),
          ev("paged_window_decode.12", 2, 2.5, KERNEL),
          ev("fusion.7", 2.5, 4, {"op": "fusion"}),
          ev("paged_decode.11", 6, 7, KERNEL),
          ev("paged_window_decode.12", 7, 7.5, KERNEL),
          ev("fusion.7", 7.5, 8, {"op": "fusion"}),
          ev("paged_window_mq.9", 8, 9, KERNEL)]
MODULES = [ev("jit__decode_traced(1)", 1, 4), ev("jit__decode_traced(1)", 6, 8)]
MAPS = [ScopeMap("jit__decode_traced", "decode", {
    "fusion.7": Scoped("moe", "fwd", "fusion", "", ""),
    "paged_decode.11": Scoped("attention", "fwd", "custom-call", "", ""),
    "paged_window_decode.12": Scoped("attention", "fwd", "custom-call", "",
                                     "", "window")}, {})]
HOST = [ev("bench.window", 0, 10),
        ev("mta.engine.decode_round", 1, 5),
        ev("mta.engine.decode.wait", 2, 4.5),
        ev("mta.engine.decode_round", 6, 8),
        ev("mta.engine.decode.wait", 6.5, 7.5)]
STATS = {"spans": [
    ev("mta.engine.decode_round", 1, 5,
       {"kv_blocks": 2000, "window_blocks": 330}),
    ev("mta.engine.decode_round", 6, 8,
       {"kv_blocks": 3000, "window_blocks": 660})]}


def read(name, run):
    return mf.load_reader(name)(run)


def test_the_bytes_against_a_count_by_hand():
    # 5,000 blocks x 16 rows x 2 full planes x (K + V) x 8 heads x 128 x 2 B
    assert laguna_bytes.paged_decode_read_bytes(CONFIG, 5000) \
        == 5000 * 16 * 8192 == 5000 * 16 * 2 * 2 * 8 * 128 * 2
    # 990 blocks x 16 rows x 3 window planes x 4,096 B
    assert laguna_bytes.paged_window_read_bytes(CONFIG, 990) \
        == 990 * 16 * 3 * 4096
    # a round that touches every pair streams 4 layers x (256 experts + the
    # shared one) x 3.146M parameters x 2 B and 4 routers of 2048 x 256
    expert = 3 * 2048 * 512 * 2
    whole = 4 * (257 * expert + 2048 * 256 * 2)
    assert 4 * 256 * expert == 6_442_450_944
    assert laguna_bytes.moe_stream_bytes(CONFIG, 1, 1.0) == whole
    assert laguna_bytes.moe_stream_bytes(CONFIG, 3, 0.5) == pytest.approx(
        3 * 4 * (128 * expert + expert + 2048 * 256 * 2))


def test_readers_on_a_run_that_names_everything():
    run = run_of(DEVICE, HOST, STATS, {"moe": MOE, "window": WINDOW},
                 MODULES, MAPS)
    assert read("decode_round_ms.code", run) == pytest.approx(3.0)
    assert read("decode_wait_ms_round.code", run) == pytest.approx(1.75)
    assert read("batch_occupancy.code", run) == pytest.approx(87.5)
    # the full layers' kernel: 2 ms in 2 rounds; the window layers' 1 ms,
    # and neither family's reader takes the other's events (nor paged_*_mq)
    assert read("paged_decode_ms_round.code", run) == pytest.approx(1.0)
    assert read("paged_window_ms_round.code", run) == pytest.approx(0.5)
    least_s = 5000 * 16 * 8192 / 819e9
    assert read("paged_decode_roofline_pct.code", run) \
        == pytest.approx(100 * least_s / 2e-3)
    least_s = 990 * 16 * 3 * 4096 / 819e9
    assert read("paged_window_roofline_pct.code", run) \
        == pytest.approx(100 * least_s / 1e-3)
    for name in ("paged_decode_roofline_pct.code",
                 "paged_window_roofline_pct.code"):
        assert 0 < read(name, run) < 100
    # attention stays the two kinds' sum: 3 ms of kernels in 2 rounds
    assert read("attention_ms_round", run) == pytest.approx(1.5)
    assert read("moe_ms_round", run) == pytest.approx(1.0)
    assert read("window_rows_walked_share.code", run) == pytest.approx(6.25)
    assert read("kv_bytes_held_per_token.code", run) == pytest.approx(9000)
    assert read("experts_touched_share.code", run) == pytest.approx(62.5)
    least_s = laguna_bytes.moe_stream_bytes(CONFIG, 2, 0.625) / 819e9
    assert read("moe_stream_roofline_pct.code", run) \
        == pytest.approx(100 * least_s / 2e-3)
    # 200 rows on the busiest experts over 10240 / 256 = 40 summed means
    assert read("expert_load_max_over_mean.code", run) \
        == pytest.approx(200 / 40)
    # the idle 10 - 6 ms of the window over 2 rounds
    assert read("host_gap_ms_round.code", run) == pytest.approx(2.0)


@pytest.mark.parametrize("name", NAMES)
def test_a_program_without_the_names_reads_zero(name):
    """The parent commit of the PR that added them: no window kernel, no
    `window_blocks` on a kept span, no `window` counters, no scope map."""
    run = run_of([ev("fusion.1", 0, 9, {"op": "fusion"})],
                 [ev("bench.window", 0, 10)],
                 engine_stats={"window": False,
                               "moe": {"decode_rounds": 3, "assignments": 90,
                                       "expert_pairs_touched": 40,
                                       "expert_pairs_possible": 0}})
    assert read(name, run) == 0.0


def test_readers_without_a_trace_give_none():
    for name in ("decode_round_ms.code", "paged_decode_ms_round.code",
                 "paged_decode_roofline_pct.code",
                 "paged_window_ms_round.code",
                 "paged_window_roofline_pct.code",
                 "moe_stream_roofline_pct.code", "host_gap_ms_round.code",
                 "decode_wait_ms_round.code"):
        assert read(name, {"engine_stats": {}}) is None
    for name in ("experts_touched_share.code",
                 "expert_load_max_over_mean.code",
                 "window_rows_walked_share.code",
                 "kv_bytes_held_per_token.code"):
        assert read(name, {}) is None
