"""The per-layer readers of ``serve.granite-4.0-h-small.rag-closed`` on a
hand-built ``run``: a 10 ms window with two decode rounds of 64 and 48 rows,
1.5 ms in the state's decode kernel, a decode module whose instructions lie
in parts ``ssm`` and ``moe``, a prefill module with 2 ms under the
``ssm/ssd_chunk`` scope in two calls of 512 positions, and the engine's
`moe` counters of a model that holds half its experts; and the same readers
on a program that names none of it."""
import pytest

from megatronapp_tpu.trace.scope_map import ScopeMap, Scoped
from perfbench import granite_bytes, manifest as mf, trace_reduce

MS = 1_000_000
KERNEL = {"op": "custom-call", "target": "tpu_custom_call"}
CONFIG = {"hidden_size": 4096, "intermediate_size": 768,
          "mamba_expand": 2, "mamba_d_state": 128, "mamba_n_heads": 128,
          "mamba_chunk_size": 256, "num_local_experts": 36,
          "layer_types": ["mamba"] * 5 + ["attention"] + ["mamba"] * 4,
          "serve": {"kv_cache_dtype": "bfloat16", "params_dtype": "bfloat16",
                    "state_dtype": "float32"}}
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
NAMES = ["decode_round_ms.rag", "decode_wait_ms_round.rag",
         "host_gap_ms_round.rag", "paged_decode_ms_round.rag",
         "ssd_update_ms_round.rag", "ssd_update_roofline_pct.rag",
         "moe_stream_roofline_pct.rag", "experts_touched_share.rag",
         "expert_load_max_over_mean.rag", "expert_rows_here_share.rag"]
SCOPED = ["ssd_chunk_ms_call.rag", "ssd_chunk_roofline_pct.rag"]
MOE = {"decode_rounds": 10, "tokens": 640, "assignments": 64000,
       "assignments_zero": 0, "assignments_here": 32640,
       "assignments_absent": 31360, "experts_here": 36,
       "expert_pairs_touched": 3564, "expert_pairs_possible": 3600,
       "here_max_rows": 1700}


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
            "engine_steps": [(0.0, 0.01, 64, 64), (0.01, 0.02, 48, 48)],
            "max_batch": 64, "scope_maps": list(maps)}


DEVICE = [ev("ssm_update.11", 1, 2, KERNEL),
          ev("fusion.7", 2, 3.5, {"op": "fusion"}),
          ev("paged_decode.3", 3.5, 4, KERNEL),
          ev("ssm_update.11", 6, 6.5, KERNEL),
          ev("fusion.7", 6.5, 7, {"op": "fusion"}),
          ev("fusion.21", 8, 9.5, {"op": "fusion"}),
          ev("fusion.22", 9.5, 10, {"op": "fusion"}),
          ev("fusion.23", 10, 10.5, {"op": "fusion"})]
MODULES = [ev("jit__decode_traced(1)", 1, 4),
           ev("jit__decode_traced(1)", 6, 7),
           ev("jit__mq_traced(2)", 8, 10.5)]
MAPS = [
    ScopeMap("jit__decode_traced", "decode", {
        "fusion.7": Scoped("moe", "fwd", "fusion", "", ""),
        "ssm_update.11": Scoped("ssm", "fwd", "custom-call", "", ""),
        "paged_decode.3": Scoped("attention", "fwd", "custom-call", "", "")},
        {}),
    ScopeMap("jit__mq_traced", "prefill", {
        "fusion.21": Scoped("ssm", "fwd", "fusion", "", "", "ssd_chunk"),
        "fusion.22": Scoped("ssm", "fwd", "fusion", "", "", "ssd_chunk"),
        "fusion.23": Scoped("ssm", "fwd", "fusion", "", "", "gated_norm")},
        {})]
HOST = [ev("bench.window", 0, 11),
        ev("mta.engine.decode_round", 1, 5),
        ev("mta.engine.decode.wait", 2, 4.5),
        ev("mta.engine.decode_round", 6, 8),
        ev("mta.engine.decode.wait", 6.5, 7.5),
        ev("mta.engine.prefill", 8, 11),
        ev("mta.engine.prefill_call", 8, 9.4),
        ev("mta.engine.prefill_call", 9.4, 10.9)]
STATS = {
    "spans": [ev("mta.engine.decode_round", 1, 5, {"batch": 64}),
              ev("mta.engine.decode_round", 6, 8, {"batch": 48}),
              ev("mta.engine.prefill_call", 8, 9.4,
                 {"tokens": 512, "width": 512, "ssd_chunks": 2}),
              ev("mta.engine.prefill_call", 9.4, 10.9,
                 {"tokens": 100, "width": 512, "ssd_chunks": 2})]}


def read(name, run):
    return mf.load_reader(name)(run)


def test_the_bytes_and_operations_against_a_count_by_hand():
    # 9 Mamba-2 layers x (read + write) x 128 x 8192 float32
    assert granite_bytes.ssd_update_bytes(CONFIG, 1) \
        == 9 * 2 * 128 * 8192 * 4 == 75_497_472
    assert granite_bytes.ssd_update_bytes(CONFIG, 112) == 112 * 75_497_472
    # a position of a layer's scan: 2 x (256 x 128 + 256 x 8192 + 2 x 128 x
    # 8192) = 8,454,144 operations
    assert granite_bytes.ssd_chunk_flops(CONFIG, 1) == 9 * 8_454_144
    # a call of 512: (2 x 8192 + 2 x 128) x 2 B + 128 x 4 B a position,
    # and the state in and out
    assert granite_bytes.ssd_chunk_bytes(CONFIG, 1, 512) \
        == 9 * (512 * (16640 * 2 + 512) + 2 * 128 * 8192 * 4)
    # a round that touches every held pair streams 10 x 36 x 9.437M x 2 B
    whole = 10 * 36 * 3 * 4096 * 768 * 2
    assert whole == 6_794_772_480
    assert granite_bytes.moe_stream_bytes(CONFIG, 1, 1.0) == whole
    assert granite_bytes.moe_stream_bytes(CONFIG, 3, 0.5) \
        == pytest.approx(1.5 * whole)


def test_readers_on_a_run_that_names_everything():
    run = run_of(DEVICE, HOST, STATS, {
        "moe": MOE, "prefill": {"width": 512, "calls": 40}}, MODULES, MAPS)
    assert read("decode_round_ms.rag", run) == pytest.approx(3.0)
    assert read("decode_wait_ms_round.rag", run) == pytest.approx(1.75)
    assert read("paged_decode_ms_round.rag", run) == pytest.approx(0.25)
    assert read("batch_occupancy.rag", run) == pytest.approx(87.5)
    assert read("ssd_update_ms_round.rag", run) == pytest.approx(0.75)
    least_s = 112 * 75_497_472 / 819e9
    assert read("ssd_update_roofline_pct.rag", run) \
        == pytest.approx(100 * least_s / 1.5e-3)
    assert read("ssm_ms_round", run) == pytest.approx(0.75)
    assert read("moe_ms_round", run) == pytest.approx(1.0)
    # the scans' 2 ms (not the gated norm's 0.5) over the window's 2 calls
    assert granite_bytes.sub_s(run, "prefill", "ssm", "ssd_chunk") \
        == pytest.approx(2e-3)
    assert granite_bytes.sub_s(run, "prefill", "ssm", "gated_norm") \
        == pytest.approx(0.5e-3)
    assert read("ssd_chunk_ms_call.rag", run) == pytest.approx(1.0)
    # memory-bound at these shapes: the bytes' time is the larger
    flops_s = granite_bytes.ssd_chunk_flops(CONFIG, 2 * 512) / 197e12
    bytes_s = granite_bytes.ssd_chunk_bytes(CONFIG, 2, 512) / 819e9
    assert bytes_s > flops_s
    assert read("ssd_chunk_roofline_pct.rag", run) \
        == pytest.approx(100 * bytes_s / 2e-3)
    assert 0 < read("ssd_chunk_roofline_pct.rag", run) < 100
    assert read("experts_touched_share.rag", run) == pytest.approx(99.0)
    least_s = 2 * 0.99 * 6_794_772_480 / 819e9
    assert read("moe_stream_roofline_pct.rag", run) \
        == pytest.approx(100 * least_s / 2e-3)
    # 1700 rows on the busiest experts over 32640 / 36 summed means
    assert read("expert_load_max_over_mean.rag", run) \
        == pytest.approx(1700 * 36 / 32640)
    assert read("expert_rows_here_share.rag", run) == pytest.approx(0.51)
    # idle: 0-1, 4-6, 7-8, 10.5-11 less what lies in prefill (10.5-11)
    assert read("host_gap_ms_round.rag", run) == pytest.approx(2.0)


@pytest.mark.parametrize("name", NAMES)
def test_a_program_without_the_names_reads_zero(name):
    run = run_of([ev("fusion.1", 0, 9, {"op": "fusion"})],
                 [ev("bench.window", 0, 10)],
                 engine_stats={"moe": {"decode_rounds": 3, "assignments": 0,
                                       "expert_pairs_touched": 40,
                                       "expert_pairs_possible": 0}})
    assert read(name, run) == 0.0


@pytest.mark.parametrize("name", SCOPED)
def test_a_program_without_the_scope_leaves_the_metric_out(name):
    """The parent commit: its maps name part `ssm` and no sub-scope."""
    maps = [ScopeMap("jit__mq_traced", "prefill", {
        "fusion.21": Scoped("ssm", "fwd", "fusion", "", "")}, {})]
    run = run_of(DEVICE, HOST, STATS, {"prefill": {"width": 512}}, MODULES,
                 maps)
    assert read(name, run) is None
    assert read(name, run_of([ev("fusion.1", 0, 9, {"op": "fusion"})],
                             [ev("bench.window", 0, 10)])) is None


def test_readers_without_a_trace_give_none():
    for name in ("decode_round_ms.rag", "paged_decode_ms_round.rag",
                 "ssd_update_ms_round.rag", "ssd_update_roofline_pct.rag",
                 "ssd_chunk_ms_call.rag", "ssd_chunk_roofline_pct.rag",
                 "moe_stream_roofline_pct.rag", "host_gap_ms_round.rag",
                 "decode_wait_ms_round.rag"):
        assert read(name, {"engine_stats": {}}) is None
    assert read("experts_touched_share.rag", {}) is None
    assert read("expert_load_max_over_mean.rag", {}) is None
    assert read("expert_rows_here_share.rag", {}) is None
