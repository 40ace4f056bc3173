"""The per-layer readers of ``train.mellum2-12b-a2.5b.packed-8k`` on a
hand-built ``run``: a 100 ms traced window of two steps with 30 ms in the
window layers' flash kernels, 10 ms in the full layer's, 20 ms in the
operations ``lax.ragged_dot`` lowers to, a train module whose instructions
lie in parts ``attention`` and ``moe``, and one ``mta.train.sync`` span with
the interval's counters; and the same readers on a program that names none
of it (the parent commit under this PR's benchmark files)."""
import pytest

from megatronapp_tpu.trace.scope_map import ScopeMap, Scoped
from perfbench import manifest as mf, mellum_flops, trace_reduce

MS = 1_000_000
KERNEL = {"op": "custom-call", "target": "tpu_custom_call"}
CONFIG = {"hidden_size": 2304, "head_dim": 128, "num_attention_heads": 32,
          "num_key_value_heads": 4, "moe_intermediate_size": 896,
          "num_experts": 16, "router_width": 64, "num_experts_per_tok": 8,
          "sliding_window": 1024, "vocab_size": 24576,
          "num_hidden_layers": 4,
          "layer_types": ["sliding_attention"] * 3 + ["full_attention"]}
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
NAMES = ["moe_ms_step", "flash_window_ms_step.packed8k",
         "flash_window_roofline_pct.packed8k",
         "expert_gemm_roofline_pct.packed8k",
         "expert_rows_here_share.packed8k",
         "expert_load_max_over_mean.packed8k"]
# two steps of 8 micro-batches of 8,192 tokens, 4 layers, 8 picks
SYNC = {"steps": 2, "assignments": 2 * 65536 * 8 * 4,
        "assignments_here": 80_000.0, "assignments_absent": 4_114_304.0,
        "here_max_rows": 7_000.0, "experts_here": 2 * 8 * 4 * 16,
        "moe_layer_passes": 2 * 8 * 4, "router_loss": 0.064}


def ev(name, start_ms, end_ms, info=None):
    return [name, round(start_ms * MS), round((end_ms - start_ms) * MS),
            dict(info or {})]


def run_of(device, stats=None, modules=(), maps=(), pairs=None):
    trace = {"planes": [
        {"name": "/device:TPU:0",
         "lines": [{"name": "XLA Ops", "events": device},
                   {"name": "XLA Modules", "events": list(modules)}]},
        {"name": "/host:CPU", "lines": [{"name": "main", "events": [
            ev("bench.window", 0, 100)]}]}]}
    return {"kind": "train", "trace": trace, "config": CONFIG,
            "peaks": PEAKS, "traced_steps": 2,
            "device_summary": trace_reduce.device_summary(trace),
            "xplane_stats": stats, "scope_maps": list(maps),
            "window_pairs_traced": pairs}


DEVICE = [ev("jvp_flash_window_fwd.3", 1, 6, KERNEL),
          ev("jvp_flash_fwd.4", 6, 10, KERNEL),
          ev("ragged-dot-none.8", 10, 18, {"op": "custom-call"}),
          ev("fusion.7", 18, 30, {"op": "fusion"}),
          ev("transpose_jvp_flash_window_bwd_dq.5", 40, 50, KERNEL),
          ev("transpose_jvp_flash_window_bwd_dkv.6", 50, 65, KERNEL),
          ev("transpose_jvp_flash_bwd_dq.9", 65, 71, KERNEL),
          ev("ragged-dot-none.11", 71, 83, {"op": "custom-call"})]
MODULES = [ev("jit_step(1)", 0.5, 99)]
MAPS = [ScopeMap("jit_step", "train", {
    "ragged-dot-none.8": Scoped("moe", "fwd", "custom-call", "", ""),
    "ragged-dot-none.11": Scoped("moe", "bwd", "custom-call", "", ""),
    "fusion.7": Scoped("moe", "fwd", "fusion", "", ""),
    "jvp_flash_window_fwd.3": Scoped("attention", "fwd", "custom-call", "",
                                     "", "window")}, {})]
STATS = {"spans": [ev("mta.train.step", 0.2, 0.4, {"iteration": 41}),
                   ev("mta.train.sync", 0.5, 99.5, SYNC),
                   # the interval before the traced one closed outside it
                   ev("mta.train.sync", -50, -0.5, SYNC)]}
PAIRS = 2 * 8 * 2_000_000          # two steps of eight rows


def readings(run):
    return {name: mf.load_reader(name)(run) for name in NAMES}


def test_the_counts_by_hand():
    assert mellum_flops.expert_flops(CONFIG) == 3 * 2 * 2304 * 896
    assert mellum_flops.window_pair_flops(CONFIG, 10) == (
        3 * 3 * 2 * 2 * 128 * 32 * 10)
    assert mellum_flops.expert_gemm_flops(CONFIG, 10) == (
        3 * 3 * 2 * 2304 * 896 * 10)


def test_readers_on_a_run_that_names_everything():
    got = readings(run_of(DEVICE, STATS, MODULES, MAPS, PAIRS))
    # 8 + 12 ms of ragged-dot and 12 ms of a fusion in part moe, two steps
    assert got["moe_ms_step"] == pytest.approx(16.0)
    # 5 + 10 + 15 ms in flash_window_*; the full layer's kernels not counted
    assert got["flash_window_ms_step.packed8k"] == pytest.approx(15.0)
    least = mellum_flops.window_pair_flops(CONFIG, PAIRS) / 197e12
    assert got["flash_window_roofline_pct.packed8k"] == pytest.approx(
        100 * least / 0.030)
    assert 0 < got["flash_window_roofline_pct.packed8k"] <= 100
    least = mellum_flops.expert_gemm_flops(CONFIG, 80_000) / 197e12
    assert got["expert_gemm_roofline_pct.packed8k"] == pytest.approx(
        100 * least / 0.020)
    assert 0 < got["expert_gemm_roofline_pct.packed8k"] <= 100
    assert got["expert_rows_here_share.packed8k"] == pytest.approx(
        80_000 / 4_194_304)
    # the most loaded expert's rows a layer pass over the mean's
    assert got["expert_load_max_over_mean.packed8k"] == pytest.approx(
        (7_000 / 64) / (80_000 / 1024))


def test_a_program_without_the_names_leaves_the_metrics_out():
    """The parent: no window kernel, no train spans; the scope readers of a
    program that registers no such part read 0.0, the others None."""
    device = [ev("jvp_flash_fwd.4", 6, 10, KERNEL),
              ev("fusion.7", 18, 30, {"op": "fusion"})]
    got = readings(run_of(device, {"spans": []}, MODULES, (), None))
    assert got.pop("moe_ms_step") == 0.0
    assert set(got.values()) == {None}


def test_readers_without_a_trace_give_none():
    run = {"kind": "train", "config": CONFIG, "peaks": PEAKS,
           "traced_steps": 0, "device_summary": None}
    for name in NAMES[1:]:
        assert mf.load_reader(name)(run) is None, name
