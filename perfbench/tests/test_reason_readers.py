"""The per-layer readers of ``serve.nemotron-3-nano-30b-a3b.reason-closed``
on a hand-built ``run``: a 10 ms window with two decode rounds of 192 and
160 rows over 900 and 700 blocks, 1.5 ms in the state's decode kernel and
0.5 ms in the paged decode kernel, a decode module whose instructions lie in
parts ``ssm`` and ``moe``, a prefill module with 2 ms under the
``ssm/ssd_chunk`` scope in two calls of 1,024 positions, and the engine's
`moe` counters of a model that holds half its experts; the same readers on a
program that names none of it; and the byte and operation functions
(``perfbench/nemotron_bytes.py``) against the sizes of the configuration's
table counted by hand."""
import json
import os

import pytest

from megatronapp_tpu.trace.scope_map import ScopeMap, Scoped
from perfbench import manifest as mf, nemotron_bytes, trace_reduce

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
with open(os.path.join(ROOT, "perfbench", "configs",
                       "nemotron-3-nano-30b-a3b.json")) as f:
    CONFIG = json.load(f)
MS = 1_000_000
KERNEL = {"op": "custom-call", "target": "tpu_custom_call"}
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
# the cell's own entries, and the rag cell's whose readers know no
# configuration and list this cell too
ROOFS = ["ssd_update_roofline_pct.reason", "paged_decode_roofline_pct.reason",
         "moe_stream_roofline_pct.reason"]
SCOPED = ["ssd_chunk_roofline_pct.reason"]
SHARED = ["decode_round_ms.rag", "decode_wait_ms_round.rag",
          "host_gap_ms_round.rag", "paged_decode_ms_round.rag",
          "ssd_update_ms_round.rag", "experts_touched_share.rag",
          "expert_load_max_over_mean.rag", "expert_rows_here_share.rag"]
MOE = {"decode_rounds": 10, "tokens": 1920, "assignments": 57600,
       "assignments_zero": 0, "assignments_here": 28900,
       "assignments_absent": 28700, "experts_here": 64,
       "expert_pairs_touched": 3168, "expert_pairs_possible": 3200,
       "here_max_rows": 1100}


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
            "engine_steps": [(0.0, 0.01, 192, 192), (0.01, 0.02, 160, 160)],
            "max_batch": 192, "scope_maps": list(maps)}


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
    "spans": [ev("mta.engine.decode_round", 1, 5,
                 {"batch": 192, "kv_blocks": 900}),
              ev("mta.engine.decode_round", 6, 8,
                 {"batch": 160, "kv_blocks": 700}),
              ev("mta.engine.prefill_call", 8, 9.4,
                 {"tokens": 1024, "width": 1024, "ssd_chunks": 8}),
              ev("mta.engine.prefill_call", 9.4, 10.9,
                 {"tokens": 100, "width": 1024, "ssd_chunks": 8})]}


def read(name, run):
    return mf.load_reader(name)(run)


def test_the_bytes_and_operations_against_a_count_by_hand():
    # 6 Mamba-2 layers x (read + write) x 128 x 4096 float32: a 2 MiB plane
    assert nemotron_bytes.ssd_update_bytes(CONFIG, 1) \
        == 6 * 2 * 128 * 4096 * 4 == 6 * 2 * (2 << 20) == 25_165_824
    assert nemotron_bytes.ssd_update_bytes(CONFIG, 352) == 352 * 25_165_824
    # a position of a layer's scan: 2 x (128 x 128 x 8 groups + 128 x 4096 +
    # 2 x 128 x 4096) = 3,407,872 operations
    assert nemotron_bytes.ssd_chunk_flops(CONFIG, 1) == 6 * 3_407_872
    # a call of 1024: x, B, C in (4096 + 2 x 8 x 128 = 6144 columns) and y
    # out (4096) at 2 B, dt 64 x 4 B a position, and the state in and out
    assert nemotron_bytes.ssd_chunk_bytes(CONFIG, 1, 1024) \
        == 6 * (1024 * (10_240 * 2 + 256) + 2 * 128 * 4096 * 4)
    # a round that touches every held pair streams 5 x 64 x 9,977,856 x 2 B
    # of experts (6.39 GB), and whatever it touches the shared experts'
    # 5 x 19,955,712 x 2 B and the routers' 5 x 344,064 x 4 B
    experts = 5 * 64 * 2 * 2688 * 1856 * 2
    always = 5 * (2 * 2688 * 3712 * 2 + 2688 * 128 * 4)
    assert (experts, always) == (6_385_827_840, 206_438_400)
    assert nemotron_bytes.moe_stream_bytes(CONFIG, 1, 1.0) \
        == experts + always
    assert nemotron_bytes.moe_stream_bytes(CONFIG, 4, 0.5) \
        == pytest.approx(4 * (0.5 * experts + always))
    # a block of 16 rows in both attention planes: 2 layers x (K + V) x 2
    # heads x 128 x 2 B = 2,048 B a cached token
    assert nemotron_bytes.paged_decode_read_bytes(CONFIG, 1) == 32_768
    assert nemotron_bytes.paged_decode_read_bytes(CONFIG, 1600) \
        == 1600 * 16 * 2048


def test_readers_on_a_run_that_names_everything():
    run = run_of(DEVICE, HOST, STATS, {
        "moe": MOE, "prefill": {"width": 1024, "calls": 40}}, MODULES, MAPS)
    assert read("decode_round_ms.rag", run) == pytest.approx(3.0)
    assert read("decode_wait_ms_round.rag", run) == pytest.approx(1.75)
    assert read("paged_decode_ms_round.rag", run) == pytest.approx(0.25)
    assert read("batch_occupancy.rag", run) == pytest.approx(
        100 * (192 + 160) / 2 / 192)
    assert read("ssd_update_ms_round.rag", run) == pytest.approx(0.75)
    least_s = 352 * 25_165_824 / 819e9
    assert read("ssd_update_roofline_pct.reason", run) \
        == pytest.approx(100 * least_s / 1.5e-3)
    least_s = 1600 * 32_768 / 819e9
    assert read("paged_decode_roofline_pct.reason", run) \
        == pytest.approx(100 * least_s / 0.5e-3)
    assert read("ssm_ms_round", run) == pytest.approx(0.75)
    assert read("moe_ms_round", run) == pytest.approx(1.0)
    assert read("ssd_chunk_ms_call.rag", run) == pytest.approx(1.0)
    # compute-bound at a call of 1,024: the operations' time is the larger
    flops_s = nemotron_bytes.ssd_chunk_flops(CONFIG, 2 * 1024) / 197e12
    bytes_s = nemotron_bytes.ssd_chunk_bytes(CONFIG, 2, 1024) / 819e9
    assert read("ssd_chunk_roofline_pct.reason", run) \
        == pytest.approx(100 * max(flops_s, bytes_s) / 2e-3)
    assert 0 < read("ssd_chunk_roofline_pct.reason", run) < 100
    assert read("experts_touched_share.rag", run) == pytest.approx(99.0)
    least_s = 2 * (0.99 * 6_385_827_840 + 206_438_400) / 819e9
    assert read("moe_stream_roofline_pct.reason", run) \
        == pytest.approx(100 * least_s / 2e-3)
    assert read("expert_load_max_over_mean.rag", run) \
        == pytest.approx(1100 * 64 / 28900)
    assert read("expert_rows_here_share.rag", run) \
        == pytest.approx(28900 / 57600)
    assert read("host_gap_ms_round.rag", run) == pytest.approx(2.0)


@pytest.mark.parametrize("name", ROOFS + SHARED)
def test_a_program_without_the_names_reads_zero(name):
    run = run_of([ev("fusion.1", 0, 9, {"op": "fusion"})],
                 [ev("bench.window", 0, 10)],
                 engine_stats={"moe": {"decode_rounds": 3, "assignments": 0,
                                       "expert_pairs_touched": 40,
                                       "expert_pairs_possible": 0}})
    assert read(name, run) == 0.0


@pytest.mark.parametrize("name", SCOPED + ["ssd_chunk_ms_call.rag"])
def test_a_program_without_the_scope_leaves_the_metric_out(name):
    """The parent commit under this PR's benchmark files: its maps name part
    `ssm` and, on another model, no sub-scope."""
    maps = [ScopeMap("jit__mq_traced", "prefill", {
        "fusion.21": Scoped("ssm", "fwd", "fusion", "", "")}, {})]
    run = run_of(DEVICE, HOST, STATS, {"prefill": {"width": 1024}}, MODULES,
                 maps)
    assert read(name, run) is None
    assert read(name, run_of([ev("fusion.1", 0, 9, {"op": "fusion"})],
                             [ev("bench.window", 0, 10)])) is None


def test_readers_without_a_trace_give_none():
    for name in ROOFS + SCOPED:
        assert read(name, {"engine_stats": {}}) is None


FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures",
                       "tpu_v5e_reason_95ms.json.gz")


def test_the_kernel_readers_on_a_slice_of_a_traced_run():
    """95 ms (three rounds and a tenth) cut out of a traced run of the cell
    on a TPU v5e (``tools/cut_fixture.py``; my chip run, PR 54, seed
    2871465301), with the ``mta.engine.decode_round`` spans' attributes
    beside it: the kernels by name, their device seconds, and the two
    shares of a roof they give with this cell's byte functions."""
    import gzip
    with gzip.open(FIXTURE, "rt") as f:
        trace = json.load(f)
    spans = trace.pop("spans")
    trace["rehearsal"] = False
    run = {"trace": trace, "config": CONFIG, "peaks": PEAKS,
           "device_summary": trace_reduce.device_summary(trace),
           "xplane_stats": {"spans": spans}, "engine_stats": {}}
    assert run["device_summary"]["window_s"] == pytest.approx(0.095)
    rounds = 95.0 / 30.6
    assert read("ssd_update_ms_round.rag", run) == pytest.approx(8.5, abs=0.3)
    assert read("paged_decode_ms_round.rag", run) == pytest.approx(
        6.3, abs=0.3)
    # every round ran its 192 slots: rows = rounds x 192
    rows = sum(s[3]["batch"] * min(s[1] + s[2], run["device_summary"][
        "window"][1]) / s[2] - s[3]["batch"] * max(
            s[1], run["device_summary"]["window"][0]) / s[2]
        for s in spans)
    assert rows == pytest.approx(rounds * 192, rel=0.02)
    update = read("ssd_update_roofline_pct.reason", run)
    assert update == pytest.approx(
        100 * rows * 25_165_824 / 819e9
        / (read("ssd_update_ms_round.rag", run) * 1e-3 * rows / 192),
        rel=0.03)
    assert 60 < update < 75
    assert 10 < read("paged_decode_roofline_pct.reason", run) < 15
