"""The per-layer readers of ``serve.evabyte-6.5b.bytegen-closed`` on a
hand-built ``run``: a 10 ms window with two decode rounds whose tables hold
4,000 and 2,400 rows, a prefill call between them, 2 ms in the paged
decode kernel and 0.3 ms in the summariser; and the same readers on a
program that names none of it."""
import pytest

from perfbench import eva_bytes, manifest as mf, trace_reduce

MS = 1_000_000
KERNEL = {"op": "custom-call", "target": "tpu_custom_call"}
CONFIG = {"num_hidden_layers": 8, "hidden_size": 4096,
          "num_attention_heads": 32, "num_key_value_heads": 32,
          "chunk_size": 16, "window_size": 2048,
          "serve": {"kv_cache_dtype": "bfloat16"}}
PEAKS = {"hbm_bytes_per_s": 819e9}
NAMES = ["decode_round_ms.bytegen", "decode_wait_ms_round.bytegen",
         "host_gap_ms_round.bytegen", "prefill_share.bytegen",
         "paged_decode_ms_round.bytegen",
         "paged_decode_roofline_pct.bytegen", "eva_summary_ms_round.bytegen",
         "eva_summary_roofline_pct.bytegen", "rows_walked_share.bytegen",
         "queue_wait_ms.bytegen"]


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
            "ttft_ms": [900.0, 700.0, 1100.0],
            # 197 plain rounds and three gaps that hold another's prefill
            "itl_ms": [18.0] * 197 + [60.0, 740.0, 2050.0]}


DEVICE = [ev("paged_decode.12", 1, 2, KERNEL),
          ev("eva_summary.3", 2, 2.1, KERNEL),
          ev("fusion.7", 2.1, 4, {"op": "fusion"}),
          ev("paged_mq.5", 5, 5.5, KERNEL),
          ev("eva_summary.4", 5.5, 5.6, KERNEL),
          ev("paged_decode.12", 6, 7, KERNEL),
          ev("eva_summary.3", 7, 7.1, KERNEL)]
HOST = [ev("bench.window", 0, 10),
        ev("mta.engine.decode_round", 1, 4.5),
        ev("mta.engine.decode.wait", 2, 4),
        ev("mta.engine.prefill", 4.8, 5.8),
        ev("mta.engine.prefill_call", 4.9, 5.7),
        ev("mta.engine.decode_round", 6, 8.5)]
STATS = {"spans": [
    ev("mta.engine.decode_round", 1, 4.5,
       {"batch": 32, "kv_rows": 4000, "kv_tokens": 13000, "summaries": 2}),
    ev("mta.engine.prefill_call", 4.9, 5.7, {"tokens": 32, "summaries": 2}),
    ev("mta.engine.decode_round", 6, 8.5,
       {"batch": 24, "kv_rows": 2400, "kv_tokens": 9000, "summaries": 3})]}
ENGINE = {"steps": {"step": {"total_s": 8.0}, "prefill": {"total_s": 2.0},
                    "queue_wait": {"count": 4, "total_s": 6.0}},
          "eva": {"rows_walked": 2900, "rows_full_attention": 10000}}


def read(name, run):
    return mf.load_reader(name)(run)


def test_byte_functions():
    # a row of one layer: 32 heads x 128 x 2 B x (K + V)
    assert eva_bytes.paged_decode_read_bytes(CONFIG, 1) == 8 * 16384
    assert eva_bytes.eva_summary_bytes(CONFIG, 1) == 8 * 17 * 16384


def test_readers_on_a_run_that_names_everything():
    run = run_of(DEVICE, HOST, STATS, ENGINE)
    assert read("decode_round_ms.bytegen", run) == pytest.approx(3.0)
    assert read("decode_wait_ms_round.bytegen", run) == pytest.approx(1.0)
    assert read("paged_decode_ms_round.bytegen", run) == pytest.approx(1.0)
    # three calls of the summariser, 0.1 ms each, over two rounds
    assert read("eva_summary_ms_round.bytegen", run) == pytest.approx(0.15)
    assert read("prefill_share.bytegen", run) == pytest.approx(25.0)
    # the window's rounds, not the engine's life-long counters: R(T)
    # summed over T + 1 summed (the context and the row a round appends)
    assert read("rows_walked_share.bytegen", run) == pytest.approx(
        100 * 6400 / (22000 + 56))
    assert read("queue_wait_ms.bytegen", run) == pytest.approx(1500.0)
    assert read("ttft_p50_ms.bytegen", run) == pytest.approx(900.0)
    assert read("itl_p50_ms.bytegen", run) == pytest.approx(18.0)
    # one in a thousand of 200 gaps: between the two longest
    assert 740.0 < read("itl_p999_ms.bytegen", run) < 2050.0
    least_s = 6400 * 8 * 16384 / 819e9
    assert read("paged_decode_roofline_pct.bytegen", run) \
        == pytest.approx(100 * least_s / 2e-3)
    # the rounds' and the prefill call's chunks: 2 + 2 + 3
    least_s = 7 * 8 * 17 * 16384 / 819e9
    assert read("eva_summary_roofline_pct.bytegen", run) \
        == pytest.approx(100 * least_s / 0.3e-3)
    for name in ("paged_decode_roofline_pct.bytegen",
                 "eva_summary_roofline_pct.bytegen"):
        assert 0 < read(name, run) < 100
    # idle 0-1, 4-5, 5.6-6, 7.1-10 less the prefill span's 4.8-5.8
    assert read("host_gap_ms_round.bytegen", run) == pytest.approx(
        (1 + .8 + .2 + 2.9) / 2)


@pytest.mark.parametrize("name", NAMES)
def test_a_program_without_the_names_reads_zero(name):
    """A program with no EVA attention: no span attribute, no summariser,
    no `eva` counters, no kept stats."""
    run = run_of([ev("fusion.1", 0, 9, {"op": "fusion"})],
                 [ev("bench.window", 0, 10)])
    assert read(name, run) == 0.0
