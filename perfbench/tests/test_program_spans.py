"""The readers of the program's own spans, counters and kernel names
(ISSUE 26), each on a hand-built ``run``: a 10 ms window with two decode
rounds, one idle gap inside a round, one between the rounds and one inside a
prefill span (which the decode loop is not charged for)."""
import json
import os

import pytest

from perfbench import manifest as mf
from perfbench import program_spans as ps
from perfbench import trace_reduce

MS = 1_000_000
KERNEL = {"op": "custom-call", "target": "tpu_custom_call"}
FUSION = {"op": "fusion"}
SERVE = ["decode_round_ms.batch", "decode_wait_ms_round.batch",
         "host_gap_ms_round.batch", "paged_decode_ms_round.batch"]
COUNTERS = ["prefill_share.batch", "queue_wait_ms.batch"]
FLASH = ["flash_fwd_ms_step", "flash_bwd_ms_step"]


def ev(name, start_ms, end_ms, info=None):
    return [name, round(start_ms * MS), round((end_ms - start_ms) * MS),
            dict(info or {})]


def trace_of(device_lines, host_events):
    planes = [{"name": f"/device:TPU:{i}",
               "lines": [{"name": "XLA Ops", "events": events}]}
              for i, events in enumerate(device_lines)]
    planes.append({"name": "/host:CPU",
                   "lines": [{"name": "stepper", "events": host_events}]})
    return {"planes": planes}


def run_of(trace, **extra):
    run = {"trace": trace,
           "device_summary": trace_reduce.device_summary(trace)}
    run.update(extra)
    return run


DEVICE = [ev("fusion.1", 0, 1, FUSION),
          ev("paged_decode.1", 1, 2, KERNEL),
          # idle 2.0 to 2.5: inside the first round
          ev("fusion.2", 2.5, 4, FUSION),
          # idle 4 to 5: between the rounds
          ev("paged_decode.1", 5, 6, KERNEL),
          ev("fusion.3", 6, 9.2, FUSION),
          # idle 9.2 to 9.6: inside a prefill span
          ev("paged_mq.2", 9.6, 10, KERNEL)]
HOST = [ev("bench.window", 0, 10),
        ev("mta.engine.step", 0.9, 4.1),
        ev("mta.engine.decode_round", 1, 4),
        ev("mta.engine.decode.stage", 1, 1.5),
        ev("mta.engine.decode.wait", 1.5, 3.5),
        ev("mta.engine.decode.record", 3.5, 4),
        ev("mta.driver.deliver", 4.2, 4.6),
        ev("mta.engine.step", 4.9, 9.9),
        ev("mta.engine.decode_round", 5, 9),
        ev("mta.engine.decode.wait", 5.5, 8.5),
        ev("mta.engine.prefill", 9, 9.8),
        ev("mta.engine.prefill_call", 9.1, 9.7)]


def read(name, run):
    return mf.load_reader(name)(run)


def test_serving_readers_on_two_rounds():
    run = run_of(trace_of([DEVICE], HOST))
    assert read("decode_round_ms.batch", run) == pytest.approx(3.5)
    assert read("decode_wait_ms_round.batch", run) == pytest.approx(2.5)
    # 0.5 ms inside a round + 1.0 ms between the rounds; the 0.4 ms inside
    # the prefill span is not the decode loop's.
    assert read("host_gap_ms_round.batch", run) == pytest.approx(0.75)
    # Two runs of paged_decode, 1 ms each; paged_mq is another family.
    assert read("paged_decode_ms_round.batch", run) == pytest.approx(1.0)


def test_a_round_across_the_window_edge_counts_by_its_share():
    host = [ev("bench.window", 2, 10)] + HOST[1:]
    spans = trace_reduce.host_spans(trace_of([DEVICE], host), prefix="mta.")
    assert ps.rounds_in(spans, (2 * MS, 10 * MS)) == pytest.approx(5 / 3)
    assert ps.clipped_s(spans, "mta.engine.decode.wait",
                        (2 * MS, 10 * MS)) == pytest.approx(4.5e-3)


@pytest.mark.parametrize("name", SERVE)
def test_zero_when_the_program_names_nothing(name):
    """The parent commit of the PR that added the spans and names, or a
    later program that dropped a phase: a number, never a missing metric
    (run.py refuses a traced line that lacks one)."""
    anonymous = [[("closed_call.13" if e[3].get("target") else e[0]),
                  e[1], e[2], e[3]] for e in DEVICE]
    run = run_of(trace_of([anonymous], [ev("bench.window", 0, 10),
                                        ev("bench.engine_step", 1, 4)]))
    assert read(name, run) == 0.0


@pytest.mark.parametrize("name", SERVE + FLASH)
def test_none_only_without_a_device_summary(name):
    run = {"trace": trace_of([DEVICE], HOST), "device_summary": None,
           "traced_steps": 2}
    assert read(name, run) is None


def test_counter_readers():
    steps = {"step": {"count": 90, "total_s": 10.0, "max_s": 2.0},
             "prefill": {"count": 9, "total_s": 6.5, "max_s": 1.9},
             "queue_wait": {"count": 4, "total_s": 2.0, "max_s": 1.0}}
    run = {"engine_stats": {"steps": steps}}
    assert read("prefill_share.batch", run) == pytest.approx(65.0)
    assert read("queue_wait_ms.batch", run) == pytest.approx(500.0)
    for name in COUNTERS:
        # An engine without the counters (the parent commit) reads 0; a
        # run with no engine at all has nothing to say.
        assert read(name, {"engine_stats": {"engine": "dynamic"}}) == 0.0
        assert read(name, {"engine_stats": {"steps": {
            "step": {"count": 0, "total_s": 0.0, "max_s": 0.0}}}}) == 0.0
        assert read(name, {}) is None


def test_flash_readers_split_the_pallas_time():
    """Two chips, two steps; under autodiff the names are wrapped
    (jvp_..., transpose_jvp_...): matched anywhere in the name."""
    def chip(shift):
        return [ev("jvp_flash_fwd_t_.1", 0 + shift, 2 + shift, KERNEL),
                ev("fusion.7", 2 + shift, 3 + shift, FUSION),
                ev("transpose_jvp_flash_bwd_dq_t__.1", 3 + shift,
                   4.5 + shift, KERNEL),
                ev("transpose_jvp_flash_bwd_dkv_t__.2", 4.5 + shift,
                   7 + shift, KERNEL),
                ev("flash_fwd.9", 7 + shift, 8 + shift, KERNEL)]
    trace = trace_of([chip(0), chip(0.5)], [ev("bench.window", 0, 10)])
    run = run_of(trace, traced_steps=2)
    fwd, bwd = (read(n, run) for n in FLASH)
    assert fwd == pytest.approx(1.5) and bwd == pytest.approx(2.0)
    assert fwd + bwd == pytest.approx(read("pallas_ms_step", run))
    # Dense attention in their place (cell 1): 0 and 0, not missing.
    dense = run_of(trace_of([[ev("fusion.1", 0, 9, FUSION)]],
                            [ev("bench.window", 0, 10)]), traced_steps=2)
    assert [read(n, dense) for n in FLASH] == [0.0, 0.0]


def test_idle_by_innermost_span():
    from perfbench.tools import idle_by_span
    out = idle_by_span.report(trace_of([DEVICE], HOST))
    idle = out["idle_s_by_span"]
    assert idle["mta.engine.decode.wait"] == pytest.approx(0.5e-3)
    # 4.0-4.1 in the step's own time, 4.2-4.6 in the driver's callbacks,
    # 4.9-5.0 in the next step before its round; the rest outside.
    assert idle["mta.engine.step"] == pytest.approx(0.2e-3)
    assert idle["mta.driver.deliver"] == pytest.approx(0.4e-3)
    assert idle["outside"] == pytest.approx(0.4e-3)
    assert idle["mta.engine.prefill_call"] == pytest.approx(0.4e-3)
    assert sum(idle.values()) == pytest.approx(out["idle_s"]) \
        == pytest.approx(1.9e-3)
    assert out["decode_rounds"] == pytest.approx(2.0)
    assert out["spans_ms"]["mta.engine.decode_round"] == {
        "count": 2, "median": 3.5, "max": 4.0}


def test_every_new_metric_has_its_reader_and_its_cells():
    with open(os.path.join(mf.ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    cells = {c["name"]: c for c in manifest["workloads"]}
    for name in SERVE + COUNTERS + FLASH:
        assert mf.load_reader(name) is not None
        entry = by_name[name]
        for cell in entry["workloads"]:
            traffic = mf.load_traffic(cells[cell])
            kind = "serve" if name.endswith(".batch") else "pretrain"
            assert traffic["runner"].startswith(kind), (name, cell)
