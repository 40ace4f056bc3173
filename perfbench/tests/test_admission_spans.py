"""The readers of the admission path's spans (ISSUE 50), each on a hand-built
``run``: a window from 0.5 to 20 ms with three pure decode steps and two that
admit (one request, then two), a step that began before the window and one
that ends after it, and idle gaps of the first chip in every kind of place:
inside a phase, inside a container, in the driver's callbacks, between steps.
"""
import json
import os

import pytest

from perfbench import admission_spans as adm
from perfbench import manifest as mf
from perfbench import program_spans as ps
from perfbench.tests.test_program_spans import (
    FUSION, KERNEL, MS, ev, read, run_of, trace_of,
)

E = "mta.engine."
CELLS = ["serve.gpt3-2.7b.batch-closed",
         "serve.deepseek-v2-lite.longgen-closed",
         "serve.jamba2-3b.chat-closed", "serve.evabyte-6.5b.bytegen-closed",
         "serve.longcat-flash-chat.agent-closed",
         "serve.lfm2-24b-a2b.assist-closed", "serve.laguna-xs.2.code-closed"]
# What only ISSUE 50's spans can say, and what the parent's say too.
NEW = ["first_sample_wait_ms", "stage_sample_ms_round", "stage_put_ms_round",
       "stage_dispatch_ms_round"]
OLD_TOO = ["admit_gap_ms_step", "round_gap_ms_round",
           "idle_unnamed_share.serve", "prefill_call_device_ms",
           "prefill_call_host_ms", "prefill_fill_share", "rounds_ahead_share"]
# Read from attributes, which the dense cell's runner does not keep.
ATTRIBUTED = ["prefill_fill_share", "rounds_ahead_share"]

# The first chip is busy except 0.5-0.8, 2.0-2.5, 4.05-5.05, 6.65-7.0,
# 7.7-8.3, 11.2-11.8, 15.5-15.8 and 19.2-19.7: 4.15 ms of the window.
DEVICE = [ev("fusion.0", 0, 0.5, FUSION), ev("fusion.1", 0.8, 2.0, FUSION),
          ev("paged_decode.1", 2.5, 4.05, KERNEL),
          ev("paged_mq.2", 5.05, 6.65, KERNEL),
          ev("fusion.2", 7.0, 7.7, FUSION), ev("fusion.3", 8.3, 11.2, FUSION),
          ev("fusion.4", 11.8, 15.5, FUSION),
          ev("fusion.5", 15.8, 19.2, FUSION), ev("fusion.6", 19.7, 22, FUSION)]


def step(start, end):
    return ev(E + "step", start, end)


def round_(start, end, batch, ahead, stage=None, wait=None):
    """A decode round; `stage` = the four edges of its children (three where
    no round runs ahead: no sampler's part)."""
    out = [ev(E + "decode_round", start, end, {"batch": batch,
                                               "ahead": ahead})]
    if stage:
        out.append(ev(E + "decode.stage", stage[0], stage[-1]))
        names = ("sample", "put", "dispatch")[4 - len(stage):]
        out += [ev(f"{E}decode.stage.{n}", a, b)
                for n, a, b in zip(names, stage, stage[1:])]
    if wait:
        out.append(ev(E + "decode.wait", *wait))
    return out


def call(start, end, tokens):
    return ev(E + "prefill_call", start, end, {"tokens": tokens, "width": 32})


HOST = [
    ev("bench.window", 0.5, 20),
    # began before the window: what it leaves idle inside is the rounds'
    step(0.0, 0.9),
    call(0.3, 0.7, 5),
    step(1.0, 4.0),
    *round_(1.1, 3.9, 2, 1, (1.2, 1.5, 1.7, 2.0), (2.0, 3.5)),
    ev(E + "decode.record", 3.5, 3.9),
    ev("mta.driver.deliver", 4.1, 4.4),
    step(5.0, 11.0),
    ev(E + "admit", 5.1, 8.0),
    ev(E + "prefill", 5.2, 7.9, {"rid": 7, "prompt_tokens": 40}),
    call(5.3, 6.0, 32), call(6.0, 6.6, 8),
    ev(E + "prefill.sample", 6.7, 7.8, {"rid": 7}),
    *round_(8.1, 10.9, 2, 1, (8.2, 8.4, 8.7, 9.0), (9.0, 10.5)),
    step(11.5, 14.0),
    *round_(11.6, 13.9, 3, 0, (11.7, 12.0, 12.3), (12.3, 13.5)),
    step(14.5, 19.0),
    ev(E + "admit", 14.6, 17.05),
    ev(E + "prefill", 14.7, 15.9, {"rid": 8}), call(14.8, 15.2, 16),
    ev(E + "prefill.sample", 15.3, 15.8, {"rid": 8}),
    ev(E + "prefill", 16.0, 17.0, {"rid": 9}), call(16.1, 16.5, 24),
    ev(E + "prefill.sample", 16.6, 16.9, {"rid": 9}),
    *round_(17.1, 18.9, 4, 1, (17.2, 17.4, 17.7, 18.0), (18.0, 18.8)),
    # ends after the window: begins in it, so it counts as a step
    step(19.5, 22.0),
    *round_(19.6, 21.9, 4, 1, (19.7, 19.9, 20.1, 20.3), (20.3, 21.5)),
]
ROUNDS = 4 + 0.4 / 2.3
# 9 ms of the prefill step on the device; 4.5 calls in the window
TABLE = {"seconds": {("prefill", "attention", "fwd"): 0.006,
                     ("prefill", "mlp", "fwd"): 0.003,
                     ("decode", "mlp", "fwd"): 0.005}}


def _run(host=HOST, attributes=True, **extra):
    """What run.py hands a reader: the trace's events carry no attribute
    (load_xplane drops them); a runner that kept them has them in
    ``xplane_stats``."""
    bare = [[n, s, d, {}] for n, s, d, _ in host]
    run = run_of(trace_of([DEVICE], bare), scope_time=TABLE, **extra)
    if attributes:
        run["xplane_stats"] = {"spans": sorted(
            (e for e in host if e[0].startswith("mta.")),
            key=lambda e: e[1])}
    return run


def _parent(host=HOST):
    """The program before ISSUE 50: no first-sample span, no child of the
    stage, and a step that says `active` and `waiting`."""
    gone = (E + "prefill.sample", E + "decode.stage.")
    return [[n, s, d, {"active": 2, "waiting": 0} if n == E + "step"
             else attrs]
            for n, s, d, attrs in host if not n.startswith(gone)]


@pytest.mark.parametrize("attributes", [True, False])
def test_the_two_gaps_split_the_idle_to_the_nanosecond(attributes):
    """Steps B and D admit (one prefill span inside B, two inside D): 1.0 +
    0.3 ms of idle; A, C and E do not and share the rest with the step
    before the window, the driver and the stepper between steps: 2.85 ms.
    The spans' names and nesting say it all, so a runner that kept no
    attributes (serve_closed.py) and the parent read the same."""
    run = _run(attributes=attributes)
    facts = adm.of(run)
    assert facts["idle_ns"] == round(4.15 * MS)
    assert sum(b - a for a, b in ps.first_chip_idle(run)) == facts["idle_ns"]
    assert (facts["admit_steps"], facts["pure_steps"]) == (2, 3)
    assert facts["admit_idle_ns"] == round(1.3 * MS)
    assert facts["admit_idle_ns"] + facts["round_idle_ns"] \
        == facts["idle_ns"]
    admit = read("admit_gap_ms_step", run)
    rest = read("round_gap_ms_round", run)
    assert admit == pytest.approx(0.65) and rest == pytest.approx(0.95)
    assert (admit * 2 + rest * 3) * MS == pytest.approx(facts["idle_ns"],
                                                        abs=1)
    parent = adm.of(_run(_parent(), attributes=attributes))
    for key in ("idle_ns", "admit_idle_ns", "round_idle_ns", "admit_steps",
                "pure_steps"):
        assert parent[key] == facts[key]


def test_idle_by_phase_and_the_share_no_phase_names():
    run = _run()
    spans = run["xplane_stats"]["spans"]
    gaps = ps.first_chip_idle(run)
    by_name = adm.idle_by_name(gaps, adm.innermost_pieces(spans))
    # the sweep is program_spans' quadratic walk, piece for piece
    assert by_name == ps.idle_by_innermost(gaps, spans)
    assert {k: round(v / MS, 6) for k, v in by_name.items()} == {
        E + "step": 0.45, E + "prefill_call": 0.2, "outside": 1.25,
        "mta.driver.deliver": 0.3,
        E + "decode.wait": 0.5, E + "prefill": 0.15,
        E + "prefill.sample": 0.7, E + "admit": 0.1,
        E + "decode_round": 0.3, E + "decode.stage.sample": 0.1,
        E + "decode.stage.put": 0.1}
    assert read("idle_unnamed_share.serve", run) \
        == pytest.approx(100 * 2.25 / 4.15)
    # The parent names less: the first samples' 0.7 ms fall to the prefill
    # spans that hold them, the stage's 0.2 ms to the stage.
    parent = _run(_parent())
    assert read("idle_unnamed_share.serve", parent) \
        == pytest.approx(100 * (2.25 + 0.7 + 0.2) / 4.15)


def test_an_admissions_own_spans():
    run = _run()
    assert read("first_sample_wait_ms", run) == pytest.approx(0.5)
    assert read("prefill_call_host_ms", run) == pytest.approx(0.5)
    # 80 prompt tokens in four calls of 32 rows; the call across the lower
    # edge began before the window
    assert read("prefill_fill_share", run) == pytest.approx(62.5)
    assert adm.of(run)["prefill_calls_in"] == pytest.approx(4.5)
    assert read("prefill_call_device_ms", run) == pytest.approx(2.0)
    assert read("rounds_ahead_share", run) == pytest.approx(80.0)
    for child, ms in (("sample", 0.9), ("put", 1.2), ("dispatch", 1.2)):
        assert read(f"stage_{child}_ms_round", run) \
            == pytest.approx(ms / ROUNDS)
    stage = ps.clipped_s(run["xplane_stats"]["spans"], adm.STAGE,
                         run["device_summary"]["window"])
    assert sum(read(f"stage_{c}_ms_round", run)
               for c in ("sample", "put", "dispatch")) \
        == pytest.approx(stage * 1e3 / ROUNDS)


def test_a_runner_that_kept_no_attributes_reads_no_attribute():
    """serve_closed.py (the dense cell): the calls' fill and the share of
    rounds ahead have nothing to read there (BENCHMARK.json does not list
    the cell for them) and say 0.0, not the engine's life-long counters;
    what needs no attribute reads as in any cell."""
    run, kept = _run(attributes=False), _run()
    run["engine_stats"] = {
        "steps": {"rounds_ahead": 30, "decode_round": {"count": 40}},
        "prefill": {"fill_share": 0.55}}
    for name in NEW + OLD_TOO:
        if name in ATTRIBUTED:
            assert read(name, run) == 0.0 < read(name, kept)
        else:
            assert read(name, run) == read(name, kept) > 0


@pytest.mark.parametrize("name", NEW + OLD_TOO)
def test_a_number_and_never_none(name):
    """The driver runs the parent under these files, and run.py refuses a
    traced line that lacks a metric: the parent's spans, a program that
    names nothing and a run without a trace all read a number; what only
    ISSUE 50's names can say reads 0.0 on the parent, the rest what it
    reads on the change."""
    parent = _run(_parent())
    value = read(name, parent)
    assert isinstance(value, float)
    if name in NEW:
        assert value == 0.0
    else:
        assert value > 0
        if name != "idle_unnamed_share.serve":
            assert value == read(name, _run())
    anonymous = run_of(trace_of([DEVICE], [ev("bench.window", 0.5, 20),
                                           ev("bench.engine_step", 1, 4)]))
    unnamed = 100.0 if name == "idle_unnamed_share.serve" else 0.0
    assert read(name, anonymous) == unnamed
    anonymous["xplane_stats"] = {"spans": []}
    del anonymous["admission_spans"]
    assert read(name, anonymous) == unnamed
    busy = run_of(trace_of([[ev("fusion.1", 0, 21, FUSION)]], HOST[:1]))
    assert read(name, busy) == 0.0
    assert read(name, {"trace": trace_of([DEVICE], HOST),
                       "device_summary": None}) == 0.0


def test_one_parse_a_run(monkeypatch):
    parses = []
    parse = adm._parse
    monkeypatch.setattr(adm, "_parse",
                        lambda run: parses.append(1) or parse(run))
    run = _run()
    values = [read(name, run) for name in NEW + OLD_TOO]
    assert len(parses) == 1 and all(v > 0 for v in values)
    assert read("admit_gap_ms_step", _run()) and len(parses) == 2


def test_the_eleven_entries_list_the_serving_cells_that_can_read_them():
    with open(os.path.join(mf.ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entries = manifest["per_layer"][-11:]
    assert [m["name"] for m in entries] == list(adm.METRICS)
    assert sorted(adm.METRICS) == sorted(NEW + OLD_TOO)
    cells = {c["name"]: c for c in manifest["workloads"]}
    for m in entries:
        assert mf.load_reader(m["name"]) is not None
        # the dense cell's runner keeps no attributes
        assert m["workloads"] == CELLS[m["name"] in ATTRIBUTED:]
        assert m["moves"] == "serve_tok_s"
        assert m["layer"] == ("device" if m["name"].startswith("idle_")
                              else "serving engine")
        for c in m["workloads"]:
            runner = mf.load_traffic(cells[c])["runner"]
            assert runner.startswith("serve")
            assert (runner == "serve_closed") == (c == CELLS[0])
