"""The reduction from a trace to device numbers, on a hand-made trace whose
numbers can be added up on paper, and on a recorded TPU v5e trace: 230 ms
(two decode rounds) cut by tools/cut_fixture.py out of the first traced run of
serve.gpt3-2.7b.batch-closed on the chip (PR 25)."""
import os

import pytest

from perfbench import trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))
FIXTURE = os.path.join(HERE, "fixtures", "tpu_v5e_serve_230ms.json.gz")


def _hlo(short, op, target=None):
    text = f"%{short} = f32[8,128]{{1,0:T(8,128)}} {op}(f32[8,128] %x)"
    if target:
        text += f', custom_call_target="{target}"'
    return text


def _trace(device_events, spans, devices=1):
    planes = []
    for d in range(devices):
        events = []
        for text, start, dur in device_events:
            short, info = tr.parse_hlo(text)
            events.append([short, start, dur, info])
        planes.append({"name": f"/device:TPU:{d}", "lines": [
            {"name": "Steps", "events": [["0", 0, 10_000, {}]]},
            {"name": "XLA Ops", "events": events}]})
    planes.append({"name": "/host:CPU", "lines": [
        {"name": "python3", "events": [[n, s, d, {}] for n, s, d in spans]}]})
    return {"planes": planes}


HAND = _trace(
    [   # a while of 600 ns holding three operations, then two more
        (_hlo("while.7", "while"), 100, 600),
        (_hlo("fusion.1", "fusion"), 100, 200),
        (_hlo("custom-call.3", "custom-call", "tpu_custom_call"), 300, 150),
        (_hlo("all-reduce.2", "all-reduce"), 500, 200),
        (_hlo("custom-call.9", "custom-call", "AllocateBuffer"), 800, 50),
        (_hlo("all-gather-start.4", "all-gather-start"), 900, 2),
        (_hlo("all-gather-done.4", "all-gather-done"), 950, 150),   # to 1100
    ],
    [("bench.window", 0, 1000), ("bench.engine_step", 650, 300),
     ("bench.prefill", 700, 100)])


def test_parse_hlo_reads_tpu_names():
    text = ('%while.275 = (s32[]{:T(128)}, bf16[4,1024,1024]{2,1,0:T(8,128)'
            '(2,1)S(1)}, /*index=5*/f32[24,1024]{1,0:T(8,128)}) while((s32[]'
            '{:T(128)}, bf16[4,1024]) %tuple.1), condition=%c, body=%b')
    short, info = tr.parse_hlo(text)
    assert (short, info["op"]) == ("while.275", "while")
    short, info = tr.parse_hlo(
        '%custom-call.61 = bf16[1024,2048]{1,0:T(8,128)(2,1)S(1)} custom-call('
        'bf16[8] %p), custom_call_target="tpu_custom_call", operand_layout')
    assert (short, info["op"], info["target"]) == (
        "custom-call.61", "custom-call", "tpu_custom_call")
    assert tr.parse_hlo("dot.188") == ("dot.188", {})


def test_hand_made_trace_adds_up():
    s = tr.device_summary(HAND)
    assert s["window"] == (0, 1000)
    assert s["window_s"] == pytest.approx(1000e-9)
    # busy: [100,700) + [800,850) + [900,902) + [950,1000) = 702 ns
    assert s["busy_s"] == pytest.approx(702e-9)
    # Pallas: only the tpu_custom_call, 150 ns (AllocateBuffer is no kernel)
    assert tr.summed_s(HAND, s["window"], tr.is_pallas) == pytest.approx(150e-9)
    # collectives: all-reduce 200 + all-gather-done clipped to the window 50
    assert tr.summed_s(HAND, s["window"], tr.is_collective) == \
        pytest.approx(250e-9)
    # the enclosing while is not counted among the operations
    assert all(not name.startswith("while") for name, _ in s["device_ops"])
    assert s["device_ops"][0][0].startswith("fusion.1")
    assert s["device_ops"][0][1] == pytest.approx(200e-9)
    # idle: [0,100) outside; [700,800) its middle 750 lies in bench.prefill
    # (innermost); [850,900) in engine_step; [902,950) in engine_step
    gaps = dict(s["idle_gaps"])
    assert gaps == {"outside any harness span": pytest.approx(100e-9),
                    "bench.prefill": pytest.approx(100e-9),
                    "bench.engine_step": pytest.approx(98e-9)}
    assert s["busy_s"] + sum(gaps.values()) == pytest.approx(s["window_s"])


def test_busy_is_averaged_over_devices():
    two = _trace([(_hlo("fusion.1", "fusion"), 0, 400)],
                 [("bench.window", 0, 1000)], devices=2)
    two["planes"][1]["lines"][1]["events"][0][2] = 800
    s = tr.device_summary(two)
    assert s["devices"] == 2
    assert s["busy_s_per_device"] == [pytest.approx(400e-9),
                                      pytest.approx(800e-9)]
    assert s["busy_s"] == pytest.approx(600e-9)


def test_no_device_plane_gives_nothing():
    host_only = {"planes": [HAND["planes"][-1]]}
    assert tr.device_summary(host_only) is None
    assert tr.summed_s(host_only, (0, 1000), tr.is_pallas) is None


def _host_xla_trace(**marks):
    """What a CPU gives: XLA's host threads and the harness's spans."""
    return dict(marks, planes=[{"name": "/host:CPU", "lines": [
        {"name": "tf_XLAEigen/123", "events": [["dot.1", 100, 300, {}]]},
        {"name": "python3", "events": [["bench.window", 0, 1000, {}]]}]}])


def test_host_threads_stand_in_only_in_a_rehearsal():
    """A trace that lost its device plane on a chip reports nothing, so that
    the line is refused; only a rehearsal reads XLA's host threads."""
    assert tr.device_op_events(_host_xla_trace()) == {}
    assert tr.device_summary(_host_xla_trace()) is None
    assert tr.summed_s(_host_xla_trace(), (0, 1000), lambda e: True) is None
    s = tr.device_summary(_host_xla_trace(rehearsal=True))
    assert s["busy_s"] == pytest.approx(300e-9)
    assert s["window_s"] == pytest.approx(1000e-9)


@pytest.mark.parametrize("rehearsal", [False, True])
def test_no_window_span_gives_nothing(rehearsal):
    """Without the ``bench.window`` span there is no window to measure
    against: the events' own extent would make busy = window."""
    no_span = {"planes": [p for p in HAND["planes"]
                          if p["name"] != "/host:CPU"],
               "rehearsal": rehearsal}
    assert tr.device_op_events(no_span)
    assert tr.device_summary(no_span) is None


def _sweep_busy(events, lo, hi):
    """Busy time by a different route: sweep over the sorted end points."""
    points = []
    for _, start, dur, _ in events:
        a, b = max(start, lo), min(start + dur, hi)
        if b > a:
            points += [(a, 1), (b, -1)]
    busy, depth, last = 0, 0, None
    for t, step in sorted(points):
        if depth > 0:
            busy += t - last
        depth += step
        last = t
    return busy


def test_recorded_tpu_trace():
    trace = tr.load_fixture(FIXTURE)
    names = [p["name"] for p in trace["planes"]]
    assert "/device:TPU:0" in names and "/host:CPU" in names
    s = tr.device_summary(trace)
    lo, hi = s["window"]
    ops = tr.device_op_events(trace)["/device:TPU:0"]
    assert 0 < s["busy_s"] <= s["window_s"]
    assert s["busy_s"] * 1e9 == pytest.approx(_sweep_busy(ops, lo, hi))
    pallas = [e for e in tr.leaves(ops) if tr.is_pallas(e)]
    assert pallas and all(e[3]["op"] == "custom-call" for e in pallas)
    by_hand = sum(min(e[1] + e[2], hi) - max(e[1], lo) for e in pallas
                  if min(e[1] + e[2], hi) > max(e[1], lo))
    assert tr.summed_s(trace, s["window"], tr.is_pallas) * 1e9 == \
        pytest.approx(by_hand)
    assert tr.summed_s(trace, s["window"], tr.is_collective) == 0
    spans = {e[0] for e in tr.host_spans(trace)}
    assert {"bench.window", "bench.engine_step"} <= spans
    assert s["busy_s"] + sum(g for _, g in s["idle_gaps"]) == \
        pytest.approx(s["window_s"], rel=1e-6)
    # The numbers of this recording, as the two routes above give them.
    assert s["window_s"] == pytest.approx(0.230)
    assert s["busy_s"] == pytest.approx(0.223768338)
    assert tr.summed_s(trace, s["window"], tr.is_pallas) == \
        pytest.approx(0.050226412)
    assert s["idle_gaps"] == [["bench.decode_round",
                               pytest.approx(0.006231662)]]
    assert s["device_ops"][0][0].startswith("closed_call.13 [tpu_custom_call]")
