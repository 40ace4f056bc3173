import copy

from perfbench import lastline

WANTED = {"train_tok_s_chip": "tokens/s/chip", "setup_s": "s",
          "step_ms": "ms"}
GOOD = {
    "correct": True, "attempted": 40, "failed": 0,
    "metrics": {"train_tok_s_chip": {"value": 30123.4, "unit": "tokens/s/chip"},
                "setup_s": {"value": 31.2, "unit": "s"},
                "step_ms": {"value": 543.2, "unit": "ms"}},
    "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
               "memory_peak_bytes": 13958643712, "busy_s": 5.1,
               "window_s": 5.4},
    "breakdown": {"device_ops": [["fusion", 3.2]], "idle_gaps": []},
}


def _faults(**changes):
    line = copy.deepcopy(GOOD)
    for path, value in changes.items():
        node = line
        *parents, leaf = path.split("/")
        for p in parents:
            node = node[p]
        if value is None:
            del node[leaf]
        else:
            node[leaf] = value
    return lastline.faults(line, WANTED, chips=1, traced=True)


def test_a_good_line_passes():
    assert _faults() == []


def test_refusals():
    assert any("step_ms" in f for f in _faults(**{"metrics/step_ms": None}))
    assert any("unit" in f for f in _faults(
        **{"metrics/step_ms": {"value": 1.0}}))
    assert any("unit" in f for f in _faults(
        **{"metrics/step_ms": {"value": 1.0, "unit": "s"}}))
    assert any("finite" in f for f in _faults(
        **{"metrics/step_ms": {"value": float("nan"), "unit": "ms"}}))
    assert any("busy_s" in f for f in _faults(**{"device/busy_s": 0.0}))
    assert any("busy_s" in f for f in _faults(**{"device/busy_s": 6.0}))
    assert any("busy_s" in f for f in _faults(**{"device/busy_s": None}))
    assert any("count" in f for f in _faults(**{"device/count": 4}))
    assert any("memory_peak" in f for f in _faults(
        **{"device/memory_peak_bytes": 0}))
    assert any("device" in f for f in _faults(device=None))
    assert any("breakdown" in f for f in _faults(
        **{"breakdown/device_ops": [["x", 1.0]] * 11}))
    assert any("not one of" in f for f in _faults(
        **{"metrics/extra": {"value": 1.0, "unit": "ms"}}))


def test_untraced_line_needs_no_busy():
    line = copy.deepcopy(GOOD)
    del line["device"]["busy_s"], line["device"]["window_s"]
    del line["breakdown"]
    assert lastline.faults(line, WANTED, chips=1, traced=False) == []
