"""Every cell of BENCHMARK.json through the real command line at tiny widths
on the CPU (PERFBENCH_REHEARSAL=1): control flow, the last line's keys,
`correct: true`. The four-chip cell runs on four virtual CPU devices. A
traced leg checks everything but the device plane, which only a chip has.
About four minutes in all."""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    MANIFEST = json.load(f)


def _run(cell, trace, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PERFBENCH_REHEARSAL="1",
               XLA_FLAGS=f"--xla_force_host_platform_device_count="
                         f"{cell['chips']}", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, os.path.join(ROOT, "perfbench", "run.py"),
         "--workload", cell["name"], "--seed", "3000000019", "--seconds", "3",
         "--trace", str(trace)], capture_output=True, text=True, env=env,
        cwd=ROOT, timeout=600)


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", MANIFEST["workloads"],
                         ids=lambda c: c["name"])
def test_cell_rehearses(cell, trace):
    out = _run(cell, trace)
    assert out.returncode == 0, out.stderr[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert set(line) >= {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert line["device"]["platform"] == "cpu" and line["rehearsal"] is True
    assert line["device"]["count"] == cell["chips"]
    groups = ["end_to_end"] + (["per_layer"] if trace else [])
    for group in groups:
        for m in MANIFEST[group]:
            if "workloads" in m and cell["name"] not in m["workloads"]:
                continue
            if m["source"] == "device_trace":
                continue        # no device plane on the CPU
            got = line["metrics"][m["name"]]
            assert got["unit"] == m["unit"] and got["value"] > 0
    if trace:
        assert 0 < line["device"]["busy_s"] <= line["device"]["window_s"]
        assert len(line["breakdown"]["device_ops"]) <= 10


def test_refuses_without_a_tpu():
    cell = MANIFEST["workloads"][0]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PERFBENCH_REHEARSAL", None)
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "perfbench", "run.py"),
         "--workload", cell["name"], "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env, cwd=ROOT,
        timeout=300)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "not a TPU" in out.stderr
