"""The reason cell's own files on the CPU: its runner through the
benchmark's command at tiny widths (cells/serve_closed_reason.py), as it
stands and with the state kept at bf16's precision
(tools/nemotron_control.py); its configuration against the catalog's row and
the preset; what BENCHMARK.json gained; its per-layer readers on a
hand-built run and on a slice of a traced one
(perfbench/tests/test_reason_readers.py, whose cases run here so that the
tier-1 run holds them)."""
import importlib.util
import json
import os
import subprocess
import sys

import pytest

from perfbench import manifest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RUN = "import runpy; runpy.run_path('perfbench/run.py', run_name='__main__')"
CONTROL = os.path.join("perfbench", "tools", "nemotron_control.py")
CELL = "serve.nemotron-3-nano-30b-a3b.reason-closed"
GRANITE = "serve.granite-4.0-h-small.rag-closed"
PARENT = "e44ba59ac52d5c8061dde7b882f2d4e52aa11f29"
MINE = ["ssd_update_roofline_pct.reason", "ssd_chunk_roofline_pct.reason",
        "paged_decode_roofline_pct.reason", "moe_stream_roofline_pct.reason"]
MODEL = manifest.load_module("models", "nemotron_h")
with open(os.path.join(ROOT, "perfbench", "configs",
                       "nemotron-3-nano-30b-a3b.json")) as f:
    PUBLISHED = json.load(f)


def _readers():
    spec = importlib.util.spec_from_file_location(
        "perfbench_tests_reason_readers", os.path.join(
            ROOT, "perfbench", "tests", "test_reason_readers.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_mod = _readers()
test_the_bytes_and_operations_against_a_count_by_hand = \
    _mod.test_the_bytes_and_operations_against_a_count_by_hand
test_readers_on_a_run_that_names_everything = \
    _mod.test_readers_on_a_run_that_names_everything
test_a_program_without_the_names_reads_zero = \
    _mod.test_a_program_without_the_names_reads_zero
test_a_program_without_the_scope_leaves_the_metric_out = \
    _mod.test_a_program_without_the_scope_leaves_the_metric_out
test_readers_without_a_trace_give_none = \
    _mod.test_readers_without_a_trace_give_none
test_the_kernel_readers_on_a_slice_of_a_traced_run = \
    _mod.test_the_kernel_readers_on_a_slice_of_a_traced_run


def _rehearse(command, trace="0"):
    out = subprocess.run(
        command + ["--workload", CELL, "--seed", "3000000029", "--seconds",
                   "2"] + (["--trace", trace] if trace else []),
        capture_output=True, text=True, cwd=ROOT, timeout=900,
        env=dict(os.environ, JAX_PLATFORMS="cpu", PERFBENCH_REHEARSAL="1",
                 PYTHONPATH=ROOT))
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1]), out.stderr


def test_the_cell_rehearses_traced_and_reports_its_metrics():
    """The benchmark's command at the model module's rehearsal widths:
    correct, no request failed, every metric that lists the cell whose
    source a CPU trace can give, the state's size and the share's counters
    in the notes."""
    from perfbench import manifest as mf
    line, err = _rehearse([sys.executable, "-c", RUN], trace="1")
    assert line["correct"] and not line["failed"] and line["rehearsal"]
    # the 2 s window's count follows the machine's load: 36 alone, 13 beside
    # five other workers (the driver's run of PR 58's tree)
    assert line["attempted"] >= 6
    wanted = {m["name"] for group in ("end_to_end", "per_layer")
              for m in mf.cell_metrics(mf.load_manifest(), CELL, group)
              if m["source"] != "device_trace"}
    assert wanted <= set(line["metrics"])
    notes = line["notes"]
    tiny = {**PUBLISHED, **MODEL.REHEARSAL,
            "serve": {"params_dtype": "bfloat16"}}
    assert notes["state_bytes_per_slot"] == MODEL.state_bytes_per_slot(
        tiny, "float32")
    assert notes["state_mixer"] == "mamba2"
    assert notes["state_fine_share"] > 0.9
    moe = notes["moe"]
    assert moe["assignments_here"] + moe["assignments_absent"] \
        == moe["assignments"] == moe["tokens"] * 3 * 3
    assert "checked" in err and "probes' states read back" in err


def test_the_state_kept_at_bf16_is_not_correct():
    """tools/nemotron_control.py --control state-bf16 through the same
    runner: the state's fine share tells it, whatever the logits say."""
    line, err = _rehearse([sys.executable, CONTROL, "--control",
                           "state-bf16"], trace=None)
    assert not line["correct"]
    assert line["notes"]["state_fine_share"] < 0.5
    assert "CONTROL: the program keeps the recurrent state at bf16" in err


@pytest.mark.parametrize("control,fields", [
    ("relu", {"activation"}), ("no-scale", {"moe_routed_scaling_factor"})])
def test_a_control_builds_the_program_with_the_fact_wrong(control, fields):
    import dataclasses
    tool = manifest.load_module("tools", "nemotron_control")
    shared = manifest.load_module("tools", "granite_control")
    spec = importlib.util.spec_from_file_location(
        "nemotron_for_" + control.replace("-", "_"), os.path.join(
            ROOT, "perfbench", "models", "nemotron_h.py"))
    model = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(model)
    right = model.model_config(PUBLISHED, "bfloat16")
    shared.FACTS.update(tool.facts())
    shared.wrong_fact(model, control)
    wrong = model.model_config(PUBLISHED, "bfloat16")
    assert {f.name for f in dataclasses.fields(right)
            if getattr(right, f.name) != getattr(wrong, f.name)} == fields


def test_the_traffic_file_is_the_issues_mix():
    with open(os.path.join(ROOT, "perfbench", "traffic",
                           "reason-closed.json")) as f:
        mix = json.load(f)
    assert (mix["kind"], mix["runner"]) == ("closed", "serve_closed_reason")
    assert mix["prompt_len"] == {"median": 512, "sigma": 0.9, "min": 128,
                                 "max": 2048}
    assert mix["answer_len"] == {"median": 1536, "sigma": 0.6, "min": 384,
                                 "max": 4096}
    assert (mix["pool_requests"], mix["clients_per_slot"],
            mix["max_total_len"], mix["ramp_tokens"], mix["shape_seed"]) \
        == (256, 2, 6144, 80000, 20261003)
    assert mix["rehearsal"] == {"pool_requests": 16, "ramp_tokens": 300}
    serve = PUBLISHED["serve"]
    assert (serve["max_batch"], serve["max_seq_len"], serve["num_blocks"]) \
        == (192, 6144, 49152)


def test_benchmark_lists_the_cell_and_only_appends():
    from perfbench import manifest as mf
    man = mf.load_manifest()
    mine = [m["name"] for m in mf.cell_metrics(man, CELL, "per_layer")]
    # every metric the Granite cell reports whose reader knows no
    # configuration, and this cell's four shares of a roof
    theirs = [m["name"] for m in mf.cell_metrics(man, GRANITE, "per_layer")]
    roofs = ["ssd_update_roofline_pct.rag", "ssd_chunk_roofline_pct.rag",
             "moe_stream_roofline_pct.rag"]
    assert mine == [n for n in theirs if n not in roofs] + MINE
    for name in mine:
        assert mf.load_reader(name) is not None, name
    assert [m["name"] for m in mf.cell_metrics(man, CELL, "end_to_end")
            ] == ["serve_tok_s", "setup_s"]
    cell = mf.find_cell(man, CELL)
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    assert "stage 1 of 4: host, idle ~4x" in cell["why"]
    config = next(c for c in man["configs"]
                  if c["name"] == "nemotron-3-nano-30b-a3b")
    assert len(config["source"]) <= 200 and len(config["why"]) <= 200
    assert config["reduced"] == PUBLISHED["reduced"]
    assert len(man["per_layer"]) <= 128 and len(man["workloads"]) >= 12
    assert sum(w["chips"] == 4 for w in man["workloads"]) == 1
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 << 10
    parent = subprocess.run(["git", "show", PARENT + ":BENCHMARK.json"],
                            capture_output=True, text=True, cwd=ROOT)
    if parent.returncode:
        return      # a checkout without history: nothing to compare with
    was = json.loads(parent.stdout)
    had = {c["name"] for c in was["workloads"]}
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for old, new in zip(was[group], man[group]):
            new = dict(new)
            if "workloads" in new:
                added = [w for w in new["workloads"] if w not in had]
                assert new["workloads"][-len(added):] == added or not added
                new["workloads"] = [w for w in new["workloads"] if w in had]
            assert old == new, old["name"]
    assert was["command"] == man["command"]
    assert was["run_seconds"] == man["run_seconds"]
    # (later PRs append theirs behind these)
    assert [w["name"] for w in man["workloads"][len(was["workloads"]):]][
        :1] == [CELL]
    assert [m["name"] for m in man["per_layer"][len(was["per_layer"]):]][
        :len(MINE)] == MINE
