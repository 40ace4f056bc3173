"""The doc cell's own files on the CPU: its runner through the benchmark's
command at tiny widths (cells/serve_closed_doc.py), as it stands and with
the state kept at bf16's precision (tools/solar_control.py); its
configuration against the catalog's row; its traffic file; what
BENCHMARK.json gained; its per-layer reader on a hand-built run and on a
slice of a traced one (perfbench/tests/test_doc_readers.py, whose cases run
here so that the tier-1 run holds them)."""
import importlib.util
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from perfbench import manifest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RUN = "import runpy; runpy.run_path('perfbench/run.py', run_name='__main__')"
CONTROL = os.path.join("perfbench", "tools", "solar_control.py")
CELL = "serve.solar-open2-250b.doc-closed"
REASON = "serve.nemotron-3-nano-30b-a3b.reason-closed"
PARENT = "815dd0dfe8dd7d09ba84d09ba448d0e1c79db7fe"
MINE = "kda_update_roofline_pct.doc"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
MODEL = manifest.load_module("models", "solar_open2")
with open(os.path.join(ROOT, "perfbench", "configs",
                       "solar-open2-250b.json")) as f:
    PUBLISHED = json.load(f)


def _readers():
    spec = importlib.util.spec_from_file_location(
        "perfbench_tests_doc_readers", os.path.join(
            ROOT, "perfbench", "tests", "test_doc_readers.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_mod = _readers()
test_the_bytes_against_a_count_by_hand = \
    _mod.test_the_bytes_against_a_count_by_hand
test_the_reader_from_known_rows_and_seconds = \
    _mod.test_the_reader_from_known_rows_and_seconds
test_without_a_device_summary_none_without_the_kernel_zero = \
    _mod.test_without_a_device_summary_none_without_the_kernel_zero
test_the_reader_on_a_slice_of_a_traced_run = \
    _mod.test_the_reader_on_a_slice_of_a_traced_run
test_the_trace_of_sizes_is_the_same_for_two_seeds = \
    _mod.test_the_trace_of_sizes_is_the_same_for_two_seeds


def _rehearse(command, trace="0"):
    out = subprocess.run(
        command + ["--workload", CELL, "--seed", "3000000059", "--seconds",
                   "2"] + (["--trace", trace] if trace else []),
        capture_output=True, text=True, cwd=ROOT, timeout=900,
        env=dict(os.environ, JAX_PLATFORMS="cpu", PERFBENCH_REHEARSAL="1",
                 PYTHONPATH=ROOT))
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1]), out.stderr


def test_the_cell_rehearses_traced_and_reports_its_metrics():
    """The benchmark's command at the model module's rehearsal widths:
    correct, no request failed, every metric that lists the cell whose
    source a CPU trace can give, the state's size, the probes' gap and the
    share's counters in the notes."""
    from perfbench import manifest as mf
    line, err = _rehearse([sys.executable, "-c", RUN], trace="1")
    assert line["correct"] and not line["failed"] and line["rehearsal"]
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
    assert notes["state_mixer"] == "kda"
    assert notes["state_fine_share"] > 0.9
    assert notes["state_gap"] < 0.1
    moe = notes["moe"]
    assert moe["assignments_here"] + moe["assignments_absent"] \
        == moe["assignments"] == moe["tokens"] * 3 * 4
    assert "checked" in err and "probes' states lie" in err


def test_the_state_kept_at_bf16_is_not_correct():
    """tools/solar_control.py --control state-bf16 through the same runner:
    the state's fine share tells it, whatever the logits say."""
    line, err = _rehearse([sys.executable, CONTROL, "--control",
                           "state-bf16"], trace=None)
    assert not line["correct"]
    assert line["notes"]["state_fine_share"] < 0.5
    assert "CONTROL: the program keeps the recurrent state at bf16" in err


@pytest.mark.parametrize("control", ["beta-1", "one-decay", "no-conv",
                                     "no-gate"])
def test_a_control_hands_the_reference_a_wrong_model(control):
    tool = manifest.load_module("tools", "solar_control")
    spec = importlib.util.spec_from_file_location(
        "solar_for_" + control.replace("-", "_"), os.path.join(
            ROOT, "perfbench", "models", "solar_open2.py"))
    model = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(model)
    tool.wrong_model(model, control)
    fields = tool.WRONG_MODELS[control][0]
    assert set(fields) <= set(model.CONTROLS)
    for name in ("reference_hidden", "reference_state"):
        assert getattr(model, name).keywords == fields


def test_the_configuration_is_the_catalogs_row_but_for_reduced():
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Solar-Open2-250B")
    assert PUBLISHED["source"] == row["source_url"]
    differs = {k for k, v in row["config"].items() if PUBLISHED.get(k) != v}
    assert differs == set(PUBLISHED["reduced"])
    assert {k: row["config"][k] for k in differs} == PUBLISHED["published"]
    assert set(PUBLISHED["assumed"]) >= {"kda_gates", "gqa_gate", "router",
                                         "router_bias", "init"}
    serve = PUBLISHED["serve"]
    assert (serve["max_batch"], serve["max_seq_len"], serve["num_blocks"],
            serve["state_dtype"]) == (128, 18432, 65536, "float32")
    assert PUBLISHED["expert_share"]["first"] == 0
    assert PUBLISHED["expert_share"]["of_chips"] == 8


def test_the_traffic_file_is_the_issues_mix():
    with open(os.path.join(ROOT, "perfbench", "traffic",
                           "doc-closed.json")) as f:
        mix = json.load(f)
    assert (mix["kind"], mix["runner"]) == ("closed", "serve_closed_doc")
    assert mix["prompt_len"] == {"median": 4096, "sigma": 0.9, "min": 512,
                                 "max": 16384}
    assert mix["answer_len"] == {"median": 512, "sigma": 0.7, "min": 128,
                                 "max": 2048}
    assert (mix["pool_requests"], mix["clients_per_slot"],
            mix["max_total_len"], mix["ramp_tokens"]) == (128, 2, 18432,
                                                          32768)
    assert mix["rehearsal"] == {"pool_requests": 16, "ramp_tokens": 300}
    from perfbench.traffic import request_sizes
    prompts, answers = request_sizes(mix, 128, 1.0)
    assert int((prompts + answers).max()) <= PUBLISHED["serve"]["max_seq_len"]
    # what the callers hold in flight at the mix's mean fits the pool
    assert 128 * float(np.mean(prompts + answers / 2)) \
        < PUBLISHED["serve"]["num_blocks"] * 16


def test_benchmark_lists_the_cell_and_only_appends():
    from perfbench import manifest as mf
    man = mf.load_manifest()
    mine = [m["name"] for m in mf.cell_metrics(man, CELL, "per_layer")]
    # every metric the reason cell reports whose reader knows neither its
    # kernels nor its bytes, and this cell's one share of a roof
    theirs = [m["name"] for m in mf.cell_metrics(man, REASON, "per_layer")]
    assert mine == [n for n in theirs if not n.startswith("ssd_")
                    and not n.endswith(".reason")] + [MINE]
    for name in mine:
        assert mf.load_reader(name) is not None, name
    assert [m["name"] for m in mf.cell_metrics(man, CELL, "end_to_end")
            ] == ["serve_tok_s", "setup_s"]
    cell = mf.find_cell(man, CELL)
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    assert "stage 1 of 12: host, idle ~12x" in cell["why"]
    config = next(c for c in man["configs"]
                  if c["name"] == "solar-open2-250b")
    assert len(config["source"]) <= 200 and len(config["why"]) <= 200
    assert config["reduced"] == PUBLISHED["reduced"]
    assert len(man["per_layer"]) <= 128 and len(man["workloads"]) >= 13
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
        :1] == [MINE]
    # nothing the parent's benchmark had is edited or gone
    changed = subprocess.run(
        ["git", "diff", "--name-status", PARENT, "--", "perfbench"],
        capture_output=True, text=True, cwd=ROOT).stdout.split("\n")
    assert all(line.startswith("A") for line in changed if line), changed
