"""The rag cell's own files on the CPU: its runner through the benchmark's
command at tiny widths (cells/serve_closed_rag.py), as it stands and with
the state kept at bf16's precision (tools/granite_control.py); its
configuration against the catalog's row; what BENCHMARK.json gained; and its
per-layer readers on a hand-built run (perfbench/tests/test_rag_readers.py,
whose cases run here so that the tier-1 run holds them)."""
import importlib.util
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RUN = "import runpy; runpy.run_path('perfbench/run.py', run_name='__main__')"
CONTROL = os.path.join("perfbench", "tools", "granite_control.py")
CELL = "serve.granite-4.0-h-small.rag-closed"
PARENT = "77aa706ceaa8277d66d65f69ae67ef7cf7e40296"
MINE = [
    "decode_round_ms.rag", "decode_wait_ms_round.rag",
    "host_gap_ms_round.rag", "batch_occupancy.rag",
    "paged_decode_ms_round.rag", "ssd_update_ms_round.rag",
    "ssd_update_roofline_pct.rag", "ssd_chunk_ms_call.rag",
    "ssd_chunk_roofline_pct.rag", "moe_stream_roofline_pct.rag",
    "experts_touched_share.rag", "expert_load_max_over_mean.rag",
    "expert_rows_here_share.rag"]


def _readers():
    spec = importlib.util.spec_from_file_location(
        "perfbench_tests_rag_readers", os.path.join(
            ROOT, "perfbench", "tests", "test_rag_readers.py"))
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


def _rehearse(command):
    out = subprocess.run(
        command + ["--workload", CELL, "--seed", "3000000029", "--seconds",
                   "2"],
        capture_output=True, text=True, cwd=ROOT, timeout=900,
        env=dict(os.environ, JAX_PLATFORMS="cpu", PERFBENCH_REHEARSAL="1",
                 PYTHONPATH=ROOT))
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1]), out.stderr


def test_the_cell_rehearses_correct_and_its_checks_read_something():
    line, err = _rehearse([sys.executable, "-c", RUN, "--trace", "0"])
    assert line["correct"] is True, err[-3000:]
    assert line["failed"] == 0 and line["attempted"] > 4
    assert set(line["metrics"]) == {"serve_tok_s", "setup_s"}
    notes = line["notes"]
    # tiny widths: 4 layers, top-3, 4 of 8 experts held
    moe = notes["moe"]
    assert moe["assignments"] == moe["tokens"] * 3 * 4 > 0
    assert moe["assignments_here"] + moe["assignments_absent"] \
        == moe["assignments"]
    assert moe["assignments_here"] > 0 < moe["assignments_absent"]
    # 3 Mamba-2 layers x (16 x 128 x 4 B + 3 x 160 x 2 B)
    assert notes["state_bytes_per_slot"] == 3 * (16 * 128 * 4 + 3 * 160 * 2)
    assert notes["state_mixer"] == "mamba2"
    assert notes["state_dropped"] == 0 and notes["state_resets"] > 4
    assert notes["state_probes"] >= 1 and notes["state_fine_share"] > 0.99
    # a rehearsal checks every request
    assert notes["reference_checked"] == notes["reference_checked_of"] > 4
    assert notes["reference_positions"] == notes["reference_positions_of"]
    # 1 plane x (K + V) x 2 heads x 16 x 2 B x 16 rows a block
    assert "pool 48 blocks x 2048 B" in err


def test_a_state_kept_at_bf16_is_not_correct():
    """The control the state's limit is sized by, through the tool: the
    pool's size and the logits do not tell it, the fine share does."""
    line, err = _rehearse([sys.executable, CONTROL, "--control",
                           "state-bf16"])
    assert line["correct"] is False
    assert line["notes"]["state_fine_share"] == 0.0
    problems = [ln for ln in err.splitlines() if "not correct" in ln]
    assert len(problems) == 1 and "recurrent state" in problems[0]


def test_the_configuration_is_the_catalog_rows_but_for_its_cut():
    """Every number of the catalog's `config` under the same key, but the
    four keys in `reduced`, whose published values stand beside them."""
    with open(os.path.join(ROOT, "perfbench", "configs",
                           "granite-4.0-h-small.json")) as f:
        mine = json.load(f)
    assert mine["reduced"] == ["num_hidden_layers", "layer_types",
                               "num_local_experts", "vocab_size"]
    assert (mine["num_hidden_layers"], mine["num_local_experts"],
            mine["vocab_size"]) == (10, 36, 50176)
    assert mine["layer_types"] == ["mamba"] * 5 + ["attention"] \
        + ["mamba"] * 4
    assert mine["published"]["num_hidden_layers"] == 40
    assert mine["layer_types"] == mine["published"]["layer_types"][:10]
    assert len(mine["published"]["layer_types"]) == 40
    assert mine["published"]["num_local_experts"] == 72
    assert mine["published"]["vocab_size"] == 100352
    assert mine["expert_share"]["first"] == 0
    assert mine["expert_share"]["of_chips"] == 2
    assert "8 chips" in mine["deployment"]
    assert "4 pipeline stages" in mine["deployment"]
    for key, value in {
            "hidden_size": 4096, "intermediate_size": 768,
            "shared_intermediate_size": 1536, "num_experts_per_tok": 10,
            "num_attention_heads": 32, "num_key_value_heads": 8,
            "mamba_n_heads": 128, "mamba_d_head": 64, "mamba_d_state": 128,
            "mamba_n_groups": 1, "mamba_d_conv": 4, "mamba_expand": 2,
            "mamba_chunk_size": 256, "embedding_multiplier": 12,
            "attention_multiplier": 0.0078125, "residual_multiplier": 0.22,
            "logits_scaling": 16, "rms_norm_eps": 1e-5,
            "position_embedding_type": "nope",
            "max_position_embeddings": 131072}.items():
        assert mine[key] == value, key
    serve = mine["serve"]
    assert (serve["max_batch"], serve["max_seq_len"], serve["num_blocks"]) \
        == (64, 7168, 16384)
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        return
    with open(catalog) as f:
        row, = (json.loads(ln) for ln in f
                if '"granite-4.0-h-small"' in ln)
    assert mine["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key not in mine["reduced"]:
            assert mine[key] == value, key
    assert mine["layer_types"] == row["config"]["layer_types"][:10]
    assert mine["published"]["num_hidden_layers"] \
        == row["config"]["num_hidden_layers"]


def test_the_traffic_is_the_issues():
    with open(os.path.join(ROOT, "perfbench", "traffic",
                           "rag-closed.json")) as f:
        mix = json.load(f)
    assert (mix["kind"], mix["runner"]) == ("closed", "serve_closed_rag")
    assert mix["prompt_len"] == {"median": 1536, "sigma": 0.8, "min": 256,
                                 "max": 6144}
    assert mix["answer_len"] == {"median": 320, "sigma": 0.7, "min": 64,
                                 "max": 1024}
    assert (mix["pool_requests"], mix["clients_per_slot"],
            mix["max_total_len"], mix["ramp_tokens"], mix["shape_seed"]) \
        == (128, 2, 7168, 12000, 20261002)


def test_benchmark_lists_the_cell_and_only_appends():
    from perfbench import admission_spans, manifest as mf
    manifest = mf.load_manifest()
    mine = [m["name"] for m in mf.cell_metrics(manifest, CELL, "per_layer")]
    assert mine == [
        "attention_ms_round", "mlp_ms_round", "moe_ms_round", "ssm_ms_round",
        "head_sampler_ms_round", "scope_other_ms_round",
        "prefill_device_share", "scope_unmatched_share.serve"] + list(
        admission_spans.METRICS) + MINE
    for name in mine:
        assert mf.load_reader(name) is not None, name
    assert [m["name"] for m in mf.cell_metrics(manifest, CELL, "end_to_end")
            ] == ["serve_tok_s", "setup_s"]
    cell = mf.find_cell(manifest, CELL)
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    assert "stage 1 of 4: host, idle ~4x" in cell["why"]
    assert "8.9 of 17.8 rows an expert" in cell["why"]
    config = next(c for c in manifest["configs"]
                  if c["name"] == "granite-4.0-h-small")
    assert len(config["source"]) <= 200 and len(config["why"]) <= 200
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 << 10
    parent = subprocess.run(["git", "show", PARENT + ":BENCHMARK.json"],
                            capture_output=True, text=True, cwd=ROOT)
    if parent.returncode:
        return      # a checkout without history: nothing to compare with
    was = json.loads(parent.stdout)
    had = {c["name"] for c in was["workloads"]}
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for old, new in zip(was[group], manifest[group]):
            new = dict(new)
            if "workloads" in new:
                added = [w for w in new["workloads"] if w not in had]
                assert new["workloads"][-len(added):] == added or not added
                new["workloads"] = [w for w in new["workloads"] if w in had]
            assert old == new, old["name"]
    assert was["command"] == manifest["command"]
    assert was["run_seconds"] == manifest["run_seconds"]
    assert manifest["workloads"][len(was["workloads"])]["name"] == CELL
    assert [m["name"] for m in manifest["per_layer"][len(was["per_layer"]):]
            ][:len(MINE)] == MINE


@pytest.mark.parametrize("control", ["attention-scale", "residual-1",
                                     "no-renorm"])
def test_a_control_builds_the_program_with_the_fact_wrong(control):
    """tools/granite_control.py's wrong facts are the program's own fields:
    the patched builder gives the model with exactly that one changed."""
    from perfbench import manifest as mf
    tool = mf.load_module("tools", "granite_control")
    spec = importlib.util.spec_from_file_location(
        "granite_for_" + control.replace("-", "_"), os.path.join(
            ROOT, "perfbench", "models", "granite_moe_hybrid.py"))
    model = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(model)
    with open(os.path.join(ROOT, "perfbench", "configs",
                           "granite-4.0-h-small.json")) as f:
        config = json.load(f)
    right = model.model_config(config, "bfloat16")
    tool.wrong_fact(model, control)
    wrong = model.model_config(config, "bfloat16")
    import dataclasses
    differ = {f.name for f in dataclasses.fields(right)
              if getattr(right, f.name) != getattr(wrong, f.name)}
    assert differ == set(tool.FACTS[control][0])
