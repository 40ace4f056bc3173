"""The assist cell's own files on the CPU: its runner through the benchmark's
command at tiny widths (cells/serve_closed_conv.py), its configuration
against the catalog's row, and its per-layer readers on a hand-built run
(perfbench/tests/test_assist_readers.py, whose cases run here so that the
tier-1 run holds them)."""
import importlib.util
import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RUN = "import runpy; runpy.run_path('perfbench/run.py', run_name='__main__')"
CELL = "serve.lfm2-24b-a2b.assist-closed"
PARENT = "8990e6eb6b234fc4771a27bc0ee95fed50267a37"


def _readers():
    spec = importlib.util.spec_from_file_location(
        "perfbench_tests_assist_readers", os.path.join(
            ROOT, "perfbench", "tests", "test_assist_readers.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_mod = _readers()
test_the_bytes_against_a_count_by_hand = \
    _mod.test_the_bytes_against_a_count_by_hand
test_readers_on_a_run_that_names_everything = \
    _mod.test_readers_on_a_run_that_names_everything
test_a_program_without_the_names_reads_zero = \
    _mod.test_a_program_without_the_names_reads_zero
test_readers_without_a_trace_give_none = \
    _mod.test_readers_without_a_trace_give_none


def test_the_cell_rehearses_correct_and_its_counters_add_up():
    out = subprocess.run(
        [sys.executable, "-c", RUN, "--workload", CELL, "--seed",
         "3000000029", "--seconds", "2", "--trace", "0"],
        capture_output=True, text=True, cwd=ROOT, timeout=900,
        env=dict(os.environ, JAX_PLATFORMS="cpu", PERFBENCH_REHEARSAL="1",
                 PYTHONPATH=ROOT))
    assert out.returncode == 0, out.stderr[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is True, out.stderr[-3000:]
    assert line["failed"] == 0 and line["attempted"] > 4
    assert set(line["metrics"]) == {"serve_tok_s", "setup_s"}
    notes = line["notes"]
    # tiny widths: 4 MoE layers, top-2; 4 convolution layers x 2 x 64 x 2 B
    moe = notes["moe"]
    assert moe["assignments"] == moe["tokens"] * 2 * 4 > 0
    assert notes["state_bytes_per_slot"] == 4 * 2 * 64 * 2
    assert notes["state_dropped"] == 0 and notes["state_resets"] > 4
    # a rehearsal checks every request; the probes' columns are the
    # reference's to bf16's rounding
    assert notes["reference_checked"] == notes["reference_checked_of"] > 4
    assert notes["reference_positions"] == notes["reference_positions_of"]
    assert notes["tail_probes"] == 3
    assert notes["tail_distance_first"] < 0.01 > 0
    assert notes["tail_distance"] < 0.05
    # 1 plane x (K + V) x 2 heads x 16 x 2 B x 16 rows a block
    assert "pool 48 blocks x 2048 B" in out.stderr


def test_the_configuration_is_the_catalog_rows_but_for_its_cut():
    """Every number of the catalog's `config` under the same key, but the
    three keys in `reduced`, whose published values stand beside them."""
    with open(os.path.join(ROOT, "perfbench", "configs",
                           "lfm2-24b-a2b.json")) as f:
        mine = json.load(f)
    assert mine["reduced"] == ["num_hidden_layers", "layer_types",
                               "num_dense_layers"]
    assert (mine["num_hidden_layers"], mine["num_dense_layers"]) == (9, 1)
    full = mine["published"]["layer_types"]
    assert len(full) == mine["published"]["num_hidden_layers"] == 40
    assert mine["layer_types"] == full[1:10]
    assert mine["published"]["num_dense_layers"] == 2
    for key, value in {
            "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
            "intermediate_size": 11776, "moe_intermediate_size": 1536,
            "norm_eps": 1e-5, "norm_topk_prob": True,
            "num_attention_heads": 32, "num_key_value_heads": 8,
            "num_experts": 64, "num_experts_per_tok": 4,
            "routed_scaling_factor": 1, "use_expert_bias": True,
            "vocab_size": 65536, "max_position_embeddings": 128000,
            "rope_parameters": {"rope_theta": 1000000,
                                "rope_type": "default"}}.items():
        assert mine[key] == value, key
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        return
    with open(catalog) as f:
        row, = (json.loads(ln) for ln in f if '"LFM2-24B-A2B"' in ln)
    assert mine["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key not in mine["reduced"]:
            assert mine[key] == value, key
        else:
            assert mine["published"][key] == value, key


def test_benchmark_lists_the_cell_and_only_appends():
    """The cell reports `serve_tok_s`, `setup_s`, the seven serving readers
    by part and its own eleven; every per-layer metric it lists has a reader
    file; what the parent's BENCHMARK.json had is there unchanged, in
    order, but for names appended to `workloads` lists (this cell's, and
    those of cells that later PRs add)."""
    from perfbench import admission_spans, manifest as mf
    manifest = mf.load_manifest()
    mine = [m["name"] for m in mf.cell_metrics(manifest, CELL, "per_layer")]
    assert mine == [
        "attention_ms_round", "mlp_ms_round", "moe_ms_round",
        "head_sampler_ms_round", "scope_other_ms_round",
        "prefill_device_share", "scope_unmatched_share.serve",
        "decode_round_ms.assist", "decode_wait_ms_round.assist",
        "host_gap_ms_round.assist", "prefill_share.assist",
        "batch_occupancy.assist", "conv_ms_round",
        "paged_decode_ms_round.assist", "paged_decode_roofline_pct.assist",
        "moe_stream_roofline_pct.assist", "experts_touched_share.assist",
        "expert_load_max_over_mean.assist"] + list(
        admission_spans.METRICS)        # ISSUE 50: every serving cell's
    for name in mine:
        assert mf.load_reader(name) is not None, name
    assert [m["name"] for m in mf.cell_metrics(manifest, CELL, "end_to_end")
            ] == ["serve_tok_s", "setup_s"]
    cell = mf.find_cell(manifest, CELL)
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
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
                if CELL in added:
                    assert added[0] == CELL
                new["workloads"] = [w for w in new["workloads"] if w in had]
            assert old == new, old["name"]
    assert was["command"] == manifest["command"]
    assert was["run_seconds"] == manifest["run_seconds"]
    assert manifest["workloads"][len(was["workloads"])]["name"] == CELL
    assert manifest["configs"][len(was["configs"])]["name"] == "lfm2-24b-a2b"
