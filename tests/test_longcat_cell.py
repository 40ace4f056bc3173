"""The agent cell's own files on the CPU: its runner through the benchmark's
command at tiny widths (cells/serve_closed_share.py), and its per-layer
readers on a hand-built run (perfbench/tests/test_agent_readers.py, whose
cases run here so that the tier-1 run holds them)."""
import importlib.util
import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RUN = "import runpy; runpy.run_path('perfbench/run.py', run_name='__main__')"
CELL = "serve.longcat-flash-chat.agent-closed"


def _readers():
    spec = importlib.util.spec_from_file_location(
        "perfbench_tests_agent_readers", os.path.join(
            ROOT, "perfbench", "tests", "test_agent_readers.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_mod = _readers()
test_readers_on_a_run_that_names_everything = \
    _mod.test_readers_on_a_run_that_names_everything
test_a_program_without_the_names_reads_zero = \
    _mod.test_a_program_without_the_names_reads_zero
test_readers_without_a_trace_give_none = \
    _mod.test_readers_without_a_trace_give_none


def test_the_cell_rehearses_correct_and_its_picks_add_up():
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
    moe = line["notes"]["moe"]
    # tiny widths: 2 layers, top-3
    assert moe["assignments_zero"] + moe["assignments_here"] \
        + moe["assignments_absent"] == moe["tokens"] * 3 * 2
    assert line["notes"]["reference_worst_gap"] < 0.05
    # 4 planes x (16 + 8) columns x 4 B (float32 compute at tiny widths
    # rounds nothing) x 16 rows a block
    assert "pool 48 blocks x 3072 B" in out.stderr


def test_benchmark_lists_the_cell_and_only_appends():
    """The cell reports `serve_tok_s`, `setup_s`, the seven serving readers
    by part and its own ten; every per-layer metric it lists has a reader
    file; what the parent's BENCHMARK.json had is there unchanged, in
    order, but for the cell's name appended to `workloads` lists."""
    from perfbench import admission_spans, manifest as mf
    manifest = mf.load_manifest()
    mine = [m["name"] for m in mf.cell_metrics(manifest, CELL, "per_layer")]
    assert mine == [
        "attention_ms_round", "mlp_ms_round", "moe_ms_round",
        "head_sampler_ms_round", "scope_other_ms_round",
        "prefill_device_share", "scope_unmatched_share.serve",
        "decode_round_ms.agent", "decode_wait_ms_round.agent",
        "host_gap_ms_round.agent", "prefill_share.agent",
        "batch_occupancy.agent", "paged_latent_ms_round.agent",
        "paged_latent_roofline_pct.agent", "zero_expert_share.agent",
        "experts_touched_share.agent", "expert_load_max_over_mean.agent"] + list(
        admission_spans.METRICS)        # ISSUE 50: every serving cell's
    for name in mine:
        assert mf.load_reader(name) is not None, name
    assert [m["name"] for m in mf.cell_metrics(manifest, CELL, "end_to_end")
            ] == ["serve_tok_s", "setup_s"]
    parent = subprocess.run(
        ["git", "show", "38888a97202e309bfed23aea91ce4748787702fe:"
         "BENCHMARK.json"], capture_output=True, text=True, cwd=ROOT)
    if parent.returncode:
        return      # a checkout without history: nothing to compare with
    was = json.loads(parent.stdout)
    had = {c["name"] for c in was["workloads"]} | {CELL}
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for old, new in zip(was[group], manifest[group]):
            new = dict(new)
            if "workloads" in new:
                # (cells that later PRs appended, behind it or to a list it
                # is not in, are theirs to hold: tests/test_lfm2_cell.py,
                # tests/test_mellum_cell.py)
                new["workloads"] = [w for w in new["workloads"] if w in had]
                if CELL in new["workloads"]:
                    assert new["workloads"][-1] == CELL
                    new["workloads"] = new["workloads"][:-1]
            assert old == new, old["name"]
    assert was["command"] == manifest["command"]
    assert was["run_seconds"] == manifest["run_seconds"]
    assert manifest["workloads"][len(was["workloads"])]["name"] == CELL
    assert manifest["configs"][len(was["configs"])]["name"] == \
        "longcat-flash-chat"
