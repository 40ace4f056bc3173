"""The training-share cell's own files on the CPU: its runner through the
benchmark's command at tiny widths (cells/pretrain_share.py), the runner's
own checks, the benchmark's additions against the parent's BENCHMARK.json,
and its per-layer readers on a hand-built run
(perfbench/tests/test_share_train_readers.py, whose cases run here so that
the tier-1 run holds them)."""
import importlib.util
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
RUN = "import runpy; runpy.run_path('perfbench/run.py', run_name='__main__')"
CELL = "train.mellum2-12b-a2.5b.packed-8k"
PARENT = "986eda080a21caae9d4f7af5f45cc44801017aac"


def _readers():
    spec = importlib.util.spec_from_file_location(
        "perfbench_tests_share_train_readers", os.path.join(
            ROOT, "perfbench", "tests", "test_share_train_readers.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_mod = _readers()
test_the_counts_by_hand = _mod.test_the_counts_by_hand
test_readers_on_a_run_that_names_everything = \
    _mod.test_readers_on_a_run_that_names_everything
test_a_program_without_the_names_leaves_the_metrics_out = \
    _mod.test_a_program_without_the_names_leaves_the_metrics_out
test_readers_without_a_trace_give_none = \
    _mod.test_readers_without_a_trace_give_none


def _rehearse(trace):
    out = subprocess.run(
        [sys.executable, "-c", RUN, "--workload", CELL, "--seed",
         "3000000029", "--seconds", "2", "--trace", str(trace)],
        capture_output=True, text=True, cwd=ROOT, timeout=900,
        env=dict(os.environ, JAX_PLATFORMS="cpu", PERFBENCH_REHEARSAL="1",
                 XLA_FLAGS="--xla_force_host_platform_device_count=1",
                 PYTHONPATH=ROOT))
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1]), out.stderr


def test_the_cell_rehearses_correct_and_its_counters_add_up():
    line, said = _rehearse(trace=1)
    assert line["correct"] is True, said[-3000:]
    assert line["failed"] == 0 and line["attempted"] >= 2
    notes = line["notes"]
    counters = notes["window_counters"]
    # tiny widths: 4 layers, top-4 of 16 outputs, 4 experts held
    tokens = notes["steps"] * 8 * 128
    assert counters["steps"] == notes["steps"]
    assert counters["assignments"] == tokens * 4 * 4
    assert (counters["assignments_here"] + counters["assignments_absent"]
            == counters["assignments"])
    assert counters["experts_here"] / counters["moe_layer_passes"] == 4
    assert abs(notes["first_grad_norm"] / notes["reference_grad_norm"] - 1
               ) < 1e-2
    assert abs(notes["first_loss_unrounded"] - notes["reference_loss"]
               ) < 1e-3
    # every element of the first step's gradient against the reference's
    assert 0 < notes["first_grad_gap"] < 2e-2
    assert "the step run once more" in said
    assert "moe here 0.2" in said
    metrics = line["metrics"]
    assert 0.15 < metrics["expert_rows_here_share.packed8k"]["value"] < 0.35
    assert metrics["expert_load_max_over_mean.packed8k"]["value"] >= 1
    # 1,024 tokens x 4 picks a call: the T*k buffer alone at this size
    assert counters["row_buffer_rows"] == counters["assignments"]
    assert metrics["expert_rows_walked_share.packed8k"]["value"] == 1.0
    assert " buffer " in said
    for name in ("step_ms", "mfu", "train_tok_s_chip", "setup_s"):
        assert metrics[name]["value"] > 0


def test_the_share_of_rows_walked_reads_the_syncs_of_the_window():
    """`expert_rows_walked_share.packed8k`: the traced interval's
    `row_buffer_rows` over its `assignments`. A program that counts the
    assignments and not the buffers' rows (the commit before the counter)
    walks all of them, 1.0: `lastline.faults` refuses a traced line that
    lacks one of its cell's metrics, so that commit has to read a number.
    None without the counters."""
    from perfbench import manifest as mf
    read = mf.load_reader("expert_rows_walked_share.packed8k")
    here = mf.load_reader("expert_rows_here_share.packed8k")

    def run(*syncs):
        return {"device_summary": {"window": (100, 200)},
                "xplane_stats": {"spans": [
                    ("mta.train.sync", start, 10, attrs)
                    for start, attrs in syncs] + [
                    ("mta.train.step", 120, 5, {"row_buffer_rows": 9e9})]}}
    full = {"assignments": 1000.0, "assignments_here": 250.0}
    got = run((110, dict(full, row_buffer_rows=375.0)),
              (150, dict(full, row_buffer_rows=250.0)),
              (195, dict(full, row_buffer_rows=1000.0)))   # ends outside
    assert read(got) == pytest.approx(625.0 / 2000.0)
    assert here(got) == pytest.approx(0.25)
    assert read(run((110, full), (150, full))) == 1.0      # the parent
    assert here(run((110, full), (150, full))) == pytest.approx(0.25)
    assert read(run()) is None and read({}) is None


@pytest.mark.parametrize("counts,want", [
    # the parent: routing counters and no tile count, every tile computed
    ({}, 1.0),
    # two steps of 8 rows: 136 causal tiles of the full layer and 3 x 45
    # band tiles of the window layers a row
    ({"flash_tiles": 2 * 8 * 271.0, "flash_tiles_computed": 2 * 8 * 200.0},
     200 / 271),
], ids=["no-count", "counted"])
def test_the_tiles_computed_share(counts, want):
    """`flash_tiles_computed_share.packed8k` on the hand-built run of
    perfbench/tests/test_share_train_readers.py (the case lives here: that
    file is the benchmark's, and not a program PR's to edit)."""
    from perfbench import manifest as mf
    ev, run_of = _mod.ev, _mod.run_of
    stats = {"spans": [ev("mta.train.sync", 0.5, 99.5,
                          {**_mod.SYNC, **counts})]}
    read = mf.load_reader("flash_tiles_computed_share.packed8k")
    assert read(run_of(_mod.DEVICE, stats, _mod.MODULES, _mod.MAPS,
                       _mod.PAIRS)) == pytest.approx(want)
    # a program without the loop's spans, and a run without a trace
    assert read(run_of(_mod.DEVICE, {"spans": []}, _mod.MODULES, (),
                       None)) is None
    assert read({"kind": "train", "config": _mod.CONFIG,
                 "peaks": _mod.PEAKS, "traced_steps": 0,
                 "device_summary": None}) is None


def test_the_runners_window_counters():
    from perfbench import manifest as mf
    runner = mf.load_module("cells", "pretrain_share")
    one = {"steps": 1, "assignments": 10.0, "loss": 5.0, "grad_norm": 1.0}
    two = {"steps": 2, "assignments": 20.0, "loss": 5.0, "grad_norm": 1.0}
    syncs = [one, one, two, two, two]
    assert runner._window_counters(syncs, 4) == {"steps": 4,
                                                 "assignments": 40.0}
    # a window that the records do not cover exactly is no window
    assert runner._window_counters(syncs, 3) is None
    assert runner._window_counters(syncs, 8) is None
    assert runner._window_counters([], 2) is None


def test_the_runners_gradient_gap_and_first_moment():
    import collections
    import jax.numpy as jnp
    import numpy as np
    from perfbench import manifest as mf
    runner = mf.load_module("cells", "pretrain_share")
    rng = np.random.default_rng(0)
    ref = {"a": rng.normal(size=(3, 5)).astype(np.float32),
           "b": {"c": rng.normal(size=(7,)).astype(np.float32)}}
    norm = float(np.sqrt(sum(np.sum(np.square(x)) for x in (
        ref["a"], ref["b"]["c"]))))
    # the optimizer's first moment is the gradient but for a factor: scaled
    # to the step's own norm it is the gradient (2 micro-batches: the
    # reference hands their sum)
    moment = {"a": jnp.asarray(ref["a"]) * 0.1,
              "b": {"c": jnp.asarray(ref["b"]["c"]) * 0.1}}
    doubled = {"a": ref["a"] * 2, "b": {"c": ref["b"]["c"] * 2}}
    gap, worst, leaf = runner._gradient_gap(moment, norm, doubled, 2)
    assert gap < 1e-6 and worst < 1e-6
    # a leaf left at zero reads 1 on that leaf and its share of the whole
    moment["b"]["c"] = jnp.zeros((7,), jnp.float32)
    gap, worst, leaf = runner._gradient_gap(
        moment, float(np.linalg.norm(ref["a"])), doubled, 2)
    assert abs(worst - 1) < 1e-6 and leaf == "['b']['c']"
    assert abs(gap - np.linalg.norm(ref["b"]["c"]) / norm) < 1e-6
    # where the state keeps it: the ZeRO-1 wrapper's dict, optax's chain
    Adam = collections.namedtuple("Adam", "count mu nu")
    assert runner._first_moment({"opt_state": {"count": 0, "mu": 1, "nu": 2}}
                                ) == 1
    assert runner._first_moment(
        {"opt_state": ((), Adam(0, 3, 4), ())}) == 3
    with pytest.raises(SystemExit):
        runner._first_moment({"opt_state": ((), ())})


def test_benchmark_lists_the_cell_and_only_appends():
    from perfbench import manifest as mf
    manifest = mf.load_manifest()
    mine = [m["name"] for m in mf.cell_metrics(manifest, CELL, "per_layer")]
    assert mine == [
        "step_ms", "mfu", "pallas_ms_step", "flash_fwd_ms_step",
        "flash_bwd_ms_step", "attention_ms_step", "head_loss_ms_step",
        "optimizer_ms_step", "scope_other_ms_step",
        "scope_unmatched_share.train", "moe_ms_step",
        "flash_window_ms_step.packed8k",
        "flash_window_roofline_pct.packed8k",
        "expert_gemm_roofline_pct.packed8k",
        "expert_rows_here_share.packed8k",
        "expert_load_max_over_mean.packed8k",
        "expert_rows_walked_share.packed8k",
        "flash_tiles_computed_share.packed8k"]
    for name in mine:
        assert mf.load_reader(name) is not None, name
    assert [m["name"] for m in mf.cell_metrics(manifest, CELL, "end_to_end")
            ] == ["train_tok_s_chip", "setup_s"]
    cell = mf.find_cell(manifest, CELL)
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    # (ten cells with this one; later PRs append theirs)
    assert [c["name"] for c in manifest["workloads"]].index(CELL) == 9
    assert sum(c["chips"] == 4 for c in manifest["workloads"]) == 1
    traffic = mf.load_traffic(cell)
    assert (traffic["kind"], traffic["runner"]) == ("train_packed",
                                                    "pretrain_share")
    assert (traffic["seq_length"], traffic["sequences_per_step"],
            traffic["pool_documents"], traffic["shape_seed"],
            traffic["log_interval"]) == (8192, 8, 4096, 20250925, 2)
    assert traffic["doc_len"] == {"median": 1500, "sigma": 1.3, "min": 32,
                                  "max": 8192}
    packed_1k = mf.load_traffic({"traffic": "packed-1k"})
    for key in ("lr", "min_lr", "lr_warmup_iters", "lr_decay_iters"):
        assert traffic[key] == packed_1k[key], key
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
                assert CELL not in added or added[0] == CELL
                assert new["workloads"][:len(old["workloads"])] == old[
                    "workloads"]
                new["workloads"] = old["workloads"]
            assert old == new, old["name"]
    assert was["command"] == manifest["command"]
    assert was["run_seconds"] == manifest["run_seconds"]
    assert was["paths"] == manifest["paths"]
    assert manifest["workloads"][len(was["workloads"])]["name"] == CELL
    assert manifest["configs"][len(was["configs"])]["name"] \
        == "mellum2-12b-a2.5b"
    # (what later PRs append comes behind the cell's own of PRs 48 and 49;
    # PR 58's flash_tiles_computed_share.packed8k is at the list's end)
    own = mine[10:-1]
    assert [m["name"] for m in manifest["per_layer"][len(was["per_layer"]):]
            ][:len(own)] == own
