"""The code cell's own files on the CPU: its runner through the benchmark's
command at tiny widths (cells/serve_closed_window.py), the benchmark's
additions against the parent's BENCHMARK.json, and its per-layer readers on
a hand-built run (perfbench/tests/test_code_readers.py, whose cases run
here so that the tier-1 run holds them)."""
import importlib.util
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RUN = "import runpy; runpy.run_path('perfbench/run.py', run_name='__main__')"
CELL = "serve.laguna-xs.2.code-closed"
PARENT = "1d56e03d492676896148d88c06ef61b6fadc3a4f"


def _readers():
    spec = importlib.util.spec_from_file_location(
        "perfbench_tests_code_readers", os.path.join(
            ROOT, "perfbench", "tests", "test_code_readers.py"))
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
    # tiny widths: 4 sparse layers, top-2
    moe = notes["moe"]
    assert moe["assignments"] == moe["tokens"] * 2 * 4 > 0
    window = notes["window"]
    assert window["blocks_taken"] == window["blocks_given_back"] > 0
    assert window["blocks_held"] == 0
    assert 0 < window["rows_walked"] < window["rows_full_walk"]
    # a window of 24 in blocks of 16: 4 blocks a slot between calls, and a
    # prefill call of 32 rows
    assert window["max_blocks_slot"] <= 4 + 2 + 1
    assert window["num_blocks"] == 4 * 4 + 2 + 1
    # a rehearsal checks every request, float32 against bf16 at tiny widths
    assert notes["reference_checked"] == notes["reference_checked_of"] > 4
    assert notes["reference_positions"] == notes["reference_positions_of"]
    assert notes["reference_mean_gap"] < 0.01
    # the window's edge: the engine's error holds nothing of a one-key-off
    # window's difference
    assert notes["edge_probes"] == 3
    assert abs(notes["edge_share_shorter"]) < 0.2
    assert abs(notes["edge_share_longer"]) < 0.2
    # 2 full planes x (K + V) x 2 heads x 16 x 2 B x 16 rows a block
    assert "pool 48 blocks x 4096 B" in out.stderr


def test_a_runner_without_window_planes_is_not_correct():
    """The runner's own checks on a program's counters: what a cache that
    walked whole tables or kept every block would read."""
    from perfbench import manifest as mf
    runner = mf.load_module("cells", "serve_closed_window")
    model = mf.load_module("models", "laguna")
    with open(os.path.join(ROOT, "perfbench", "configs",
                           "laguna-xs.2.json")) as f:
        config = json.load(f)
    good = {"window": {"planes_full": 2, "planes_window": 3, "window": 512,
                       "blocks_taken": 900, "blocks_given_back": 900,
                       "blocks_held": 0, "blocks_slot_bound": 34,
                       "max_blocks_slot": 160}}
    facts = {"window_bytes_per_block": 3 * 4096 * 16}
    assert runner.window_problems(good, config, model, facts, 2048) == []
    for change, needle in (
            ({"blocks_given_back": 890}, "do not add up"),
            ({"max_blocks_slot": 1216}, "a slot held 1216"),
            ({"planes_window": 0}, "0 window planes"),
            ({"window": 513}, "of window 513")):
        said = runner.window_problems(
            {"window": dict(good["window"], **change)}, config, model,
            facts, 2048)
        assert any(needle in s for s in said), (change, said)
    assert "no window planes" in runner.window_problems(
        {"window": False}, config, model, facts, 2048)[0]
    assert "takes 196608" in runner.window_problems(
        good, config, model, {"window_bytes_per_block": 5 * 4096 * 16},
        2048)[0]


def test_benchmark_lists_the_cell_and_only_appends():
    """The cell reports `serve_tok_s`, `setup_s`, the seven serving readers
    by part and its own thirteen; every per-layer metric it lists has a
    reader file; what the parent's BENCHMARK.json had is there unchanged, in
    order, but for names appended to `workloads` lists."""
    from perfbench import admission_spans, manifest as mf
    manifest = mf.load_manifest()
    mine = [m["name"] for m in mf.cell_metrics(manifest, CELL, "per_layer")]
    assert mine == [
        "attention_ms_round", "mlp_ms_round", "moe_ms_round",
        "head_sampler_ms_round", "scope_other_ms_round",
        "prefill_device_share", "scope_unmatched_share.serve",
        "decode_round_ms.code", "decode_wait_ms_round.code",
        "host_gap_ms_round.code", "batch_occupancy.code",
        "paged_decode_ms_round.code", "paged_decode_roofline_pct.code",
        "paged_window_ms_round.code", "paged_window_roofline_pct.code",
        "window_rows_walked_share.code", "kv_bytes_held_per_token.code",
        "experts_touched_share.code", "expert_load_max_over_mean.code",
        "moe_stream_roofline_pct.code"] + list(
        admission_spans.METRICS)        # ISSUE 50: every serving cell's
    for name in mine:
        assert mf.load_reader(name) is not None, name
    assert [m["name"] for m in mf.cell_metrics(manifest, CELL, "end_to_end")
            ] == ["serve_tok_s", "setup_s"]
    cell = mf.find_cell(manifest, CELL)
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    assert len(manifest["workloads"]) >= 9
    assert sum(c["chips"] == 4 for c in manifest["workloads"]) == 1
    traffic = mf.load_traffic(cell)
    assert (traffic["pool_requests"], traffic["shape_seed"],
            traffic["max_total_len"], traffic["ramp_tokens"],
            traffic["clients_per_slot"]) == (64, 20261001, 19456, 16000, 2)
    assert traffic["prompt_len"] == {"median": 6144, "sigma": 0.9,
                                     "min": 512, "max": 16384}
    assert traffic["answer_len"] == {"median": 1024, "sigma": 0.7,
                                     "min": 256, "max": 3072}
    serve = mf.load_config(manifest, cell)["serve"]
    assert (serve["max_batch"], serve["max_seq_len"], serve["num_blocks"]
            ) == (32, 19456, 32768)
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
    assert manifest["configs"][len(was["configs"])]["name"] == "laguna-xs.2"


@pytest.mark.parametrize("control", ["one-table", "no-gate"])
def test_the_control_tool_hands_the_reference_a_wrong_model(control):
    from perfbench import manifest as mf
    tool = mf.load_module("tools", "window_control")
    model = mf.load_module("models", "laguna")
    seen = {}

    class Fake:
        pass
    fake = Fake()
    fake.reference_hidden = lambda *a, **kw: seen.update(kw) or "x"
    tool.wrong_reference(fake, control)
    assert fake.reference_hidden(1, 2) == "x" and seen == {"control": control}
    assert control in model.reference_hidden.__doc__


@pytest.mark.parametrize("control,by", [("window-1", -1), ("window+1", 1)])
def test_the_control_tool_moves_the_programs_window_by_a_key(control, by):
    """The program's configuration is patched, the reference's file is not:
    what the runner's edge probe has to tell (the rehearsal above reads
    0.98-1.00 of the difference under these, 0.01 without)."""
    from perfbench import manifest as mf
    tool = mf.load_module("tools", "window_control")
    model = mf.load_module("models", "laguna")
    with open(os.path.join(ROOT, "perfbench", "configs",
                           "laguna-xs.2.json")) as f:
        config = json.load(f)

    class Fake:
        model_config = staticmethod(model.model_config)
    fake = Fake()
    tool.program_window(fake, by)
    assert fake.model_config(config, "bfloat16").sliding_window == 512 + by
    assert model.model_config(config, "bfloat16").sliding_window == 512


def test_the_probes_lie_past_the_window_and_past_a_prefill_call():
    from perfbench import manifest as mf
    runner = mf.load_module("cells", "serve_closed_window")
    lengths = runner.probe_lengths(512, 2048, 19456)
    assert lengths == [521, 778, 1031, 2051, 2690, 3661]
    assert sum(n + runner.PROBE_DECODED - 1 for n in lengths) < 19456
    assert runner.probe_lengths(24, 32, 128) == [33, 55, 35]
