"""chip_smoke.py's control flow on the CPU, and the compile-cache helper.

The real run is on the chip (`python chip_smoke.py` through the chip tool).
Here: the verdict logic on recorded lines, one end-to-end rehearsal of each
mode at `--tiny` size in child processes, and the refusal to report success
on anything but a TPU.
"""

import json
import os
import subprocess
import sys

import jax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402 — the parent half imports no JAX

TPU = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1, "mesh": None}
CONTRACT_LINE = ('{"ok": true, "device": {"platform": "tpu", '
                 '"kind": "TPU v5 lite", "count": 1}}')


# ---------------------------------------------------------------------------
# Compile cache helper
# ---------------------------------------------------------------------------

@pytest.fixture
def cache_config():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_env_is_left_to_jax(monkeypatch, cache_config):
    from megatronapp_tpu.utils import platform
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a, **k: updates.append(a))
    assert platform.enable_compile_cache() == "/somewhere/else"
    assert updates == []          # the code set no directory of its own


def test_compile_cache_default_is_one_fixed_path(monkeypatch, tmp_path,
                                                 cache_config):
    from megatronapp_tpu.utils import platform
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    paths = []
    for name in ("a", "b"):
        d = tmp_path / name
        d.mkdir()
        monkeypatch.chdir(d)
        paths.append(platform.enable_compile_cache())
        assert jax.config.jax_compilation_cache_dir == paths[-1]
    assert paths[0] == paths[1] == os.path.join(ROOT, ".jax_cache")


def test_entry_points_call_the_helper():
    """parse_args (every pretrain_*.py), the server tool and bench.py's
    child all go through enable_compile_cache; nobody sets a directory of
    their own."""
    for rel in ("megatronapp_tpu/config/arguments.py",
                "tools/run_text_generation_server.py", "bench.py"):
        with open(os.path.join(ROOT, rel)) as f:
            assert "enable_compile_cache()" in f.read(), rel
    hits = subprocess.run(
        ["grep", "-rl", "--include=*.py", "compilation_cache_dir", ROOT,
         "--exclude-dir=.git", "--exclude-dir=tests",
         "--exclude-dir=build"],
        capture_output=True, text=True).stdout.split()
    assert [os.path.relpath(h, ROOT) for h in hits] == [
        "megatronapp_tpu/utils/platform.py"]


# ---------------------------------------------------------------------------
# Verdict logic on recorded lines
# ---------------------------------------------------------------------------

def _train_lines(losses, impl="pallas", device=TPU, mode="(compiled)"):
    att = ("attention: self-attention -> pallas flash kernel, 256x512, "
           "S=1024 D=64 " + mode if impl == "pallas" else
           f"attention: self-attention -> xla dense ({impl})")
    lines = [chip_smoke.DEVICE_LINE_PREFIX + json.dumps(device), att]
    for i, loss in enumerate(losses):
        lines.append(f"iter {i+1:6d}/{len(losses)} | loss {loss:.4f} | "
                     f"grad_norm 1.000 | lr 3.00e-04 | skipped 0 | "
                     f"{5000.0 if i == 0 else 50.0:.1f} ms/step | "
                     "80,000 tok/s | 70.0 TFLOP/s/dev")
    lines.append(chip_smoke.RESULT_PREFIX + json.dumps({
        "losses": losses, "wall_s": 9.0, "compile_s": 4.5, "cache_hits": 0,
        "cache_misses": 3, "peak_bytes_in_use": 123,
        "index_builders": "not used (synthetic data)"}))
    return lines


FALLING = [10.98 - 0.004 * i for i in range(12)]


def test_check_train_passes_on_a_good_run():
    out = chip_smoke.check_train(0, _train_lines(FALLING), "pallas")
    assert out["ok"], out["problems"]
    assert out["steps"] == 12 and out["first_step_s"] == 5.0
    assert out["step_s_median_after_warmup"] == pytest.approx(0.05)
    assert out["device"]["platform"] == "tpu"


@pytest.mark.parametrize("impl,tiny,said,ok", [
    # the real size on the chip: `auto` takes the flash kernels
    ("auto", False, "pallas", True),
    ("auto", False, "auto: S=1024 under 2048", False),
    # --tiny: S 64, dense under `auto` wherever it runs
    ("auto", True, "auto: S=64 under 512", True),
    ("auto", True, "auto: backend cpu", True),
    # the forced side of the A/B
    ("reference", False, "reference", True),
    ("reference", False, "pallas", False),
])
def test_check_train_follows_what_auto_announces(impl, tiny, said, ok):
    out = chip_smoke.check_train(0, _train_lines(FALLING, said), impl, tiny)
    assert out["ok"] == ok, out["problems"]


@pytest.mark.parametrize("case,needle", [
    ("nan", "not finite"),
    ("flat", "did not fall"),
    ("interpreted", "expected the trainer to say"),
    ("wrong-impl", "expected the trainer to say"),
    ("crashed", "child exited 1"),
    ("silent", "no result line"),
])
def test_check_train_names_what_failed(case, needle):
    losses, impl, mode, rc = FALLING, "pallas", "(compiled)", 0
    if case == "nan":
        losses = FALLING[:5] + [float("nan")] + FALLING[6:]
    elif case == "flat":
        losses = [10.98] * 12
    elif case == "interpreted":
        mode = "(interpreted)"       # on a TPU the kernel must be compiled
    elif case == "crashed":
        rc = 1
    lines = _train_lines(losses, "auto" if case == "wrong-impl" else impl,
                         mode=mode)
    if case == "silent":
        lines = lines[:2]
    out = chip_smoke.check_train(rc, lines, impl)
    assert not out["ok"]
    assert any(needle in p for p in out["problems"]), out["problems"]


def _phase(name, ok=True, device=TPU, **kw):
    return {"phase": name, "ok": ok,
            "problems": [] if ok else ["it broke"], "device": device, **kw}


def test_verdict():
    good = [_phase("train-auto", losses=[10.98]),
            _phase("train-reference", losses=[10.981]), _phase("server")]
    ok, device, reasons = chip_smoke.verdict(good, 1)
    assert ok and not reasons
    assert device == {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}

    def refused(phases, want=1):
        ok, _, reasons = chip_smoke.verdict(phases, want)
        assert not ok
        return " | ".join(reasons)

    assert "it broke" in refused(good[:2] + [_phase("server", ok=False)])
    cpu = dict(TPU, platform="cpu", kind="cpu")
    assert "not a TPU" in refused(good[:2] + [_phase("server", device=cpu)])
    assert "this run is for 4" in refused(good, want=4)
    assert "differ by" in refused(
        [good[0], _phase("train-reference", losses=[11.2]), good[2]])
    assert "no phase ran" in refused([])


def _run_with(monkeypatch, capsys, server_ok):
    monkeypatch.setattr(
        chip_smoke, "phase_train",
        lambda impl, tiny: _phase(f"train-{impl}", losses=[10.98]))
    monkeypatch.setattr(chip_smoke, "phase_server",
                        lambda tiny: _phase("server", ok=server_ok))
    monkeypatch.setattr(chip_smoke, "phase_hybrid",
                        lambda tiny: _phase("hybrid"))
    monkeypatch.setattr(chip_smoke, "phase_eva", lambda tiny: _phase("eva"))
    monkeypatch.setattr(chip_smoke, "phase_share",
                        lambda tiny: _phase("share"))
    monkeypatch.setattr(chip_smoke, "phase_conv",
                        lambda tiny: _phase("conv"))
    monkeypatch.setattr(chip_smoke, "phase_window",
                        lambda tiny: _phase("window"))
    monkeypatch.setattr(chip_smoke, "phase_ssd", lambda tiny: _phase("ssd"))
    rc = chip_smoke.main([])
    return rc, capsys.readouterr().out.strip().splitlines()


def test_run_prints_exactly_the_contract_line_last(monkeypatch, capsys):
    rc, lines = _run_with(monkeypatch, capsys, server_ok=True)
    assert rc == 0
    assert lines[-1] == CONTRACT_LINE
    phases = [json.loads(ln[len("phase: "):]) for ln in lines
              if ln.startswith("phase: ")]
    assert [p["phase"] for p in phases] == ["train-auto", "train-reference",
                                            "server", "hybrid", "eva",
                                            "share", "conv", "window", "ssd"]


def _window_lines(device=TPU, **kw):
    res = {"tokens": 251, "in_vocab": True, "train_loss": 6.31,
           "train_grads_finite": True,
           "train_kernels": ["flash_window_bwd_dkv", "flash_window_bwd_dq",
                             "flash_window_fwd"],
           "moe": {"tokens": 79, "assignments": 632, "experts_here": 8},
           "window": {"blocks_taken": 18, "blocks_given_back": 18,
                      "blocks_held": 0, "rows_walked": 2872,
                      "rows_full_walk": 6920},
           "kernels": ["grouped_gemm", "paged_decode", "paged_window_decode"],
           "pool_shapes": [[2, 128, 16, 2, 64], [2, 128, 16, 2, 64],
                           [3, 37, 16, 2, 64], [3, 37, 16, 2, 64]],
           "pool_bytes": 100, "alias_bytes": 128, **kw}
    return [chip_smoke.DEVICE_LINE_PREFIX + json.dumps(device),
            "attention: paged decode -> pallas paged kernel (compiled)",
            "attention: paged decode, sliding window 32 -> pallas paged "
            "kernel (compiled)",
            chip_smoke.RESULT_PREFIX + json.dumps(res)]


@pytest.mark.parametrize("kw,needle", [
    ({}, None),
    ({"pool_shapes": [[2, 128, 16, 2, 64], [2, 128, 16, 2, 64]]},
     "not two full planes"),
    ({"alias_bytes": 64}, "a pool is copied"),
    ({"kernels": ["grouped_gemm", "paged_decode"]}, "not both paged"),
    ({"tokens": 150}, "tokens came back"),
    ({"window": {"blocks_taken": 18, "blocks_given_back": 17,
                 "blocks_held": 1, "rows_walked": 1, "rows_full_walk": 2}},
     "not all given back"),
    ({"window": {"blocks_taken": 18, "blocks_given_back": 18,
                 "blocks_held": 0, "rows_walked": 9, "rows_full_walk": 9}},
     "no row spared"),
    ({"moe": {"tokens": 79, "assignments": 630, "experts_here": 8}},
     "the picks are not tokens"),
    ({"train_kernels": ["flash_window_fwd"]}, "not the three flash_window"),
    ({"train_loss": float("inf")}, "is not finite"),
    ({"train_grads_finite": False}, "is not finite"),
])
def test_check_window(kw, needle):
    """The window phase's facts: two pairs of page pools, aliased in and
    out; both families of paged kernel in the compiled step; every token
    back; the window planes' blocks all given back and rows spared; every
    pick counted."""
    out = chip_smoke.check_window(0, _window_lines(**kw))
    assert out["ok"] is (needle is None), out["problems"]
    assert needle is None or needle in " | ".join(out["problems"])
    assert not chip_smoke.check_window(1, _window_lines())["ok"]


def _hybrid_lines(device=TPU, **kw):
    res = {"tokens": 155, "in_vocab": True, "ssm_update_calls": 2,
           "state": {"layers": 6, "resets": 3}, "pool_bytes": 100,
           "alias_bytes": 128, **kw}
    return [chip_smoke.DEVICE_LINE_PREFIX + json.dumps(device),
            "attention: paged decode -> pallas paged kernel (compiled)",
            chip_smoke.RESULT_PREFIX + json.dumps(res)]


@pytest.mark.parametrize("kw,needle", [
    ({}, None),
    ({"ssm_update_calls": 6}, "not one a layer loop"),
    ({"alias_bytes": 64}, "a pool is copied"),
    ({"tokens": 150}, "tokens came back"),
    ({"state": {"layers": 6, "resets": 2}}, "state counters"),
])
def test_check_hybrid(kw, needle):
    """The hybrid phase's facts: one ssm_update a scanned run of
    state-space layers in the compiled decode step, the state pools
    aliased in and out, every token back, a reset an admission."""
    out = chip_smoke.check_hybrid(0, _hybrid_lines(**kw))
    assert out["ok"] is (needle is None), out["problems"]
    assert needle is None or needle in " | ".join(out["problems"])
    assert not chip_smoke.check_hybrid(1, _hybrid_lines())["ok"]


def _ssd_lines(device=TPU, **kw):
    res = {"tokens": 155, "in_vocab": True, "ssm_update_calls": 2,
           "e_tiles": 2, "pool_bytes": 100, "alias_bytes": 128,
           "state": {"mixer": "mamba2", "layers": 6, "resets": 3,
                     "conv_channels": 512},
           "moe": {"tokens": 33, "assignments": 792, "assignments_here": 363,
                   "assignments_absent": 429}, **kw}
    return [chip_smoke.DEVICE_LINE_PREFIX + json.dumps(device),
            chip_smoke.RESULT_PREFIX + json.dumps(res)]


@pytest.mark.parametrize("kw,needle", [
    ({}, None),
    ({"ssm_update_calls": 6}, "not one a layer loop"),
    ({"e_tiles": 1}, "tiles of E"),
    ({"alias_bytes": 64}, "a pool is copied"),
    ({"tokens": 150}, "tokens came back"),
    ({"state": {"mixer": "mamba1", "layers": 6, "resets": 3,
                "conv_channels": 256}}, "state counters"),
    ({"moe": {"tokens": 33, "assignments": 792, "assignments_here": 792,
              "assignments_absent": 0}}, "do not add up"),
])
def test_check_ssd(kw, needle):
    """The ssd phase's facts: one ssm_update a scanned run of Mamba-2
    layers, a plane in tiles of E, the state pools aliased in and out, every
    token back, every pick held or absent."""
    out = chip_smoke.check_ssd(0, _ssd_lines(**kw))
    assert out["ok"] is (needle is None), out["problems"]
    assert needle is None or needle in " | ".join(out["problems"])
    assert not chip_smoke.check_ssd(1, _ssd_lines())["ok"]


def _eva_lines(device=TPU, **kw):
    res = {"tokens": 1249, "in_vocab": True, "eva_summary_calls": 1,
           "eva": {"windows_closed": 4, "blocks_freed": 64,
                   "max_blocks_slot": 19},
           "table_blocks": 20, "blocks_in_use_after": 0, "pool_bytes": 100,
           "alias_bytes": 128, **kw}
    return [chip_smoke.DEVICE_LINE_PREFIX + json.dumps(device),
            "attention: paged decode -> pallas paged kernel (compiled)",
            chip_smoke.RESULT_PREFIX + json.dumps(res)]


@pytest.mark.parametrize("kw,needle", [
    ({}, None),
    ({"eva_summary_calls": 4}, "not one a layer loop"),
    ({"alias_bytes": 64}, "a pool is copied"),
    ({"tokens": 1200}, "tokens came back"),
    ({"eva": {"windows_closed": 0, "blocks_freed": 0,
              "max_blocks_slot": 19}}, "eva counters"),
    ({"eva": {"windows_closed": 4, "blocks_freed": 64,
              "max_blocks_slot": 40}}, "a slot held 40 blocks"),
    ({"blocks_in_use_after": 3}, "still held"),
])
def test_check_eva(kw, needle):
    """The EVA phase's facts: one summariser a layer loop in the compiled
    decode step, the pools aliased, every token back, windows closed and
    their blocks freed, a slot's blocks bounded by its table."""
    out = chip_smoke.check_eva(0, _eva_lines(**kw))
    assert out["ok"] is (needle is None), out["problems"]
    assert needle is None or needle in " | ".join(out["problems"])
    assert not chip_smoke.check_eva(1, _eva_lines())["ok"]


def _share_lines(device=TPU, **kw):
    res = {"tokens": 155, "in_vocab": True, "latent_kernel_calls": 2,
           "moe": {"tokens": 33, "assignments_zero": 60,
                   "assignments_here": 70, "assignments_absent": 68,
                   "experts_here": 4},
           "pool_shapes": [[4, 64, 16, 512], [4, 64, 16, 64]],
           "pool_bytes": 100, "alias_bytes": 128, **kw}
    return [chip_smoke.DEVICE_LINE_PREFIX + json.dumps(device),
            "attention: paged decode -> pallas paged kernel (compiled)",
            chip_smoke.RESULT_PREFIX + json.dumps(res)]


@pytest.mark.parametrize("kw,needle", [
    ({}, None),
    ({"latent_kernel_calls": 4}, "not two a layer loop"),
    ({"pool_shapes": [[2, 64, 16, 512], [2, 64, 16, 64]]},
     "not two planes a layer"),
    ({"alias_bytes": 64}, "a pool is copied"),
    ({"tokens": 150}, "tokens came back"),
    ({"moe": {"tokens": 33, "assignments_zero": 60, "assignments_here": 70,
              "assignments_absent": 60, "experts_here": 4}},
     "the picks are not held"),
    ({"moe": {"tokens": 33, "assignments_zero": 60, "assignments_here": 138,
              "assignments_absent": 0, "experts_here": 4}},
     "the picks are not held"),
])
def test_check_share(kw, needle):
    """The share phase's facts: two latent kernels a layer loop in the
    compiled decode step, pools of two planes a layer aliased in and out,
    every token back, every pick held, absent or zero-compute."""
    out = chip_smoke.check_share(0, _share_lines(**kw))
    assert out["ok"] is (needle is None), out["problems"]
    assert needle is None or needle in " | ".join(out["problems"])
    assert not chip_smoke.check_share(1, _share_lines())["ok"]


def _conv_lines(device=TPU, **kw):
    res = {"tokens": 155, "in_vocab": True, "expert_stack_slices": 0,
           "moe": {"tokens": 33, "assignments": 264, "experts_here": 8},
           "state": {"kind": "conv", "layers": 4, "resets": 3},
           "pool_shapes": [[1, 64, 16, 2, 64], [1, 64, 16, 2, 64],
                           [4, 8, 512]],
           "pool_bytes": 100, "alias_bytes": 128, **kw}
    return [chip_smoke.DEVICE_LINE_PREFIX + json.dumps(device),
            "attention: paged decode -> pallas paged kernel (compiled)",
            chip_smoke.RESULT_PREFIX + json.dumps(res)]


@pytest.mark.parametrize("kw,needle", [
    ({}, None),
    ({"pool_shapes": [[1, 64, 16, 2, 64], [1, 64, 16, 2, 64],
                      [4, 8, 8, 256], [4, 8, 512]]}, "not one attention"),
    ({"alias_bytes": 64}, "a pool is copied"),
    ({"expert_stack_slices": 2}, "out of their stacks"),
    ({"tokens": 150}, "tokens came back"),
    ({"state": {"kind": "ssm", "layers": 4, "resets": 3}},
     "not 4 convolution"),
    ({"moe": {"tokens": 33, "assignments": 260, "experts_here": 8}},
     "the picks are not tokens"),
])
def test_check_conv(kw, needle):
    """The conv phase's facts: one attention plane and one pool of tails,
    aliased in and out; the experts read in their stacks; every token back;
    the tails reset once a request; every pick counted."""
    out = chip_smoke.check_conv(0, _conv_lines(**kw))
    assert out["ok"] is (needle is None), out["problems"]
    assert needle is None or needle in " | ".join(out["problems"])
    assert not chip_smoke.check_conv(1, _conv_lines())["ok"]


def test_a_failing_phase_fails_the_run(monkeypatch, capsys):
    rc, lines = _run_with(monkeypatch, capsys, server_ok=False)
    assert rc != 0
    last = json.loads(lines[-1])
    assert last["ok"] is False and last["device"]["platform"] == "tpu"
    assert any(ln.startswith("FAILED: server") for ln in lines)


@pytest.mark.parametrize("module", ["chip_smoke", "bench"])
def test_parents_that_start_children_import_no_jax(module):
    """A parent that has touched JAX holds the chip its child needs."""
    code = (f"import sys, {module}; "
            "sys.exit(any(m == 'jax' or m.startswith('jax.') "
            "for m in sys.modules))")
    assert subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                          timeout=120).returncode == 0


def test_bench_without_a_chip_fails_and_prints_no_record(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    proc = subprocess.run([sys.executable, os.path.join(ROOT, "bench.py")],
                          capture_output=True, text=True, timeout=300,
                          env=env, cwd=str(tmp_path))
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""          # no number under any name
    assert "needs a TPU" in proc.stderr


def test_no_peak_rate_for_a_cpu():
    from megatronapp_tpu.utils.flops import TPU_PEAK_FLOPS
    assert not any("cpu" in k for k in TPU_PEAK_FLOPS)
    assert [v for k, v in TPU_PEAK_FLOPS.items()
            if k in "tpu v5 lite"] == [197e12]


# ---------------------------------------------------------------------------
# End to end, in child processes, on the CPU
# ---------------------------------------------------------------------------

def _smoke(args, tmp_path, devices=1, timeout=600):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"),
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chip_smoke.py"), *args],
        capture_output=True, text=True, timeout=timeout, env=env,
        cwd=str(tmp_path))
    lines = proc.stdout.strip().splitlines()
    phases = [json.loads(ln[len("phase: "):]) for ln in lines
              if ln.startswith("phase: ")]
    return proc.returncode, json.loads(lines[-1]), phases, lines


def test_forced_to_the_cpu_it_refuses(tmp_path):
    """No --tiny: the real size is not run on a CPU, and nothing is
    reported as a success."""
    rc, last, phases, lines = _smoke([], tmp_path)
    assert rc != 0 and last["ok"] is False
    assert [p["phase"] for p in phases] == ["train-auto"]
    assert not phases[0]["ok"]
    assert any("refusing to run the real size" in ln for ln in lines)
    assert not any(ln.startswith("[train-auto] iter") for ln in lines)


@pytest.fixture(scope="module")
def rehearsal(tmp_path_factory):
    """`chip_smoke.py --tiny`, once: nine children one after another, the
    dearest thing tier 1 runs. Each phase's record is read by a case of its
    own below, the run's contract by another."""
    rc, last, phases, lines = _smoke(["--tiny"],
                                     tmp_path_factory.mktemp("rehearsal"))
    return rc, last, {p["phase"]: p for p in phases}, lines


def _rehearsed_train_auto(auto, phases):
    assert abs(auto["losses"][0] - phases["train-pallas"]["losses"][0]) < 1e-2


def _rehearsed_train_pallas(train, phases):
    assert train["steps"] == 20 and train["compile_s"] > 0
    assert any("pallas flash kernel" in a and "(interpreted)" in a
               for a in train["attention"])


def _rehearsed_server(server, phases):
    assert server["driver_max_active"] >= 2
    assert server["max_blocks_in_use_seen"] > 0
    assert server["blocks_in_use_after"] == 0
    # 12 layers, each one paged_decode and a paged_append for K and for V
    assert server["decode_pallas_calls_per_step"] == 36


def _rehearsed_hybrid(hybrid, phases):
    assert hybrid["state"]["resets"] == 3 and hybrid["state"]["layers"] == 6
    assert hybrid["alias_bytes"] >= hybrid["pool_bytes"] > 0


def _rehearsed_eva(eva, phases):
    assert eva["eva"]["windows_closed"] == 3 and eva["table_blocks"] == 20
    assert eva["alias_bytes"] >= eva["pool_bytes"] > 0


def _rehearsed_share(share, phases):
    assert share["moe"]["experts_here"] == 4
    assert share["pool_shapes"][0][0] == 4      # two planes a layer
    assert share["alias_bytes"] >= share["pool_bytes"] > 0


def _rehearsed_conv(conv, phases):
    assert conv["state"]["kind"] == "conv" and conv["state"]["resets"] == 3
    assert conv["moe"]["assignments"] == conv["moe"]["tokens"] * 2 * 4
    assert conv["expert_stack_slices"] == 0
    assert conv["alias_bytes"] >= conv["pool_bytes"] > 0


def _rehearsed_window(window, phases):
    assert window["window"]["blocks_taken"] == window["window"][
        "blocks_given_back"] > 0
    assert window["pool_shapes"][2][:2] == [3, 37]
    assert window["moe"]["assignments"] == window["moe"]["tokens"] * 2 * 4
    assert window["alias_bytes"] >= window["pool_bytes"] > 0
    assert 5.5 < window["train_loss"] < 7.5 and window["train_grads_finite"]


def _rehearsed_ssd(ssd, phases):
    assert ssd["state"]["mixer"] == "mamba2" and ssd["e_tiles"] == 2
    assert ssd["moe"]["assignments_here"] + ssd["moe"][
        "assignments_absent"] == ssd["moe"]["tokens"] * 3 * 8
    assert ssd["alias_bytes"] >= ssd["pool_bytes"] > 0


# The rehearsal's phases in the order they run, each with what its record
# has to say: a model's new phase is one more entry.
REHEARSED = {
    "train-auto": _rehearsed_train_auto,
    "train-pallas": _rehearsed_train_pallas, "server": _rehearsed_server,
    "hybrid": _rehearsed_hybrid, "eva": _rehearsed_eva,
    "share": _rehearsed_share, "conv": _rehearsed_conv,
    "window": _rehearsed_window, "ssd": _rehearsed_ssd}


@pytest.mark.parametrize("name", REHEARSED)
def test_tiny_rehearsal_runs_both_phases_and_still_refuses(rehearsal, name):
    _, _, phases, _ = rehearsal
    phase = phases[name]
    assert phase["ok"], (name, phase["problems"])   # its own checks passed
    REHEARSED[name](phase, phases)


def test_tiny_rehearsal_runs_every_phase_and_is_refused_all_the_same(
        rehearsal):
    rc, last, phases, lines = rehearsal
    assert list(phases) == list(REHEARSED)
    # every phase passed, and the run is refused all the same: not a TPU
    assert rc != 0
    assert last == {"ok": False, "device": {"platform": "cpu",
                                            "kind": "cpu", "count": 1}}
    assert sum("not a TPU" in ln for ln in lines) == 9


def test_four_chip_option_on_four_virtual_devices(tmp_path):
    rc, last, phases, _ = _smoke(["--chips", "4", "--tiny"], tmp_path,
                                 devices=4)
    assert [p["phase"] for p in phases] == ["multichip"]
    ph = phases[0]
    assert ph["ok"], ph["problems"]
    legs = ph["legs"]
    assert set(legs) == {"one_device", "tp2_dp2", "tp2_pp2"}
    assert legs["tp2_dp2"]["q_kernel"]["devices,shards"] == [4, 2]
    assert legs["tp2_dp2"]["batch_tokens"]["devices,shards"] == [4, 2]
    assert legs["tp2_dp2"]["collectives_in_compiled_step"]["all-reduce"] > 0
    for name in ("tp2_dp2", "tp2_pp2"):
        assert legs[name]["max_abs_loss_gap_vs_one_device"] < ph["tolerance"]
    assert rc != 0 and last["ok"] is False
    assert last["device"]["count"] == 4
