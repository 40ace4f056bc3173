"""Mellum2-12B-A2.5B through the training path at a small size on the CPU:
the program's loss and every leaf's gradient against the plain reference
(`perfbench/models/mellum.py`), each wrong model past the tolerance, the four
shares of the experts adding up to the whole layer, the router's loss and the
routing counts through the hybrid layer loop, the step's sums, the loop's
spans and log line, the preset, the configuration file and the counts."""

import copy
import dataclasses
import json
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from megatronapp_tpu.config.parallel_config import ParallelConfig  # noqa: E402
from megatronapp_tpu.config.training_config import (  # noqa: E402
    OptimizerConfig, TrainingConfig,
)
from megatronapp_tpu.models.gpt import gpt_loss, init_gpt_params  # noqa: E402
from megatronapp_tpu.models.presets import PRESETS  # noqa: E402
from megatronapp_tpu.transformer import moe  # noqa: E402
from perfbench import manifest, mellum_flops  # noqa: E402

model = manifest.load_module("models", "mellum")
generator = manifest.load_module("generators", "train_packed")
CONFIG_FILE = os.path.join(ROOT, "perfbench", "configs",
                           "mellum2-12b-a2.5b.json")
SEQ = 96


def _file():
    with open(CONFIG_FILE) as f:
        return json.load(f)


def _small(**over):
    config = copy.deepcopy(_file())
    config.update(model.REHEARSAL)
    config.update(over)
    return config


def _cfg(config, **extra):
    extra.setdefault("compute_dtype", jnp.float32)
    return model.model_config(config, "float32", remat_policy="selective",
                              **extra)


def _micro(config, packed, rows=2, seed=11):
    job = {"sequences_per_step": rows, "seq_length": SEQ,
           "doc_len": {"median": 30, "sigma": 1.0, "min": 4, "max": SEQ},
           "pool_documents": 64, "shape_seed": 5}
    batch = next(generator.batches(job, seed, config["vocab_size"], SEQ))
    if not packed:
        batch["segment_ids"] = np.zeros_like(batch["segment_ids"])
        batch["position_ids"] = np.tile(np.arange(SEQ, dtype=np.int32),
                                        (rows, 1))
        batch["loss_mask"] = np.ones_like(batch["loss_mask"])
    return batch


def _program(cfg, params, micro):
    def loss(p):
        return gpt_loss(p, jnp.asarray(micro["tokens"]),
                        jnp.asarray(micro["labels"]),
                        jnp.asarray(micro["loss_mask"]), cfg,
                        segment_ids=jnp.asarray(micro["segment_ids"]))
    # one program (eagerly: a compile a primitive, 20 s and more a case)
    with jax.default_matmul_precision("highest"):
        (value, metrics), grads = jax.jit(
            jax.value_and_grad(loss, has_aux=True))(params)
    return float(value), metrics, grads


def _norm(tree):
    return float(jnp.sqrt(sum(jnp.sum(jnp.square(x))
                              for x in jax.tree.leaves(tree))))


@pytest.fixture(scope="module")
def seeded():
    config = _small()
    cfg = _cfg(config)
    return config, cfg, model.init_params(cfg, 7)


@pytest.fixture(scope="module")
def packed(seeded):
    """The packed micro-batch every case below reads, the program's loss,
    metrics and gradients on it and the reference's loss and gradients:
    each worked out once."""
    config, cfg, params = seeded
    micro = _micro(config, packed=True)
    return (micro, _program(cfg, params, micro),
            model.reference_loss_and_grads(params, config, micro))


@pytest.mark.parametrize("is_packed", [False, True], ids=["plain", "packed"])
def test_loss_and_every_leafs_gradient_match_the_reference(seeded, packed,
                                                           is_packed):
    config, cfg, params = seeded
    if is_packed:
        _, (value, metrics, grads), (ref_value, ref_grads) = packed
    else:
        micro = _micro(config, packed=False)
        value, metrics, grads = _program(cfg, params, micro)
        ref_value, ref_grads = model.reference_loss_and_grads(params, config,
                                                              micro)
    assert abs(value - ref_value) < 2e-6
    mine = dict(jax.tree_util.tree_leaves_with_path(grads))
    theirs = dict(jax.tree_util.tree_leaves_with_path(ref_grads))
    assert set(mine) == set(theirs) and len(mine) == 19
    for path, leaf in mine.items():
        scale = float(jnp.max(jnp.abs(theirs[path])))
        assert scale > 0, jax.tree_util.keystr(path)
        np.testing.assert_allclose(
            leaf, theirs[path], atol=2e-5 * scale, rtol=0,
            err_msg=jax.tree_util.keystr(path))
    # the router's loss reaches the step's metrics through the hybrid loop
    assert float(metrics["moe_aux_loss"]) > 0
    assert abs(float(metrics["lm_loss"] + metrics["moe_aux_loss"])
               - ref_value) < 2e-6


# Past these the program would not be "correct": the gradients' distance
# from the reference's over their norm, and the loss's distance. The
# program reads 1e-7 and 2e-7 (test above).
GRAD_TOL, LOSS_TOL = 1e-4, 1e-5


@pytest.mark.parametrize("control", model.CONTROLS)
def test_each_wrong_model_is_past_the_tolerance(seeded, packed, control):
    config, cfg, params = seeded
    micro, (value, _, grads), (right_value, right_grads) = packed
    wrong_value, wrong_grads = model.reference_loss_and_grads(
        params, config, micro, control=control)
    moved = _norm(jax.tree.map(jnp.subtract, grads, wrong_grads)) / _norm(
        grads)
    assert moved > GRAD_TOL or abs(value - wrong_value) > LOSS_TOL
    assert _norm(jax.tree.map(jnp.subtract, grads, right_grads)) / _norm(
        grads) < GRAD_TOL / 50
    assert abs(value - right_value) < LOSS_TOL / 5


def test_the_reference_in_bf16_is_another_reading(seeded, packed):
    """The precision control that the cell's limits have to refuse on the
    chip: the same equations on bf16 arrays with one-pass products."""
    config, _, params = seeded
    micro, _, (value, grads) = packed
    low_value, low_grads = model.reference_loss_and_grads(
        params, config, micro, compute="bfloat16")
    assert 1e-4 < abs(_norm(low_grads) / _norm(grads) - 1) < 0.1
    assert abs(low_value - value) < 0.05


def test_the_four_shares_add_up_to_the_whole_layer():
    """moe_experts_held = (0,4), (4,4), (8,4), (12,4) of 16 experts: the
    shares' outputs and their gradients by the input add up to the layer
    that holds every expert, in the program and by the plain reference."""
    base = dict(num_layers=1, hidden_size=32, num_attention_heads=2,
                kv_channels=16, ffn_hidden_size=64, vocab_size=64,
                num_moe_experts=16, moe_router_topk=4, moe_ffn_hidden_size=24,
                moe_aux_loss_coeff=0.01, compute_dtype=jnp.float32)
    from megatronapp_tpu.config.transformer_config import (
        ActivationKind, TransformerConfig,
    )
    base["activation"] = ActivationKind.swiglu
    whole_cfg = TransformerConfig(**base)
    whole, _ = moe.init_moe_params(jax.random.PRNGKey(3), whole_cfg, 0.02)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 24, 32), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(5), (2, 24, 32), jnp.float32)

    def run(cfg, p):
        def f(x_):
            out, aux = moe.moe_forward(p, x_, cfg)
            return jnp.sum(out * w), (out, aux)
        with jax.default_matmul_precision("highest"):
            (_, (out, aux)), dx = jax.value_and_grad(f, has_aux=True)(x)
        return out, aux, dx

    out, aux, dx = run(whole_cfg, whole)
    parts = []
    for first in (0, 4, 8, 12):
        cfg = TransformerConfig(**base, moe_experts_held=(first, 4))
        p = dict(whole, fc1_kernel=whole["fc1_kernel"][first:first + 4],
                 fc2_kernel=whole["fc2_kernel"][first:first + 4])
        parts.append(run(cfg, p))
        # the router's loss is over all 16 outputs, whatever is held
        np.testing.assert_allclose(parts[-1][1], aux, rtol=1e-6)
    np.testing.assert_allclose(sum(p[0] for p in parts), out, atol=1e-6)
    np.testing.assert_allclose(sum(p[2] for p in parts), dx, atol=1e-6)

    # ... and the plain reference's whole layer is that sum too
    st = model._Static(groups=1, window=0, eps=1e-6, top_k=4, first=0,
                       width=16, coef=0.01, control="", precision="highest")
    with jax.default_matmul_precision("highest"):
        ref_out, ref_aux = model._moe(x.reshape(48, 32), whole, st)
    np.testing.assert_allclose(ref_out.reshape(2, 24, 32), out, atol=1e-6)
    np.testing.assert_allclose(ref_aux, aux, rtol=1e-6)


@pytest.fixture
def small_rungs(monkeypatch):
    """The held experts' ladder at a test's size: a micro-batch of 2 x 96
    tokens x 4 picks = 768 rows of which 192 land here by chance, in tiles
    of 8, where the program offers a compact buffer only to calls that skip
    8,192 rows."""
    monkeypatch.setattr(moe, "_RUNG_MIN_SKIPPED", 64)
    monkeypatch.setattr(moe, "_RUNG_TILE", 8)
    assert moe._row_buffer_rungs(768, 4, 16) == (240, 288, 384, 768)


@pytest.mark.parametrize("policy", ["none", "selective", "full"])
def test_the_hybrid_loop_carries_loss_and_counts_under_recomputation(
        policy, small_rungs):
    config = _small(num_hidden_layers=8,
                    layer_types=model.REHEARSAL["layer_types"] * 2,
                    mlp_layer_types=["sparse"] * 8)
    cfg = dataclasses.replace(_cfg(config), remat_policy=policy)
    params = model.init_params(cfg, 3)
    micro = _micro(config, packed=True)
    value, metrics, grads = _program(cfg, params, micro)
    ref_value, ref_grads = model.reference_loss_and_grads(params, config,
                                                          micro)
    assert abs(value - ref_value) < 5e-6
    assert _norm(jax.tree.map(jnp.subtract, grads, ref_grads)) < (
        1e-5 * _norm(ref_grads))
    sums = jax.tree.map(float, metrics["sums"])
    tokens = micro["tokens"].size
    assert sums["assignments"] == tokens * 4 * 8
    assert (sums["assignments_here"] + sums["assignments_absent"]
            == sums["assignments"])
    assert sums["experts_here"] == 4 * 8 and sums["moe_layer_passes"] == 8
    assert sums["here_max_rows"] >= sums["assignments_here"] / 4
    assert abs(sums["router_loss"] - float(metrics["moe_aux_loss"])) < 1e-9
    # eight layer calls, each on one of the rungs (240, 288, 384, 768)
    assert (sums["assignments_here"] <= sums["row_buffer_rows"]
            < sums["assignments"])
    assert sums["row_buffer_rows"] % 48 == 0
    assert sums["row_buffer_rows"] >= 8 * 240


def test_a_packed_step_through_the_flash_kernels_counts_its_tiles(seeded):
    """With the flash kernels asked for (tiles of 32 over rows of 96: three
    tiles a sequence, so the kernels are handed the documents' table), the
    loss is the one under XLA's dense attention and the step's sums gain the
    tiles of the causal triangle (1 full layer) and the band (3 window
    layers of 24 keys) and how many of them the table lets the kernels
    compute; XLA's dense attention counts none."""
    from megatronapp_tpu.ops.pallas import flash_attention as fa
    config, dense_cfg, params = seeded
    cfg = dataclasses.replace(dense_cfg, attention_impl="pallas",
                              flash_block_q=32, flash_block_kv=32)
    micro = _micro(config, packed=True)

    def forward(cfg):
        with jax.default_matmul_precision("highest"):
            return gpt_loss(params, jnp.asarray(micro["tokens"]),
                            jnp.asarray(micro["labels"]),
                            jnp.asarray(micro["loss_mask"]), cfg,
                            segment_ids=jnp.asarray(micro["segment_ids"]))

    value, metrics = forward(cfg)
    dense_value, dense_metrics = forward(dense_cfg)
    assert abs(float(value) - float(dense_value)) < 5e-6
    sums = jax.tree.map(float, metrics["sums"])
    ids = jnp.asarray(micro["segment_ids"])
    full = fa.segment_tile_counts(ids, 32, 32)
    band = fa.segment_tile_counts(ids, 32, 32, window=24)
    assert (full[0], band[0]) == (2 * 6, 2 * 5)
    assert sums["flash_tiles"] == full[0] + 3 * band[0]
    assert sums["flash_tiles_computed"] == int(full[1]) + 3 * int(band[1])
    assert 2 * 3 * 4 <= sums["flash_tiles_computed"] < sums["flash_tiles"]
    assert "flash_tiles" not in dense_metrics["sums"]
    assert "assignments" in dense_metrics["sums"]


@pytest.mark.parametrize("dtype", ["float32", "bf16"])
def test_the_laddered_model_is_the_full_buffers(dtype, small_rungs):
    """The whole model, jitted, with its held experts on the ladder and on
    the T*k buffer (the program at this size without the fixture): the same
    loss and counts, and every leaf's gradient to a float32 rounding (to
    one of bf16 where that is the compute type). What is equal bit for bit,
    the layer's sum and the gradients of what enters it, is held in
    tests/test_moe.py, a layer alone; two whole programs XLA:CPU fuses
    differently."""
    config = _small(num_hidden_layers=2, mlp_layer_types=["sparse"] * 2,
                    layer_types=model.REHEARSAL["layer_types"][2:])
    compute = jnp.float32 if dtype == "float32" else jnp.bfloat16
    cfg = _cfg(config, compute_dtype=compute)
    params = model.init_params(cfg, 7)
    micro = {k: jnp.asarray(v) for k, v in _micro(config, packed=True).items()}

    def program():
        def loss(p):        # traced afresh: jax keeps a function's jaxpr
            return gpt_loss(p, micro["tokens"], micro["labels"],
                            micro["loss_mask"], cfg,
                            segment_ids=micro["segment_ids"])
        (value, metrics), grads = jax.jit(
            jax.value_and_grad(loss, has_aux=True))(params)
        return float(value), metrics, grads

    value, metrics, grads = program()
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(moe, "_row_buffer_rungs", lambda rows, c, w: (rows,))
        full_value, full_metrics, full_grads = program()
    assert value == full_value
    sums, full_sums = (jax.tree.map(float, m["sums"])
                       for m in (metrics, full_metrics))
    assert full_sums.pop("row_buffer_rows") == sums["assignments"]
    assert sums.pop("row_buffer_rows") < sums["assignments"]
    assert sums == full_sums
    for got, want in zip(jax.tree.leaves(grads), jax.tree.leaves(full_grads),
                         strict=True):
        np.testing.assert_allclose(
            got, want, rtol=0, atol=(1e-5 if dtype == "float32" else 2e-2)
            * float(jnp.abs(want).max()))


def test_a_hybrid_stack_takes_held_experts_and_the_routers_loss():
    cfg = PRESETS["mellum2-12b-a2.5b"](num_layers=4,
                                       moe_experts_held=(16, 16),
                                       moe_z_loss_coeff=1e-3)
    assert cfg.moe_counts_load and cfg.moe_picks_unheld
    assert (cfg.num_window_layers, cfg.num_attention_layers) == (3, 1)
    with pytest.raises(ValueError, match="moe_zero_experts"):
        PRESETS["mellum2-12b-a2.5b"](moe_zero_experts=4)


def _train_step(cfg, num_micro, copies=True):
    from megatronapp_tpu.parallel.mesh import build_mesh
    from megatronapp_tpu.training.optimizer import get_optimizer
    from megatronapp_tpu.training.train import gpt_microbatch_loss
    from megatronapp_tpu.training.train_state import setup_train_state
    from megatronapp_tpu.training.train_step import make_train_step
    ctx = build_mesh(ParallelConfig(), devices=jax.devices()[:1])
    opt = OptimizerConfig(lr=1e-3)
    optimizer = get_optimizer(opt, 10)
    state, shardings, _ = setup_train_state(
        jax.random.PRNGKey(0), lambda k: init_gpt_params(k, cfg), optimizer,
        ctx)
    loss_fn = gpt_microbatch_loss(cfg, ctx=ctx)
    if not copies:
        del loss_fn.compute_copies
    step = make_train_step(loss_fn, optimizer, opt, ctx, shardings, 10,
                           donate=False)
    return ctx, state, step


def test_the_steps_sums_are_totals_and_its_copies_change_nothing():
    config = _small()
    cfg = _cfg(config, compute_dtype=jnp.bfloat16)
    batch = _micro(config, packed=True, rows=4)
    batch = {k: jnp.asarray(v).reshape(4, 1, SEQ) for k, v in batch.items()}
    out = {}
    for copies in (True, False):
        ctx, state, step = _train_step(cfg, 4, copies)
        with ctx.mesh:
            new_state, metrics = step(state, batch)
        out[copies] = (new_state, jax.device_get(metrics))
    sums = out[True][1]["sums"]
    assert sums["assignments"] == 4 * SEQ * 4 * 4       # rows x S x k x L
    assert sums["moe_layer_passes"] == 4 * 4 and sums["experts_here"] == 64
    assert 0 < out[True][1]["moe_aux_loss"] < 0.1       # a mean, not a total
    np.testing.assert_allclose(sums["router_loss"],
                               4 * out[True][1]["moe_aux_loss"], rtol=1e-5)
    # differentiated by the compute-type copies or by the float32 leaves:
    # the same loss, and gradients that differ by a rounding to bf16 which
    # XLA may spare the float32 leaves' casts (excess precision)
    assert out[True][1]["loss"] == out[False][1]["loss"]
    np.testing.assert_allclose(out[True][1]["grad_norm"],
                               out[False][1]["grad_norm"], rtol=1e-5)


def test_the_precision_controls_on_either_side_of_the_runners_limit(seeded,
                                                                    packed):
    """The cell's runner holds |g - r| / |r| of the first gradient to
    FIRST_GRAD_GAP_TOL. At a small size too, the reference in the precision
    below the configuration's (operands in float8) lies beyond it and the
    reference in the program's own arithmetic (bf16 arrays, and with bf16
    accumulators) inside it. tools/share_train_control.py puts the same
    controls through the cell's own command on the chip."""
    config, _, params = seeded
    limit = manifest.load_module("cells", "pretrain_share").FIRST_GRAD_GAP_TOL
    micro, _, (_, right) = packed
    size = np.sqrt(sum(float(jnp.sum(jnp.square(g)))
                       for g in jax.tree.leaves(right)))
    gaps = {}
    for compute in ("bfloat16", "bfloat16-accumulate", "float8"):
        _, got = model.reference_loss_and_grads(params, config, micro,
                                                compute=compute)
        gaps[compute] = np.sqrt(sum(
            float(jnp.sum(jnp.square(a - b))) for a, b in zip(
                jax.tree.leaves(got), jax.tree.leaves(right)))) / size
    assert gaps["bfloat16"] < limit and gaps["bfloat16-accumulate"] < limit
    assert gaps["float8"] > 1.5 * limit, gaps


def test_compute_dtype_kernels_casts_what_is_multiplied_in_it(seeded):
    from megatronapp_tpu.training.train import compute_dtype_kernels
    _, _, params = seeded
    cast = compute_dtype_kernels(params, jnp.bfloat16)
    kinds = {jax.tree_util.keystr(p): leaf.dtype for p, leaf in
             jax.tree_util.tree_leaves_with_path(cast)}
    assert kinds["['block']['ffn']['moe']['fc1_kernel']"] == jnp.bfloat16
    assert kinds["['block']['mixers_swa']['attention']['q_kernel']"] == (
        jnp.bfloat16)
    assert kinds["['output']"] == jnp.bfloat16
    for kept in ("['block']['ffn']['moe']['router_kernel']",
                 "['embedding']['word']", "['final_ln_scale']",
                 "['block']['ffn']['ln2_scale']",
                 "['block']['mixers_attn']['attention']['q_ln_scale']"):
        assert kinds[kept] == jnp.float32, kept
    # by name, whatever the model: a mixer's projections are multiplied in
    # the compute type, its taps and a router's kernel in float32
    other = compute_dtype_kernels(
        {"mixers_conv": {"conv": {k: jnp.zeros((2, 2), jnp.float32)
                                  for k in ("in_kernel", "conv_kernel",
                                            "out_kernel")}}},
        jnp.bfloat16)["mixers_conv"]["conv"]
    assert {k: v.dtype for k, v in other.items()} == {
        "in_kernel": jnp.bfloat16, "conv_kernel": jnp.float32,
        "out_kernel": jnp.bfloat16}
    # every model whose parameters' type is not its compute type is
    # differentiated by them, a dense one too
    from megatronapp_tpu.config.transformer_config import TransformerConfig
    from megatronapp_tpu.training.train import gpt_microbatch_loss
    dense = dict(num_layers=2, hidden_size=32, num_attention_heads=4,
                 vocab_size=64, max_position_embeddings=16)
    assert hasattr(gpt_microbatch_loss(TransformerConfig(**dense)),
                   "compute_copies")
    assert not hasattr(gpt_microbatch_loss(TransformerConfig(
        **dense, compute_dtype=jnp.float32)), "compute_copies")


def test_the_loop_logs_moe_and_emits_its_spans(tmp_path, small_rungs,
                                               monkeypatch):
    from megatronapp_tpu.parallel.mesh import build_mesh
    from megatronapp_tpu.trace.request_trace import get_request_tracer
    from megatronapp_tpu.trace.tracer import get_tracer
    from megatronapp_tpu.training.train import pretrain_gpt
    # MegaScan's tracer is one a process, and `pretrain_gpt` configures it
    # only where it is asked to trace: a test that traced earlier in this
    # worker (tests/test_scope_map.py, test_megascan.py, ...) leaves it
    # enabled, the loop then takes every step for a traced one and syncs
    # after each ([1, 1, 1, 1] where the log interval gives [2, 2]).
    monkeypatch.setattr(get_tracer(), "enabled", False)
    sys.path.insert(0, os.path.join(ROOT, "perfbench"))
    pretrain_cell = manifest.load_module("cells", "pretrain")
    config = _small()
    cfg = _cfg(config, compute_dtype=jnp.bfloat16)
    job = {"sequences_per_step": 4, "seq_length": SEQ,
           "doc_len": {"median": 30, "sigma": 1.0, "min": 4, "max": SEQ},
           "pool_documents": 64, "shape_seed": 5}
    train = TrainingConfig(micro_batch_size=2, global_batch_size=4,
                           seq_length=SEQ, train_iters=4, log_interval=2)
    lines = []
    ring = get_request_tracer()
    ring.reset()
    ring.configure(enabled=True)
    try:
        pretrain_gpt(cfg, ParallelConfig(), train, OptimizerConfig(lr=1e-3),
                     ctx=build_mesh(ParallelConfig(),
                                    devices=jax.devices()[:1]),
                     batch_iter=generator.batches(job, 1, 512, SEQ),
                     log_fn=lines.append)
    finally:
        records = ring.dump()
        ring.configure(enabled=False)
        ring.reset()
    logged = [ln for ln in lines if pretrain_cell.ITER_RE.search(ln)]
    assert len(logged) == 2
    for ln in logged:
        m = re.search(r"skipped 0 \| moe here (\S+) buffer (\S+) max/mean "
                      r"(\S+) router (\S+) \| \S+ ms/step", ln)
        assert m, ln
        assert 0.15 < float(m[1]) < 0.35 and float(m[3]) >= 1
        # rows walked over picks held: the ladder's, under the T*k
        # buffer's 1 / here
        assert 1 <= float(m[2]) < 0.9 / float(m[1])
        assert 5e-4 < float(m[4]) < 2e-3       # ~ the coefficient a layer
    steps = [r for r in records if r["name"] == "train-step"
             and r["ph"] == "B"]
    assert [r["args"]["iteration"] for r in steps] == [1, 2, 3, 4]
    assert steps[0]["args"] == {"iteration": 1, "micro_batches": 2,
                                "tokens": 4 * SEQ}
    syncs = [r["args"] for r in records if r["name"] == "train-sync"
             and r["ph"] == "E"]
    assert [s["steps"] for s in syncs] == [2, 2]
    for s in syncs:
        assert s["assignments"] == 2 * 4 * SEQ * 4 * 4
        assert (s["assignments_here"] + s["assignments_absent"]
                == s["assignments"])
        assert s["experts_here"] / s["moe_layer_passes"] == 4
        assert s["loss"] > 0 and s["grad_norm"] > 0
        # 2 steps x 2 micro-batches x 4 layers, each on a rung of
        # (240, 288, 384, 768)
        assert (s["assignments_here"] <= s["row_buffer_rows"]
                < s["assignments"])
        assert s["row_buffer_rows"] % 48 == 0


def test_a_dense_models_log_line_and_metrics_are_what_they_were():
    from megatronapp_tpu.training.train import _moe_log_part
    assert _moe_log_part({}) == ""
    assert _moe_log_part({
        "assignments": 800.0, "assignments_here": 200.0,
        "row_buffer_rows": 262.0, "here_max_rows": 40.0,
        "experts_here": 32.0, "moe_layer_passes": 8.0,
        "router_loss": 0.008}) == (
        "moe here 0.250 buffer 1.31 max/mean 0.80 router 1.0e-03 | ")


# ---- the preset, the file, the counts --------------------------------------

def test_the_configuration_file_is_the_catalog_rows_but_for_its_cut():
    mine = _file()
    assert mine["reduced"] == ["num_hidden_layers", "layer_types",
                               "mlp_layer_types", "num_experts",
                               "vocab_size"]
    pub = mine["published"]
    assert (mine["num_hidden_layers"], pub["num_hidden_layers"]) == (4, 28)
    assert mine["layer_types"] == pub["layer_types"][:4] == [
        "sliding_attention"] * 3 + ["full_attention"]
    assert mine["mlp_layer_types"] == pub["mlp_layer_types"][:4]
    assert (mine["num_experts"], pub["num_experts"],
            mine["router_width"]) == (16, 64, 64)
    assert mine["expert_share"]["first"] == 0
    assert mine["expert_share"]["of_chips"] == 4
    assert pub["vocab_size"] == 98304
    assert mine["vocab_size"] * 8 >= pub["vocab_size"]      # the floor
    for key, value in {
            "hidden_size": 2304, "head_dim": 128, "num_attention_heads": 32,
            "num_key_value_heads": 4, "intermediate_size": 7168,
            "moe_intermediate_size": 896, "num_experts_per_tok": 8,
            "sliding_window": 1024, "rms_norm_eps": 1e-6,
            "norm_topk_prob": True, "max_position_embeddings": 131072,
            }.items():
        assert mine[key] == value, key
    assert len(mine["source"]) <= 200
    assert set(mine["assumed"]) == {"qk_norm", "rope_pairing",
                                    "router_aux_loss_coef", "init_std",
                                    "mtp_head"}
    for entry in mine["assumed"].values():
        assert set(entry) == {"value", "what", "evidence"}
    assert (mine["assumed"]["router_aux_loss_coef"]["value"]
            == mine["train"]["moe_aux_loss_coeff"] == 0.001)
    assert "seven pipeline stages of four layers" in mine["deployment"]
    assert "four chips share each layer" in mine["deployment"]
    assert mine["train"]["micro_batch_size"] in (1, 2, 4)
    # the file parses to the sizes its arithmetic states
    millions = round(model.params_count(mine) / 1e6, 1)
    assert f"{millions}M parameters" in mine["reduced_why"]
    assert f"{model.params_count(mine) * 16 / 1e9:.2f} GB" in mine[
        "reduced_why"]
    cfg = model.model_config(mine, "float32")
    abstract = jax.eval_shape(lambda k: init_gpt_params(k, cfg)[0],
                              jax.random.PRNGKey(0))
    assert sum(a.size for a in jax.tree.leaves(abstract)) == (
        model.params_count(mine))

    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        return
    with open(catalog) as f:
        row, = (json.loads(ln) for ln in f if '"Mellum2-12B-A2.5B' in ln)
    assert mine["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key not in mine["reduced"]:
            assert mine[key] == value, key
        else:
            assert mine["published"][key] == value, key


def test_the_preset_is_the_file_at_its_published_depth():
    mine = _file()
    cut = dataclasses.asdict(model.model_config(mine, "float32"))
    preset = dataclasses.asdict(PRESETS["mellum2-12b-a2.5b"](
        num_layers=4, moe_experts_held=(0, 16),
        vocab_size=mine["vocab_size"], vocab_slice_of=98304))
    assert cut == preset
    whole = PRESETS["mellum2-12b-a2.5b"]()
    assert (whole.num_layers, whole.num_attention_layers,
            whole.num_window_layers) == (28, 7, 21)
    assert [i for i in range(28) if whole.layer_is_attention(i)] == [
        3, 7, 11, 15, 19, 23, 27]
    published = dict(mine, **mine["published"])
    assert round(model.params_count(published) / 1e9, 2) == 12.15
    assert abs(whole.yarn_attention_factor - (0.1 * np.log(16) + 1)) < 1e-12


def test_a_stage_is_initialised_at_the_models_depth():
    """scaled_init_layers: the residual-out projections' std divides by the
    published depth, not by the layers run."""
    config = _small(hidden_size=128, moe_intermediate_size=64)
    cfg = _cfg(config)
    assert cfg.scaled_init_layers == 28 and cfg.num_layers == 4
    block = model.init_params(cfg, 1)["block"]
    for leaf in (block["mixers_swa"]["attention"]["out_kernel"],
                 block["ffn"]["moe"]["fc2_kernel"]):
        assert abs(float(jnp.std(leaf)) / (0.02 / np.sqrt(56)) - 1) < 0.02
    assert abs(float(jnp.std(block["ffn"]["moe"]["fc1_kernel"])) / 0.02
               - 1) < 0.02
    by_run = model.init_params(dataclasses.replace(
        cfg, scaled_init_layers=None), 1)["block"]
    assert abs(float(jnp.std(by_run["ffn"]["moe"]["fc2_kernel"]))
               / (0.02 / np.sqrt(8)) - 1) < 0.02


def test_the_operations_of_a_token():
    from megatronapp_tpu.utils.flops import flops_per_token
    mine = _file()
    cfg = model.model_config(mine, "float32")
    h, d, f = 2304, 128, 896
    proj = 2 * h * (32 + 2 * 4) * d + 2 * 32 * d * h
    pair = 2 * 2 * d * 32
    expert = 3 * 2 * h * f
    router = 2 * h * 64
    head = 2 * h * mine["vocab_size"]
    for seq in (512, 8192):
        keys = 3 * min(seq, 1024) + seq
        # the program counts the model: all 8 picks
        assert flops_per_token(cfg, seq) == 3.0 * (
            4 * proj + pair * keys + 4 * (8 * expert + router) + head)
        # the benchmark counts the share: the 8 x 16/64 picks that land here
        assert mellum_flops.flops_per_token(mine, seq) == 3 * (
            4 * proj + pair * keys + 4 * (2 * expert + router) + head)
    # a dense model's count is what it was
    dense = PRESETS["gpt2-125m"]()
    assert flops_per_token(dense, 1024) == 3.0 * (12 * (
        2 * 768 * 768 * 4 + 2 * 2 * 1024 * 768 + 2 * 2 * 768 * 3072)
        + 2 * 768 * 50304)


def test_the_window_pairs_of_packed_rows():
    rng = np.random.default_rng(0)
    segs = np.sort(rng.integers(0, 5, size=(3, 200)), axis=1)
    at = np.arange(200)
    for window in (1, 7, 64, 500):
        brute = sum(int(((at[:, None] >= at[None, :])
                         & (at[:, None] - at[None, :] < window)
                         & (row[:, None] == row[None, :])).sum())
                    for row in segs)
        assert mellum_flops.window_pairs(segs, window) == brute
    one_doc = np.zeros((1, 8192), np.int32)
    assert mellum_flops.window_pairs(one_doc, 1024) == (
        1024 * 1025 // 2 + (8192 - 1024) * 1024)
