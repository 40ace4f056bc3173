"""The dropless experts move rows by gathers alone (ISSUE 60): the sort's
permutation and its inverse carry dispatch, combine and both transposes.
Each test holds the program to the parent's form, kept here as plain
``jax.numpy``: ``jnp.bincount`` for the groups' sizes and a float32
scatter-add of the T*k pick rows for the sum."""

import copy
import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from megatronapp_tpu.config.transformer_config import TransformerConfig
from megatronapp_tpu.ops.activations import ActivationKind
from megatronapp_tpu.transformer import block, moe
from megatronapp_tpu.utils.dispatch import launch_stats

T, K, H = 64, 4, 32
ROWS = T * K

# What a layer holds and what its router may pick, by case.
LAYERS = {
    "every-expert-held": dict(num_moe_experts=8),
    "a-share-held": dict(num_moe_experts=16, moe_experts_held=(4, 4)),
    "zero-compute": dict(num_moe_experts=8, moe_zero_experts=4),
    "a-share-and-zero-compute": dict(num_moe_experts=12, moe_zero_experts=4,
                                     moe_experts_held=(2, 6)),
}
# The ladder of a 256-row call whose layer holds 4 of 16 experts, in tiles of
# 8 (tests/test_moe.py::TestRowBufferLadder's): a buffer of each size.
RUNGS = (80, 96, 128, 256)


def _cfg(dtype=jnp.float32, **kw):
    d = dict(num_layers=1, hidden_size=H, num_attention_heads=4,
             vocab_size=64, max_position_embeddings=32, moe_router_topk=K,
             moe_ffn_hidden_size=16, compute_dtype=dtype,
             activation=ActivationKind.swiglu, remat_policy="none")
    d.update(kw)
    return TransformerConfig(**d)


@functools.lru_cache(maxsize=None)
def _layer(case, dtype=jnp.float32):
    """(cfg, params, picks [T, K] distinct a token, weights [T, K], x)."""
    cfg = _cfg(dtype, **LAYERS[case])
    p, _ = moe.init_moe_params(jax.random.PRNGKey(0), cfg, 0.02)
    rng = np.random.default_rng(3)
    idx = jnp.asarray(np.stack([rng.permutation(cfg.moe_router_width)[:K]
                                for _ in range(T)]), jnp.int32)
    probs = jax.nn.softmax(jax.random.normal(jax.random.PRNGKey(2), (T, K)))
    x = jax.random.normal(jax.random.PRNGKey(1), (T, H), dtype)
    return cfg, p, idx, probs, x


@functools.lru_cache(maxsize=None)
def _few_held_picks():
    """Picks of the a-share-held layer of which 64 land here (one a token):
    every rung of RUNGS holds them."""
    rng = np.random.default_rng(5)
    return jnp.asarray(np.stack([np.concatenate([
        rng.permutation(np.arange(4, 8))[:1],
        rng.permutation(np.r_[0:4, 8:16])[:K - 1]])[rng.permutation(K)]
        for _ in range(T)]), jnp.int32)


def _scattered(fc1, fc2, x, idx, probs, cfg):
    """The parent's dropless experts (9eb0e4a): the groups' sizes by
    ``jnp.bincount``, the sum by a float32 scatter-add of the T*k pick rows
    in sorted order; the rows behind the groups masked on every side."""
    t, k = idx.shape
    dt = cfg.compute_dtype
    slot, count = moe._held_slot(idx.reshape(t * k), cfg)
    order = jnp.argsort(slot)
    token_of = order // k
    sizes = jnp.bincount(slot, length=count + 1)[:count].astype(jnp.int32)
    live = (jnp.arange(t * k) < jnp.sum(sizes))[:, None]
    xs = jnp.where(live, jnp.take(x.astype(dt), token_of, axis=0), 0)
    y1 = jnp.where(live, jax.lax.ragged_dot(xs, fc1.astype(dt), sizes), 0)
    y2 = jax.lax.ragged_dot(jnp.where(live, moe._apply_act(cfg, y1), 0),
                            fc2.astype(dt), sizes)
    w = jnp.take(probs.reshape(-1).astype(jnp.float32), order)[:, None]
    out = jnp.zeros((t, x.shape[1]), jnp.float32).at[token_of].add(
        jnp.where(live, y2.astype(jnp.float32) * w, 0.0))
    if cfg.moe_zero_experts:
        w_zero = jnp.sum(jnp.where(idx >= cfg.num_moe_experts,
                                   probs.astype(jnp.float32), 0.0), axis=-1)
        out = out + w_zero[:, None] * x.astype(jnp.float32)
    return out


@pytest.fixture
def undefined_rows(monkeypatch):
    """The grouped products as the chip leaves them: NaN in every row behind
    the last group, where XLA:CPU writes zeros."""
    masked = moe._ragged_dot

    def product(x, w, group_sizes):
        keep = (jnp.arange(x.shape[0]) < jnp.sum(group_sizes))[:, None]
        return jnp.where(keep, masked(x, w, group_sizes), jnp.nan)
    monkeypatch.setattr(moe, "_ragged_dot", product)


def _close(got, want):
    """Equal to a float32 rounding of the largest element (the same terms,
    summed in another order)."""
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=1e-6 * max(np.abs(want).max(), 1e-30))


@pytest.mark.parametrize("case,dtype", [
    (case, jnp.float32)
    for case in list(LAYERS) + [f"rung-{r}" for r in RUNGS]] + [
    (case, jnp.bfloat16)
    for case in ("every-expert-held", "a-share-and-zero-compute", "rung-80")])
def test_the_gathered_sum_is_the_scattered_one(case, dtype, undefined_rows):
    """A layer's output against the scatter-add form, whatever it holds and
    whatever buffer its held picks are given, with NaN in every row of the
    products that belongs to no expert: finite, and equal to a float32
    rounding (products in the compute dtype, the sum over a token's k picks
    in float32, in both)."""
    if case in LAYERS:
        cfg, p, idx, probs, x = _layer(case, dtype)

        def program(x, probs):
            return moe._dropless_experts(p, x, idx, probs, cfg)
    else:
        cfg, p, _, probs, x = _layer("a-share-held", dtype)
        idx = _few_held_picks()
        slot, count = moe._held_slot(idx.reshape(ROWS), cfg)

        def program(x, probs):
            return moe._held_rows(
                (p["fc1_kernel"], p["fc2_kernel"]), x, probs,
                *moe._sorted_picks(slot, count), cfg, int(case[5:]))[0]
    got, want = jax.jit(lambda x, probs: (program(x, probs), _scattered(
        p["fc1_kernel"], p["fc2_kernel"], x, idx, probs, cfg)))(x, probs)
    assert got.dtype == jnp.float32 and got.shape == (T, H)
    assert np.abs(np.asarray(want)).max() > 0
    _close(got, want)


@pytest.mark.parametrize("rows", [RUNGS[0], RUNGS[-1]])
def test_the_pair_and_its_transposes_read_no_row_behind_the_groups(rows):
    """_combine_rows and _dispatch_rows alone over a buffer of `rows`, NaN
    in every row behind the n real ones of what each pass gathers from: the
    sum, its cotangents and the tokens' are finite and the scatter-add
    form's."""
    cfg, _, _, probs, x = _layer("a-share-held")
    slot, count = moe._held_slot(_few_held_picks().reshape(ROWS), cfg)
    order, inv, sizes = moe._sorted_picks(slot, count)
    n = int(jnp.sum(sizes))
    assert n == 64
    at = inv.reshape(T, K).T
    token_of = order[:rows] // K
    live = (jnp.arange(rows) < n)[:, None]
    y = jax.random.normal(jax.random.PRNGKey(4), (rows, H))
    g = jax.random.normal(jax.random.PRNGKey(5), (T, H))

    def scattered(y, w):
        w_rows = jnp.take(w.reshape(-1), order[:rows])[:, None]
        return jnp.zeros((T, H)).at[token_of].add(
            jnp.where(live, y * w_rows, 0.0))
    dxs = jax.random.normal(jax.random.PRNGKey(6), (rows, H))

    @jax.jit
    def both(hole):
        out, vjp = jax.vjp(
            lambda y, w: moe._combine_rows(y, w, order, at, n),
            jnp.where(live, y, hole), probs)
        want, want_vjp = jax.vjp(scattered, jnp.where(live, y, 0.0), probs)
        sent, sent_vjp = jax.vjp(
            lambda x: moe._dispatch_rows(x, token_of, at, n), x)
        return ((out, *vjp(g), sent, *sent_vjp(jnp.where(live, dxs, hole))),
                (want, *want_vjp(g), jnp.take(x, token_of, axis=0),
                 jnp.zeros((T, H)).at[token_of].add(
                     jnp.where(live, dxs, 0.0))))
    for got, want in zip(*both(jnp.nan)):
        _close(got, want)


@pytest.mark.parametrize("policy", ["none", "selective"])
@pytest.mark.parametrize("case", ["every-expert-held",
                                  "a-share-and-zero-compute", "laddered"])
def test_the_gradients_are_the_scattered_forms(case, policy, monkeypatch):
    """Gradients of the tokens, the router's weights and both kernels
    against the scatter-add form's, with the layer loop's 'selective' policy
    (jax.checkpoint keeping the products) and without; `laddered`: through
    the ladder's switch and its own backward pass, on a compact rung."""
    if case == "laddered":
        monkeypatch.setattr(moe, "_RUNG_MIN_SKIPPED", 64)
        monkeypatch.setattr(moe, "_RUNG_TILE", 8)
        cfg, p, _, probs, x = _layer("a-share-held")
        idx = _few_held_picks()
        assert moe._row_buffer_rungs(ROWS, 4, 16) == RUNGS
    else:
        cfg, p, idx, probs, x = _layer(case)
    weigh = jnp.cos(jnp.arange(T * H, dtype=jnp.float32)).reshape(T, H)

    def program(fc1, fc2, x, probs):
        return moe._dropless_experts(
            dict(p, fc1_kernel=fc1, fc2_kernel=fc2), x, idx, probs, cfg)

    def reference(fc1, fc2, x, probs):
        return _scattered(fc1, fc2, x, idx, probs, cfg)

    def grads(layer):
        def loss(*operands):
            return jnp.sum(layer(*operands) * weigh)
        if policy == "selective":
            loss = jax.checkpoint(loss, policy=block._SAVE_MATMULS)
        return jax.grad(loss, argnums=(0, 1, 2, 3))

    got, want = jax.jit(lambda *operands: (
        grads(program)(*operands), grads(reference)(*operands)))(
        p["fc1_kernel"], p["fc2_kernel"], x, probs)
    for got, want in zip(got, want):
        assert got.dtype == want.dtype and got.shape == want.shape
        assert np.abs(np.asarray(want)).max() > 0
        _close(got, want)


@pytest.mark.parametrize("picks", ["random", "one-expert-takes-all",
                                   "none-lands-here"])
def test_the_groups_sizes_are_bincounts(picks):
    """_sorted_picks: the sizes by compare-and-sum equal jnp.bincount's, the
    order is the stable sort's and `inv` its inverse."""
    count = 9
    slot = {"random": np.random.default_rng(1).integers(0, count + 1, 512),
            "one-expert-takes-all": np.full(512, 3),
            "none-lands-here": np.full(512, count)}[picks]
    slot = jnp.asarray(slot, jnp.int32)
    order, inv, sizes = jax.jit(moe._sorted_picks, static_argnums=1)(
        slot, count)
    assert sizes.dtype == jnp.int32
    assert np.array_equal(sizes, jnp.bincount(slot, length=count + 1)[:count])
    assert np.array_equal(order, np.argsort(np.asarray(slot), kind="stable"))
    assert np.array_equal(np.asarray(inv)[np.asarray(order)], np.arange(512))


def _abstract_layer(rows, **kw):
    """(cfg, abstract params, abstract [1, rows, H] tokens) of a layer at a
    cell's widths: traced from shapes, nothing is drawn or compiled."""
    cfg = TransformerConfig(
        num_layers=1, num_attention_heads=4, vocab_size=64,
        max_position_embeddings=32, compute_dtype=jnp.bfloat16,
        params_dtype=jnp.bfloat16, activation=ActivationKind.swiglu, **kw)
    p = jax.eval_shape(
        lambda: moe.init_moe_params(jax.random.PRNGKey(0), cfg, 0.02)[0])
    return cfg, p, jax.ShapeDtypeStruct((1, rows, cfg.hidden_size),
                                        jnp.bfloat16)


# the granite cell's layer (36 of 72 experts held, 10 picks a token) and the
# assist cell's (every one of 64 held, 4 picks)
CELL_LAYERS = {
    "a-share-held": dict(hidden_size=4096, moe_ffn_hidden_size=768,
                         num_moe_experts=72, moe_router_topk=10,
                         moe_experts_held=(0, 36)),
    "every-expert-held": dict(hidden_size=2048, moe_ffn_hidden_size=1536,
                              num_moe_experts=64, moe_router_topk=4),
}


@pytest.mark.parametrize("rows", [64, 512], ids=["decode", "prefill"])
@pytest.mark.parametrize("held", list(CELL_LAYERS))
def test_a_serving_layer_scatters_nothing(held, rows):
    """launch_stats of moe_forward alone, traced at a decode round's and a
    prefill call's rows with the serving steps' counters on: no scatter on
    either dropless body."""
    cfg, p, x = _abstract_layer(rows, **CELL_LAYERS[held])
    count = jax.ShapeDtypeStruct((1, rows), jnp.bool_)
    stats = launch_stats(
        lambda p, x, c: moe.moe_forward(p, x, cfg, count_rows=c), p, x, count)
    assert stats["scatters"] == 0, stats
    # the counter counts: the parent's form holds `bincount`'s and the sum's
    cfg, p, idx, probs, x = _layer(held)
    assert launch_stats(
        lambda x: _scattered(p["fc1_kernel"], p["fc2_kernel"], x, idx, probs,
                             cfg), x)["scatters"] == 2


def test_a_training_layer_under_the_ladder_scatters_nothing(monkeypatch):
    """The gradient of moe_forward at tests/test_mellum.py's tiny layer (4 of
    16 experts held, 2 x 96 tokens x 4 picks on the ladder 240, 288, 384,
    768), under the layer loop's 'selective' policy: no scatter in the
    forward switch, the backward one or the router's transpose."""
    from perfbench import manifest
    model = manifest.load_module("models", "mellum")
    monkeypatch.setattr(moe, "_RUNG_MIN_SKIPPED", 64)
    monkeypatch.setattr(moe, "_RUNG_TILE", 8)
    with open(os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "perfbench", "configs",
            "mellum2-12b-a2.5b.json")) as f:
        config = dict(copy.deepcopy(json.load(f)), **model.REHEARSAL)
    cfg = model.model_config(config, "float32", remat_policy="selective",
                             compute_dtype=jnp.float32)
    assert moe._row_buffer_rungs(768, *cfg.moe_experts_here[1:],
                                 cfg.moe_router_width) == (240, 288, 384, 768)
    p = jax.eval_shape(
        lambda: moe.init_moe_params(jax.random.PRNGKey(0), cfg, 0.02)[0])
    x = jax.ShapeDtypeStruct((2, 96, cfg.hidden_size), jnp.float32)

    def loss(p, x):
        out, aux = moe.moe_forward(p, x, cfg)
        return jnp.sum(out) + aux
    grad = jax.grad(jax.checkpoint(loss, policy=block._SAVE_MATMULS), (0, 1))
    stats = launch_stats(grad, p, x)
    assert stats["scatters"] == 0, stats
