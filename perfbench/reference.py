"""A plain float32 GPT (GPT-2 / GPT-3 dense): the reference of `correct`.

Straightforward ``jax.numpy``: learned absolute positions, pre-layer-norm
blocks, causal attention restricted to the tokens of one segment, tanh-GELU
MLP, tied output head. No kernels, no cache, no batching tricks. Matrix
multiplications run at ``jax.default_matmul_precision("highest")`` (on a TPU
a float32 matmul is otherwise done in bf16 passes). The weights are the
program's own parameter tree (``models/gpt.py::init_gpt_params``), upcast one
layer at a time so that the reference fits beside the model.

Departures from the published models: none in the mathematics; GPT-3's
alternating banded-sparse layers are dense here, as they are in the program.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _layer_norm(x, scale, bias, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * scale + bias


@functools.partial(jax.jit, static_argnames=("heads", "eps"))
def _layer(x, lp, segment_ids, heads: int, eps: float):
    """x [B,S,H] float32 -> [B,S,H]; lp is one layer's parameters."""
    lp = jax.tree.map(lambda a: a.astype(F32), lp)
    at = lp["attention"]
    b, s, hdim = x.shape
    d = at["q_kernel"].shape[1] // heads
    h = _layer_norm(x, lp["ln1_scale"], lp["ln1_bias"], eps)
    q = (h @ at["q_kernel"] + at["q_bias"]).reshape(b, s, heads, d)
    kv = (h @ at["kv_kernel"] + at["kv_bias"]).reshape(b, s, 2 * heads, d)
    k, v = kv[:, :, :heads], kv[:, :, heads:]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(F32(d))
    pos = jnp.arange(s)
    allowed = (pos[:, None] >= pos[None, :])[None, None]
    allowed = allowed & (segment_ids[:, None, :, None]
                         == segment_ids[:, None, None, :])
    probs = jax.nn.softmax(jnp.where(allowed, scores, -jnp.inf), axis=-1)
    ctx = jnp.einsum("bhqk,bkhd->bqhd", probs, v).reshape(b, s, heads * d)
    x = x + ctx @ at["out_kernel"] + at["out_bias"]
    h = _layer_norm(x, lp["ln2_scale"], lp["ln2_bias"], eps)
    h = jax.nn.gelu(h @ lp["mlp"]["fc1_kernel"] + lp["mlp"]["fc1_bias"],
                    approximate=True)
    return x + h @ lp["mlp"]["fc2_kernel"] + lp["mlp"]["fc2_bias"]


@jax.jit
def _embed(word, pos_table, tokens, position_ids):
    return (jnp.take(word, tokens, axis=0).astype(F32)
            + jnp.take(pos_table, position_ids, axis=0).astype(F32))


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, scale, bias, word, eps: float):
    h = _layer_norm(x, scale.astype(F32), bias.astype(F32), eps)
    return h @ word.astype(F32).T


def logits(params, config: dict, tokens, segment_ids, position_ids):
    """tokens/segment_ids/position_ids [B,S] -> logits [B,S,V] float32 over
    the whole (padded) vocabulary table. `config` is a configuration file's
    top level (it gives the number of heads and the norm's epsilon)."""
    heads, eps = config["num_attention_heads"], config["layer_norm_epsilon"]
    with jax.default_matmul_precision("highest"):
        x = _embed(params["embedding"]["word"], params["embedding"]["pos"],
                   tokens, position_ids)
        block = params["block"]
        n_layers = jax.tree.leaves(block)[0].shape[0]
        for i in range(n_layers):
            lp = jax.tree.map(lambda a: a[i], block)
            x = _layer(x, lp, segment_ids, heads=heads, eps=eps)
        return _head(x, params["final_ln_scale"], params["final_ln_bias"],
                     params["embedding"]["word"], eps=eps)


def masked_loss(params, config: dict, batch) -> float:
    """Mean cross entropy over the positions whose loss_mask is 1, for one
    micro-batch of ``generators/train_packed.py`` rows."""
    lg = logits(params, config, jnp.asarray(batch["tokens"]),
                jnp.asarray(batch["segment_ids"]),
                jnp.asarray(batch["position_ids"]))
    logz = jax.nn.logsumexp(lg, axis=-1)
    picked = jnp.take_along_axis(
        lg, jnp.asarray(batch["labels"])[..., None], axis=-1)[..., 0]
    mask = jnp.asarray(batch["loss_mask"], F32)
    return float(jnp.sum((logz - picked) * mask) / jnp.maximum(mask.sum(), 1))
