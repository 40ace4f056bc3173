"""``"model": "deepseek_v2"``: DeepSeek-V2 / V2-Lite as their ``config.json``
publishes them (the configuration file keeps the source's own keys), built
as the program's ``models/gpt.py`` model, with its plain reference and its
counts. What a model module gives the runners is listed in
``models/gpt_dense.py``.

The reference (``reference_logits``) is the published forward pass written
out in ``jax.numpy``, float32, matmuls at precision "highest": RMSNorm;
multi-head latent attention UNABSORBED (the normed latent expanded by
``kv_b`` to per-head keys and values, the one roped key broadcast to every
head, a full causal softmax with the scale ``(nope + rope)^-0.5 * (0.1 *
mscale_all_dim * ln(factor) + 1)^2``, rope frequencies by YaRN's NTK-by-parts
and the cos/sin factor ``mscale / mscale_all_dim``); the router as a softmax
over all experts, top-k, renormalised only if ``norm_topk_prob``, times
``routed_scaling_factor``; every token through its k experts (a loop over the
experts with the router's weight, 0 where not chosen) plus the shared SwiGLU;
the first ``first_k_dense_replace`` layers dense. No cache, no kernels, no
absorbed form, no ``ragged_dot``. It reads the program's own parameter tree
(``lead_block``, ``block``), one layer at a time, each expert upcast as it is
used.

Departures from the published model, all of layout, none of mathematics:
- rope rotates the pairs (i, i + d/2) of the roped 64 columns, the layout of
  this repository's ``ops/rotary.py``; the published code first permutes its
  interleaved columns (2i, 2i+1) into that order, so the two differ by a fixed
  permutation of ``q_proj``'s and ``kv_a_proj``'s roped columns, which random
  weights do not see;
- the 2 shared experts are one SwiGLU of twice the width, as the published
  code builds them (``moe_intermediate_size * n_shared_experts``);
- gate and up projections are one ``fc1`` matrix ``[gate | up]`` (the tree's
  layout), ``kv_a_proj_with_mqa`` is ``kv_down`` ``[latent | rope key]``;
- ``n_group`` / ``topk_group`` 1 and ``topk_method`` greedy are the only
  routing the reference (and the program) computes.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from perfbench import manifest

# Weights come from the seed the same way for every models/gpt.py model.
init_params = manifest.load_module("models", "gpt_dense").init_params

F32 = jnp.float32
DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}

REHEARSAL = {"num_layers": 3, "num_hidden_layers": 3, "hidden_size": 64,
             "num_attention_heads": 4, "num_key_value_heads": 4,
             "intermediate_size": 160, "moe_intermediate_size": 48,
             "n_routed_experts": 8, "num_experts_per_tok": 3,
             "n_shared_experts": 1, "kv_lora_rank": 32,
             "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
             "v_head_dim": 16, "vocab_size": 512,
             "max_position_embeddings": 512,
             "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                              "mscale": 0.707, "mscale_all_dim": 0.707,
                              "original_max_position_embeddings": 64,
                              "type": "yarn"}}


def _depth(config: dict):
    """(layers run, leading dense layers among them): a depth cut keeps the
    leading dense layers and at least one expert layer."""
    n = config["num_layers"]
    return n, min(config["first_k_dense_replace"], n - 1)


def kv_bytes_per_token(config: dict, dtype: str) -> int:
    """The compressed latent and the one roped key of every layer, in
    `dtype`: what MLA caches. An expanded per-head cache takes 14 times as
    much and a lower-precision one less; neither is this deployment."""
    return (config["num_layers"]
            * (config["kv_lora_rank"] + config["qk_rope_head_dim"])
            * jnp.dtype(DTYPES[dtype]).itemsize)


def flops_per_token(config: dict, seq_len: int) -> float:
    """Forward and backward matmul operations per token (3 x 2 x the
    parameters a token meets, plus causal attention over `seq_len`), the
    yardstick an ``mfu`` reader would use; no cell of this model trains."""
    h, nh = config["hidden_size"], config["num_attention_heads"]
    nope, rope = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    dv, klat = config["v_head_dim"], config["kv_lora_rank"]
    n, lead = _depth(config)
    attn = (h * nh * (nope + rope) + h * (klat + rope)
            + klat * nh * (nope + dv) + nh * dv * h)
    dense = 3 * h * config["intermediate_size"]
    moe = (3 * h * config["moe_intermediate_size"]
           * (config["num_experts_per_tok"] + config["n_shared_experts"])
           + h * config["n_routed_experts"])
    params = (n * attn + lead * dense + (n - lead) * moe
              + h * config["vocab_size"])
    scores = n * nh * (nope + rope + dv) * seq_len / 2
    return 6.0 * (params + scores)


def model_config(config: dict, params_dtype: str, **extra):
    """The program's TransformerConfig for a configuration file that keeps
    the source's keys. Everything not named stays at the program's default.
    A program that lacks a field this model needs (the commit before the
    one that added it) fails here, at once."""
    from megatronapp_tpu.config.transformer_config import (
        ActivationKind, NormKind, PositionEmbeddingKind, TransformerConfig,
    )
    rs = config["rope_scaling"]
    if (config["q_lora_rank"] is not None or config["scoring_func"] != "softmax"
            or config["topk_method"] != "greedy" or config["n_group"] != 1
            or config["hidden_act"] != "silu" or rs["type"] != "yarn"
            or rs["mscale"] != rs["mscale_all_dim"]
            or config["tie_word_embeddings"] or config["attention_bias"]
            or config["moe_layer_freq"] != 1):
        raise SystemExit("perfbench: models/deepseek_v2.py builds the "
                         "DeepSeek-V2-Lite form only (no query latent, "
                         "softmax greedy routing, YaRN with mscale == "
                         "mscale_all_dim, untied head, no biases)")
    n, lead = _depth(config)
    return TransformerConfig(
        num_layers=n,
        hidden_size=config["hidden_size"],
        num_attention_heads=config["num_attention_heads"],
        ffn_hidden_size=config["intermediate_size"],
        vocab_size=config["vocab_size"],
        max_position_embeddings=config["max_position_embeddings"],
        normalization=NormKind.rmsnorm,
        layernorm_epsilon=config["rms_norm_eps"],
        activation=ActivationKind.swiglu, add_bias_linear=False,
        untie_embeddings_and_output_weights=True,
        position_embedding=PositionEmbeddingKind.yarn,
        rotary_base=float(config["rope_theta"]),
        rope_scaling_factor=float(rs["factor"]),
        yarn_original_max_position=rs["original_max_position_embeddings"],
        yarn_beta_fast=float(rs["beta_fast"]),
        yarn_beta_slow=float(rs["beta_slow"]),
        yarn_mscale_coeff=0.1 * rs["mscale_all_dim"],
        multi_latent_attention=True, q_lora_rank=None,
        kv_lora_rank=config["kv_lora_rank"],
        qk_head_dim=config["qk_nope_head_dim"],
        qk_pos_emb_head_dim=config["qk_rope_head_dim"],
        v_head_dim=config["v_head_dim"],
        num_moe_experts=config["n_routed_experts"],
        moe_router_topk=config["num_experts_per_tok"],
        moe_ffn_hidden_size=config["moe_intermediate_size"],
        moe_shared_expert_intermediate_size=(
            config["moe_intermediate_size"] * config["n_shared_experts"]),
        moe_router_norm_topk_prob=config["norm_topk_prob"],
        moe_routed_scaling_factor=float(config["routed_scaling_factor"]),
        moe_first_k_dense=lead,
        params_dtype=DTYPES[params_dtype], **extra)


# ---- the plain reference ---------------------------------------------------

def _rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * weight


def _yarn_inv_freq(dim: int, base: float, rs: dict):
    """YaRN's NTK-by-parts frequencies [dim/2] (the published
    ``DeepseekV2YarnRotaryEmbedding``)."""
    exponent = jnp.arange(0, dim, 2, dtype=F32) / dim
    extrapolated = 1.0 / base ** exponent
    interpolated = extrapolated / rs["factor"]

    def correction_dim(rotations):
        return (dim * math.log(rs["original_max_position_embeddings"]
                               / (rotations * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(correction_dim(rs["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rs["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=F32) - low) / (high - low),
                    0.0, 1.0)
    return interpolated * ramp + extrapolated * (1.0 - ramp)


def _mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def _rope(x, cos, sin):
    """x [B,S,heads,d], cos/sin [B,S,d/2]: rotate the pairs (i, i + d/2)."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    c, s = cos[:, :, None, :], sin[:, :, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def _attention(x, at, cos, sin, segment_ids, dims, eps, scale):
    heads, nope, rope, dv, klat = dims
    b, s, _ = x.shape
    q = (x @ at["q_proj"]).reshape(b, s, heads, nope + rope)
    q_nope, q_pe = q[..., :nope], q[..., nope:]
    ckv = x @ at["kv_down"]
    latent, k_pe = ckv[..., :klat], ckv[..., klat:]
    kv = (_rms_norm(latent, at["kv_ln_scale"], eps)
          @ at["kv_up"]).reshape(b, s, heads, nope + dv)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    q_pe = _rope(q_pe, cos, sin)
    k_pe = jnp.broadcast_to(_rope(k_pe[:, :, None, :], cos, sin),
                            (b, s, heads, rope))
    q = jnp.concatenate([q_nope, q_pe], axis=-1)
    k = jnp.concatenate([k_nope, k_pe], axis=-1)
    pos = jnp.arange(s)
    allowed = (pos[:, None] >= pos[None, :])[None]
    allowed = allowed & (segment_ids[:, :, None] == segment_ids[:, None, :])

    def one_head(qkv):          # one head at a time: [S, S] scores, not 16
        qh, kh, vh = qkv
        scores = jnp.einsum("bqd,bkd->bqk", qh, kh) * scale
        probs = jax.nn.softmax(jnp.where(allowed, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bqk,bkd->bqd", probs, vh)

    ctx = jax.lax.map(one_head, tuple(jnp.moveaxis(a, 2, 0)
                                      for a in (q, k, v)))
    ctx = jnp.moveaxis(ctx, 0, 2).reshape(b, s, heads * dv)
    return ctx @ at["out_kernel"]


def _swiglu(x, fc1, fc2):
    gate, up = jnp.split(x @ fc1, 2, axis=-1)
    return (jax.nn.silu(gate) * up) @ fc2


def _moe(x, mp, top_k: int, renormalise: bool, scaling: float):
    """Every token through its top_k experts, weighted by the router's
    softmax probability, plus the shared experts. One expert at a time over
    ALL tokens, with weight 0 for the tokens that did not choose it."""
    b, s, h = x.shape
    flat = x.reshape(b * s, h)
    probs = jax.nn.softmax(flat @ mp["router_kernel"].astype(F32), axis=-1)
    top_p, top_i = jax.lax.top_k(probs, top_k)
    if renormalise:
        top_p = top_p / jnp.sum(top_p, -1, keepdims=True)
    top_p = top_p * scaling
    experts = probs.shape[-1]
    weights = jnp.sum(jax.nn.one_hot(top_i, experts, dtype=F32)
                      * top_p[..., None], axis=1)            # [T, E]

    def one_expert(acc, xs):
        fc1, fc2, w = xs
        return acc + _swiglu(flat, fc1.astype(F32),
                             fc2.astype(F32)) * w[:, None], None

    out, _ = jax.lax.scan(one_expert, jnp.zeros_like(flat),
                          (mp["fc1_kernel"], mp["fc2_kernel"], weights.T))
    out = out + _swiglu(flat, mp["shared_fc1"].astype(F32),
                        mp["shared_fc2"].astype(F32))
    return out.reshape(b, s, h)


@functools.partial(jax.jit, static_argnames=(
    "dims", "eps", "scale", "top_k", "renormalise", "scaling"))
def _layer(x, lp, cos, sin, segment_ids, dims, eps, scale, top_k,
           renormalise, scaling):
    """x [B,S,H] float32 -> [B,S,H]; lp is one layer's parameters (the
    experts stay in their own type until each is used)."""
    small = {k: v for k, v in lp.items() if k != "moe"}
    small = jax.tree.map(lambda a: a.astype(F32), small)
    x = x + _attention(_rms_norm(x, small["ln1_scale"], eps),
                       small["attention"], cos, sin, segment_ids, dims, eps,
                       scale)
    h = _rms_norm(x, small["ln2_scale"], eps)
    if "moe" in lp:
        return x + _moe(h, lp["moe"], top_k, renormalise, scaling)
    return x + _swiglu(h, small["mlp"]["fc1_kernel"],
                       small["mlp"]["fc2_kernel"])


@functools.partial(jax.jit, static_argnames=("eps", "size"))
def _head(x, scale, output, start, eps: float, size: int):
    x = jax.lax.dynamic_slice_in_dim(x, start, size, axis=1)
    return _rms_norm(x, scale.astype(F32), eps) @ output.astype(F32)


def reference_logits(params, config: dict, tokens, segment_ids, position_ids,
                     rows=None):
    """tokens/segment_ids/position_ids [B,S] -> logits float32 over the
    vocabulary, [B,S,V], or [B,size,V] for the positions start..start+size
    when `rows` = (start, size) (the head over 102400 columns is the largest
    array of a pass). `config` is a configuration file's top level."""
    rs = config["rope_scaling"]
    rope = config["qk_rope_head_dim"]
    dims = (config["num_attention_heads"], config["qk_nope_head_dim"], rope,
            config["v_head_dim"], config["kv_lora_rank"])
    eps = config["rms_norm_eps"]
    scale = ((config["qk_nope_head_dim"] + rope) ** -0.5
             * _mscale(rs["factor"], rs["mscale_all_dim"]) ** 2)
    table_scale = (_mscale(rs["factor"], rs["mscale"])
                   / _mscale(rs["factor"], rs["mscale_all_dim"]))
    static = dict(dims=dims, eps=eps, scale=scale,
                  top_k=config["num_experts_per_tok"],
                  renormalise=bool(config["norm_topk_prob"]),
                  scaling=float(config["routed_scaling_factor"]))
    with jax.default_matmul_precision("highest"):
        angles = (position_ids.astype(F32)[..., None]
                  * _yarn_inv_freq(rope, float(config["rope_theta"]), rs))
        cos, sin = jnp.cos(angles) * table_scale, jnp.sin(angles) * table_scale
        x = jnp.take(params["embedding"]["word"], tokens, axis=0).astype(F32)
        for stack in ("lead_block", "block"):
            block = params.get(stack)
            if block is None:
                continue
            for i in range(jax.tree.leaves(block)[0].shape[0]):
                lp = jax.tree.map(lambda a: a[i], block)
                x = _layer(x, lp, cos, sin, segment_ids, **static)
        start, size = rows if rows is not None else (0, x.shape[1])
        return _head(x, params["final_ln_scale"], params["output"],
                     jnp.int32(start), eps=eps, size=size)


def reference_loss(params, config: dict, batch) -> float:
    """Mean cross entropy over the positions whose loss_mask is 1, for one
    micro-batch of ``generators/train_packed.py`` rows (what a training
    cell of this model would be held to; none exists yet)."""
    lg = reference_logits(params, config, jnp.asarray(batch["tokens"]),
                          jnp.asarray(batch["segment_ids"]),
                          jnp.asarray(batch["position_ids"]))
    logz = jax.nn.logsumexp(lg, axis=-1)
    picked = jnp.take_along_axis(
        lg, jnp.asarray(batch["labels"])[..., None], axis=-1)[..., 0]
    mask = jnp.asarray(batch["loss_mask"], F32)
    return float(jnp.sum((logz - picked) * mask) / jnp.maximum(mask.sum(), 1))
