"""``"model": "laguna"``: poolside's Laguna mixture-of-experts models as their
``config.json`` publishes them (the configuration file keeps the source's own
keys), built as the program's ``models/gpt.py`` model, with its plain
reference and its counts. What a model module gives the runners is listed in
``models/gpt_dense.py``; this one adds ``reference_hidden`` /
``reference_head`` (the head over 100,352 columns a block of rows at a time:
a whole pass's logits would be 7.8 GB) and ``window_bytes_per_token``.

The model, layer ``l`` of kind ``layer_types[l]`` with ``h_l =
num_attention_heads_per_layer[l]`` query heads over ``num_key_value_heads``
key/value heads of ``head_dim``; RMSNorm (``rms_norm_eps``) before both
halves, no bias anywhere, a final norm and an untied head.

The reference (``reference_logits``) is that forward pass written out in
``jax.numpy``, float32, matmuls at precision "highest". With ``RMS(x; g) = x /
sqrt(mean(x^2) + eps) * g``:

1. ``u = RMS(x; n1)``; ``q = u W_q`` -> ``[T, h_l, D]``; ``[k | v] = u W_kv``
   -> ``[T, kv, D]`` each; ``g = sigmoid(u W_g)`` -> ``[T, h_l]``.
2. ``q``, ``k`` <- RMS over D of each head (one scale vector for q, one for
   k), then rotary on the pairs ``(i, i + r/2)`` of the first ``r =
   partial_rotary_factor x D`` columns: a ``full_attention`` layer with YaRN
   frequencies (``_yarn_inv_freq``: theta, factor, original length, beta
   fast/slow) and ``cos``, ``sin`` times ``attention_factor``; a
   ``sliding_attention`` layer with plain frequencies at its own theta.
3. Causal softmax attention at scale ``D ** -0.5``, inside a segment, query
   head ``j`` on key/value head ``j // (h_l / kv)``; in a sliding layer
   position ``t`` sees the keys ``t - sliding_window + 1 .. t``. Computed a
   block of ``Q_BLOCK`` queries and one head at a time, so that a
   19,456-token pass holds ``[Q_BLOCK, S]`` scores and never ``[S, S]``.
4. ``a_j <- g_j a_j`` for each head; ``x <- x + concat(a) W_o``.
5. ``m = RMS(x; n2)``. A ``dense`` layer: ``x <- x + W_2(silu(m W_g') * m
   W_u)``. A ``sparse`` layer: ``s = sigmoid(float32(m) W_r)``; the top-k of
   ``s + b``; weights ``s_i / (sum of the chosen s + 1e-6) x
   moe_routed_scaling_factor``; ``x <- x + sum_i w_i E_i(m) + E_shared(m)``:
   EVERY expert over all positions with weight 0 where it was not chosen,
   one expert's matrices upcast at a time; the weight on the expert's
   output (``moe_apply_router_weight_on_input`` false).
6. Final RMS norm, ``logits = x W_head``.

No cache, no kernels, no ``ragged_dot``, no window arithmetic shared with
the program: the band is ``(t - s < sliding_window)`` on a ``[Q_BLOCK, S]``
mask. It reads the program's own parameter tree (``block``: ``mixers_attn``,
``mixers_swa``, ``ffn_lead``, ``ffn``) a matrix at a time and shares no code
with ``megatronapp_tpu/``.

What the configuration does not say and the family's convention fills is in
the configuration file under ``assumed``, each a VALUE there that this file
reads (``gate``, ``router``, ``qk_norm``, ``shared_expert_gate``): a reader
with the modelling file flips a value, not code. Departures from the
published layout, none of mathematics: gate and up projections are one
``fc1`` matrix ``[gate | up]``, ``k_proj`` and ``v_proj`` one ``kv_kernel``
``[k | v]``.
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
FULL, SLIDING = "full_attention", "sliding_attention"
Q_BLOCK = 1024          # queries a step of the reference's attention

REHEARSAL = {"num_hidden_layers": 5, "hidden_size": 64, "head_dim": 16,
             "num_attention_heads": 6, "num_key_value_heads": 2,
             "num_attention_heads_per_layer": [6, 8, 8, 8, 6],
             "layer_types": [FULL, SLIDING, SLIDING, SLIDING, FULL],
             "mlp_layer_types": ["dense"] + ["sparse"] * 4,
             "intermediate_size": 96, "moe_intermediate_size": 32,
             "shared_expert_intermediate_size": 32,
             "num_experts": 8, "num_experts_per_tok": 2,
             "sliding_window": 24, "vocab_size": 512,
             "max_position_embeddings": 4096}


def _kinds(config: dict):
    """(full layers, sliding layers) among the layers run."""
    types = config["layer_types"]
    return types.count(FULL), types.count(SLIDING)


def _heads(config: dict):
    """(query heads of a full layer, of a sliding layer): the published list
    gives every layer of a kind the same count; one that does not is
    refused."""
    per = {}
    for kind, n in zip(config["layer_types"],
                       config["num_attention_heads_per_layer"]):
        if per.setdefault(kind, n) != n:
            raise SystemExit("perfbench: models/laguna.py builds stacks "
                             "whose layers of one kind have one head count")
    return (per.get(FULL, config["num_attention_heads"]),
            per.get(SLIDING, config["num_attention_heads"]))


def _pattern(config: dict):
    """(period, offset) such that layer i is a full-attention layer iff
    i % period == offset: how the program lays a two-kind stack out."""
    types = config["layer_types"]
    n = config["num_hidden_layers"]
    if (len(types) != n or set(types) - {FULL, SLIDING}
            or len(config["mlp_layer_types"]) != n
            or len(config["num_attention_heads_per_layer"]) != n):
        raise SystemExit("perfbench: layer_types, mlp_layer_types and "
                         "num_attention_heads_per_layer must name "
                         "num_hidden_layers layers")
    at = [i for i, t in enumerate(types) if t == FULL]
    period = at[1] - at[0] if len(at) > 1 else len(types)
    if not at or [i for i in range(n) if i % period == at[0] % period] != at:
        raise SystemExit("perfbench: models/laguna.py builds stacks whose "
                         f"full layers lie one a period; got them at {at}")
    return period, at[0] % period


def _lead(config: dict) -> int:
    """The leading dense layers; every layer behind them is sparse."""
    kinds = config["mlp_layer_types"]
    lead = kinds.index("sparse") if "sparse" in kinds else len(kinds)
    if set(kinds[lead:]) - {"sparse"} or not 0 < lead < len(kinds):
        raise SystemExit("perfbench: models/laguna.py builds leading dense "
                         "layers before the sparse ones")
    return lead


def kv_bytes_per_token(config: dict, dtype: str) -> int:
    """Keys and values of every key/value head of the FULL layers, in
    `dtype`: what a token takes in the planes that keep every row (a block
    of the engine's main pool). The sliding layers' rows live in planes of
    their own, ``window_bytes_per_token``."""
    return (2 * _kinds(config)[0] * config["num_key_value_heads"]
            * config["head_dim"] * jnp.dtype(DTYPES[dtype]).itemsize)


def window_bytes_per_token(config: dict, dtype: str) -> int:
    """The same of the SLIDING layers: what a row of the window planes
    takes, of which a slot holds the window's and a few more."""
    return (2 * _kinds(config)[1] * config["num_key_value_heads"]
            * config["head_dim"] * jnp.dtype(DTYPES[dtype]).itemsize)


def params_count(config: dict) -> int:
    """Every parameter of the layers run, the embedding and the untied
    head (the configuration file's ``reduced_why`` does this sum by hand)."""
    h, d = config["hidden_size"], config["head_dim"]
    kv = config["num_key_value_heads"]
    n_full, n_slide = _kinds(config)
    h_full, h_slide = _heads(config)
    lead = _lead(config)

    def attn(heads):
        return (2 * h * heads * d + 2 * h * kv * d + h * heads + 2 * d + h)

    moe = (config["num_experts"] * 3 * h * config["moe_intermediate_size"]
           + 3 * h * config["shared_expert_intermediate_size"]
           + h * config["num_experts"] + config["num_experts"] + h)
    dense = 3 * h * config["intermediate_size"] + h
    return (n_full * attn(h_full) + n_slide * attn(h_slide) + lead * dense
            + (config["num_hidden_layers"] - lead) * moe
            + 2 * config["vocab_size"] * h + h)


def params_per_token(config: dict) -> float:
    """Matrix parameters a token meets: its layers' attention, the leading
    dense SwiGLUs, and in a sparse layer the router, the shared expert and
    its ``num_experts_per_tok`` experts; the head once."""
    h, d = config["hidden_size"], config["head_dim"]
    kv = config["num_key_value_heads"]
    n_full, n_slide = _kinds(config)
    h_full, h_slide = _heads(config)
    lead = _lead(config)
    attn = sum(n * (2 * h * heads * d + 2 * h * kv * d + h * heads)
               for n, heads in ((n_full, h_full), (n_slide, h_slide)))
    moe = (h * config["num_experts"]
           + 3 * h * config["shared_expert_intermediate_size"]
           + config["num_experts_per_tok"] * 3 * h
           * config["moe_intermediate_size"])
    return (attn + lead * 3 * h * config["intermediate_size"]
            + (config["num_hidden_layers"] - lead) * moe
            + h * config["vocab_size"])


def flops_per_token(config: dict, seq_len: int) -> float:
    """Forward and backward matmul operations per token (3 x 2 x the
    parameters a token meets, plus causal attention over `seq_len` in the
    full layers and over the window in the sliding ones), the yardstick an
    ``mfu`` reader would use; no cell of this model trains."""
    n_full, n_slide = _kinds(config)
    h_full, h_slide = _heads(config)
    d = config["head_dim"]
    scores = (n_full * h_full * 2 * d * seq_len / 2
              + n_slide * h_slide * 2 * d
              * min(seq_len / 2, config["sliding_window"]))
    return 6.0 * (params_per_token(config) + scores)


def model_config(config: dict, params_dtype: str, **extra):
    """The program's TransformerConfig for a configuration file that keeps
    the source's keys. Everything not named stays at the program's default.
    A program that lacks a field this model needs (the commit before the
    one that added it) fails here, at once."""
    from megatronapp_tpu.config.transformer_config import (
        ActivationKind, NormKind, PositionEmbeddingKind, TransformerConfig,
    )
    assumed = config["assumed"]
    rope_full = config["rope_parameters"][FULL]
    rope_slide = config["rope_parameters"][SLIDING]
    if (config["attention_bias"] or config["tie_word_embeddings"]
            or config["moe_apply_router_weight_on_input"]
            or rope_full["rope_type"] != "yarn"
            or rope_slide["rope_type"] != "default"
            or assumed["gate"]["value"] != "per-head sigmoid"
            or assumed["router"]["value"]
            != "sigmoid, selection bias, normalised top-k"
            or assumed["qk_norm"]["value"] is not True
            or assumed["shared_expert_gate"]["value"] is not False):
        raise SystemExit(
            "perfbench: models/laguna.py builds the form the configuration "
            "file's `assumed` states (a per-head sigmoid gate, a sigmoid "
            "router with a selection bias and normalised top-k, RMS norms "
            "on q and k, an ungated shared expert), no bias, an untied "
            "head, YaRN on the full layers and plain RoPE on the sliding "
            "ones")
    period, offset = _pattern(config)
    h_full, h_slide = _heads(config)
    return TransformerConfig(
        num_layers=config["num_hidden_layers"],
        hidden_size=config["hidden_size"],
        num_attention_heads=h_full,
        num_query_groups=config["num_key_value_heads"],
        kv_channels=config["head_dim"],
        ffn_hidden_size=config["intermediate_size"],
        vocab_size=config["vocab_size"],
        max_position_embeddings=config["max_position_embeddings"],
        normalization=NormKind.rmsnorm,
        layernorm_epsilon=config["rms_norm_eps"],
        activation=ActivationKind.swiglu, add_bias_linear=False,
        untie_embeddings_and_output_weights=True,
        qk_layernorm=True, attention_output_gate=True,
        position_embedding=PositionEmbeddingKind.yarn,
        rotary_base=float(rope_full["rope_theta"]),
        rotary_percent=float(rope_full["partial_rotary_factor"]),
        rope_scaling_factor=float(rope_full["factor"]),
        yarn_original_max_position=int(
            rope_full["original_max_position_embeddings"]),
        yarn_beta_fast=float(rope_full["beta_fast"]),
        yarn_beta_slow=float(rope_full["beta_slow"]),
        yarn_attention_factor=float(rope_full["attention_factor"]),
        attn_layer_period=period, attn_layer_offset=offset,
        sliding_window=config["sliding_window"],
        sliding_window_heads=h_slide,
        sliding_rotary_base=float(rope_slide["rope_theta"]),
        sliding_rotary_percent=float(rope_slide["partial_rotary_factor"]),
        num_moe_experts=config["num_experts"],
        moe_router_topk=config["num_experts_per_tok"],
        moe_ffn_hidden_size=config["moe_intermediate_size"],
        moe_shared_expert_intermediate_size=config[
            "shared_expert_intermediate_size"],
        moe_first_k_dense=_lead(config),
        moe_router_score="sigmoid", moe_router_selection_bias=True,
        moe_router_norm_topk_prob=True,
        moe_routed_scaling_factor=float(config["moe_routed_scaling_factor"]),
        params_dtype=DTYPES[params_dtype], **extra)


# ---- the plain reference ---------------------------------------------------

def _rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * weight


def _row(tree, i):
    """Layer i of a stack, upcast: cut inside the jitted layer by a traced
    index, so one program a kind and shape."""
    return jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, False).astype(F32),
        tree)


def _yarn_inv_freq(rot: int, rope: dict):
    """YaRN's frequencies for `rot` rotated columns: below the ramp the
    plain ones (extrapolation), above it the plain ones over `factor`
    (interpolation), between them a linear blend by the column's index; the
    ramp's ends are the columns that turn beta_fast and beta_slow times
    over the original length."""
    base = float(rope["rope_theta"])
    plain = base ** -(jnp.arange(0, rot, 2, dtype=F32) / rot)

    def column(turns):
        return (rot * math.log(rope["original_max_position_embeddings"]
                               / (turns * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(column(rope["beta_fast"])), 0)
    high = min(math.ceil(column(rope["beta_slow"])), rot - 1)
    ramp = jnp.clip((jnp.arange(rot // 2, dtype=F32) - low)
                    / max(high - low, 1), 0.0, 1.0)
    return plain * (1 - ramp) + plain / float(rope["factor"]) * ramp


def rope_tables(config: dict, kind: str, position_ids):
    """(cos, sin) [B, S, r/2] of a layer kind's rotary table at
    position_ids [B, S], r = partial_rotary_factor x head_dim columns."""
    rope = config["rope_parameters"][kind]
    rot = int(config["head_dim"] * rope["partial_rotary_factor"])
    if rope["rope_type"] == "yarn":
        inv_freq, factor = _yarn_inv_freq(rot, rope), rope["attention_factor"]
    else:
        inv_freq = float(rope["rope_theta"]) ** -(
            jnp.arange(0, rot, 2, dtype=F32) / rot)
        factor = 1.0
    angles = position_ids.astype(F32)[..., None] * inv_freq
    return jnp.cos(angles) * factor, jnp.sin(angles) * factor


def _rope(x, cos, sin):
    """x [B,S,heads,D], cos/sin [B,S,r/2]: rotate the pairs (i, i + r/2) of
    the first r columns, pass the others."""
    half = cos.shape[-1]
    x1, x2, rest = x[..., :half], x[..., half:2 * half], x[..., 2 * half:]
    c, s = cos[:, :, None, :], sin[:, :, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s, rest], axis=-1)


def attention(u, at, cos, sin, segment_ids, groups: int, window: int, eps,
              gated: bool = True):
    """Steps 1-4 without the residual: u [B,S,H] -> [B,S,H]. window 0: a
    full layer. `gated` False leaves the gate out (a control)."""
    b, s, _ = u.shape
    d = at["q_ln_scale"].shape[-1]
    heads = at["q_kernel"].shape[-1] // d
    q = (u @ at["q_kernel"]).reshape(b, s, heads, d)
    k, v = jnp.split((u @ at["kv_kernel"]).reshape(b, s, 2 * groups, d), 2,
                     axis=2)
    q = _rope(_rms_norm(q, at["q_ln_scale"], eps), cos, sin)
    k = _rope(_rms_norm(k, at["k_ln_scale"], eps), cos, sin)
    block = min(Q_BLOCK, s)
    n_blocks = -(-s // block)
    pad = n_blocks * block - s
    qp = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    seg_q = jnp.pad(segment_ids, ((0, 0), (0, pad)), constant_values=-1)
    at_k = jnp.arange(s)

    def one_block(i):
        q0 = i * block
        qb = jax.lax.dynamic_slice_in_dim(qp, q0, block, axis=1)
        sq = jax.lax.dynamic_slice_in_dim(seg_q, q0, block, axis=1)
        at_q = q0 + jnp.arange(block)
        allowed = (at_q[:, None] >= at_k[None, :])[None]
        if window:
            allowed &= (at_q[:, None] - at_k[None, :] < window)[None]
        allowed &= sq[:, :, None] == segment_ids[:, None, :]

        def one_head(j):            # [block, S] scores a head, not heads x
            qh = qb[:, :, j]
            kh, vh = k[:, :, j // (heads // groups)], v[:, :, j // (
                heads // groups)]
            scores = jnp.einsum("bqd,bkd->bqk", qh, kh) * d ** -0.5
            probs = jax.nn.softmax(jnp.where(allowed, scores, -1e30), -1)
            return jnp.einsum("bqk,bkd->bqd", probs, vh)

        return jax.lax.map(one_head, jnp.arange(heads))   # [heads,B,block,d]

    ctx = jax.lax.map(one_block, jnp.arange(n_blocks))
    ctx = jnp.transpose(ctx, (2, 0, 3, 1, 4)).reshape(
        b, n_blocks * block, heads, d)[:, :s]
    if gated:
        ctx = ctx * jax.nn.sigmoid(u @ at["gate_kernel"])[..., None]
    return ctx.reshape(b, s, heads * d) @ at["out_kernel"]


def _swiglu(x, fc1, fc2):
    gate, up = jnp.split(x @ fc1, 2, axis=-1)
    return (jax.nn.silu(gate) * up) @ fc2


def router_weights(flat, router_kernel, router_bias, top_k: int,
                   scaling: float, biased: bool = True):
    """flat [T,H] -> [T,E] float32: an expert's weight for each token, 0
    where it was not chosen. `biased` False makes the selection ignore the
    bias (a control)."""
    s = jax.nn.sigmoid(flat @ router_kernel.astype(F32))
    _, top_i = jax.lax.top_k(s + router_bias if biased else s, top_k)
    top_s = jnp.take_along_axis(s, top_i, axis=-1)
    top_s = top_s / (jnp.sum(top_s, -1, keepdims=True) + 1e-6) * scaling
    return jnp.sum(jax.nn.one_hot(top_i, s.shape[-1], dtype=F32)
                   * top_s[..., None], axis=1)


def _experts(flat, weights, fc1_stack, fc2_stack, layer):
    """Σ_e weights[:, e] * SwiGLU_e(flat): every expert over ALL tokens, one
    expert's two matrices cut out of the stacks [L, E, ., .] and upcast at
    a time."""
    def one_expert(acc, e):
        fc1 = jax.lax.dynamic_slice(
            fc1_stack, (layer, e, 0, 0), (1, 1) + fc1_stack.shape[2:])[0, 0]
        fc2 = jax.lax.dynamic_slice(
            fc2_stack, (layer, e, 0, 0), (1, 1) + fc2_stack.shape[2:])[0, 0]
        w = jax.lax.dynamic_index_in_dim(weights, e, 1, keepdims=True)
        return acc + _swiglu(flat, fc1.astype(F32), fc2.astype(F32)) * w, None

    return jax.lax.scan(one_expert, jnp.zeros_like(flat),
                        jnp.arange(fc1_stack.shape[1], dtype=jnp.int32))[0]


@functools.partial(jax.jit, static_argnames=("groups", "window", "eps",
                                             "gated"))
def _attend(x, mixers, i, cos, sin, segment_ids, groups, window, eps, gated):
    mixer = _row(mixers, i)
    u = _rms_norm(x, mixer["ln1_scale"], eps)
    return x + attention(u, mixer["attention"], cos, sin, segment_ids,
                         groups, window, eps, gated)


@functools.partial(jax.jit, static_argnames=("eps",))
def _dense(x, ffns, i, eps):
    ffn = _row(ffns, i)
    return x + _swiglu(_rms_norm(x, ffn["ln2_scale"], eps),
                       ffn["mlp"]["fc1_kernel"], ffn["mlp"]["fc2_kernel"])


@functools.partial(jax.jit, static_argnames=("eps", "top_k", "scaling",
                                             "biased"))
def _moe(x, ffns, i, eps, top_k, scaling, biased):
    b, s, h = x.shape
    moe = ffns["moe"]
    small = _row({k: moe[k] for k in ("router_kernel", "router_bias",
                                      "shared_fc1", "shared_fc2")}, i)
    flat = _rms_norm(x, _row(ffns["ln2_scale"], i), eps).reshape(b * s, h)
    weights = router_weights(flat, small["router_kernel"],
                             small["router_bias"], top_k, scaling, biased)
    out = _experts(flat, weights, moe["fc1_kernel"], moe["fc2_kernel"], i)
    out = out + _swiglu(flat, small["shared_fc1"], small["shared_fc2"])
    return x + out.reshape(b, s, h)


@functools.partial(jax.jit, static_argnames=("eps", "size"))
def _head(x, scale, out_kernel, start, eps: float, size: int):
    x = jax.lax.dynamic_slice_in_dim(x, start, size, axis=1)
    return _rms_norm(x, scale.astype(F32), eps) @ out_kernel.astype(F32)


def reference_hidden(params, config: dict, tokens, segment_ids, position_ids,
                     control: str = ""):
    """The stack over tokens [B,S]: x [B,S,H] float32 before the final norm.
    `control` (``tools``' and the tests' wrong models, each of which has to
    come out as not correct): "window-1" / "window+1" move the window by a
    key, "one-table" rotates every layer by the full layers' table,
    "no-gate" leaves the gate out, "no-bias" selects experts without b."""
    block = params["block"]
    eps = config["rms_norm_eps"]
    groups = config["num_key_value_heads"]
    window = config["sliding_window"] + {"window-1": -1, "window+1": 1}.get(
        control, 0)
    tables = {kind: rope_tables(config, kind, position_ids)
              for kind in (FULL, SLIDING)}
    if control == "one-table":
        tables[SLIDING] = tables[FULL]
    lead = _lead(config)
    moe_static = dict(eps=eps, top_k=config["num_experts_per_tok"],
                      scaling=float(config["moe_routed_scaling_factor"]),
                      biased=control != "no-bias")
    with jax.default_matmul_precision("highest"):
        x = jnp.take(params["embedding"]["word"], tokens, axis=0).astype(F32)
        seen = {FULL: 0, SLIDING: 0}
        for i, kind in enumerate(config["layer_types"]):
            stack = block["mixers_attn" if kind == FULL else "mixers_swa"]
            x = _attend(x, stack, jnp.int32(seen[kind]), *tables[kind],
                        segment_ids, groups=groups,
                        window=window if kind == SLIDING else 0, eps=eps,
                        gated=control != "no-gate")
            seen[kind] += 1
            if i < lead:
                x = _dense(x, block["ffn_lead"], jnp.int32(i), eps=eps)
            else:
                x = _moe(x, block["ffn"], jnp.int32(i - lead), **moe_static)
        return x


def reference_head(params, config: dict, x, start: int = 0, size=None):
    """logits float32 [B, size, V] of rows start..start+size of x (the
    head over 100,352 columns is the largest array of a pass: a caller at
    the published width takes it a block of rows at a time)."""
    with jax.default_matmul_precision("highest"):
        return _head(x, params["final_ln_scale"], params["output"],
                     jnp.int32(start), eps=config["rms_norm_eps"],
                     size=x.shape[1] - start if size is None else size)


def reference_logits(params, config: dict, tokens, segment_ids, position_ids,
                     control: str = ""):
    """tokens/segment_ids/position_ids [B,S] -> logits float32 [B,S,V].
    `config` is a configuration file's top level."""
    return reference_head(params, config, reference_hidden(
        params, config, tokens, segment_ids, position_ids, control))


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
