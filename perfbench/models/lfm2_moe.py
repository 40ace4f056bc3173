"""``"model": "lfm2_moe"``: LiquidAI's LFM2 mixture-of-experts hybrids as their
``config.json`` publishes them (the configuration file keeps the source's own
keys), built as the program's ``models/gpt.py`` model, with its plain
reference and its counts. What a model module gives the runners is listed in
``models/gpt_dense.py``; this one adds ``state_bytes_per_slot`` and
``reference_state``, by which ``cells/serve_closed_conv.py`` holds the
engine's second tenant (a convolution's tail a slot) to its size and content.

The model: ``layer_types[i]`` says whether layer i's operator is a gated
short convolution (``"conv"``) or grouped-query attention
(``"full_attention"``); the first ``num_dense_layers`` layers end in a dense
SwiGLU of ``intermediate_size``, the others in ``num_experts`` experts of
``moe_intermediate_size`` of which a token takes ``num_experts_per_tok``;
RMSNorm before both halves, the final norm before a head tied to the
embedding, no positional term outside the attention's RoPE.

The reference (``reference_logits``) is that forward pass written out in
``jax.numpy``, float32, matmuls at precision "highest". With ``RMS(x; g) = x /
sqrt(mean(x^2) + norm_eps) * g``:

- a layer: ``x1 = x + Op(RMS(x; n1))``, ``out = x1 + F(RMS(x1; n2))``;
- the gated short convolution: ``[B | C | z] = u W_in``; ``v = B * z``;
  ``c_t = w_0 v_{t-2} + w_1 v_{t-1} + w_2 v_t`` as ``conv_L_cache`` SHIFTED
  PRODUCTS, a term counting as 0 where its position lies before the row's
  start or in another segment; ``Op(u) = (C * c) W_out``; no bias;
- attention: per-head RMS norms on q and k over the head's columns (one
  scale for all heads) before RoPE (theta from ``rope_parameters``, all
  columns), ``softmax(q k / sqrt(d))`` causal and within a segment, each
  query head on key/value head ``head // (heads / kv heads)``, one query
  head at a time;
- the router: ``s = sigmoid(float32(h) W_r)``; the top-k of ``s + b``
  (``use_expert_bias``); weights ``s`` unbiased, divided by their sum + 1e-6
  (``norm_topk_prob``), times ``routed_scaling_factor``; EVERY expert over
  all positions with weight 0 where it was not chosen, one expert's matrices
  upcast at a time. ``route`` = ("softmax", ...) or (..., False) makes the
  controls: softmax scores, or a selection that ignores ``b``.

No cache, no kernels, no ``ragged_dot``. It reads the program's own parameter
tree (``block``: ``mixers_conv``, ``mixers_attn``, ``ffn_lead``, ``ffn``) a
matrix at a time and shares no code with ``megatronapp_tpu/transformer/``.

Departures from the published model, all of layout, none of mathematics:
- RoPE rotates the pairs (i, i + d/2), this repository's ``ops/rotary.py``
  layout and the published code's ``rotate_half`` alike;
- gate and up projections (``w1``, ``w3``) are one ``fc1`` matrix ``[gate |
  up]``, ``k_proj`` and ``v_proj`` one ``kv_kernel`` ``[k | v]``, the taps
  ``conv_kernel [taps, H]`` where the published depthwise weight is ``[H, 1,
  taps]`` (row j multiplies the input ``taps - 1 - j`` positions back in
  both);
- a slot caches the TWO columns ``(v_{t-2}, v_{t-1})`` a layer: the published
  cache keeps ``conv_L_cache`` = 3 and never reads the oldest.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from perfbench import manifest

# Weights come from the seed the same way for every models/gpt.py model.
init_params = manifest.load_module("models", "gpt_dense").init_params

F32 = jnp.float32
DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}
CONV, ATTN = "conv", "full_attention"

REHEARSAL = {"num_hidden_layers": 5, "hidden_size": 64,
             "num_attention_heads": 4, "num_key_value_heads": 2,
             "intermediate_size": 96, "moe_intermediate_size": 32,
             "num_experts": 8, "num_experts_per_tok": 2,
             "num_dense_layers": 1, "vocab_size": 512,
             "layer_types": [CONV, ATTN, CONV, CONV, CONV],
             "max_position_embeddings": 512}


def _head_dim(config: dict) -> int:
    return config["hidden_size"] // config["num_attention_heads"]


def _kinds(config: dict):
    """(convolution layers, attention layers) among the layers run."""
    types = config["layer_types"]
    return types.count(CONV), types.count(ATTN)


def _pattern(config: dict):
    """(period, offset) such that layer i attends iff i % period == offset:
    how the program lays a hybrid stack out. The published list is such a
    pattern; one that is not is refused."""
    types = config["layer_types"]
    if len(types) != config["num_hidden_layers"] or set(types) - {CONV, ATTN}:
        raise SystemExit("perfbench: layer_types must name num_hidden_layers "
                         f"layers, each {CONV!r} or {ATTN!r}")
    at = [i for i, t in enumerate(types) if t == ATTN]
    period = at[1] - at[0] if len(at) > 1 else len(types)
    if not at or [i for i in range(len(types))
                  if i % period == at[0] % period] != at:
        raise SystemExit("perfbench: models/lfm2_moe.py builds stacks whose "
                         "attention layers lie one a period; got attention "
                         f"at {at}")
    return period, at[0] % period


def kv_bytes_per_token(config: dict, dtype: str) -> int:
    """Keys and values of every key/value head of the ATTENTION layers, in
    `dtype`: a convolution layer caches no token."""
    return (2 * _kinds(config)[1] * config["num_key_value_heads"]
            * _head_dim(config) * jnp.dtype(DTYPES[dtype]).itemsize)


def state_bytes_per_slot(config: dict, dtype: str) -> int:
    """What one sequence's convolution tails take, whatever its length:
    for every convolution layer the last ``conv_L_cache - 1`` gated inputs
    ``[., hidden]`` in `dtype` (the type the model computes in)."""
    return (_kinds(config)[0] * (config["conv_L_cache"] - 1)
            * config["hidden_size"] * jnp.dtype(DTYPES[dtype]).itemsize)


def params_per_token(config: dict) -> float:
    """Matrix parameters a token meets: its layers' operators, the leading
    dense SwiGLUs, and in an MoE layer the router and its
    ``num_experts_per_tok`` experts; the tied head once."""
    h, d = config["hidden_size"], _head_dim(config)
    n_conv, n_attn = _kinds(config)
    lead = config["num_dense_layers"]
    conv = 3 * h * h + h * h
    attn = (2 * h * config["num_attention_heads"] * d
            + 2 * h * config["num_key_value_heads"] * d)
    moe = (h * config["num_experts"] + config["num_experts_per_tok"]
           * 3 * h * config["moe_intermediate_size"])
    return (n_conv * conv + n_attn * attn
            + lead * 3 * h * config["intermediate_size"]
            + (n_conv + n_attn - lead) * moe + h * config["vocab_size"])


def flops_per_token(config: dict, seq_len: int) -> float:
    """Forward and backward matmul operations per token (3 x 2 x the
    parameters a token meets, plus causal attention over `seq_len` in the
    attention layers), the yardstick an ``mfu`` reader would use; no cell of
    this model trains. The taps (2 x 3 a channel) are elementwise and not
    counted."""
    scores = (_kinds(config)[1] * config["num_attention_heads"]
              * 2 * _head_dim(config) * seq_len / 2)
    return 6.0 * (params_per_token(config) + scores)


def model_config(config: dict, params_dtype: str, **extra):
    """The program's TransformerConfig for a configuration file that keeps
    the source's keys. Everything not named stays at the program's default.
    A program that lacks a field this model needs (the commit before the
    one that added it) fails here, at once."""
    from megatronapp_tpu.config.transformer_config import (
        ActivationKind, NormKind, PositionEmbeddingKind, TransformerConfig,
    )
    rope = config["rope_parameters"]
    if (config["conv_bias"] or not config["use_expert_bias"]
            or rope["rope_type"] != "default"
            or not 0 < config["num_dense_layers"]
            < config["num_hidden_layers"]):
        raise SystemExit("perfbench: models/lfm2_moe.py builds the published "
                         "form only (no bias on the convolution, a router "
                         "with a selection bias, default RoPE, leading dense "
                         "layers before the MoE ones)")
    period, offset = _pattern(config)
    return TransformerConfig(
        num_layers=config["num_hidden_layers"],
        hidden_size=config["hidden_size"],
        num_attention_heads=config["num_attention_heads"],
        num_query_groups=config["num_key_value_heads"],
        ffn_hidden_size=config["intermediate_size"],
        vocab_size=config["vocab_size"],
        max_position_embeddings=config["max_position_embeddings"],
        normalization=NormKind.rmsnorm,
        layernorm_epsilon=config["norm_eps"],
        activation=ActivationKind.swiglu, add_bias_linear=False,
        position_embedding=PositionEmbeddingKind.rope,
        rotary_base=float(rope["rope_theta"]), qk_layernorm=True,
        attn_layer_period=period, attn_layer_offset=offset,
        shortconv_kernel=config["conv_L_cache"],
        num_moe_experts=config["num_experts"],
        moe_router_topk=config["num_experts_per_tok"],
        moe_ffn_hidden_size=config["moe_intermediate_size"],
        moe_first_k_dense=config["num_dense_layers"],
        moe_router_score="sigmoid", moe_router_selection_bias=True,
        moe_router_norm_topk_prob=bool(config["norm_topk_prob"]),
        moe_routed_scaling_factor=float(config["routed_scaling_factor"]),
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


def _rope(x, cos, sin):
    """x [B,S,heads,d], cos/sin [B,S,d/2]: rotate the pairs (i, i + d/2)."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    c, s = cos[:, :, None, :], sin[:, :, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def _shifted(a, back: int, segment_ids):
    """a [B,S,H] as seen `back` positions later: a[t - back] at t, 0 where
    t - back lies before the row or in another segment."""
    if not back:
        return a
    s = a.shape[1]
    moved = jnp.pad(a, ((0, 0), (back, 0), (0, 0)))[:, :s]
    seg = jnp.pad(segment_ids, ((0, 0), (back, 0)),
                  constant_values=-1)[:, :s]
    return jnp.where((seg == segment_ids)[..., None], moved, 0.0)


def short_conv(u, cp, segment_ids):
    """u [B,S,H] -> (Op(u) [B,S,H], v [B,S,H]: the gated inputs the taps
    read, whose last columns are what a slot caches)."""
    b_, c_, z = jnp.split(u @ cp["in_kernel"], 3, axis=-1)
    v = b_ * z
    taps = cp["conv_kernel"]
    k = taps.shape[0]
    conv = sum(_shifted(v, k - 1 - j, segment_ids) * taps[j]
               for j in range(k))
    return (c_ * conv) @ cp["out_kernel"], v


def attention(u, at, cos, sin, segment_ids, heads: int, groups: int, eps):
    b, s, hidden = u.shape
    d = hidden // heads
    q = (u @ at["q_kernel"]).reshape(b, s, heads, d)
    k, v = jnp.split((u @ at["kv_kernel"]).reshape(b, s, 2 * groups, d), 2,
                     axis=2)
    q = _rope(_rms_norm(q, at["q_ln_scale"], eps), cos, sin)
    k = _rope(_rms_norm(k, at["k_ln_scale"], eps), cos, sin)
    k = jnp.repeat(k, heads // groups, axis=2)
    v = jnp.repeat(v, heads // groups, axis=2)
    pos = jnp.arange(s)
    allowed = (pos[:, None] >= pos[None, :])[None]
    allowed = allowed & (segment_ids[:, :, None] == segment_ids[:, None, :])

    def one_head(qkv):          # one head at a time: [S, S] scores, not 32
        qh, kh, vh = qkv
        scores = jnp.einsum("bqd,bkd->bqk", qh, kh) * d ** -0.5
        probs = jax.nn.softmax(jnp.where(allowed, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bqk,bkd->bqd", probs, vh)

    ctx = jax.lax.map(one_head, tuple(jnp.moveaxis(a, 2, 0)
                                      for a in (q, k, v)))
    return jnp.moveaxis(ctx, 0, 2).reshape(b, s, heads * d) @ at["out_kernel"]


def _swiglu(x, fc1, fc2):
    gate, up = jnp.split(x @ fc1, 2, axis=-1)
    return (jax.nn.silu(gate) * up) @ fc2


def router_weights(flat, router_kernel, router_bias, top_k: int,
                   renormalise: bool, scaling: float, route):
    """flat [T,H] -> [T,E] float32: an expert's weight for each token, 0
    where it was not chosen. route = (score function, whether the selection
    sees the bias): ("sigmoid", True) is the model."""
    score, biased = route
    logits = flat @ router_kernel.astype(F32)
    s = (jax.nn.sigmoid(logits) if score == "sigmoid"
         else jax.nn.softmax(logits, axis=-1))
    _, top_i = jax.lax.top_k(s + router_bias if biased else s, top_k)
    top_s = jnp.take_along_axis(s, top_i, axis=-1)
    if renormalise:
        top_s = top_s / (jnp.sum(top_s, -1, keepdims=True) + 1e-6)
    top_s = top_s * scaling
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


@functools.partial(jax.jit, static_argnames=("heads", "groups", "eps"))
def _operator(x, mixers, i, cos, sin, segment_ids, heads, groups, eps):
    """x + Op(RMS(x; n1)) for row i of `mixers` (a stack of one kind), and
    the convolution's gated inputs (None for attention)."""
    mixer = _row(mixers, i)
    u = _rms_norm(x, mixer["ln1_scale"], eps)
    if "conv" in mixer:
        out, v = short_conv(u, mixer["conv"], segment_ids)
        return x + out, v
    return x + attention(u, mixer["attention"], cos, sin, segment_ids,
                         heads, groups, eps), None


@functools.partial(jax.jit, static_argnames=("eps",))
def _dense(x, ffns, i, eps):
    ffn = _row(ffns, i)
    return x + _swiglu(_rms_norm(x, ffn["ln2_scale"], eps),
                       ffn["mlp"]["fc1_kernel"], ffn["mlp"]["fc2_kernel"])


@functools.partial(jax.jit, static_argnames=(
    "eps", "top_k", "renormalise", "scaling", "route"))
def _moe(x, ffns, i, eps, top_k, renormalise, scaling, route):
    b, s, h = x.shape
    moe = ffns["moe"]
    small = _row({k: moe[k] for k in ("router_kernel", "router_bias")}, i)
    flat = _rms_norm(x, _row(ffns["ln2_scale"], i), eps).reshape(b * s, h)
    weights = router_weights(flat, small["router_kernel"],
                             small["router_bias"], top_k, renormalise,
                             scaling, route)
    return x + _experts(flat, weights, moe["fc1_kernel"], moe["fc2_kernel"],
                        i).reshape(b, s, h)


@functools.partial(jax.jit, static_argnames=("eps", "size"))
def _head(x, scale, word, start, eps: float, size: int):
    x = jax.lax.dynamic_slice_in_dim(x, start, size, axis=1)
    return _rms_norm(x, scale.astype(F32), eps) @ word.astype(F32).T


def _layers(params, config: dict, tokens, segment_ids, position_ids,
            route=("sigmoid", True)):
    """The stack over tokens [B,S]: (x [B,S,H] float32 before the final
    norm, the convolution layers' gated inputs v [B,S,H] in their order)."""
    block = params["block"]
    d = _head_dim(config)
    eps = config["norm_eps"]
    inv_freq = 1.0 / float(config["rope_parameters"]["rope_theta"]) ** (
        jnp.arange(0, d, 2, dtype=F32) / d)
    angles = position_ids.astype(F32)[..., None] * inv_freq
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    static = dict(heads=config["num_attention_heads"],
                  groups=config["num_key_value_heads"], eps=eps)
    moe_static = dict(
        eps=eps, top_k=config["num_experts_per_tok"],
        renormalise=bool(config["norm_topk_prob"]),
        scaling=float(config["routed_scaling_factor"]), route=tuple(route))
    lead = config["num_dense_layers"]
    x = jnp.take(params["embedding"]["word"], tokens, axis=0).astype(F32)
    seen = {CONV: 0, ATTN: 0}
    gated = []
    for i, kind in enumerate(config["layer_types"]):
        stack = block["mixers_conv" if kind == CONV else "mixers_attn"]
        x, v = _operator(x, stack, jnp.int32(seen[kind]), cos, sin,
                         segment_ids, **static)
        seen[kind] += 1
        if v is not None:
            gated.append(v)
        if i < lead:
            x = _dense(x, block["ffn_lead"], jnp.int32(i), eps=eps)
        else:
            x = _moe(x, block["ffn"], jnp.int32(i - lead), **moe_static)
    return x, gated


def reference_logits(params, config: dict, tokens, segment_ids, position_ids,
                     rows=None, route=("sigmoid", True)):
    """tokens/segment_ids/position_ids [B,S] -> logits float32 over the
    vocabulary, [B,S,V], or [B,size,V] for the positions start..start+size
    when `rows` = (start, size) (the head over 65536 columns is the largest
    array of a pass). `config` is a configuration file's top level; `route`
    as ``router_weights`` has it (the controls)."""
    with jax.default_matmul_precision("highest"):
        x, _ = _layers(params, config, tokens, segment_ids, position_ids,
                       route)
        start, size = rows if rows is not None else (0, x.shape[1])
        return _head(x, params["final_ln_scale"],
                     params["embedding"]["word"], jnp.int32(start),
                     eps=config["norm_eps"], size=size)


def reference_state(params, config: dict, tokens, lengths=None):
    """tokens [B,S], one sequence a row from position 0, row b's first
    lengths[b] positions real (None: all S) -> the ``conv_L_cache - 1``
    gated inputs each convolution layer read last, float32 [layers, B,
    conv_L_cache - 1, H]: what a slot of the engine's tail pool should hold
    once it has read those tokens (zeros where the sequence is shorter than
    that; what lies behind a row's length changes nothing, the model being
    causal)."""
    k = config["conv_L_cache"]
    b, s = tokens.shape
    if lengths is None:
        lengths = jnp.full((b,), s, jnp.int32)
    zeros = jnp.zeros(tokens.shape, jnp.int32)
    positions = jnp.broadcast_to(jnp.arange(s), tokens.shape)
    with jax.default_matmul_precision("highest"):
        _, gated = _layers(params, config, tokens, zeros, positions)
    # column j of the tail is position length - (k - 1) + j of v, padded in
    # front by k - 1 zeros: index length + j of the padded array
    at = (lengths[:, None] + jnp.arange(k - 1)[None, :])[..., None]
    return jnp.stack([
        jnp.take_along_axis(jnp.pad(v, ((0, 0), (k - 1, 0), (0, 0))), at,
                            axis=1) for v in gated])


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
