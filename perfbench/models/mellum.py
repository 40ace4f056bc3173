"""``"model": "mellum"``: JetBrains' Mellum-2 mixture-of-experts models as
their ``config.json`` publishes them (the configuration file keeps the
source's own keys), built as the program's ``models/gpt.py`` model for ONE
CHIP'S SHARE of an expert-parallel training job, with its plain reference
(forward, loss and gradients) and its counts. What a model module gives the
runners is listed in ``models/gpt_dense.py``; this one adds
``reference_loss_and_grads``.

The model: ``num_hidden_layers`` pre-norm layers, ``x <- x + Attn(RMS(x))``;
``x <- x + MoE(RMS(x))``, RMSNorm ``rms_norm_eps``, no bias, a final norm and
an untied head. With ``RMS(x; g) = x / sqrt(mean(x^2) + eps) * g``:

1. ``u = RMS(x; n1)``; ``q = u W_q`` -> ``[T, heads, D]``; ``[k | v] = u
   W_kv`` -> ``[T, kv, D]`` each.
2. ``q``, ``k`` <- RMS over D of each head (one scale vector for q, one for
   k: ``assumed.qk_norm``), then rotary on the pairs ``(i, i + D/2)`` of the
   whole head: a ``full_attention`` layer with YaRN frequencies
   (``_yarn_inv_freq``) and ``cos``, ``sin`` times ``attention_factor``; a
   ``sliding_attention`` layer with plain frequencies at its ``rope_theta``.
3. Causal softmax attention at scale ``D ** -0.5`` inside a segment, query
   head ``j`` on key/value head ``j // (heads / kv)``; in a sliding layer
   position ``t`` sees the keys ``t - sliding_window + 1 .. t``. Computed
   ``Q_BLOCK`` query rows at a time against all S keys under a ``[Q_BLOCK,
   S]`` mask, each block recomputed in the backward pass, so that 8,192
   positions fit: the one trick here. ``x <- x + concat(a) W_o``.
4. ``m = RMS(x; n2)``; ``p = softmax(float32(m) W_r)`` over the router's
   whole width; the ``num_experts_per_tok`` largest, divided by their sum
   (``norm_topk_prob``); ``x <- x + sum_{e held} w_e W2_e(silu(W1_e m) * W3_e
   m)``: a plain loop (a scan) over the experts HELD, each over all
   positions with weight 0 where it was not chosen.
5. The router's loss of a layer, over ALL the router's outputs whatever is
   held: ``coef x E x sum_e f_e P_e``, ``f_e`` the share of the micro-batch's
   T x k picks that chose e, ``P_e`` the mean of ``p_e`` over its tokens.
6. Final RMS norm, ``logits = x W_head``; the loss is the mean cross entropy
   over the positions whose ``loss_mask`` is 1, plus the layers' router
   losses.

Gradients are ``jax.vjp`` of those equations, one layer at a time from the
last to the first (the forward pass keeps each layer's input), so that one
layer's intermediates are alive at once; float32, matmuls at precision
"highest". No kernels, no scan over layers, no ``ragged_dot``, no window
arithmetic shared with the program. It reads the program's own parameter
tree (``block``: ``mixers_attn``, ``mixers_swa``, ``ffn``) a layer at a time
and shares no code with ``megatronapp_tpu/``.

Departures from the published model, each because this is one chip's share
(the configuration file's ``deployment``), in the program and here alike:
THE SHARE: what the experts held elsewhere would have added to a token is
left out and the partial sum goes on, no exchange stands in for them. THE
SLICE: the embedding and the head hold ``vocab_size`` rows of the published
vocabulary; ids are drawn from them and the loss is over them. NO MTP HEAD:
the model card mentions one and no key of the configuration describes it.
The router's loss is divided by the top-k (Megatron's form of the family's
``load_balancing_loss_func``). None of mathematics otherwise: gate and up
projections are one ``fc1`` matrix ``[gate | up]``, ``k_proj`` and ``v_proj``
one ``kv_kernel`` ``[k | v]``.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from perfbench import manifest, mellum_flops

# Weights come from the seed the same way for every models/gpt.py model.
init_params = manifest.load_module("models", "gpt_dense").init_params
flops_per_token = mellum_flops.flops_per_token

F32 = jnp.float32
DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}
FULL, SLIDING = "full_attention", "sliding_attention"
Q_BLOCK = 512           # query rows a step of the reference's attention
ACCUMULATE_CHUNK = 128  # columns a partial sum of the bf16-accumulate control

# The wrong models of the tests and of tools/share_train_control.py, each of
# which has to come out as not correct.
CONTROLS = ("window-1", "window+1", "no-band", "no-segments", "no-renorm",
            "no-yarn-factor", "absent-added", "router-loss-held-only")

REHEARSAL = {"num_hidden_layers": 4, "hidden_size": 64, "head_dim": 16,
             "num_attention_heads": 4, "num_key_value_heads": 2,
             "layer_types": [SLIDING, SLIDING, SLIDING, FULL],
             "mlp_layer_types": ["sparse"] * 4,
             "intermediate_size": 96, "moe_intermediate_size": 32,
             "num_experts": 4, "router_width": 16, "num_experts_per_tok": 4,
             "sliding_window": 24, "vocab_size": 512,
             "max_position_embeddings": 128}


def _pattern(config: dict):
    """(period, offset) such that layer i is a full-attention layer iff
    i % period == offset: how the program lays a two-kind stack out."""
    types = config["layer_types"]
    n = config["num_hidden_layers"]
    if (len(types) != n or set(types) - {FULL, SLIDING}
            or config["mlp_layer_types"] != ["sparse"] * n):
        raise SystemExit("perfbench: layer_types and mlp_layer_types must "
                         "name num_hidden_layers layers, every one sparse")
    at = [i for i, t in enumerate(types) if t == FULL]
    period = at[1] - at[0] if len(at) > 1 else len(types)
    if not at or [i for i in range(n) if i % period == at[0] % period] != at:
        raise SystemExit("perfbench: models/mellum.py builds stacks whose "
                         f"full layers lie one a period; got them at {at}")
    return period, at[0] % period


def params_count(config: dict) -> int:
    """Every parameter of the share: the layers run with the experts held,
    the embedding's and the untied head's rows held (the configuration
    file's ``reduced_why`` does this sum by hand)."""
    h, d = config["hidden_size"], config["head_dim"]
    nq, kv = config["num_attention_heads"], config["num_key_value_heads"]
    layer = (2 * h * nq * d + 2 * h * kv * d + 2 * d + 2 * h
             + h * config["router_width"]
             + config["num_experts"] * 3 * h * config["moe_intermediate_size"])
    return (config["num_hidden_layers"] * layer
            + 2 * config["vocab_size"] * h + h)


def kv_bytes_per_token(config: dict, dtype: str) -> int:
    """Keys and values of every key/value head of the layers run, in
    `dtype`: what a served token would cache (no cell serves this model)."""
    return (2 * config["num_hidden_layers"] * config["num_key_value_heads"]
            * config["head_dim"] * jnp.dtype(DTYPES[dtype]).itemsize)


def model_config(config: dict, params_dtype: str, **extra):
    """The program's TransformerConfig for a configuration file that keeps
    the source's keys. Everything not named stays at the program's default.
    A program that lacks what this model needs (the commit before the one
    that added it) fails here, at once."""
    from megatronapp_tpu.config.transformer_config import (
        ActivationKind, NormKind, PositionEmbeddingKind, TransformerConfig,
    )
    assumed = config["assumed"]
    rope_full = config["rope_parameters"][FULL]
    rope_slide = config["rope_parameters"][SLIDING]
    if (config["attention_bias"] or config["tie_word_embeddings"]
            or config["hidden_act"] != "silu"
            or not config["norm_topk_prob"]
            or rope_full["rope_type"] != "yarn"
            or rope_slide["rope_type"] != "default"
            or assumed["qk_norm"]["value"] is not True
            or assumed["rope_pairing"]["value"] != "half rotation"):
        raise SystemExit(
            "perfbench: models/mellum.py builds the form the configuration "
            "file states (no bias, an untied head, SwiGLU experts, "
            "normalised top-k, YaRN on the full layers and plain RoPE on "
            "the sliding ones) and its `assumed` (RMS norms on q and k, "
            "half-rotation pairing)")
    period, offset = _pattern(config)
    share = config["expert_share"]
    return TransformerConfig(
        num_layers=config["num_hidden_layers"],
        hidden_size=config["hidden_size"],
        num_attention_heads=config["num_attention_heads"],
        num_query_groups=config["num_key_value_heads"],
        kv_channels=config["head_dim"],
        ffn_hidden_size=config["intermediate_size"],
        vocab_size=config["vocab_size"],
        vocab_slice_of=config["published"]["vocab_size"],
        max_position_embeddings=config["max_position_embeddings"],
        normalization=NormKind.rmsnorm,
        layernorm_epsilon=config["rms_norm_eps"],
        activation=ActivationKind.swiglu, add_bias_linear=False,
        untie_embeddings_and_output_weights=True, qk_layernorm=True,
        init_method_std=float(assumed["init_std"]["value"]),
        scaled_init_layers=config["published"]["num_hidden_layers"],
        position_embedding=PositionEmbeddingKind.yarn,
        rotary_base=float(rope_full["rope_theta"]),
        rope_scaling_factor=float(rope_full["factor"]),
        yarn_original_max_position=int(
            rope_full["original_max_position_embeddings"]),
        yarn_beta_fast=float(rope_full["beta_fast"]),
        yarn_beta_slow=float(rope_full["beta_slow"]),
        yarn_attention_factor=float(rope_full["attention_factor"]),
        attn_layer_period=period, attn_layer_offset=offset,
        sliding_window=config["sliding_window"],
        sliding_rotary_base=float(rope_slide["rope_theta"]),
        num_moe_experts=config["router_width"],
        moe_experts_held=(share["first"], config["num_experts"]),
        moe_router_topk=config["num_experts_per_tok"],
        moe_ffn_hidden_size=config["moe_intermediate_size"],
        moe_router_norm_topk_prob=True,
        moe_aux_loss_coeff=float(config["train"]["moe_aux_loss_coeff"]),
        params_dtype=DTYPES[params_dtype], **extra)


# ---- the plain reference ---------------------------------------------------

class _Static(NamedTuple):
    """What a layer's equations take from the configuration file."""
    groups: int
    window: int             # 0: a full layer
    eps: float
    top_k: int
    first: int              # the first expert held, of `width`
    width: int
    coef: float
    control: str
    precision: str          # "highest" | "default" (the bf16 controls)
    chunk: int = 0          # > 0: products' sums are carried in bf16
    fp8: bool = False       # products' operands rounded to float8_e4m3fn


def _rms_norm(x, weight, eps):
    scale = jax.lax.rsqrt(jnp.mean(jnp.square(x.astype(F32)), -1,
                                   keepdims=True) + eps)
    return x * scale.astype(x.dtype) * weight


def _row(tree, i, dtype):
    """Layer i of a stack in `dtype`: cut inside the jitted layer by a
    traced index, so one program a kind and shape."""
    return jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, False).astype(dtype),
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


def rope_tables(config: dict, kind: str, position_ids, control: str = ""):
    """(cos, sin) [B, S, D/2] of a layer kind's rotary table at
    position_ids [B, S]."""
    rope = config["rope_parameters"][kind]
    rot = config["head_dim"]
    if rope["rope_type"] == "yarn":
        inv_freq, factor = _yarn_inv_freq(rot, rope), rope["attention_factor"]
        if control == "no-yarn-factor":
            factor = 1.0
    else:
        inv_freq = float(rope["rope_theta"]) ** -(
            jnp.arange(0, rot, 2, dtype=F32) / rot)
        factor = 1.0
    angles = position_ids.astype(F32)[..., None] * inv_freq
    return jnp.cos(angles) * factor, jnp.sin(angles) * factor


def _operand(x, fp8: bool):
    """x as a product's operand: itself, or under the ``compute="float8"``
    control rounded to float8_e4m3fn at the scale that puts its largest
    element at the format's largest (448), as fp8 training scales a
    tensor. The rounding is of the value alone: a cotangent passes it
    unchanged (cast to the format it would vanish)."""
    if not fp8:
        return x
    scale = (jnp.maximum(jnp.max(jnp.abs(x.astype(F32))), 1e-30) / 448.0
             ).astype(x.dtype)
    rounded = (x / scale).astype(jnp.float8_e4m3fn).astype(x.dtype) * scale
    return x + jax.lax.stop_gradient(rounded - x)


def _summed_in_chunks(a, b, chunk: int):
    """a [..., K] @ b [K, N] as a step that ACCUMULATES in the arrays' own
    type would: the contraction `chunk` columns at a time, the running sum
    rounded to the arrays' type after every chunk (the precision control of
    ``compute="bfloat16-accumulate"``; `chunk` 0 is the plain product)."""
    k = a.shape[-1]
    if not chunk or k <= chunk:
        return a @ b
    parts = (jnp.moveaxis(a.reshape(a.shape[:-1] + (k // chunk, chunk)),
                          -2, 0),
             b.reshape((k // chunk, chunk) + b.shape[1:]))
    return jax.lax.scan(
        lambda acc, ab: ((acc + ab[0] @ ab[1]).astype(a.dtype), None),
        jnp.zeros(a.shape[:-1] + b.shape[1:], a.dtype), parts)[0]


def _rope(x, cos, sin):
    """x [B,S,heads,D], cos/sin [B,S,D/2]: rotate the pairs (i, i + D/2)."""
    x1, x2 = jnp.split(x, 2, axis=-1)
    c, s = cos[:, :, None, :].astype(x.dtype), sin[:, :, None, :].astype(
        x.dtype)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def _attention(u, at, cos, sin, segment_ids, st: _Static):
    """Steps 1-3 without the residual: u [B,S,H] -> [B,S,H]."""
    b, s, _ = u.shape
    d = at["q_ln_scale"].shape[-1]
    heads = at["q_kernel"].shape[-1] // d
    at = {name: _operand(w, st.fp8) if name.endswith("_kernel") else w
          for name, w in at.items()}
    u = _operand(u, st.fp8)
    q = (u @ at["q_kernel"]).reshape(b, s, heads, d)
    k, v = jnp.split((u @ at["kv_kernel"]).reshape(b, s, 2 * st.groups, d),
                     2, axis=2)
    q = _rope(_rms_norm(q, at["q_ln_scale"], st.eps), cos, sin)
    k = _rope(_rms_norm(k, at["k_ln_scale"], st.eps), cos, sin)
    q = _operand(q, st.fp8).reshape(b, s, st.groups, heads // st.groups, d)
    k, v = _operand(k, st.fp8), _operand(v, st.fp8)
    at_k = jnp.arange(s)

    @jax.checkpoint
    def block(qb, seg_q, at_q):
        allowed = at_q[:, None] >= at_k[None, :]
        if st.window:
            allowed &= at_q[:, None] - at_k[None, :] < st.window
        allowed = allowed[None]
        if st.control != "no-segments":
            allowed = allowed & (seg_q[:, :, None] == segment_ids[:, None, :])
        scores = jnp.einsum("bqgjd,bkgd->bgjqk", qb, k).astype(F32) * d ** -0.5
        probs = _operand(jax.nn.softmax(
            jnp.where(allowed[:, None, None], scores, -1e30), -1), st.fp8)
        if st.chunk:
            # [b,g,j,q,k] @ [b,g,k,d], the keys' sum carried in v's type
            out = jax.vmap(jax.vmap(lambda p_, v_: _summed_in_chunks(
                p_, v_, st.chunk)))(probs.astype(v.dtype),
                                    jnp.moveaxis(v, 2, 1))
            return jnp.moveaxis(out, 3, 1)
        return jnp.einsum("bgjqk,bkgd->bqgjd", probs.astype(v.dtype), v)

    size = min(Q_BLOCK, s)
    pad = -s % size
    blocks = [jnp.moveaxis(jnp.pad(
        x, [(0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 2),
        constant_values=fill).reshape(
            (b, (s + pad) // size, size) + x.shape[2:]), 1, 0)
        for x, fill in ((q, 0), (segment_ids, -1),
                        (jnp.broadcast_to(at_k, (b, s)), s))]
    out = jax.lax.map(lambda xs: block(xs[0], xs[1], xs[2][0]), blocks)
    return _operand(jnp.moveaxis(out, 0, 1).reshape(
        b, s + pad, heads * d)[:, :s], st.fp8) @ at["out_kernel"]


def _moe(m, moe, st: _Static):
    """Steps 4-5: m [T,H] -> (sum over the experts held [T,H], the layer's
    router loss)."""
    t = m.shape[0]
    held = moe["fc1_kernel"].shape[0]
    probs = jax.nn.softmax(m.astype(F32) @ moe["router_kernel"].astype(F32),
                           axis=-1)
    top_p, top_i = jax.lax.top_k(probs, st.top_k)
    if st.control != "no-renorm":
        top_p = top_p / jnp.sum(top_p, -1, keepdims=True)
    chosen = jax.nn.one_hot(top_i, st.width, dtype=F32)          # [T,k,E]
    weights = jnp.sum(chosen * top_p[..., None], axis=1)         # [T,E]
    mine = weights[:, st.first:st.first + held]                  # [T,held]
    if st.control == "absent-added":
        # a wrong model: an absent expert's picks computed by the held
        # expert of its index modulo the held count
        mine = jnp.sum(weights.reshape(t, -1, held), axis=1)

    m_in = _operand(m, st.fp8)

    def one_expert(out, expert):
        fc1, fc2, w = expert
        gate, up = jnp.split(_summed_in_chunks(
            m_in, _operand(fc1, st.fp8), st.chunk), 2, axis=-1)
        return out + _summed_in_chunks(
            _operand(jax.nn.silu(gate) * up, st.fp8), _operand(fc2, st.fp8),
            st.chunk) * w[:, None].astype(m.dtype), None

    out, _ = jax.lax.scan(one_expert, jnp.zeros_like(m),
                          (moe["fc1_kernel"], moe["fc2_kernel"], mine.T))
    share = jnp.sum(chosen, axis=(0, 1)) / (t * st.top_k)        # f_e
    product = share * jnp.mean(probs, axis=0)
    if st.control == "router-loss-held-only":
        product = product[st.first:st.first + held]
    return out, st.coef * st.width * jnp.sum(product)


def _layer(x, mixer, ffn, cos, sin, segment_ids, st: _Static):
    """One layer: x [B,S,H] -> (x, its router loss)."""
    with jax.default_matmul_precision(st.precision):
        x = x + _attention(_rms_norm(x, mixer["ln1_scale"], st.eps),
                           mixer["attention"], cos, sin, segment_ids, st)
        b, s, h = x.shape
        out, aux = _moe(_rms_norm(x, ffn["ln2_scale"], st.eps).reshape(
            b * s, h), ffn["moe"], st)
        return x + out.reshape(b, s, h), aux


@functools.partial(jax.jit, static_argnames=("st",))
def _layer_forward(x, mixers, ffns, k, i, cos, sin, segment_ids, st):
    return _layer(x, _row(mixers, k, x.dtype), _row(ffns, i, x.dtype), cos,
                  sin, segment_ids, st)


@functools.partial(jax.jit, static_argnames=("st",))
def _layer_backward(x, mixers, ffns, k, i, cos, sin, segment_ids, dx, st):
    """(dx, d mixer, d ffn) of one layer from the cotangent of its output;
    the router loss's cotangent is 1, since it is a term of the loss."""
    _, vjp = jax.vjp(
        lambda x_, m_, f_: _layer(x_, m_, f_, cos, sin, segment_ids, st),
        x, _row(mixers, k, x.dtype), _row(ffns, i, x.dtype))
    return vjp((dx, jnp.ones((), F32)))


@functools.partial(jax.jit, static_argnames=("eps", "precision", "fp8"))
def _head_loss(x, scale, out_kernel, labels, loss_mask, eps, precision,
               fp8=False):
    """Step 6: (loss, (dx, d scale, d head))."""
    def loss(x_, scale_, out_):
        with jax.default_matmul_precision(precision):
            lg = (_operand(_rms_norm(x_, scale_.astype(x_.dtype), eps), fp8)
                  @ _operand(out_.astype(x_.dtype), fp8)).astype(F32)
        logz = jax.nn.logsumexp(lg, axis=-1)
        picked = jnp.take_along_axis(lg, labels[..., None], axis=-1)[..., 0]
        return jnp.sum((logz - picked) * loss_mask) / jnp.maximum(
            loss_mask.sum(), 1)
    return jax.value_and_grad(loss, (0, 1, 2))(x, scale, out_kernel)


def _stack_inputs(params, config: dict, position_ids, segment_ids, control,
                  precision, chunk=0, fp8=False):
    """[(kind's static, the arguments of its layer programs)] in layer
    order: each layer's stacks, its row among its kind, its row of "ffn",
    its kind's rotary table and the segment ids."""
    block = params["block"]
    window = config["sliding_window"] + {"window-1": -1, "window+1": 1}.get(
        control, 0)
    if control == "no-band":
        window = 0
    tables = {kind: rope_tables(config, kind, position_ids, control)
              for kind in (FULL, SLIDING)}
    layers, seen = [], {FULL: 0, SLIDING: 0}
    for i, kind in enumerate(config["layer_types"]):
        static = _Static(
            groups=config["num_key_value_heads"],
            window=window if kind == SLIDING else 0,
            eps=config["rms_norm_eps"], top_k=config["num_experts_per_tok"],
            first=config["expert_share"]["first"],
            width=config["router_width"],
            coef=float(config["train"]["moe_aux_loss_coeff"]),
            control=control, precision=precision, chunk=chunk, fp8=fp8)
        layers.append((static, (
            block["mixers_attn" if kind == FULL else "mixers_swa"],
            block["ffn"], jnp.int32(seen[kind]), jnp.int32(i),
            *tables[kind], segment_ids)))
        seen[kind] += 1
    return layers


def reference_logits(params, config: dict, tokens, segment_ids, position_ids):
    """tokens/segment_ids/position_ids [B,S] -> logits float32 [B,S,V] over
    the rows of the vocabulary held."""
    x = jnp.take(params["embedding"]["word"], tokens, axis=0).astype(F32)
    for static, args in _stack_inputs(params, config, position_ids,
                                      segment_ids, "", "highest"):
        x, _ = _layer_forward(x, *args, st=static)
    with jax.default_matmul_precision("highest"):
        return _rms_norm(x, params["final_ln_scale"].astype(F32),
                         config["rms_norm_eps"]) @ params["output"].astype(F32)


def reference_loss_and_grads(params, config: dict, batch, control: str = "",
                             compute: str = "float32"):
    """(loss, gradients as the program's parameter tree, float32) of one
    micro-batch of ``generators/train_packed.py`` rows. `control`: one of
    CONTROLS, a wrong model. `compute` "bfloat16" runs the same equations on
    bf16 arrays with one-pass matmuls, which is the program's own arithmetic
    (products of bf16 arrays summed in float32, rounded once); "bfloat16-
    accumulate" also carries the sums of the attention's probabilities x
    values and of the experts' two products in bf16, ACCUMULATE_CHUNK
    columns at a time: a step that accumulated those products in bf16.
    "float8" is the bf16 run with every product's operands (the router's
    excepted) rounded to float8_e4m3fn: the precision below the one the
    configuration states, the precision control."""
    if control and control not in CONTROLS:
        raise ValueError(f"control {control!r} is none of {CONTROLS}")
    fp8 = compute == "float8"
    dtype = DTYPES["bfloat16" if fp8 else compute.partition("-")[0]]
    precision = "highest" if compute == "float32" else "default"
    chunk = ACCUMULATE_CHUNK if compute.endswith("-accumulate") else 0
    tokens, labels = jnp.asarray(batch["tokens"]), jnp.asarray(batch["labels"])
    layers = _stack_inputs(
        params, config, jnp.asarray(batch["position_ids"]),
        jnp.asarray(batch["segment_ids"]), control, precision, chunk, fp8)

    xs = [jnp.take(params["embedding"]["word"], tokens, axis=0).astype(dtype)]
    loss = jnp.zeros((), F32)
    for static, args in layers:
        x, aux = _layer_forward(xs[-1], *args, st=static)
        xs.append(x)
        loss = loss + aux
    ce, (dx, d_scale, d_head) = _head_loss(
        xs.pop(), params["final_ln_scale"], params["output"], labels,
        jnp.asarray(batch["loss_mask"], F32), eps=config["rms_norm_eps"],
        precision=precision, fp8=fp8)
    rows = {"mixers_attn": [], "mixers_swa": [], "ffn": []}
    for kind, (static, args) in reversed(list(zip(config["layer_types"],
                                                  layers))):
        dx, d_mixer, d_ffn = _layer_backward(xs.pop(), *args, dx, st=static)
        rows["mixers_attn" if kind == FULL else "mixers_swa"].insert(
            0, d_mixer)
        rows["ffn"].insert(0, d_ffn)
    grads = {
        "block": {name: jax.tree.map(
            lambda *leaves: jnp.stack(leaves).astype(F32), *got)
            for name, got in rows.items() if got},
        "embedding": {"word": jnp.zeros(
            params["embedding"]["word"].shape, F32).at[tokens].add(
            dx.astype(F32))},
        "final_ln_scale": d_scale.astype(F32),
        "output": d_head.astype(F32)}
    return float(loss + ce), grads


def reference_loss(params, config: dict, batch) -> float:
    """The loss alone (``reference_loss_and_grads`` without its second half
    is not offered: a training cell of this model asks for both)."""
    return reference_loss_and_grads(params, config, batch)[0]
