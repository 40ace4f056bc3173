"""``"model": "solar_open2"``: Upstage's Solar Open 2 (HF model type
``solar_open2``) as its ``config.json`` publishes it (the configuration file
keeps the source's own keys), built as the program's ``models/gpt.py`` model,
with the plain reference and the counts. What a model module gives the
runners is listed in ``models/gpt_dense.py``; this one exports what
``models/nemotron_h.py`` does (``state_bytes_per_slot``, ``reference_state``,
``reference_hidden`` / ``reference_head``, ``reference_layer_terms``,
``calibrated_bias``).

The model, with ``RMS(x; g) = x / sqrt(mean(x^2) + rms_norm_eps) * g``:

- ``x0 = E[token]``; no positional term anywhere (``use_rope`` false); every
  layer i is pre-norm, ``x <- x + Mixer_i(RMS(x))`` then ``x <- x +
  MoE(RMS(x))`` (``first_k_dense_replace`` 0: no dense layer;
  ``intermediate_size`` is read by no layer); ``logits = RMS(x_L; g_f)
  W_head``, ``W_head`` untied from E. ``Mixer_i`` is GQA for i in
  ``gqa_layers`` (every ``gqa_interval + 1``-th), KDA otherwise;
- KDA, Kimi delta attention (Kimi Linear, arXiv:2510.26692; ``heads`` =
  ``linear_attn_config.num_heads`` heads of K = V = ``linear_attn_config.
  head_dim``; u the normed input)::

      [q~ | k~ | v] = silu(conv_k(u W_qkv))   # causal, depthwise, k SHIFTED
                                              # PRODUCTS, no bias
      q = q~ / sqrt(|q~|^2 + 1e-6) K^-1/2 ;  k = k~ / sqrt(|k~|^2 + 1e-6)
      g = -exp(A_log[h]) softplus(u W_f1 W_f2 + dt_bias)     # [heads, K]
      b = 2 sigmoid(u W_b)                                   # [heads]
      S' = diag(exp(g_t)) S_{t-1} ; S_t = S' + b_t k_t (v_t - S'^T k_t)^T
      o_t = S_t^T q_t
      out = (RMS_head(o_t; w) * sigmoid(u W_g1 W_g2 + b_g)) W_o

  as a SEQUENTIAL ``lax.scan`` over the positions, one at a time, from a
  zero state at every segment's first position (no chunks, no triangular
  solve, no kernel, no cache);
- GQA: ``num_attention_heads`` query heads over ``num_key_value_heads``
  key/value heads of ``head_dim``, no bias, NO rotary table, a causal
  softmax of ``q.k / sqrt(head_dim)`` within a segment, by its dense scores
  ``QUERY_ROWS`` query rows at a time, then ``out = (ctx * sigmoid(u
  W_gate)) W_o`` with one gate an ELEMENT of the heads' outputs;
- MoE: ``s = sigmoid(float32(h) W_r)`` (``n_routed_experts`` outputs as
  PUBLISHED); the ``num_experts_per_tok`` largest of ``s + b``
  (``e_score_correction_bias``, no group limit); ``w = s`` of those over
  their sum + 1e-20, times ``routed_scaling_factor``; each expert ``(silu(h
  W1) * (h W3)) W2`` at width ``moe_intermediate_size``, ONE HELD EXPERT AT
  A TIME over all tokens with weight 0 where it was not chosen; plus one
  shared expert of the same form and width, always on.

THE SHARE. A configuration file whose ``n_routed_experts`` is under
``published.n_routed_experts`` describes one chip's share of an
expert-parallel deployment: the tree holds the experts ``expert_share.first
.. + n_routed_experts`` of each layer, the router stays as wide as published,
and what the absent experts would have added is left out, here as in the
program. ``reference_layer_terms`` gives a layer's parts one by one, for the
test that adds the shares up. ``vocab_size`` rows of ``published.vocab_size``
are a smaller vocabulary.

It reads the program's own parameter tree (``block``: ``mixers_attn``,
``mixers_kda``, ``ffn``, each the layers of one kind in layer order), a
matrix group at a time upcast to float32, matmuls at precision "highest",
and shares no code with ``megatronapp_tpu/transformer/``.

Departures from the published model, and what the config does not say (the
configuration file's ``assumed`` gives the evidence for each):
- layout: ``q_proj``, ``k_proj`` and ``v_proj`` of a KDA layer are one
  ``qkv_kernel`` ``[q | k | v]`` and their three convolutions one
  ``conv_kernel [k, 3E]``; ``k_proj`` / ``v_proj`` of a GQA layer one
  ``kv_kernel``; an expert's ``w1`` and ``w3`` one ``fc1 [gate | up]``; the
  state is computed as ``S [heads, K, V]`` and handed over in the program's
  ``h [K, E]`` (``h[c, head * V + j] = S[head][c, j]``);
- the program's sigmoid router divides the picks' weights by their sum +
  1e-6 (``transformer/moe.py``), this reference by the sum + 1e-20: eight
  sigmoids add up to ~4, so a weight differs by 2.5e-7 of itself;
- the residual stream is the compute type's in the program and float32
  here.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from perfbench import manifest

_init_params = manifest.load_module("models", "gpt_dense").init_params
# Positions of the pass that calibrates the routers' selection bias.
CALIBRATION_TOKENS = 2048
QUERY_ROWS = 1024       # query rows of one block of a GQA layer's scores

F32 = jnp.float32
DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}

REHEARSAL = {"num_hidden_layers": 4, "gqa_layers": [0], "hidden_size": 96,
             "num_attention_heads": 6, "num_key_value_heads": 2,
             "head_dim": 16, "intermediate_size": 80,
             "moe_intermediate_size": 40, "n_routed_experts": 4,
             "num_experts_per_tok": 3,
             "linear_attn_config": {"short_conv_kernel_size": 4,
                                    "head_dim": 16, "num_heads": 4,
                                    "num_kv_heads": None},
             "chunk_size": 32, "vocab_size": 512,
             "max_position_embeddings": 512,
             "published": {"num_hidden_layers": 8, "gqa_layers": [0, 4],
                           "n_routed_experts": 8, "vocab_size": 1024},
             "expert_share": {"first": 0}}


def _kda(config: dict):
    """(heads, a head's key channels = value columns, taps)."""
    la = config["linear_attn_config"]
    return la["num_heads"], la["head_dim"], la["short_conv_kernel_size"]


def _inner(config: dict) -> int:
    heads, d, _ = _kda(config)
    return heads * d


def _layers_run(config: dict) -> int:
    """The layers that are run: the file's, or its first ``num_layers``
    where this repository's tools cut a copy of a file."""
    return config.get("num_layers", config["num_hidden_layers"])


def _is_gqa(config: dict):
    """[layer i attends] for the layers that are run; the published list is
    every (gqa_interval + 1)-th layer from 0, which is what the program's
    period/offset form spells."""
    n = config["num_hidden_layers"]
    period = config["gqa_interval"] + 1
    if list(config["gqa_layers"]) != list(range(0, n, period)):
        raise SystemExit(
            f"perfbench: gqa_layers {config['gqa_layers']} is not every "
            f"{period}-th of {n} layers from 0, the one form "
            "models/solar_open2.py builds")
    return [i % period == 0 for i in range(_layers_run(config))]


def _published(config: dict, key: str):
    """The source's value of a key this file may have reduced."""
    return config.get("published", {}).get(key, config[key])


def _share(config: dict):
    """(published experts, first held, held here)."""
    return (_published(config, "n_routed_experts"),
            config.get("expert_share", {}).get("first", 0),
            config["n_routed_experts"])


def kv_bytes_per_token(config: dict, dtype: str) -> int:
    """Keys and values of every key/value head of the GQA layers, in
    `dtype`: a KDA layer caches no token."""
    return (2 * sum(_is_gqa(config)) * config["num_key_value_heads"]
            * config["head_dim"] * jnp.dtype(DTYPES[dtype]).itemsize)


def state_bytes_per_slot(config: dict, dtype: str) -> int:
    """What one sequence's recurrent state takes, whatever its length: for
    every KDA layer the heads' matrix states, ``K x E`` elements in `dtype`
    (the configuration's ``serve.state_dtype``), and the convolutions' last
    ``taps - 1`` inputs over q, k and v (3E columns) in the type the model
    computes in (``serve.params_dtype``)."""
    heads, d, taps = _kda(config)
    tail = DTYPES[config.get("serve", {}).get("params_dtype", "bfloat16")]
    kda_layers = len(_is_gqa(config)) - sum(_is_gqa(config))
    return kda_layers * (
        d * heads * d * jnp.dtype(DTYPES[dtype]).itemsize
        + (taps - 1) * 3 * heads * d * jnp.dtype(tail).itemsize)


def params_per_token(config: dict) -> float:
    """Matrix parameters a token meets: its layers' mixers, routers and
    shared experts, its top-k's share of the HELD experts, the untied head
    once (the embedding is gathered by row)."""
    h, d = config["hidden_size"], config["head_dim"]
    e, r = _inner(config), _kda(config)[1]
    experts, _, held = _share(config)
    gqa = sum(_is_gqa(config))
    kda_p = h * 3 * e + 2 * (h * r + r * e) + h * _kda(config)[0] + e * h
    gqa_p = (3 * h * config["num_attention_heads"] * d
             + 2 * h * config["num_key_value_heads"] * d)
    expert = 3 * h * config["moe_intermediate_size"]
    moe = (h * experts + expert
           + config["num_experts_per_tok"] * held / experts * expert)
    return ((len(_is_gqa(config)) - gqa) * kda_p + gqa * gqa_p
            + len(_is_gqa(config)) * moe + h * config["vocab_size"])


def flops_per_token(config: dict, seq_len: int) -> float:
    """Forward and backward matmul operations per token (3 x 2 x the
    parameters a token meets, plus causal attention over `seq_len` in the
    GQA layers and the KDA layers' chunk products), the yardstick an
    ``mfu`` reader would use; no cell of this model trains."""
    gqa = sum(_is_gqa(config))
    heads, d, _ = _kda(config)
    q = config.get("chunk_size", 64)
    scores = gqa * config["num_attention_heads"] * 2 * config[
        "head_dim"] * seq_len / 2
    chunk = (len(_is_gqa(config)) - gqa) * (
        2 * q * d * heads + 2 * q * heads * d + 3 * d * heads * d)
    return 6.0 * (params_per_token(config) + scores + chunk)


def init_params(model_cfg, seed: int, device=None):
    """``gpt_dense.init_params`` (the program's own initialiser, one jitted
    program on the device), then the routers' selection bias calibrated
    (``calibrated_bias``)."""
    params = _init_params(model_cfg, seed, device)
    return _with_bias(params, calibrated_bias(params, model_cfg, seed))


def _with_bias(params, bias):
    """`params` with the routers' selection bias [layers, experts]."""
    block = params["block"]
    return dict(params, block=dict(block, ffn=dict(block["ffn"], moe=dict(
        block["ffn"]["moe"], router_bias=bias))))


def model_config(config: dict, params_dtype: str, **extra):
    """The program's TransformerConfig for a configuration file that keeps
    the source's keys. Everything not named stays at the program's default.
    A program that lacks a field this model needs (the commit before the
    one that added it) fails here, at once and in words."""
    from megatronapp_tpu.config.transformer_config import (
        ActivationKind, NormKind, PositionEmbeddingKind, TransformerConfig,
    )
    if (config["use_rope"] or not config["use_gqa_gate"]
            or config["kda_use_full_proj"]
            or not config["kda_allow_neg_eigval"]
            or config["first_k_dense_replace"]
            or config["tie_word_embeddings"] or not config["norm_topk_prob"]
            or config["n_shared_experts"] != 1
            or config["linear_attn_config"]["num_kv_heads"] is not None):
        raise SystemExit(
            "perfbench: models/solar_open2.py builds the published form "
            "only (no rotary table, a gated GQA layer, low-rank KDA gates, "
            "b in (0, 2), no dense layer, an untied head, renormalised "
            "picks beside one shared expert, no grouped key heads)")
    heads, d, taps = _kda(config)
    experts, first, held = _share(config)
    _is_gqa(config)         # the list is the period's, or this exits
    try:
        return TransformerConfig(
            num_layers=_layers_run(config),
            hidden_size=config["hidden_size"],
            num_attention_heads=config["num_attention_heads"],
            num_query_groups=config["num_key_value_heads"],
            kv_channels=config["head_dim"],
            ffn_hidden_size=config["intermediate_size"],
            vocab_size=config["vocab_size"],
            vocab_slice_of=_published(config, "vocab_size"),
            max_position_embeddings=config["max_position_embeddings"],
            normalization=NormKind.rmsnorm,
            layernorm_epsilon=config["rms_norm_eps"],
            activation=ActivationKind.swiglu, add_bias_linear=False,
            position_embedding=PositionEmbeddingKind.none,
            untie_embeddings_and_output_weights=True,
            scaled_init_layers=_published(config, "num_hidden_layers"),
            attn_layer_period=config["gqa_interval"] + 1,
            attn_layer_offset=0,
            attention_output_gate=True, attention_gate_elementwise=True,
            kda_heads=heads, ssm_head_dim=d, ssm_state_dim=d,
            ssm_conv_kernel=taps, ssm_chunk_size=config.get("chunk_size", 64),
            num_moe_experts=experts,
            moe_experts_held=(first, held) if held < experts else None,
            moe_router_topk=config["num_experts_per_tok"],
            moe_ffn_hidden_size=config["moe_intermediate_size"],
            moe_shared_expert_intermediate_size=config[
                "moe_intermediate_size"],
            moe_router_score="sigmoid", moe_router_selection_bias=True,
            moe_router_norm_topk_prob=True,
            moe_routed_scaling_factor=float(config["routed_scaling_factor"]),
            params_dtype=DTYPES[params_dtype], **extra)
    except TypeError as e:
        raise SystemExit(
            "perfbench: this program's TransformerConfig lacks a field "
            f"solar_open2 needs ({e})") from None


# ---- the plain reference ---------------------------------------------------

def _rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * weight


def _f32(a):
    """A leaf of the tree as the reference reads it: float32.
    ``tools/solar_control.py`` rounds the matrices here."""
    return a.astype(F32)


def _row(tree, i):
    """Layer i of a stack, upcast: cut inside the jitted layer by a traced
    index, so one program a kind and shape."""
    return jax.tree.map(
        lambda a: _f32(jax.lax.dynamic_index_in_dim(a, i, 0, False)), tree)


def _shifted(a, back: int, segment_ids):
    """a [B,S,C] as seen `back` positions later: a[t - back] at t, 0 where
    t - back lies before the row or in another segment."""
    if not back:
        return a
    s = a.shape[1]
    moved = jnp.pad(a, ((0, 0), (back, 0), (0, 0)))[:, :s]
    seg = jnp.pad(segment_ids, ((0, 0), (back, 0)),
                  constant_values=-1)[:, :s]
    return jnp.where((seg == segment_ids)[..., None], moved, 0.0)


def kda(u, kp, segment_ids, heads: int, eps: float, state_dtype=F32,
        live=None, beta_factor=2.0, one_decay=False, no_conv=False):
    """u [B,S,H] -> (Mixer(u) [B,S,H], S after the last position
    [B, heads, K, V]). A segment's first position starts from a zero state.
    S is rounded to `state_dtype` after every position: float32 is the
    model; a lower type is the control a state check is sized by. live
    [B,S] bool (None: all): a position that is not live leaves S as it was
    (the padding behind a row's length). beta_factor, one_decay, no_conv:
    three wrong models, for the controls (b in (0, 1); every key channel of
    a head decays by the head's mean log decay; q, k and v skip their
    convolutions)."""
    bsz, s, _ = u.shape
    e = kp["out_kernel"].shape[0]
    d = e // heads
    qkv = u @ kp["qkv_kernel"]
    if not no_conv:
        taps = kp["conv_kernel"].shape[0]
        qkv = sum(_shifted(qkv, taps - 1 - j, segment_ids)
                  * kp["conv_kernel"][j] for j in range(taps))
    q, k, v = (t.reshape(bsz, s, heads, d)
               for t in jnp.split(jax.nn.silu(qkv), 3, axis=-1))
    q = q / jnp.sqrt(jnp.sum(q * q, -1, keepdims=True) + 1e-6) * d ** -0.5
    k = k / jnp.sqrt(jnp.sum(k * k, -1, keepdims=True) + 1e-6)
    g = -jnp.exp(kp["A_log"])[:, None] * jax.nn.softplus(
        (u @ kp["f_down"]) @ kp["f_up"] + kp["dt_bias"]).reshape(
            bsz, s, heads, d)
    if one_decay:
        g = jnp.broadcast_to(jnp.mean(g, -1, keepdims=True), g.shape)
    beta = beta_factor * jax.nn.sigmoid(u @ kp["beta_kernel"])
    first = jnp.pad(segment_ids, ((0, 0), (1, 0)),
                    constant_values=-1)[:, :s] != segment_ids
    if live is None:
        live = jnp.ones((bsz, s), bool)

    def position(state, xs):                # state [B, heads, K, V]
        q_t, k_t, v_t, g_t, b_t, first_t, live_t = xs
        old = jnp.where(first_t[:, None, None, None], 0.0, state)
        new = jnp.exp(g_t)[..., None] * old
        pseudo = b_t[..., None] * (
            v_t - jnp.einsum("bhkv,bhk->bhv", new, k_t))
        new = new + k_t[..., None] * pseudo[:, :, None, :]
        if state_dtype != F32:  # not a cast pair: XLA:TPU removes one
            info = jnp.finfo(state_dtype)
            new = jax.lax.reduce_precision(new, info.nexp, info.nmant)
        o = jnp.einsum("bhkv,bhk->bhv", new, q_t)
        return jnp.where(live_t[:, None, None, None], new, state), o

    state, o = jax.lax.scan(
        position, jnp.zeros((bsz, heads, d, d), F32),
        tuple(jnp.moveaxis(t, 1, 0)
              for t in (q, k, v, g, beta, first, live)), unroll=8)
    o = _rms_norm(jnp.moveaxis(o, 0, 1), kp["norm_scale"], eps)
    gate = jax.nn.sigmoid((u @ kp["g_down"]) @ kp["g_up"] + kp["g_bias"])
    return (o.reshape(bsz, s, e) * gate) @ kp["out_kernel"], state


def attention(u, at, segment_ids, heads: int, groups: int, d: int,
              no_gate=False):
    """The gated NoPE GQA layer, its dense scores ``QUERY_ROWS`` query rows
    at a time, one query head at a time."""
    b, s, _ = u.shape
    q = (u @ at["q_kernel"]).reshape(b, s, heads, d)
    k, v = jnp.split((u @ at["kv_kernel"]).reshape(b, s, 2 * groups, d), 2,
                     axis=2)
    rows = min(QUERY_ROWS, s)
    blocks = -(-s // rows)
    pad = blocks * rows - s
    q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    seg_q = jnp.pad(segment_ids, ((0, 0), (0, pad)), constant_values=-2)
    pos_q = jnp.arange(blocks * rows)
    pos_k = jnp.arange(s)

    def one_block(xs):          # rows query rows of every head
        qb, seg_b, pos_b = xs   # [B, rows, heads, d], [B, rows], [rows]
        allowed = (pos_b[:, None] >= pos_k[None, :])[None] & (
            seg_b[:, :, None] == segment_ids[:, None, :])

        def one_head(h):
            qh = jax.lax.dynamic_index_in_dim(qb, h, 2, False)
            kh = jax.lax.dynamic_index_in_dim(k, h // (heads // groups), 2,
                                              False)
            vh = jax.lax.dynamic_index_in_dim(v, h // (heads // groups), 2,
                                              False)
            scores = jnp.einsum("bqd,bkd->bqk", qh, kh) * d ** -0.5
            probs = jax.nn.softmax(jnp.where(allowed, scores, -jnp.inf),
                                   axis=-1)
            # a padded query row sees no key: its softmax is NaN, and the
            # row is cut off below
            return jnp.einsum("bqk,bkd->bqd", probs, vh)

        return jax.lax.map(one_head, jnp.arange(heads))     # [heads,B,rows,d]

    ctx = jax.lax.map(one_block, (
        jnp.moveaxis(q.reshape(b, blocks, rows, heads, d), 1, 0),
        jnp.moveaxis(seg_q.reshape(b, blocks, rows), 1, 0),
        pos_q.reshape(blocks, rows)))           # [blocks,heads,B,rows,d]
    ctx = jnp.transpose(ctx, (2, 0, 3, 1, 4)).reshape(
        b, blocks * rows, heads * d)[:, :s]
    if not no_gate:
        ctx = ctx * jax.nn.sigmoid(u @ at["gate_kernel"])
    return ctx @ at["out_kernel"]


def _swiglu(x, fc1, fc2):
    gate, up = jnp.split(x @ fc1, 2, axis=-1)
    return (jax.nn.silu(gate) * up) @ fc2


def router_weights(flat, router_kernel, bias, top_k: int, scale: float):
    """flat [T,H] -> [T, experts] float32: an expert's weight for each
    token, 0 where it was not chosen: the sigmoid scores of the top_k
    largest of s + bias, over their sum + 1e-20, times `scale`."""
    s = jax.nn.sigmoid(flat @ router_kernel.astype(F32))
    _, top_i = jax.lax.top_k(s + bias.astype(F32), top_k)
    w = jnp.take_along_axis(s, top_i, -1)
    w = scale * w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    return jnp.sum(jax.nn.one_hot(top_i, s.shape[-1], dtype=F32)
                   * w[..., None], axis=1)


def _experts(flat, weights, fc1_stack, fc2_stack, layer):
    """sum_e weights[:, e] * Expert_e(flat) over the stacks' experts [L,
    held, ., .]: every held expert over ALL tokens, one expert's matrices
    cut out and upcast at a time."""
    def one_expert(acc, e):
        fc1 = jax.lax.dynamic_slice(
            fc1_stack, (layer, e, 0, 0), (1, 1) + fc1_stack.shape[2:])[0, 0]
        fc2 = jax.lax.dynamic_slice(
            fc2_stack, (layer, e, 0, 0), (1, 1) + fc2_stack.shape[2:])[0, 0]
        w = jax.lax.dynamic_index_in_dim(weights, e, 1, keepdims=True)
        return acc + _swiglu(flat, _f32(fc1), _f32(fc2)) * w, None

    return jax.lax.scan(one_expert, jnp.zeros_like(flat),
                        jnp.arange(fc1_stack.shape[1], dtype=jnp.int32))[0]


@functools.partial(jax.jit, static_argnames=(
    "heads", "groups", "d", "kda_heads", "eps", "state_dtype", "beta_factor",
    "one_decay", "no_conv", "no_gate"))
def _mixer(x, mixers, i, segment_ids, live, heads, groups, d, kda_heads, eps,
           state_dtype=F32, beta_factor=2.0, one_decay=False, no_conv=False,
           no_gate=False):
    """x + Mixer(RMS(x; g)) for row i of `mixers` (a stack of one kind), and
    a KDA layer's final state (None for attention)."""
    mixer = _row(mixers, i)
    u = _rms_norm(x, mixer["ln1_scale"], eps)
    if "kda" in mixer:
        out, state = kda(u, mixer["kda"], segment_ids, kda_heads, eps,
                         state_dtype, live, beta_factor, one_decay, no_conv)
        return x + out, state
    return x + attention(u, mixer["attention"], segment_ids, heads, groups,
                         d, no_gate), None


@functools.partial(jax.jit, static_argnames=("eps", "top_k", "first",
                                             "scale"))
def _moe_terms(x, ffns, i, eps, top_k, first, scale):
    """(the held experts' term, the shared expert's) of row i of the expert
    layers on the stream x."""
    b, s, h = x.shape
    moe = ffns["moe"]
    flat = _rms_norm(x, _row(ffns["ln2_scale"], i), eps).reshape(b * s, h)
    weights = router_weights(flat, _row(moe["router_kernel"], i),
                             _row(moe["router_bias"], i), top_k, scale)
    held = moe["fc1_kernel"].shape[1]
    routed = _experts(flat, weights[:, first:first + held],
                      moe["fc1_kernel"], moe["fc2_kernel"], i)
    shared = _swiglu(flat, _row(moe["shared_fc1"], i),
                     _row(moe["shared_fc2"], i))
    return routed.reshape(b, s, h), shared.reshape(b, s, h)


@functools.partial(jax.jit, static_argnames=("eps", "size"))
def _head(x, scale, out_kernel, start, eps: float, size: int):
    x = jax.lax.dynamic_slice_in_dim(x, start, size, axis=1)
    return _rms_norm(x, _f32(scale), eps) @ _f32(out_kernel)


CONTROLS = ("beta_factor", "one_decay", "no_conv", "no_gate")


def _statics(config: dict, **control):
    """(the mixers' static arguments, the expert layers') from the file;
    `control` replaces a fact of the model by a wrong one (``CONTROLS``,
    ``tools/solar_control.py``)."""
    unknown = set(control) - set(CONTROLS)
    if unknown:
        raise TypeError(f"no control {sorted(unknown)} (have {CONTROLS})")
    mix = dict(heads=config["num_attention_heads"],
               groups=config["num_key_value_heads"], d=config["head_dim"],
               kda_heads=_kda(config)[0], eps=config["rms_norm_eps"],
               beta_factor=float(control.get("beta_factor", 2.0)),
               one_decay=bool(control.get("one_decay", False)),
               no_conv=bool(control.get("no_conv", False)),
               no_gate=bool(control.get("no_gate", False)))
    moe = dict(eps=config["rms_norm_eps"],
               top_k=config["num_experts_per_tok"], first=_share(config)[1],
               scale=float(config["routed_scaling_factor"]))
    return mix, moe


def _layers(params, config: dict, tokens, segment_ids, live=None,
            state_dtype=F32, calibrate=False, **control):
    """The stack over tokens [B,S]: (x [B,S,H] float32 before the final
    norm, the KDA layers' final states [B, heads, K, V] in their order, the
    routers' selection bias: the tree's, or with `calibrate` the one
    ``calibrated_bias`` describes, each layer routing by its own)."""
    block = params["block"]
    ffns = block["ffn"]
    mix, moe = _statics(config, **control)
    x = jnp.take(params["embedding"]["word"], tokens, axis=0).astype(F32)
    if live is None:
        live = jnp.ones(tokens.shape, bool)
    seen = {True: 0, False: 0}
    states = []
    for i, gqa in enumerate(_is_gqa(config)):
        stack = block["mixers_attn" if gqa else "mixers_kda"]
        x, state = _mixer(x, stack, jnp.int32(seen[gqa]), segment_ids, live,
                          state_dtype=state_dtype, **mix)
        seen[gqa] += 1
        if state is not None:
            states.append(state)
        k = jnp.int32(i)
        if calibrate:
            ffns = dict(ffns, moe=dict(
                ffns["moe"], router_bias=ffns["moe"]["router_bias"].at[
                    k].set(_levelled_bias(x, ffns, k, eps=moe["eps"],
                                          top_k=moe["top_k"]))))
        routed, shared = _moe_terms(x, ffns, k, **moe)
        x = x + routed + shared
    return x, states, ffns["moe"]["router_bias"]


LEVEL_STEPS = 300       # updates of a layer's bias over the calibration pass


@functools.partial(jax.jit, static_argnames=("eps", "top_k"))
def _levelled_bias(x, ffns, i, eps, top_k):
    """Row i of the expert layers' selection bias, levelled over x's
    positions (``models/nemotron_h.py``'s rule: from what equalises the
    experts' MEAN scores, LEVEL_STEPS updates b_e += rate x (mean load -
    load_e) / mean load, the rate falling from 0.02 of a score to
    nothing)."""
    flat = _rms_norm(x, _row(ffns["ln2_scale"], i), eps).reshape(
        -1, x.shape[-1])
    scores = jax.nn.sigmoid(flat @ _row(ffns["moe"]["router_kernel"], i))
    mean = jnp.mean(scores, axis=0)
    level = scores.shape[0] * top_k / scores.shape[1]

    def update(step, bias):
        _, picks = jax.lax.top_k(scores + bias, top_k)
        load = jnp.zeros_like(bias).at[picks.reshape(-1)].add(1.0)
        rate = 0.02 * (1.0 - step / LEVEL_STEPS)
        return bias + rate * jnp.clip((level - load) / level, -1.0, 1.0)

    return jax.lax.fori_loop(0, LEVEL_STEPS, update, jnp.mean(mean) - mean)


def calibrated_bias(params, model_cfg, seed: int):
    """The routers' ``e_score_correction_bias`` [layers, experts] for seeded
    weights, levelled as ``models/nemotron_h.py: calibrated_bias`` does and
    for its reason (PERF.md section 6, PR 54): the published bias is trained
    until the experts' loads are level; nothing trained a seeded model's,
    and with zeros its picks pile onto the few experts the stream's common
    direction favours. Two float32 passes of ``CALIBRATION_TOKENS``
    positions: over ids drawn from the seed, then over what the model so
    far emits for them. The reference and the program read the same bias
    from the tree."""
    heads = model_cfg.kda_heads
    config = {
        "num_hidden_layers": model_cfg.num_layers,
        "gqa_interval": model_cfg.attn_layer_period - 1,
        "gqa_layers": list(range(0, model_cfg.num_layers,
                                 model_cfg.attn_layer_period)),
        "num_attention_heads": model_cfg.num_attention_heads,
        "num_key_value_heads": model_cfg.num_query_groups,
        "head_dim": model_cfg.kv_channels,
        "linear_attn_config": {
            "num_heads": heads, "head_dim": model_cfg.ssm_head_dim,
            "short_conv_kernel_size": model_cfg.ssm_conv_kernel},
        "rms_norm_eps": model_cfg.layernorm_epsilon,
        "num_experts_per_tok": model_cfg.moe_router_topk,
        "routed_scaling_factor": model_cfg.moe_routed_scaling_factor,
        "n_routed_experts": model_cfg.moe_experts_here[1],
        "expert_share": {"first": model_cfg.moe_experts_here[0]}}
    tokens = jax.random.randint(
        jax.random.fold_in(jax.random.PRNGKey(seed % (2 ** 31)), 59),
        (1, CALIBRATION_TOKENS), 0, model_cfg.vocab_size)
    segments = jnp.zeros(tokens.shape, jnp.int32)
    with jax.default_matmul_precision("highest"):
        x, _, bias = _layers(params, config, tokens, segments,
                             calibrate=True)
        emitted = jnp.argmax(reference_head(params, config, x), axis=-1)
        return _layers(_with_bias(params, bias), config, emitted, segments,
                       calibrate=True)[2]


def reference_hidden(params, config: dict, tokens, segment_ids, **control):
    """tokens/segment_ids [B,S] -> the stack's output before the final
    norm, float32 [B,S,H]."""
    with jax.default_matmul_precision("highest"):
        return _layers(params, config, tokens, segment_ids, **control)[0]


def reference_head(params, config: dict, x, rows=None):
    """x float32 [B,S,H], rows of ``reference_hidden`` -> logits float32
    [B,S,V] (or [B,size,V] from `rows` = (start, size)): the final norm and
    the untied head."""
    start, size = rows if rows is not None else (0, x.shape[1])
    with jax.default_matmul_precision("highest"):
        return _head(x, params["final_ln_scale"], params["output"],
                     jnp.int32(start), eps=config["rms_norm_eps"], size=size)


def reference_logits(params, config: dict, tokens, segment_ids, position_ids,
                     rows=None, **control):
    """tokens/segment_ids/position_ids [B,S] -> logits float32 over the
    vocabulary slice, [B,S,V], or [B,size,V] for the positions
    start..start+size when `rows` = (start, size). position_ids are not
    read: the model has no positional term. A row may hold several
    sequences as segments: attention, the convolutions and the recurrence
    all stay inside one."""
    del position_ids
    return reference_head(
        params, config,
        reference_hidden(params, config, tokens, segment_ids, **control),
        rows)


def reference_state(params, config: dict, tokens, lengths=None,
                    state_dtype: str = "float32", **control):
    """tokens [B,S], one sequence a row from position 0, row b's first
    lengths[b] positions real (None: all S) -> what each KDA layer's
    recurrence holds after the row's last real position, in the program's
    layout, float32 [layers, B, K, E] (``h[c, head * V + j] = S[head][c,
    j]``): what a slot of the engine's state pool should hold once it has
    read those tokens. With a `state_dtype` below float32 the recurrence
    rounds S to it at every position and nothing else changes."""
    b, s = tokens.shape
    live = None if lengths is None else \
        jnp.arange(s)[None, :] < jnp.asarray(lengths)[:, None]
    with jax.default_matmul_precision("highest"):
        states = _layers(params, config, tokens,
                         jnp.zeros(tokens.shape, jnp.int32), live,
                         DTYPES[state_dtype], **control)[1]
    return jnp.stack([jnp.swapaxes(st, 1, 2).reshape(b, st.shape[2], -1)
                      for st in states])


def reference_layer_terms(params, config: dict, x, layer: int):
    """x [B,S,H] float32, the stream INTO layer `layer`'s experts (behind
    its mixer) -> (the held experts' term, the shared expert's term): the
    layer's second half is x + their sum. For the test that adds the shares
    of a deployment up."""
    _, moe = _statics(config)
    with jax.default_matmul_precision("highest"):
        return _moe_terms(x, params["block"]["ffn"], jnp.int32(layer), **moe)


def reference_loss(params, config: dict, batch) -> float:
    """Mean cross entropy over the positions whose loss_mask is 1, for one
    micro-batch of ``generators/train_packed.py`` rows (what a training
    cell of this model would be held to; none exists)."""
    lg = reference_logits(params, config, jnp.asarray(batch["tokens"]),
                          jnp.asarray(batch["segment_ids"]), None)
    logz = jax.nn.logsumexp(lg, axis=-1)
    picked = jnp.take_along_axis(
        lg, jnp.asarray(batch["labels"])[..., None], axis=-1)[..., 0]
    mask = jnp.asarray(batch["loss_mask"], F32)
    return float(jnp.sum((logz - picked) * mask) / jnp.maximum(mask.sum(), 1))
