"""``"model": "nemotron_h"``: NVIDIA's Nemotron-H hybrids (HF model type
``nemotron_h``) as their ``config.json`` publishes them (the configuration
file keeps the source's own keys), built as the program's ``models/gpt.py``
model, with the plain reference and the counts. What a model module gives
the runners is listed in ``models/gpt_dense.py``; this one adds
``state_bytes_per_slot`` and ``reference_state`` (``models/jamba.py``'s pair,
for a matrix state a head), ``reference_hidden`` / ``reference_head`` and
``reference_layer_terms`` (``models/granite_moe_hybrid.py``'s).

The model, with ``RMS(x; g) = x / sqrt(mean(x^2) + norm_eps) * g``:

- ``x0 = E[token]`` (no multiplier); layer i is ONE sublayer, its kind the
  i-th letter of ``hybrid_override_pattern`` (``M``, ``*`` or ``E``; a
  ``-``, a dense feed-forward alone, stands in no published position and is
  refused by name here, though the program's stack runs it)::

      x' = x + Sub_i(RMS(x; g_i))

  and ``logits = RMS(x_L; g_f) W_head``, ``W_head`` untied from E;
- ``*``, attention: ``num_attention_heads`` query heads over
  ``num_key_value_heads`` key/value heads of ``head_dim`` (heads x head_dim
  is not the hidden size), no bias, NO positional term (``nemotron_h``'s
  attention applies no rotary table), a causal softmax of ``q.k /
  sqrt(head_dim)``, within a segment, one query head at a time;
- ``M``, the Mamba-2 mixer (E = ``mamba_num_heads`` x ``mamba_head_dim``,
  which is NOT ``expand`` x hidden; N = ``ssm_state_size``; G = ``n_groups``
  groups of heads / G heads)::

      [z | xBC | dt] = u W_in          # E | E + 2GN | heads, no bias
      xBC = silu(conv_k(xBC) + b)      # causal, depthwise, k SHIFTED PRODUCTS
      [x | B | C] = xBC                # x [heads, P]; B, C [G, N]
      D_h = softplus(dt_h + dt_bias_h) ;  a_h = -exp(A_log_h)
      S_t[h] = exp(D_h a_h) S_{t-1}[h] + D_h x_t[h] (x) B_t[h // (heads/G)]
      y_t[h] = S_t[h] C_t[h // (heads/G)] + D_h x_t[h]
      out = RMS_group(y * silu(z); g_n) W_out   # over each group's E/G columns

  as a SEQUENTIAL ``lax.scan`` over the positions, one at a time, from a
  zero state at every segment's first position (no chunks, no associative
  scan, no kernel, no cache);
- ``E``, the experts: ``l = float32(u) W_r`` (``n_routed_experts`` outputs
  as PUBLISHED, no bias); ``s = sigmoid(l)``; the ``num_experts_per_tok``
  largest of ``s + b`` (``e_score_correction_bias``; ``n_group`` 1,
  ``topk_group`` 1: no group limit); ``w = s`` of those, ``w <- routed_
  scaling_factor * w / (sum w + 1e-20)``; ``MoE(u) = sum_e w_e W2_e relu(u
  W1_e)^2`` (width ``moe_intermediate_size``, no gate, no bias) ``+ W2_s
  relu(u W1_s)^2`` (the shared expert, always on); ONE HELD EXPERT AT A TIME
  over all tokens, with weight 0 where it was not chosen.

THE SHARE. A configuration file whose ``n_routed_experts`` is under
``published.n_routed_experts`` describes one chip's share of an
expert-parallel deployment: the tree holds the experts ``expert_share.first
.. + n_routed_experts`` of each layer, the router stays as wide as published,
and what the absent experts would have added is left out, here as in the
program. ``reference_layer_terms`` gives an expert layer's parts one by one,
for the test that adds the shares up. ``vocab_size`` rows of
``published.vocab_size`` are a smaller vocabulary.

It reads the program's own parameter tree (``block``: ``mixers_ssm``,
``mixers_attn``, ``ffn``, each the layers of one kind in layer order), a
matrix group at a time upcast to float32, so that a pass fits beside the
bf16 weights, matmuls at precision "highest", and shares no code with
``megatronapp_tpu/transformer/``.

Departures from the published model:
- layout: the state is computed as the published ``S [heads, P, N]``; the
  program keeps it as ``h [N, E]`` (``h[n, head * P + p] = S[head][p, n]``),
  and ``reference_state`` hands it over in that layout; ``k_proj`` and
  ``v_proj`` are one ``kv_kernel`` ``[k | v]``; the taps ``conv_kernel [k, E
  + 2GN]`` where the published depthwise weight is ``[E + 2GN, 1, k]``;
- the program's sigmoid router divides the picks' weights by their sum +
  1e-6 (``transformer/moe.py``: ``lfm2_moe``'s constant), this reference by
  the sum + 1e-20 as ``nemotron_h`` does: six sigmoids add up to ~3, so a
  weight differs by 3e-7 of itself, a hundredth of what bf16 holds;
- the published ``time_step_limit`` (0, inf) clamps nothing and is left out;
  ``rope_theta`` / ``partial_rotary_factor`` are read by no layer; the
  residual stream is the compute type's (``residual_in_fp32`` false) in the
  program and float32 here.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from perfbench import manifest

# Weights come from the seed the same way for every models/gpt.py model: the
# program's own initialiser, one jitted program on the device. The head is
# untied and nothing multiplies the embedding, so a seeded model does not
# return its input token (granite_moe_hybrid.EMBEDDING_INIT_SHRINK says what
# that looks like; the cell's notes carry the share of emitted tokens that
# are the reference's argmax, and tools/nemotron_control.py that the share
# falls under a wrong model). What a seeded model of this family does do is
# pile its picks onto few experts: init_params levels the routers' selection
# bias (calibrated_bias).
_init_params = manifest.load_module("models", "gpt_dense").init_params
# Positions of the pass that calibrates the routers' selection bias.
CALIBRATION_TOKENS = 2048

F32 = jnp.float32
DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}
MAMBA, ATTN, MOE = "M", "*", "E"

REHEARSAL = {"num_hidden_layers": 7, "hybrid_override_pattern": "MEMEM*E",
             "hidden_size": 96, "num_attention_heads": 6,
             "num_key_value_heads": 2, "head_dim": 16,
             "intermediate_size": 40, "moe_intermediate_size": 40,
             "moe_shared_expert_intermediate_size": 72,
             "n_routed_experts": 4, "num_experts_per_tok": 3,
             "mamba_num_heads": 4, "mamba_head_dim": 16, "n_groups": 2,
             "ssm_state_size": 16, "chunk_size": 16, "vocab_size": 512,
             "max_position_embeddings": 512,
             "published": {"num_hidden_layers": 14, "n_routed_experts": 8,
                           "vocab_size": 1024,
                           "hybrid_override_pattern": "MEMEM*EMEMEM*E"},
             "expert_share": {"first": 0}}


def _inner(config: dict) -> int:
    """E: the Mamba-2 mixer's inner width (heads x head columns)."""
    return config["mamba_num_heads"] * config["mamba_head_dim"]


def _conv_channels(config: dict) -> int:
    return _inner(config) + 2 * config["n_groups"] * config["ssm_state_size"]


def _pattern(config: dict) -> str:
    """The letters of the layers that are run: the file's pattern, or its
    first ``num_layers`` where this repository's tools cut a copy of a file
    (``tools/compile_rehearsal_state.py``)."""
    pattern = config["hybrid_override_pattern"]
    if len(pattern) != config["num_hidden_layers"]:
        raise SystemExit(
            f"perfbench: hybrid_override_pattern {pattern!r} names "
            f"{len(pattern)} layers, num_hidden_layers "
            f"{config['num_hidden_layers']}")
    if set(pattern) - {MAMBA, ATTN, MOE}:
        raise SystemExit(
            "perfbench: models/nemotron_h.py builds the published kinds of "
            f"layer, {MAMBA!r}, {ATTN!r} and {MOE!r}; the pattern "
            f"{pattern!r} asks for {sorted(set(pattern) - {MAMBA, ATTN, MOE})}"
            " ('-' is a dense feed-forward alone, which no published "
            "position holds)")
    return pattern[:config.get("num_layers", len(pattern))]


def _published(config: dict, key: str):
    """The source's value of a key this file may have reduced."""
    return config.get("published", {}).get(key, config[key])


def _share(config: dict):
    """(published experts, first held, held here)."""
    return (_published(config, "n_routed_experts"),
            config.get("expert_share", {}).get("first", 0),
            config["n_routed_experts"])


def kv_bytes_per_token(config: dict, dtype: str) -> int:
    """Keys and values of every key/value head of the ATTENTION layers, in
    `dtype`: no other kind of layer caches a token."""
    return (2 * _pattern(config).count(ATTN) * config["num_key_value_heads"]
            * config["head_dim"] * jnp.dtype(DTYPES[dtype]).itemsize)


def state_bytes_per_slot(config: dict, dtype: str) -> int:
    """What one sequence's recurrent state takes, whatever its length: for
    every Mamba-2 layer the heads' matrix states, ``ssm_state_size x E``
    elements in `dtype` (the configuration's ``serve.state_dtype``), and the
    convolution's last ``conv_kernel - 1`` inputs over x, B and C in the type
    the model computes in (``serve.params_dtype``)."""
    tail = DTYPES[config.get("serve", {}).get("params_dtype", "bfloat16")]
    return _pattern(config).count(MAMBA) * (
        config["ssm_state_size"] * _inner(config)
        * jnp.dtype(DTYPES[dtype]).itemsize
        + (config["conv_kernel"] - 1) * _conv_channels(config)
        * jnp.dtype(tail).itemsize)


def ssd_flops_per_token(config: dict) -> float:
    """Matmul operations a position of ONE Mamba-2 layer's chunked scan
    costs (forward): within its chunk of Q positions Q scores of N a GROUP
    and Q x P a head, N x E into the chunk's state and N x E out of the one
    that came in; 2 operations a multiply-add."""
    q, n, e = (config["chunk_size"], config["ssm_state_size"],
               _inner(config))
    return 2.0 * (q * n * config["n_groups"] + q * e + 2 * n * e)


def params_per_token(config: dict) -> float:
    """Matrix parameters a token meets: its layers' mixers, routers and
    shared experts, its top-k's share of the HELD experts (top_k x held /
    published of them on average), the untied head once (the embedding is
    gathered by row)."""
    h, d = config["hidden_size"], config["head_dim"]
    pattern = _pattern(config)
    e = _inner(config)
    experts, _, held = _share(config)
    mamba = (h * (e + _conv_channels(config) + config["mamba_num_heads"])
             + e * h)
    attn = (2 * h * config["num_attention_heads"] * d
            + 2 * h * config["num_key_value_heads"] * d)
    moe = (h * experts
           + 2 * h * config["moe_shared_expert_intermediate_size"]
           + config["num_experts_per_tok"] * held / experts
           * 2 * h * config["moe_intermediate_size"])
    return (pattern.count(MAMBA) * mamba + pattern.count(ATTN) * attn
            + pattern.count(MOE) * moe + h * config["vocab_size"])


def flops_per_token(config: dict, seq_len: int) -> float:
    """Forward and backward matmul operations per token (3 x 2 x the
    parameters a token meets, plus causal attention over `seq_len` in the
    attention layers and 3 x the chunked scans' products), the yardstick an
    ``mfu`` reader would use; no cell of this model trains."""
    pattern = _pattern(config)
    scores = (pattern.count(ATTN) * config["num_attention_heads"]
              * 2 * config["head_dim"] * seq_len / 2)
    return (6.0 * (params_per_token(config) + scores)
            + 3.0 * pattern.count(MAMBA) * ssd_flops_per_token(config))


def init_params(model_cfg, seed: int, device=None):
    """``gpt_dense.init_params`` (the program's own initialiser, one jitted
    program on the device), then the routers' selection bias calibrated
    (``calibrated_bias``)."""
    params = _init_params(model_cfg, seed, device)
    return _with_bias(params, calibrated_bias(params, model_cfg, seed))


def _with_bias(params, bias):
    """`params` with the routers' selection bias [E layers, experts]."""
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
    if (config["mlp_hidden_act"] != "relu2"
            or config["mamba_hidden_act"] != "silu"
            or config["attention_bias"] or config["mlp_bias"]
            or config["use_bias"] or config["mamba_proj_bias"]
            or not config["use_conv_bias"] or config["tie_word_embeddings"]
            or not config["norm_topk_prob"] or config["n_shared_experts"] != 1
            or config["n_group"] != 1 or config["topk_group"] != 1):
        raise SystemExit("perfbench: models/nemotron_h.py builds the "
                         "published form only (relu^2 experts beside one "
                         "shared expert, silu in the mixer, an untied head, "
                         "no bias but the convolution's, a router whose "
                         "picks know no group limit)")
    experts, first, held = _share(config)
    try:
        return TransformerConfig(
            num_layers=len(_pattern(config)),
            layer_pattern=_pattern(config),
            hidden_size=config["hidden_size"],
            num_attention_heads=config["num_attention_heads"],
            num_query_groups=config["num_key_value_heads"],
            kv_channels=config["head_dim"],
            ffn_hidden_size=config["intermediate_size"],
            vocab_size=config["vocab_size"],
            vocab_slice_of=_published(config, "vocab_size"),
            max_position_embeddings=config["max_position_embeddings"],
            normalization=NormKind.rmsnorm,
            layernorm_epsilon=config["norm_eps"],
            activation=ActivationKind.squared_relu, add_bias_linear=False,
            position_embedding=PositionEmbeddingKind.none,
            untie_embeddings_and_output_weights=True,
            scaled_init_layers=_published(config, "num_hidden_layers"),
            ssm_state_dim=config["ssm_state_size"],
            ssm_conv_kernel=config["conv_kernel"],
            ssm_heads=config["mamba_num_heads"],
            ssm_head_dim=config["mamba_head_dim"],
            ssm_groups=config["n_groups"],
            ssm_chunk_size=config["chunk_size"],
            num_moe_experts=experts,
            moe_experts_held=(first, held) if held < experts else None,
            moe_router_topk=config["num_experts_per_tok"],
            moe_ffn_hidden_size=config["moe_intermediate_size"],
            moe_shared_expert_intermediate_size=config[
                "moe_shared_expert_intermediate_size"],
            moe_router_score="sigmoid", moe_router_selection_bias=True,
            moe_router_norm_topk_prob=True,
            moe_routed_scaling_factor=float(config["routed_scaling_factor"]),
            params_dtype=DTYPES[params_dtype], **extra)
    except TypeError as e:
        raise SystemExit(
            "perfbench: this program's TransformerConfig lacks a field "
            f"nemotron_h needs ({e})") from None


# ---- the plain reference ---------------------------------------------------

def _rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * weight


def _f32(a):
    """A leaf of the tree as the reference reads it: float32.
    ``tools/nemotron_control.py`` rounds the matrices here."""
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


def mamba2(u, mp, segment_ids, heads: int, n: int, groups: int, eps: float,
           state_dtype=F32, live=None, one_group=False, norm_all=False):
    """u [B,S,H] -> (Mixer(u) [B,S,H], S after the last position
    [B, heads, P, N]). A segment's first position starts from a zero state.
    S is rounded to `state_dtype` after every position: float32 is the
    model; a lower type is the control a state check is sized by. live
    [B,S] bool (None: all): a position that is not live leaves S as it was
    (the padding behind a row's length). one_group, norm_all: two wrong
    models, for the controls (every head reads group 0's B and C; the gated
    norm runs over all E columns)."""
    bsz, s, _ = u.shape
    e = mp["out_kernel"].shape[0]
    p = e // heads
    proj = u @ mp["in_kernel"]
    z, xbc, dt = (proj[..., :e], proj[..., e:2 * e + 2 * groups * n],
                  proj[..., 2 * e + 2 * groups * n:])
    k = mp["conv_kernel"].shape[0]
    conv = sum(_shifted(xbc, k - 1 - j, segment_ids) * mp["conv_kernel"][j]
               for j in range(k))
    xbc = jax.nn.silu(conv + mp["conv_bias"])
    x = xbc[..., :e].reshape(bsz, s, heads, p)
    # head h reads group h // (heads / G): B and C a head, [B,S,heads,N]
    b, c = (t.reshape(bsz, s, groups, n) for t in (
        xbc[..., e:e + groups * n], xbc[..., e + groups * n:]))
    if one_group:
        b, c = b[:, :, :1], c[:, :, :1]
    b, c = (jnp.repeat(t, heads // t.shape[2], axis=2) for t in (b, c))
    delta = jax.nn.softplus(dt + mp["dt_bias"])             # [B,S,heads]
    a = -jnp.exp(mp["A_log"])                               # [heads]
    first = jnp.pad(segment_ids, ((0, 0), (1, 0)),
                    constant_values=-1)[:, :s] != segment_ids
    if live is None:
        live = jnp.ones((bsz, s), bool)

    def position(state, xs):                # state [B, heads, P, N]
        d_t, x_t, b_t, c_t, first_t, live_t = xs
        old = jnp.where(first_t[:, None, None, None], 0.0, state)
        new = jnp.exp(d_t * a)[:, :, None, None] * old \
            + (d_t[:, :, None] * x_t)[..., None] * b_t[:, :, None, :]
        if state_dtype != F32:  # not a cast pair: XLA:TPU removes one
            info = jnp.finfo(state_dtype)
            new = jax.lax.reduce_precision(new, info.nexp, info.nmant)
        y = jnp.einsum("bhpn,bhn->bhp", new, c_t) + mp["D"][:, None] * x_t
        return jnp.where(live_t[:, None, None, None], new, state), y

    # unroll: eight positions a loop iteration, still one after the other
    state, y = jax.lax.scan(
        position, jnp.zeros((bsz, heads, p, n), F32),
        tuple(jnp.moveaxis(t, 1, 0)
              for t in (delta, x, b, c, first, live)), unroll=8)
    y = jnp.moveaxis(y, 0, 1).reshape(bsz, s, e) * jax.nn.silu(z)
    norm_groups = 1 if norm_all else groups
    y = _rms_norm(y.reshape(bsz, s, norm_groups, -1),
                  mp["norm_scale"].reshape(norm_groups, -1), eps)
    return y.reshape(bsz, s, e) @ mp["out_kernel"], state


def attention(u, at, segment_ids, heads: int, groups: int, d: int):
    b, s, _ = u.shape
    q = (u @ at["q_kernel"]).reshape(b, s, heads, d)
    k, v = jnp.split((u @ at["kv_kernel"]).reshape(b, s, 2 * groups, d), 2,
                     axis=2)
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


def _relu2(x, fc1, fc2, power: int = 2):
    return jax.nn.relu(x @ fc1) ** power @ fc2


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


def _experts(flat, weights, fc1_stack, fc2_stack, layer, power):
    """sum_e weights[:, e] * W2_e relu(flat W1_e)^2 over the stacks' experts
    [L, held, ., .]: every held expert over ALL tokens, one expert's two
    matrices cut out and upcast at a time."""
    def one_expert(acc, e):
        fc1 = jax.lax.dynamic_slice(
            fc1_stack, (layer, e, 0, 0), (1, 1) + fc1_stack.shape[2:])[0, 0]
        fc2 = jax.lax.dynamic_slice(
            fc2_stack, (layer, e, 0, 0), (1, 1) + fc2_stack.shape[2:])[0, 0]
        w = jax.lax.dynamic_index_in_dim(weights, e, 1, keepdims=True)
        return acc + _relu2(flat, _f32(fc1), _f32(fc2), power) * w, None

    return jax.lax.scan(one_expert, jnp.zeros_like(flat),
                        jnp.arange(fc1_stack.shape[1], dtype=jnp.int32))[0]


@functools.partial(jax.jit, static_argnames=(
    "heads", "groups", "d", "ssm_heads", "n", "ssm_groups", "eps",
    "state_dtype", "one_group", "norm_all"))
def _mixer(x, mixers, i, segment_ids, live, heads, groups, d, ssm_heads, n,
           ssm_groups, eps, state_dtype=F32, one_group=False,
           norm_all=False):
    """x + Mixer(RMS(x; g)) for row i of `mixers` (a stack of one kind), and
    a Mamba-2 layer's final state (None for attention)."""
    mixer = _row(mixers, i)
    u = _rms_norm(x, mixer["ln1_scale"], eps)
    if "ssm" in mixer:
        out, state = mamba2(u, mixer["ssm"], segment_ids, ssm_heads, n,
                            ssm_groups, eps, state_dtype, live, one_group,
                            norm_all)
        return x + out, state
    return x + attention(u, mixer["attention"], segment_ids, heads, groups,
                         d), None


@functools.partial(jax.jit, static_argnames=(
    "eps", "top_k", "first", "scale", "power"))
def _moe_terms(x, ffns, i, eps, top_k, first, scale, power=2):
    """(the held experts' term, the shared expert's) of row i of the expert
    layers on the stream x."""
    b, s, h = x.shape
    moe = ffns["moe"]
    flat = _rms_norm(x, _row(ffns["ln2_scale"], i), eps).reshape(b * s, h)
    weights = router_weights(flat, _row(moe["router_kernel"], i),
                             _row(moe["router_bias"], i), top_k, scale)
    held = moe["fc1_kernel"].shape[1]
    routed = _experts(flat, weights[:, first:first + held],
                      moe["fc1_kernel"], moe["fc2_kernel"], i, power)
    shared = _relu2(flat, _row(moe["shared_fc1"], i),
                    _row(moe["shared_fc2"], i), power)
    return routed.reshape(b, s, h), shared.reshape(b, s, h)


@functools.partial(jax.jit, static_argnames=("eps", "size"))
def _head(x, scale, out_kernel, start, eps: float, size: int):
    x = jax.lax.dynamic_slice_in_dim(x, start, size, axis=1)
    return _rms_norm(x, _f32(scale), eps) @ _f32(out_kernel)


def _statics(config: dict, **control):
    """(the mixers' static arguments, the expert layers') from the file;
    `control` replaces a fact of the model by a wrong one (the controls of
    ``tools/nemotron_control.py``): ``one_group``, ``norm_all``, ``power``
    (1: relu for relu^2), ``routed_scaling_factor``."""
    mix = dict(heads=config["num_attention_heads"],
               groups=config["num_key_value_heads"], d=config["head_dim"],
               ssm_heads=config["mamba_num_heads"],
               n=config["ssm_state_size"], ssm_groups=config["n_groups"],
               eps=config["norm_eps"],
               one_group=bool(control.get("one_group", False)),
               norm_all=bool(control.get("norm_all", False)))
    moe = dict(eps=config["norm_eps"], top_k=config["num_experts_per_tok"],
               first=_share(config)[1], power=int(control.get("power", 2)),
               scale=float(control.get("routed_scaling_factor",
                                       config["routed_scaling_factor"])))
    return mix, moe


def _layers(params, config: dict, tokens, segment_ids, live=None,
            state_dtype=F32, calibrate=False, **control):
    """The stack over tokens [B,S]: (x [B,S,H] float32 before the final
    norm, the Mamba-2 layers' final states [B, heads, P, N] in their
    order, the routers' selection bias: the tree's, or with `calibrate`
    the one ``calibrated_bias`` describes, each layer routing by its
    own)."""
    block = params["block"]
    ffns = block.get("ffn")
    mix, moe = _statics(config, **control)
    x = jnp.take(params["embedding"]["word"], tokens, axis=0).astype(F32)
    if live is None:
        live = jnp.ones(tokens.shape, bool)
    seen = {MAMBA: 0, ATTN: 0, MOE: 0}
    states = []
    for kind in _pattern(config):
        k = jnp.int32(seen[kind])
        seen[kind] += 1
        if kind == MOE:
            if calibrate:
                ffns = dict(ffns, moe=dict(
                    ffns["moe"], router_bias=ffns["moe"]["router_bias"].at[
                        k].set(_levelled_bias(x, ffns, k, eps=moe["eps"],
                                              top_k=moe["top_k"]))))
            routed, shared = _moe_terms(x, ffns, k, **moe)
            x = x + routed + shared
            continue
        stack = block["mixers_ssm" if kind == MAMBA else "mixers_attn"]
        x, state = _mixer(x, stack, k, segment_ids, live,
                          state_dtype=state_dtype, **mix)
        if state is not None:
            states.append(state)
    return x, states, ffns and ffns["moe"]["router_bias"]


LEVEL_STEPS = 300       # updates of a layer's bias over the calibration pass


@functools.partial(jax.jit, static_argnames=("eps", "top_k"))
def _levelled_bias(x, ffns, i, eps, top_k):
    """Row i of the expert layers' selection bias, levelled over x's
    positions: from what equalises the experts' MEAN scores, LEVEL_STEPS
    updates b_e += rate x (mean load - load_e) / mean load (the published
    rule moves by the sign alone, over a training run; the rate falls from
    0.02 of a score to nothing), load_e the positions whose top_k of s + b
    hold expert e."""
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
    """The routers' ``e_score_correction_bias`` [E layers, experts] for
    seeded weights. The published bias is trained (by the sign of each
    expert's load against the mean, no gradient) until the experts' loads
    are level; nothing trained a seeded model's, and with zeros its picks
    pile onto the few experts that the stream's common direction favours
    (on the chip: 73-79% of the held experts touched by 192 rows x 6 picks
    a round, the busiest with ten times the mean, and `serve_tok_s` spread
    by 1.2% over six seeds with that share; PERF.md section 6, PR 54). So
    the bias is levelled (``_levelled_bias``), layer by layer, each layer
    routing by the bias it has just been given, in two float32 passes of
    ``CALIBRATION_TOKENS`` positions: over ids drawn from the seed, and
    then over what the model so far emits for them (the reference's argmax
    a position: a seeded model under greedy sampling emits few of its
    tokens often, and a decode round's rows are of those). The reference
    and the program read the same bias from the tree."""
    block = params["block"]
    if MOE not in model_cfg.layer_pattern:
        return block["ffn"]["moe"]["router_bias"]
    config = {
        "num_hidden_layers": model_cfg.num_layers,
        "hybrid_override_pattern": model_cfg.layer_pattern,
        "num_attention_heads": model_cfg.num_attention_heads,
        "num_key_value_heads": model_cfg.num_query_groups,
        "head_dim": model_cfg.kv_channels,
        "mamba_num_heads": model_cfg.ssm_heads,
        "ssm_state_size": model_cfg.ssm_state_dim,
        "n_groups": model_cfg.ssm_groups,
        "norm_eps": model_cfg.layernorm_epsilon,
        "num_experts_per_tok": model_cfg.moe_router_topk,
        "routed_scaling_factor": model_cfg.moe_routed_scaling_factor,
        "n_routed_experts": model_cfg.moe_experts_here[1],
        "expert_share": {"first": model_cfg.moe_experts_here[0]}}
    tokens = jax.random.randint(
        jax.random.fold_in(jax.random.PRNGKey(seed % (2 ** 31)), 54),
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
                     jnp.int32(start), eps=config["norm_eps"], size=size)


def reference_logits(params, config: dict, tokens, segment_ids, position_ids,
                     rows=None, **control):
    """tokens/segment_ids/position_ids [B,S] -> logits float32 over the
    vocabulary slice, [B,S,V], or [B,size,V] for the positions
    start..start+size when `rows` = (start, size). position_ids are not
    read: the model has no positional term. A row may hold several
    sequences as segments: attention, the convolution and the recurrence
    all stay inside one. `config` is a configuration file's top level;
    `params` the program's tree, which holds the share of the experts the
    file states."""
    del position_ids
    return reference_head(
        params, config,
        reference_hidden(params, config, tokens, segment_ids, **control),
        rows)


def reference_state(params, config: dict, tokens, lengths=None,
                    state_dtype: str = "float32"):
    """tokens [B,S], one sequence a row from position 0, row b's first
    lengths[b] positions real (None: all S) -> what each Mamba-2 layer's
    recurrence holds after the row's last real position, in the program's
    layout, float32 [layers, B, N, E] (``h[n, head * P + p] = S[head][p,
    n]``): what a slot of the engine's state pool should hold once it has
    read those tokens. With a `state_dtype` below float32 the recurrence
    rounds S to it at every position and nothing else changes."""
    b, s = tokens.shape
    live = None if lengths is None else \
        jnp.arange(s)[None, :] < jnp.asarray(lengths)[:, None]
    with jax.default_matmul_precision("highest"):
        states = _layers(params, config, tokens,
                         jnp.zeros(tokens.shape, jnp.int32), live,
                         DTYPES[state_dtype])[1]
    return jnp.stack([jnp.transpose(st, (0, 3, 1, 2)).reshape(
        b, st.shape[3], -1) for st in states])


def reference_layer_terms(params, config: dict, x, layer: int, **control):
    """x [B,S,H] float32, the stream INTO expert layer `layer` (its index
    among the ``E`` layers) -> (the held experts' term, the shared expert's
    term): the layer is x + their sum. For the test that adds the shares of
    a deployment up."""
    _, moe = _statics(config, **control)
    with jax.default_matmul_precision("highest"):
        return _moe_terms(x, params["block"]["ffn"], jnp.int32(layer), **moe)


def reference_loss(params, config: dict, batch) -> float:
    """Mean cross entropy over the positions whose loss_mask is 1, for one
    micro-batch of ``generators/train_packed.py`` rows (what a training
    cell of this model would be held to; none exists: the program's
    state-space layers refuse packed segments)."""
    lg = reference_logits(params, config, jnp.asarray(batch["tokens"]),
                          jnp.asarray(batch["segment_ids"]), None)
    logz = jax.nn.logsumexp(lg, axis=-1)
    picked = jnp.take_along_axis(
        lg, jnp.asarray(batch["labels"])[..., None], axis=-1)[..., 0]
    mask = jnp.asarray(batch["loss_mask"], F32)
    return float(jnp.sum((logz - picked) * mask) / jnp.maximum(mask.sum(), 1))
