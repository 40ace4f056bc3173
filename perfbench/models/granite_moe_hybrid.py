"""``"model": "granite_moe_hybrid"``: IBM's Granite 4.0-H hybrids (HF model
type ``granitemoehybrid``) as their ``config.json`` publishes them (the
configuration file keeps the source's own keys), built as the program's
``models/gpt.py`` model, with the plain reference and the counts. What a
model module gives the runners is listed in ``models/gpt_dense.py``; this one
adds ``state_bytes_per_slot`` and ``reference_state`` (``models/jamba.py``'s
pair, for a matrix state a head) and ``reference_hidden`` /
``reference_head``.

The model, with ``RMS(x; g) = x / sqrt(mean(x^2) + rms_norm_eps) * g``:

- ``x0 = embedding_multiplier * E[token]``; layer i (``layer_types[i]``
  ``"attention"`` or ``"mamba"``)::

      x' = x  + residual_multiplier * Mixer_i(RMS(x;  g1))
      u  =                            RMS(x'; g2)
      x" = x' + residual_multiplier * (MoE(u) + Shared(u))

  and ``logits = RMS(x_L; g_f) E^T / logits_scaling`` (a tied head);
- attention: ``num_attention_heads`` query heads over
  ``num_key_value_heads`` key/value heads, no bias, NO positional term
  (``position_embedding_type`` ``nope``), a causal softmax of ``q.k *
  attention_multiplier`` (which is not 1 / sqrt(head size)), within a
  segment, one query head at a time;
- the Mamba-2 mixer (E = ``mamba_expand`` x hidden = ``mamba_n_heads`` x
  ``mamba_d_head``; N = ``mamba_d_state``; one group)::

      [z | xBC | dt] = u W_in          # E | E + 2N | heads, no bias
      xBC = silu(conv_k(xBC) + b)      # causal, depthwise, k SHIFTED PRODUCTS
      [x | B | C] = xBC                # x [heads, P]; B, C [N] for every head
      D_h = softplus(dt_h + dt_bias_h) ;  a_h = -exp(A_log_h)
      S_t[h] = exp(D_h a_h) S_{t-1}[h] + D_h x_t[h] (x) B_t     # [P, N]
      y_t[h] = S_t[h] C_t + D_h x_t[h]
      out = RMS(y * silu(z); g_n) W_out    # the norm over all E columns

  as a SEQUENTIAL ``lax.scan`` over the positions, one at a time, from a
  zero state at every segment's first position (no chunks, no associative
  scan, no kernel, no cache); a term of the convolution counts as 0 where
  its position lies before the row's start or in another segment;
- the experts: ``l = float32(u) W_r`` (``num_local_experts`` outputs as
  PUBLISHED, no bias); the ``num_experts_per_tok`` largest; ``w = softmax``
  over those alone; ``MoE(u) = sum_e w_e W2_e (silu(g_e) * v_e)``, ``[g_e |
  v_e] = u W1_e`` of width ``intermediate_size``; ONE HELD EXPERT AT A TIME
  over all tokens, with weight 0 where it was not chosen; ``Shared`` the
  same SwiGLU at ``shared_intermediate_size`` on the same u, always on.

THE SHARE. A configuration file whose ``num_local_experts`` is under
``published.num_local_experts`` describes one chip's share of an
expert-parallel deployment: the tree holds the experts ``expert_share.first
.. + num_local_experts`` of each layer, the router stays as wide as
published, and what the absent experts would have added is left out, here as
in the program. ``reference_layer_terms`` gives a layer's parts one by one,
for the test that adds the shares up. ``vocab_size`` rows of
``published.vocab_size`` are a smaller vocabulary.

It reads the program's own parameter tree (``block``: ``mixers_ssm``,
``mixers_attn``, ``ffn``), a matrix group at a time upcast to float32, so
that a pass fits beside the bf16 weights, matmuls at precision "highest",
and shares no code with ``megatronapp_tpu/transformer/``.

Departures from the published model, all of layout, none of mathematics:
- the state is computed as the published ``S [heads, P, N]``; the program
  keeps it as ``h [N, E]`` (``h[n, head * P + p] = S[head][p, n]``), and
  ``reference_state`` hands it over in that layout;
- gate and up projections are one ``fc1`` matrix ``[gate | up]``, ``k_proj``
  and ``v_proj`` one ``kv_kernel`` ``[k | v]``; the taps ``conv_kernel [k,
  E + 2N]`` where the published depthwise weight is ``[E + 2N, 1, k]`` (row
  j multiplies the input ``k - 1 - j`` positions back in both);
- the published code's ``time_step_limit`` (0, inf) clamps nothing and is
  left out; ``rope_theta`` / ``rope_scaling`` are read by no layer
  (``nope``); ``normalization_function`` is ``rmsnorm`` only.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from perfbench import manifest

# Weights come from the seed the same way for every models/gpt.py model ...
_init_params = manifest.load_module("models", "gpt_dense").init_params
# ... but for the embedding's scale. Rows of std 0.02 under a multiplier of 12
# and a TIED head make a seeded model return its input token: the token's own
# row reads 12 |E|^2 / rms(x) / 16 = 5.1 where the other 50,175 logits have a
# standard deviation of 0.08, so every emitted token is the reference's
# argmax whatever the layers computed (my chip runs, PR 52: 35,000 emitted
# tokens, largest gap 0.0, not one off the argmax) and `correct` tells
# nothing. With the rows at 1/96 of that the stream that reaches the head is
# the layers' own and the token's own row lies a few standard deviations up,
# as in the other tied-head cell (jamba2-3b). The file's `assumed.init` says
# so.
EMBEDDING_INIT_SHRINK = 96.0


def init_params(model_cfg, seed: int, device=None):
    """``gpt_dense.init_params`` (the program's own initialiser, one jitted
    program on the device), then the embedding's rows divided by
    ``EMBEDDING_INIT_SHRINK`` in place."""
    params = _init_params(model_cfg, seed, device)
    shrink = jax.jit(lambda w: (w.astype(F32) / EMBEDDING_INIT_SHRINK
                                ).astype(w.dtype), donate_argnums=0)
    params["embedding"] = dict(params["embedding"],
                               word=shrink(params["embedding"]["word"]))
    return params

F32 = jnp.float32
DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}
MAMBA, ATTN = "mamba", "attention"

REHEARSAL = {"num_hidden_layers": 4, "hidden_size": 64,
             "num_attention_heads": 4, "num_key_value_heads": 2,
             "intermediate_size": 32, "shared_intermediate_size": 48,
             "num_local_experts": 4, "num_experts_per_tok": 3,
             "mamba_n_heads": 4, "mamba_d_head": 32, "mamba_d_state": 16,
             "mamba_chunk_size": 16, "vocab_size": 512,
             "layer_types": [MAMBA, ATTN, MAMBA, MAMBA],
             "attention_multiplier": 0.125,
             "max_position_embeddings": 512,
             "published": {"num_hidden_layers": 8, "num_local_experts": 8,
                           "vocab_size": 1024},
             "expert_share": {"first": 0}}


def _head_dim(config: dict) -> int:
    return config["hidden_size"] // config["num_attention_heads"]


def _inner(config: dict) -> int:
    """E: the Mamba-2 mixer's expanded width."""
    return config["mamba_expand"] * config["hidden_size"]


def _conv_channels(config: dict) -> int:
    return _inner(config) + 2 * config["mamba_n_groups"] \
        * config["mamba_d_state"]


def _types(config: dict):
    """The kinds of the layers that are run: the file's ``layer_types``, or
    its first ``num_layers`` where this repository's tools cut a copy of a
    file (``tools/compile_rehearsal_state.py``)."""
    types = config["layer_types"]
    return types[:config.get("num_layers", len(types))]


def _kinds(config: dict):
    """(Mamba-2 layers, attention layers) among the layers run."""
    types = _types(config)
    return types.count(MAMBA), types.count(ATTN)


def _published(config: dict, key: str):
    """The source's value of a key this file may have reduced."""
    return config.get("published", {}).get(key, config[key])


def _share(config: dict):
    """(published experts, first held, held here)."""
    return (_published(config, "num_local_experts"),
            config.get("expert_share", {}).get("first", 0),
            config["num_local_experts"])


def _pattern(config: dict):
    """(period, offset) such that layer i attends iff i % period == offset:
    how the program lays a hybrid stack out. The published list is such a
    pattern; one that is not is refused."""
    types = config["layer_types"]
    if len(types) != config["num_hidden_layers"] or set(types) - {MAMBA,
                                                                  ATTN}:
        raise SystemExit("perfbench: layer_types must name num_hidden_layers "
                         f"layers, each {MAMBA!r} or {ATTN!r}")
    at = [i for i, t in enumerate(types) if t == ATTN]
    period = at[1] - at[0] if len(at) > 1 else len(types)
    if not at or [i for i in range(len(types))
                  if i % period == at[0] % period] != at:
        raise SystemExit("perfbench: models/granite_moe_hybrid.py builds "
                         "stacks whose attention layers lie one a period; "
                         f"got attention at {at}")
    return period, at[0] % period


def kv_bytes_per_token(config: dict, dtype: str) -> int:
    """Keys and values of every key/value head of the ATTENTION layers, in
    `dtype`: a Mamba-2 layer caches no token."""
    return (2 * _kinds(config)[1] * config["num_key_value_heads"]
            * _head_dim(config) * jnp.dtype(DTYPES[dtype]).itemsize)


def state_bytes_per_slot(config: dict, dtype: str) -> int:
    """What one sequence's recurrent state takes, whatever its length: for
    every Mamba-2 layer the heads' matrix states, ``mamba_d_state x E``
    elements in `dtype` (the configuration's ``serve.state_dtype``), and the
    convolution's last ``mamba_d_conv - 1`` inputs over x, B and C in the
    type the model computes in (``serve.params_dtype``)."""
    tail = DTYPES[config.get("serve", {}).get("params_dtype", "bfloat16")]
    return _kinds(config)[0] * (
        config["mamba_d_state"] * _inner(config)
        * jnp.dtype(DTYPES[dtype]).itemsize
        + (config["mamba_d_conv"] - 1) * _conv_channels(config)
        * jnp.dtype(tail).itemsize)


def ssd_flops_per_token(config: dict) -> float:
    """Matmul operations a position of ONE Mamba-2 layer's chunked scan
    costs (forward): within its chunk of Q positions Q scores of N and Q x P
    a head, N x E into the chunk's state and N x E out of the one that came
    in; 2 operations a multiply-add."""
    q, n, e = (config["mamba_chunk_size"], config["mamba_d_state"],
               _inner(config))
    return 2.0 * (q * n + q * e + 2 * n * e)


def params_per_token(config: dict) -> float:
    """Matrix parameters a token meets: its layers' mixers, routers and
    shared experts, its top-k's share of the HELD experts (top_k x held /
    published of them on average), the tied head once."""
    h, d = config["hidden_size"], _head_dim(config)
    n_ssm, n_attn = _kinds(config)
    e = _inner(config)
    experts, _, held = _share(config)
    mamba = h * (e + _conv_channels(config) + config["mamba_n_heads"]) + e * h
    attn = (2 * h * config["num_attention_heads"] * d
            + 2 * h * config["num_key_value_heads"] * d)
    ffn = (h * experts + 3 * h * config["shared_intermediate_size"]
           + config["num_experts_per_tok"] * held / experts
           * 3 * h * config["intermediate_size"])
    return (n_ssm * mamba + n_attn * attn + (n_ssm + n_attn) * ffn
            + h * config["vocab_size"])


def flops_per_token(config: dict, seq_len: int) -> float:
    """Forward and backward matmul operations per token (3 x 2 x the
    parameters a token meets, plus causal attention over `seq_len` in the
    attention layers and 3 x the chunked scans' products), the yardstick an
    ``mfu`` reader would use; no cell of this model trains."""
    n_ssm, n_attn = _kinds(config)
    scores = (n_attn * config["num_attention_heads"]
              * 2 * _head_dim(config) * seq_len / 2)
    return (6.0 * (params_per_token(config) + scores)
            + 3.0 * n_ssm * ssd_flops_per_token(config))


def model_config(config: dict, params_dtype: str, **extra):
    """The program's TransformerConfig for a configuration file that keeps
    the source's keys. Everything not named stays at the program's default.
    A program that lacks a field this model needs (the commit before the
    one that added it) fails here, at once and in words."""
    from megatronapp_tpu.config.transformer_config import (
        ActivationKind, NormKind, PositionEmbeddingKind, TransformerConfig,
    )
    if (config["position_embedding_type"] != "nope"
            or config["normalization_function"] != "rmsnorm"
            or config["hidden_act"] != "silu" or config["attention_bias"]
            or not config["tie_word_embeddings"]
            or not config["mamba_conv_bias"] or config["mamba_proj_bias"]):
        raise SystemExit("perfbench: models/granite_moe_hybrid.py builds the "
                         "published form only (no positional term, RMS "
                         "norms, silu, a tied head, no bias but the "
                         "convolution's)")
    period, offset = _pattern(config)
    experts, first, held = _share(config)
    try:
        return TransformerConfig(
            num_layers=len(_types(config)),
            hidden_size=config["hidden_size"],
            num_attention_heads=config["num_attention_heads"],
            num_query_groups=config["num_key_value_heads"],
            ffn_hidden_size=config["shared_intermediate_size"],
            vocab_size=config["vocab_size"],
            vocab_slice_of=_published(config, "vocab_size"),
            max_position_embeddings=config["max_position_embeddings"],
            normalization=NormKind.rmsnorm,
            layernorm_epsilon=config["rms_norm_eps"],
            activation=ActivationKind.swiglu, add_bias_linear=False,
            position_embedding=PositionEmbeddingKind.none,
            attn_layer_period=period, attn_layer_offset=offset,
            scaled_init_layers=_published(config, "num_hidden_layers"),
            ssm_state_dim=config["mamba_d_state"],
            ssm_conv_kernel=config["mamba_d_conv"],
            ssm_expand=config["mamba_expand"],
            ssm_heads=config["mamba_n_heads"],
            ssm_head_dim=config["mamba_d_head"],
            ssm_groups=config["mamba_n_groups"],
            ssm_chunk_size=config["mamba_chunk_size"],
            num_moe_experts=experts,
            moe_experts_held=(first, held) if held < experts else None,
            moe_router_topk=config["num_experts_per_tok"],
            moe_ffn_hidden_size=config["intermediate_size"],
            moe_shared_expert_intermediate_size=config[
                "shared_intermediate_size"],
            moe_router_norm_topk_prob=True,
            embedding_multiplier=float(config["embedding_multiplier"]),
            attention_multiplier=float(config["attention_multiplier"]),
            residual_multiplier=float(config["residual_multiplier"]),
            logits_scaling=float(config["logits_scaling"]),
            params_dtype=DTYPES[params_dtype], **extra)
    except TypeError as e:
        raise SystemExit(
            "perfbench: this program's TransformerConfig lacks a field "
            f"granite_moe_hybrid needs ({e})") from None


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


def mamba2(u, mp, segment_ids, heads: int, n: int, eps: float,
           state_dtype=F32, live=None):
    """u [B,S,H] -> (Mixer(u) [B,S,H], S after the last position
    [B, heads, P, N]). A segment's first position starts from a zero state.
    S is rounded to `state_dtype` after every position: float32 is the
    model; a lower type is the control a state check is sized by. live
    [B,S] bool (None: all): a position that is not live leaves S as it was
    (the padding behind a row's length)."""
    bsz, s, _ = u.shape
    e = mp["out_kernel"].shape[0]
    p = e // heads
    proj = u @ mp["in_kernel"]
    z, xbc, dt = proj[..., :e], proj[..., e:2 * e + 2 * n], \
        proj[..., 2 * e + 2 * n:]
    k = mp["conv_kernel"].shape[0]
    conv = sum(_shifted(xbc, k - 1 - j, segment_ids) * mp["conv_kernel"][j]
               for j in range(k))
    xbc = jax.nn.silu(conv + mp["conv_bias"])
    x = xbc[..., :e].reshape(bsz, s, heads, p)
    b, c = xbc[..., e:e + n], xbc[..., e + n:]
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
            + (d_t[:, :, None] * x_t)[..., None] * b_t[:, None, None, :]
        if state_dtype != F32:  # not a cast pair: XLA:TPU removes one
            info = jnp.finfo(state_dtype)
            new = jax.lax.reduce_precision(new, info.nexp, info.nmant)
        y = jnp.einsum("bhpn,bn->bhp", new, c_t) + mp["D"][:, None] * x_t
        return jnp.where(live_t[:, None, None, None], new, state), y

    # unroll: eight positions a loop iteration, still one after the other
    state, y = jax.lax.scan(
        position, jnp.zeros((bsz, heads, p, n), F32),
        tuple(jnp.moveaxis(t, 1, 0)
              for t in (delta, x, b, c, first, live)), unroll=8)
    y = jnp.moveaxis(y, 0, 1).reshape(bsz, s, e)
    y = _rms_norm(y * jax.nn.silu(z), mp["norm_scale"], eps)
    return y @ mp["out_kernel"], state


def attention(u, at, segment_ids, heads: int, groups: int, scale: float):
    b, s, hidden = u.shape
    d = hidden // heads
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
        scores = jnp.einsum("bqd,bkd->bqk", qh, kh) * scale
        probs = jax.nn.softmax(jnp.where(allowed, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bqk,bkd->bqd", probs, vh)

    ctx = jax.lax.map(one_head, tuple(jnp.moveaxis(a, 2, 0)
                                      for a in (q, k, v)))
    return jnp.moveaxis(ctx, 0, 2).reshape(b, s, heads * d) @ at["out_kernel"]


def _swiglu(x, fc1, fc2):
    gate, up = jnp.split(x @ fc1, 2, axis=-1)
    return (jax.nn.silu(gate) * up) @ fc2


def router_weights(flat, router_kernel, top_k: int, renormalise=True):
    """flat [T,H] -> [T, experts] float32: an expert's weight for each
    token, 0 where it was not chosen: the softmax over the top_k largest
    logits alone. renormalise False is a control: the chosen experts'
    shares of a softmax over ALL the logits, which do not add up to 1."""
    logits = flat @ router_kernel.astype(F32)
    top_l, top_i = jax.lax.top_k(logits, top_k)
    w = (jax.nn.softmax(top_l, axis=-1) if renormalise else
         jnp.take_along_axis(jax.nn.softmax(logits, axis=-1), top_i, -1))
    return jnp.sum(jax.nn.one_hot(top_i, logits.shape[-1], dtype=F32)
                   * w[..., None], axis=1)


def _experts(flat, weights, fc1_stack, fc2_stack, layer):
    """sum_e weights[:, e] * SwiGLU_e(flat) over the stacks' experts
    [L, held, ., .]: every held expert over ALL tokens, one expert's two
    matrices cut out and upcast at a time."""
    def one_expert(acc, e):
        fc1 = jax.lax.dynamic_slice(
            fc1_stack, (layer, e, 0, 0), (1, 1) + fc1_stack.shape[2:])[0, 0]
        fc2 = jax.lax.dynamic_slice(
            fc2_stack, (layer, e, 0, 0), (1, 1) + fc2_stack.shape[2:])[0, 0]
        w = jax.lax.dynamic_index_in_dim(weights, e, 1, keepdims=True)
        return acc + _swiglu(flat, fc1.astype(F32), fc2.astype(F32)) * w, None

    return jax.lax.scan(one_expert, jnp.zeros_like(flat),
                        jnp.arange(fc1_stack.shape[1], dtype=jnp.int32))[0]


@functools.partial(jax.jit, static_argnames=(
    "heads", "groups", "ssm_heads", "n", "eps", "scale", "residual",
    "state_dtype"))
def _mixer(x, mixers, i, segment_ids, live, heads, groups, ssm_heads, n, eps,
           scale, residual, state_dtype=F32):
    """x + residual * Mixer(RMS(x; g1)) for row i of `mixers` (a stack of
    one kind), and a Mamba-2 layer's final state (None for attention)."""
    mixer = _row(mixers, i)
    u = _rms_norm(x, mixer["ln1_scale"], eps)
    if "ssm" in mixer:
        out, state = mamba2(u, mixer["ssm"], segment_ids, ssm_heads, n, eps,
                            state_dtype, live)
        return x + residual * out, state
    return x + residual * attention(u, mixer["attention"], segment_ids,
                                    heads, groups, scale), None


@functools.partial(jax.jit, static_argnames=(
    "eps", "top_k", "first", "residual", "renormalise"))
def _ffn_terms(x, ffns, i, eps, top_k, first, residual, renormalise=True):
    """(the held experts' term, the shared expert's), each times the
    residual multiplier, of layer i's second half on the stream x."""
    b, s, h = x.shape
    moe = ffns["moe"]
    flat = _rms_norm(x, _row(ffns["ln2_scale"], i), eps).reshape(b * s, h)
    weights = router_weights(flat, _row(moe["router_kernel"], i), top_k,
                             renormalise)
    held = moe["fc1_kernel"].shape[1]
    routed = _experts(flat, weights[:, first:first + held],
                      moe["fc1_kernel"], moe["fc2_kernel"], i)
    shared = _swiglu(flat, _row(moe["shared_fc1"], i),
                     _row(moe["shared_fc2"], i))
    return (residual * routed.reshape(b, s, h),
            residual * shared.reshape(b, s, h))


@functools.partial(jax.jit, static_argnames=("eps", "size", "scaling"))
def _head(x, scale, word, start, eps: float, size: int, scaling: float):
    x = jax.lax.dynamic_slice_in_dim(x, start, size, axis=1)
    return _rms_norm(x, scale.astype(F32), eps) @ word.astype(F32).T / scaling


def _statics(config: dict, **control):
    """(the mixers' static arguments, the feed-forwards') from the file;
    `control` replaces a fact of the model by a wrong one (the controls of
    ``tools/granite_control.py``)."""
    mix = dict(heads=config["num_attention_heads"],
               groups=config["num_key_value_heads"],
               ssm_heads=config["mamba_n_heads"], n=config["mamba_d_state"],
               eps=config["rms_norm_eps"],
               scale=float(control.get("attention_multiplier",
                                       config["attention_multiplier"])),
               residual=float(control.get("residual_multiplier",
                                          config["residual_multiplier"])))
    ffn = dict(eps=config["rms_norm_eps"], top_k=config["num_experts_per_tok"],
               first=_share(config)[1], residual=mix["residual"],
               renormalise=bool(control.get("renormalise", True)))
    return mix, ffn


def _layers(params, config: dict, tokens, segment_ids, live=None,
            state_dtype=F32, **control):
    """The stack over tokens [B,S]: (x [B,S,H] float32 before the final
    norm, the Mamba-2 layers' final states [B, heads, P, N] in their
    order)."""
    block = params["block"]
    mix, ffn = _statics(config, **control)
    x = float(config["embedding_multiplier"]) * jnp.take(
        params["embedding"]["word"], tokens, axis=0).astype(F32)
    if live is None:
        live = jnp.ones(tokens.shape, bool)
    seen = {MAMBA: 0, ATTN: 0}
    states = []
    for i, kind in enumerate(_types(config)):
        stack = block["mixers_ssm" if kind == MAMBA else "mixers_attn"]
        x, state = _mixer(x, stack, jnp.int32(seen[kind]), segment_ids, live,
                          state_dtype=state_dtype, **mix)
        seen[kind] += 1
        if state is not None:
            states.append(state)
        routed, shared = _ffn_terms(x, block["ffn"], jnp.int32(i), **ffn)
        x = x + routed + shared
    return x, states


def reference_hidden(params, config: dict, tokens, segment_ids, **control):
    """tokens/segment_ids [B,S] -> the stack's output before the final
    norm, float32 [B,S,H]."""
    with jax.default_matmul_precision("highest"):
        return _layers(params, config, tokens, segment_ids, **control)[0]


def reference_head(params, config: dict, x, rows=None):
    """x float32 [B,S,H], rows of ``reference_hidden`` -> logits float32
    [B,S,V] (or [B,size,V] from `rows` = (start, size)): the final norm, the
    tied embedding, and the division by ``logits_scaling``."""
    start, size = rows if rows is not None else (0, x.shape[1])
    with jax.default_matmul_precision("highest"):
        return _head(x, params["final_ln_scale"], params["embedding"]["word"],
                     jnp.int32(start), eps=config["rms_norm_eps"], size=size,
                     scaling=float(config["logits_scaling"]))


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
        _, states = _layers(params, config, tokens,
                            jnp.zeros(tokens.shape, jnp.int32), live,
                            DTYPES[state_dtype])
    return jnp.stack([jnp.transpose(st, (0, 3, 1, 2)).reshape(
        b, st.shape[3], -1) for st in states])


def reference_layer_terms(params, config: dict, x, layer: int, **control):
    """x [B,S,H] float32, the stream INTO layer `layer`'s second half ->
    (the held experts' term, the shared expert's term), each already times
    ``residual_multiplier``: the layer's second half is x + their sum. For
    the test that adds the shares of a deployment up."""
    _, ffn = _statics(config, **control)
    with jax.default_matmul_precision("highest"):
        return _ffn_terms(x, params["block"]["ffn"], jnp.int32(layer), **ffn)


def reference_loss(params, config: dict, batch) -> float:
    """Mean cross entropy over the positions whose loss_mask is 1, for one
    micro-batch of ``generators/train_packed.py`` rows (what a training
    cell of this model would be held to; none exists: the program's
    state-space layers refuse packed segments, ROADMAP M4 (c))."""
    lg = reference_logits(params, config, jnp.asarray(batch["tokens"]),
                          jnp.asarray(batch["segment_ids"]), None)
    logz = jax.nn.logsumexp(lg, axis=-1)
    picked = jnp.take_along_axis(
        lg, jnp.asarray(batch["labels"])[..., None], axis=-1)[..., 0]
    mask = jnp.asarray(batch["loss_mask"], F32)
    return float(jnp.sum((logz - picked) * mask) / jnp.maximum(mask.sum(), 1))
