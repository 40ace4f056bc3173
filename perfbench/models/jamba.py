"""``"model": "jamba"``: AI21's Jamba hybrids as their ``config.json``
publishes them (the configuration file keeps the source's own keys), built
as the program's ``models/gpt.py`` model, with its plain reference and its
counts. What a model module gives the runners is listed in
``models/gpt_dense.py``; this one adds ``state_bytes_per_slot``, by which
``cells/serve_closed_state.py`` holds the engine's recurrent state to its
stated type, ``reference_state`` (the reference's recurrence after a
sequence's last token, in the stated type or rounded to a lower one: the
control that runner's limit on the state's precision is sized by), and
``reference_logits`` in its two halves, ``reference_hidden`` and
``reference_head``, for a runner that passes several requests a batch and
wants the head over each row's own answer.

The model: layer i attends iff ``i % attn_layer_period ==
attn_layer_offset`` and is a Mamba-1 selective-state-space layer otherwise;
every layer ends in one dense SwiGLU (``num_experts`` 1); RMSNorm, a tied
head, no positional term anywhere.

The reference (``reference_logits``) is that forward pass written out in
``jax.numpy``, float32, matmuls at precision "highest". A Mamba mixer:
``[u, z] = W_in y``; the causal depthwise convolution as ``mamba_d_conv``
shifted products plus its bias, then silu; ``[dt, B, C] = W_x u``, each
through an RMS norm with a scale of its own; ``delta = softplus(W_dt dt +
b_dt)``; ``A = -exp(A_log)``; the recurrence ``h_t = exp(delta_t * A) *
h_{t-1} + (delta_t * u_t) (x) B_t``, ``y_t = h_t C_t + D * u_t`` as a
SEQUENTIAL ``lax.scan`` over the positions from a zero state (no associative
scan, no chunks, no kernel, no cache); ``out = W_out (y * silu(z))``.
Attention: dense, causal, softmax scale ``head_dim ** -0.5``, the
``num_key_value_heads`` key/value heads broadcast over their query groups,
one query head at a time. It reads the program's own parameter tree
(``block``: ``mixers_ssm``, ``mixers_attn``, ``ffn``), one layer upcast at a
time, and shares no code with ``megatronapp_tpu/transformer/ssm.py``.

Departures from the published model, all of layout, none of mathematics:
- the scan keeps ``h`` as ``[B, N, E]`` (the published code ``[B, E, N]``):
  the same numbers with the wide axis minor;
- gate and up projections are one ``fc1`` matrix ``[gate | up]``, ``k_proj``
  and ``v_proj`` one ``kv_kernel`` ``[k | v]``, ``in_proj`` one ``in_kernel``
  ``[u | z]``, ``x_proj`` ``[dt | B | C]`` (the tree's layouts);
- ``num_experts`` 1 only: the ``expert_layer_*`` keys then select nothing;
- ``sliding_window`` null and ``num_logits_to_keep`` are not the forward
  pass's business; ``use_mamba_kernels`` names an implementation.
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

REHEARSAL = {"num_hidden_layers": 4, "hidden_size": 64,
             "num_attention_heads": 4, "num_key_value_heads": 1,
             "intermediate_size": 128, "vocab_size": 512,
             "attn_layer_period": 4, "attn_layer_offset": 1,
             "mamba_d_state": 8, "mamba_dt_rank": 8,
             "max_position_embeddings": 512}


def _depth(config: dict) -> int:
    """The layers that are run: the source's ``num_hidden_layers``, or the
    ``num_layers`` by which this repository's tools cut a copy of a file."""
    return config.get("num_layers", config["num_hidden_layers"])


def _kinds(config: dict):
    """(state-space layers, attention layers) among the layers run."""
    attends = [i % config["attn_layer_period"] == config["attn_layer_offset"]
               for i in range(_depth(config))]
    return len(attends) - sum(attends), sum(attends)


def _head_dim(config: dict) -> int:
    return config["hidden_size"] // config["num_attention_heads"]


def kv_bytes_per_token(config: dict, dtype: str) -> int:
    """Keys and values of every key/value head of the ATTENTION layers, in
    `dtype`: the state-space layers cache no token."""
    return (2 * _kinds(config)[1] * config["num_key_value_heads"]
            * _head_dim(config) * jnp.dtype(DTYPES[dtype]).itemsize)


def state_bytes_per_slot(config: dict, dtype: str) -> int:
    """What one sequence's recurrent state takes, whatever its length: for
    every state-space layer h ``[mamba_d_state, E]`` in `dtype` (the
    configuration's ``serve.state_dtype``) and the convolution's last
    ``mamba_d_conv - 1`` inputs ``[., E]`` in the type the model computes in
    (``serve.params_dtype``)."""
    e = config["mamba_expand"] * config["hidden_size"]
    tail = DTYPES[config.get("serve", {}).get("params_dtype", "bfloat16")]
    return _kinds(config)[0] * (
        config["mamba_d_state"] * e * jnp.dtype(DTYPES[dtype]).itemsize
        + (config["mamba_d_conv"] - 1) * e * jnp.dtype(tail).itemsize)


def flops_per_token(config: dict, seq_len: int) -> float:
    """Forward and backward matmul operations per token (3 x 2 x the
    parameters a token meets, plus causal attention over `seq_len` in the
    attention layers), the yardstick an ``mfu`` reader would use; no cell of
    this model trains. The recurrence itself (6 x N x E a layer) is
    elementwise and not counted."""
    h = config["hidden_size"]
    e, n, r = (config["mamba_expand"] * h, config["mamba_d_state"],
               config["mamba_dt_rank"])
    d = _head_dim(config)
    n_ssm, n_attn = _kinds(config)
    mamba = h * 2 * e + e * (r + 2 * n) + r * e + e * h
    attn = (h * config["num_attention_heads"] * d * 2
            + 2 * h * config["num_key_value_heads"] * d)
    ffn = 3 * h * config["intermediate_size"]
    params = (n_ssm * mamba + n_attn * attn + (n_ssm + n_attn) * ffn
              + h * config["vocab_size"])
    scores = n_attn * config["num_attention_heads"] * 2 * d * seq_len / 2
    return 6.0 * (params + scores)


def model_config(config: dict, params_dtype: str, **extra):
    """The program's TransformerConfig for a configuration file that keeps
    the source's keys. Everything not named stays at the program's default.
    A program that lacks a field this model needs (the commit before the
    one that added it) fails here, at once."""
    from megatronapp_tpu.config.transformer_config import (
        ActivationKind, NormKind, PositionEmbeddingKind, TransformerConfig,
    )
    if (config["num_experts"] != 1 or config["hidden_act"] != "silu"
            or not config["tie_word_embeddings"]
            or config.get("sliding_window") is not None
            or not config["mamba_conv_bias"] or config["mamba_proj_bias"]):
        raise SystemExit("perfbench: models/jamba.py builds the dense form "
                         "only (num_experts 1, silu, a tied head, no "
                         "sliding window, a bias on the convolution and "
                         "none on the mixer's projections)")
    return TransformerConfig(
        num_layers=_depth(config),
        hidden_size=config["hidden_size"],
        num_attention_heads=config["num_attention_heads"],
        num_query_groups=config["num_key_value_heads"],
        ffn_hidden_size=config["intermediate_size"],
        vocab_size=config["vocab_size"],
        max_position_embeddings=config["max_position_embeddings"],
        normalization=NormKind.rmsnorm,
        layernorm_epsilon=config["rms_norm_eps"],
        activation=ActivationKind.swiglu, add_bias_linear=False,
        position_embedding=PositionEmbeddingKind.none,
        attn_layer_period=config["attn_layer_period"],
        attn_layer_offset=config["attn_layer_offset"],
        ssm_state_dim=config["mamba_d_state"],
        ssm_conv_kernel=config["mamba_d_conv"],
        ssm_expand=config["mamba_expand"],
        ssm_dt_rank=config["mamba_dt_rank"],
        ssm_inner_norms=True,
        params_dtype=DTYPES[params_dtype], **extra)


# ---- the plain reference ---------------------------------------------------

def _rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * weight


def _mamba(x, mp, n: int, r: int, eps: float, state_dtype=F32):
    """x [B,S,H] -> ([B,S,H], h after the last position [B,N,E]), from a
    zero state. h is rounded to `state_dtype` after every position: float32
    is the model; a lower type is the control a state check is sized by."""
    s = x.shape[1]
    u, z = jnp.split(x @ mp["in_kernel"], 2, axis=-1)
    k = mp["conv_kernel"].shape[0]
    conv = 0.0
    for j in range(k):      # tap j sees the input k-1-j positions back
        back = k - 1 - j
        conv = conv + jnp.pad(u, ((0, 0), (back, 0), (0, 0)))[:, :s] \
            * mp["conv_kernel"][j]
    u = jax.nn.silu(conv + mp["conv_bias"])
    proj = u @ mp["x_proj"]
    dt, b, c = proj[..., :r], proj[..., r:r + n], proj[..., r + n:]
    if "dt_ln_scale" in mp:
        dt = _rms_norm(dt, mp["dt_ln_scale"], eps)
        b = _rms_norm(b, mp["b_ln_scale"], eps)
        c = _rms_norm(c, mp["c_ln_scale"], eps)
    delta = jax.nn.softplus(dt @ mp["dt_proj"] + mp["dt_bias"])
    a = -jnp.exp(mp["A_log"]).T                                 # [N, E]

    def position(h, xs):                    # h [B, N, E]
        d_t, u_t, b_t, c_t = xs
        h = jnp.exp(d_t[:, None, :] * a[None]) * h \
            + (d_t * u_t)[:, None, :] * b_t[:, :, None]
        if state_dtype != F32:  # not a cast pair: XLA:TPU removes one
            info = jnp.finfo(state_dtype)
            h = jax.lax.reduce_precision(h, info.nexp, info.nmant)
        return h, jnp.sum(h * c_t[:, :, None], axis=1) + mp["D"] * u_t

    h0 = jnp.zeros((x.shape[0], n, u.shape[-1]), F32)
    # unroll: eight positions a loop iteration, still one after the other
    # (a TPU's loop overhead is most of a 26 x 2048-step pass otherwise)
    h, y = jax.lax.scan(position, h0, tuple(
        jnp.moveaxis(t, 1, 0) for t in (delta, u, b, c)), unroll=8)
    return (jnp.moveaxis(y, 0, 1) * jax.nn.silu(z)) @ mp["out_kernel"], h


def _attention(x, at, heads: int, groups: int, segment_ids):
    b, s, hidden = x.shape
    d = hidden // heads
    q = (x @ at["q_kernel"]).reshape(b, s, heads, d)
    k, v = jnp.split((x @ at["kv_kernel"]).reshape(b, s, 2 * groups, d), 2,
                     axis=2)
    k = jnp.repeat(k, heads // groups, axis=2)
    v = jnp.repeat(v, heads // groups, axis=2)
    pos = jnp.arange(s)
    allowed = (pos[:, None] >= pos[None, :])[None]
    allowed = allowed & (segment_ids[:, :, None] == segment_ids[:, None, :])

    def one_head(qkv):          # one head at a time: [S, S] scores, not 20
        qh, kh, vh = qkv
        scores = jnp.einsum("bqd,bkd->bqk", qh, kh) * d ** -0.5
        probs = jax.nn.softmax(jnp.where(allowed, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bqk,bkd->bqd", probs, vh)

    ctx = jax.lax.map(one_head, tuple(jnp.moveaxis(a, 2, 0)
                                      for a in (q, k, v)))
    return jnp.moveaxis(ctx, 0, 2).reshape(b, s, heads * d) @ at["out_kernel"]


@functools.partial(jax.jit, static_argnames=("heads", "groups", "n", "r",
                                             "eps", "state_dtype"))
def _layer(x, mixers, ffns, at, segment_ids, heads, groups, n, r, eps,
           state_dtype=F32):
    """x [B,S,H] float32 -> ([B,S,H], a state-space layer's final state or
    None). `mixers` is the stack of this layer's kind and `ffns` the stack
    of every layer's feed-forward, in their own type; `at` = (row of
    mixers, row of ffns). The rows are cut here, by traced indices, and
    upcast: one program a kind and shape, not one eager slice a layer and
    leaf (hundreds of small compiles in every process, PERF.md, PR 32)."""
    mixer, ffn = (jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, False).astype(F32), t)
        for t, i in zip((mixers, ffns), at))
    y = _rms_norm(x, mixer["ln1_scale"], eps)
    h = None
    if "ssm" in mixer:
        out, h = _mamba(y, mixer["ssm"], n, r, eps, state_dtype)
        x = x + out
    else:
        x = x + _attention(y, mixer["attention"], heads, groups, segment_ids)
    y = _rms_norm(x, ffn["ln2_scale"], eps)
    gate, up = jnp.split(y @ ffn["mlp"]["fc1_kernel"], 2, axis=-1)
    return x + (jax.nn.silu(gate) * up) @ ffn["mlp"]["fc2_kernel"], h


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, scale, word, eps: float):
    return _rms_norm(x, scale.astype(F32), eps) @ word.astype(F32).T


def _layers(params, config: dict, tokens, segment_ids, state_dtype=F32):
    """The stack over tokens [B,S]: (x [B,S,H] float32 before the final
    norm, the state-space layers' final states in their order)."""
    block = params["block"]
    period, offset = config["attn_layer_period"], config["attn_layer_offset"]
    static = dict(heads=config["num_attention_heads"],
                  groups=config["num_key_value_heads"],
                  n=config["mamba_d_state"], r=config["mamba_dt_rank"],
                  eps=config["rms_norm_eps"], state_dtype=state_dtype)
    x = jnp.take(params["embedding"]["word"], tokens, axis=0).astype(F32)
    seen = {"mixers_ssm": 0, "mixers_attn": 0}
    states = []
    for i in range(jax.tree.leaves(block["ffn"])[0].shape[0]):
        kind = "mixers_attn" if i % period == offset else "mixers_ssm"
        x, h = _layer(x, block[kind], block["ffn"],
                      (jnp.int32(seen[kind]), jnp.int32(i)), segment_ids,
                      **static)
        seen[kind] += 1
        if h is not None:
            states.append(h)
    return x, states


def reference_hidden(params, config: dict, tokens, segment_ids):
    """tokens/segment_ids [B,S] -> the stack's output before the final
    norm, float32 [B,S,H]: ``reference_logits`` without its head, for a
    caller whose rows want the head over different positions."""
    with jax.default_matmul_precision("highest"):
        return _layers(params, config, tokens, segment_ids)[0]


def reference_head(params, config: dict, x):
    """x float32 [..., H], rows of ``reference_hidden`` -> logits float32
    [..., V]: the final norm and the tied embedding."""
    with jax.default_matmul_precision("highest"):
        return _head(x, params["final_ln_scale"], params["embedding"]["word"],
                     eps=config["rms_norm_eps"])


def reference_logits(params, config: dict, tokens, segment_ids, position_ids,
                     rows=None):
    """tokens/segment_ids/position_ids [B,S] -> logits float32 over the
    vocabulary, [B,S,V], or [B,size,V] for the positions start..start+size
    when `rows` = (start, size). position_ids are not read: the model has no
    positional term. segment_ids mask the attention layers only; a
    state-space layer's state runs on from position 0, so give it rows that
    hold one sequence each. `config` is a configuration file's top level."""
    del position_ids
    x = reference_hidden(params, config, tokens, segment_ids)
    if rows is not None:
        x = jax.lax.dynamic_slice_in_dim(x, rows[0], rows[1], axis=1)
    return reference_head(params, config, x)


def reference_state(params, config: dict, tokens, state_dtype: str = "float32"):
    """tokens [B,S], every position real -> what each state-space layer's
    recurrence holds after position S-1, float32 [layers, B, N, E]: what a
    slot of the engine's state pool should hold once it has read those S
    tokens. With a `state_dtype` below float32 the recurrence rounds h to it
    at every position and nothing else changes: the control by which
    ``cells/serve_closed_state.py``'s limit is sized."""
    with jax.default_matmul_precision("highest"):
        _, states = _layers(params, config, tokens,
                            jnp.zeros(tokens.shape, jnp.int32),
                            DTYPES[state_dtype])
    return jnp.stack(states)


def reference_loss(params, config: dict, batch) -> float:
    """Mean cross entropy over the positions whose loss_mask is 1, for one
    micro-batch of ``generators/train_packed.py`` rows (what a training
    cell of this model would be held to; none exists yet). A packed row's
    documents are masked apart in the attention layers only: a state-space
    layer's state runs through them here as in the program's training pass."""
    lg = reference_logits(params, config, jnp.asarray(batch["tokens"]),
                          jnp.asarray(batch["segment_ids"]), None)
    logz = jax.nn.logsumexp(lg, axis=-1)
    picked = jnp.take_along_axis(
        lg, jnp.asarray(batch["labels"])[..., None], axis=-1)[..., 0]
    mask = jnp.asarray(batch["loss_mask"], F32)
    return float(jnp.sum((logz - picked) * mask) / jnp.maximum(mask.sum(), 1))
