"""``"model": "evabyte"``: EvaByte as its ``config.json`` publishes it (the
configuration file keeps the source's own keys), built as the program's
``models/gpt.py`` model, with its plain reference and its counts. What a
model module gives the runners is listed in ``models/gpt_dense.py``; this one
adds ``rows_walked(config, T)``, the rows a decode step at context length T
has to read, by which ``eva_bytes.py`` and the runner size the cache's walk.

The model: byte-level (vocabulary 320), ``num_hidden_layers`` identical
layers, ``num_attention_heads`` query and ``num_key_value_heads`` key/value
heads of ``hidden_size / num_attention_heads``, RoPE (theta ``rope_theta``)
on the whole head in the half-rotation layout, no bias; SwiGLU
``intermediate_size``; RMSNorm (``rms_norm_eps``) whose scale is ``1 + g``
(``norm_add_unit_offset``); an untied embedding ``[vocab, H]`` and a head
``[H, vocab x num_pred_heads]`` (head j predicts byte t+1+j; generation
reads columns 0..vocab-1). Attention is EVA (``attention_class`` "eva",
``window_size`` W, ``chunk_size`` C; Zheng et al. 2023, "Efficient Attention
via Control Variates", deterministic form). With ``s = head_dim ** -0.5``
and two learned vectors a head, phi and mu:

- chunk c holds positions ``C c .. C c + C - 1``; its summary is one key
  and one value: ``alpha_j = softmax_{j in c}(s k_j . phi)``,
  ``k~_c = sum_j alpha_j k_j + mu``, ``v~_c = sum_j alpha_j v_j``;
- query t sees exactly the rows of its own aligned window,
  ``S_t = {j : W (t // W) <= j <= t}``, and one summary a chunk of every
  earlier window, ``C_t = {c : c < (W / C) (t // W)}``, under one softmax.

The reference (``reference_logits``) is that forward pass written out in
``jax.numpy``, float32, matmuls at precision "highest": every chunk's
summary from the whole sequence's keys, then for each window a dense masked
softmax over ``[its W rows | the summaries before it]``, one window and one
head at a time. No cache, no pages, no kernel, and no code shared with
``megatronapp_tpu/transformer/eva.py``. It reads the program's own parameter
tree (``block``: ``attention`` with ``eva_phi`` / ``eva_mu``, ``mlp``), one
layer upcast at a time. A sequence is padded here to whole windows; pad
positions lie behind every real one, so no real query sees them.

Departures from the published model, of layout or precision, none of
mathematics: ``k_proj`` and ``v_proj`` are one ``kv_kernel`` ``[k | v]`` and
gate and up one ``fc1`` ``[gate | up]`` (the tree's layouts);
``fp32_skip_add`` and ``mixedp_attn`` name the published code's precisions
(a float32 residual stream), where the program computes in the type the
configuration's ``serve`` section states and this reference in float32
throughout. The configuration file lists under ``assumed`` what the
catalog's row does not settle.
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

# chunk 16 is the engine's block size, which a cell's runner leaves at the
# program's default; 16 chunks a window is the least that fills a block
REHEARSAL = {"num_hidden_layers": 2, "hidden_size": 64,
             "num_attention_heads": 4, "num_key_value_heads": 4,
             "intermediate_size": 128, "window_size": 256, "chunk_size": 16,
             "max_position_embeddings": 2048}


def _depth(config: dict) -> int:
    """The layers that are run: the source's ``num_hidden_layers``, or the
    ``num_layers`` by which this repository's tools cut a copy of a file."""
    return config.get("num_layers", config["num_hidden_layers"])


def _head_dim(config: dict) -> int:
    return config["hidden_size"] // config["num_attention_heads"]


def kv_bytes_per_token(config: dict, dtype: str) -> int:
    """Keys and values of every key/value head of every layer, in `dtype`:
    what one cached row takes, an exact row and a chunk's summary alike."""
    return (2 * _depth(config) * config["num_key_value_heads"]
            * _head_dim(config) * jnp.dtype(DTYPES[dtype]).itemsize)


def rows_walked(config: dict, length):
    """R(T): the rows a decode step at context length T reads, its own new
    row among them, where full attention reads T + 1: one summary a chunk
    of every closed window, then the open window's rows."""
    w = config["window_size"]
    return (w // config["chunk_size"]) * (length // w) + length % w + 1


def flops_per_token(config: dict, seq_len: int) -> float:
    """Forward and backward matmul operations per token (3 x 2 x the
    parameters a token meets, plus the scores and weighted sums over the
    R(t) rows a position sees, averaged over `seq_len`), the yardstick an
    ``mfu`` reader would use; no cell of this model trains."""
    h, d = config["hidden_size"], _head_dim(config)
    attn = (2 * h * config["num_attention_heads"] * d
            + 2 * h * config["num_key_value_heads"] * d)
    params = (_depth(config) * (attn + 3 * h * config["intermediate_size"])
              + h * config["vocab_size"] * config["num_pred_heads"])
    seen = sum(rows_walked(config, t) for t in range(seq_len)) / seq_len
    scores = _depth(config) * config["num_attention_heads"] * 2 * d * seen
    return 6.0 * (params + scores)


def model_config(config: dict, params_dtype: str, **extra):
    """The program's TransformerConfig for a configuration file that keeps
    the source's keys. Everything not named stays at the program's default.
    A program that lacks a field this model needs (the commit before the
    one that added it) fails here, at once."""
    from megatronapp_tpu.config.transformer_config import (
        ActivationKind, NormKind, PositionEmbeddingKind, TransformerConfig,
    )
    if (config["attention_class"] != "eva" or config["hidden_act"] != "silu"
            or config["attention_bias"] or config["tie_word_embeddings"]
            or config["rope_scaling"] is not None
            or not config["norm_add_unit_offset"]
            or config["num_chunks"] is not None):
        raise SystemExit("perfbench: models/evabyte.py builds the published "
                         "form only (EVA attention, silu, no bias, an untied "
                         "head, plain RoPE, norms with a unit offset)")
    try:
        return TransformerConfig(**{**dict(
            num_layers=_depth(config),
            hidden_size=config["hidden_size"],
            num_attention_heads=config["num_attention_heads"],
            num_query_groups=config["num_key_value_heads"],
            ffn_hidden_size=config["intermediate_size"],
            vocab_size=config["vocab_size"],
            true_vocab_size=config["vocab_size"],
            max_position_embeddings=config["max_position_embeddings"],
            normalization=NormKind.rmsnorm,
            layernorm_epsilon=config["rms_norm_eps"],
            norm_unit_offset=True,
            activation=ActivationKind.swiglu, add_bias_linear=False,
            untie_embeddings_and_output_weights=True,
            num_pred_heads=config["num_pred_heads"],
            position_embedding=PositionEmbeddingKind.rope,
            rotary_base=float(config["rope_theta"]),
            init_method_std=config["init_std"],
            eva_window_size=config["window_size"],
            eva_chunk_size=config["chunk_size"],
            params_dtype=DTYPES[params_dtype]), **extra})
    except TypeError as e:
        raise SystemExit(f"perfbench: this program's TransformerConfig has "
                         f"no EVA attention ({e})")


# ---- the plain reference ---------------------------------------------------

def _rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * (1.0 + g)


def _rope(x, cos, sin):
    """x [B,S,heads,d], cos/sin [B,S,d/2]: rotate the pairs (i, i + d/2)."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    c, s = cos[:, :, None, :], sin[:, :, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def _eva(q, k, v, phi, mu, window: int, chunk: int):
    """q [B,S,H,D], k / v [B,S,Hkv,D] (roped), S whole windows; phi, mu
    [Hkv,D] -> [B,S,H,D]."""
    b, s, heads, d = q.shape
    kv_heads = k.shape[2]
    scale = d ** -0.5
    n_chunks, per_window = s // chunk, window // chunk
    # every chunk's summary, from the whole sequence's keys
    kc = k.reshape(b, n_chunks, chunk, kv_heads, d)
    vc = v.reshape(b, n_chunks, chunk, kv_heads, d)
    alpha = jax.nn.softmax(
        scale * jnp.einsum("bcjhd,hd->bcjh", kc, phi), axis=2)
    k_sum = jnp.einsum("bcjh,bcjhd->bchd", alpha, kc) + mu
    v_sum = jnp.einsum("bcjh,bcjhd->bchd", alpha, vc)
    pos = jnp.arange(window)
    own = pos[:, None] >= pos[None, :]                         # [W, W]

    def one_window(n):
        lo = n * window
        qw = jax.lax.dynamic_slice_in_dim(q, lo, window, axis=1)
        kw = jax.lax.dynamic_slice_in_dim(k, lo, window, axis=1)
        vw = jax.lax.dynamic_slice_in_dim(v, lo, window, axis=1)
        # S_t: the window's own rows up to t; C_t: the chunks of the
        # windows before it
        allowed = jnp.concatenate(
            [own, jnp.broadcast_to(
                jnp.arange(n_chunks)[None, :] < per_window * n,
                (window, n_chunks))], axis=1)

        def one_head(hd):
            g = hd // (heads // kv_heads)
            keys = jnp.concatenate([kw[:, :, g], k_sum[:, :, g]], axis=1)
            vals = jnp.concatenate([vw[:, :, g], v_sum[:, :, g]], axis=1)
            scores = jnp.einsum("bqd,bkd->bqk", qw[:, :, hd], keys) * scale
            probs = jax.nn.softmax(
                jnp.where(allowed[None], scores, -jnp.inf), axis=-1)
            return jnp.einsum("bqk,bkd->bqd", probs, vals)

        return jax.lax.map(one_head, jnp.arange(heads))       # [H,B,W,D]

    out = jax.lax.map(one_window, jnp.arange(s // window))    # [N,H,B,W,D]
    return jnp.transpose(out, (2, 0, 3, 1, 4)).reshape(b, s, heads, d)


def _swiglu(x, fc1, fc2):
    gate, up = jnp.split(x @ fc1, 2, axis=-1)
    return (jax.nn.silu(gate) * up) @ fc2


@functools.partial(jax.jit, static_argnames=(
    "heads", "kv_heads", "window", "chunk", "eps"))
def _layer(x, block, i, cos, sin, heads, kv_heads, window, chunk, eps):
    """x [B,S,H] float32 -> [B,S,H] through layer `i` of the stacked
    parameters (a traced index: one program a shape, not one a layer)."""
    lp = jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, i, keepdims=False)
        .astype(F32), block)
    at = lp["attention"]
    b, s, _ = x.shape
    y = _rms_norm(x, lp["ln1_scale"], eps)
    q = (y @ at["q_kernel"]).reshape(b, s, heads, -1)
    kv = (y @ at["kv_kernel"]).reshape(b, s, 2 * kv_heads, -1)
    k, v = kv[:, :, :kv_heads], kv[:, :, kv_heads:]
    o = _eva(_rope(q, cos, sin), _rope(k, cos, sin), v, at["eva_phi"],
             at["eva_mu"], window, chunk)
    x = x + o.reshape(b, s, -1) @ at["out_kernel"]
    z = _rms_norm(x, lp["ln2_scale"], eps)
    return x + _swiglu(z, lp["mlp"]["fc1_kernel"], lp["mlp"]["fc2_kernel"])


@functools.partial(jax.jit, static_argnames=("eps", "size"))
def _head(x, g, output, start, eps: float, size: int):
    x = jax.lax.dynamic_slice_in_dim(x, start, size, axis=1)
    return _rms_norm(x, g.astype(F32), eps) @ output.astype(F32)


def reference_logits(params, config: dict, tokens, segment_ids, position_ids,
                     rows=None):
    """tokens / position_ids [B,S] (one sequence a row, from position 0;
    `segment_ids` is not read) -> logits float32 over the head's columns
    ``[B,S,vocab x num_pred_heads]``, or ``[B,size,.]`` for the positions
    start..start+size when `rows` = (start, size). `config` is a
    configuration file's top level."""
    del segment_ids
    window, chunk = config["window_size"], config["chunk_size"]
    eps = config["rms_norm_eps"]
    s = tokens.shape[1]
    pad = -s % window
    tokens = jnp.pad(tokens, ((0, 0), (0, pad)))
    position_ids = jnp.pad(position_ids, ((0, 0), (0, pad)))
    d = _head_dim(config)
    with jax.default_matmul_precision("highest"):
        inv_freq = 1.0 / (float(config["rope_theta"])
                          ** (jnp.arange(0, d, 2, dtype=F32) / d))
        angles = position_ids.astype(F32)[..., None] * inv_freq
        cos, sin = jnp.cos(angles), jnp.sin(angles)
        x = jnp.take(params["embedding"]["word"], tokens, axis=0).astype(F32)
        for i in range(_depth(config)):
            x = _layer(x, params["block"], jnp.int32(i), cos, sin,
                       heads=config["num_attention_heads"],
                       kv_heads=config["num_key_value_heads"],
                       window=window, chunk=chunk, eps=eps)
        start, size = rows if rows is not None else (0, s)
        return _head(x, params["final_ln_scale"], params["output"],
                     jnp.int32(start), eps=eps, size=size)


def reference_loss(params, config: dict, batch) -> float:
    """Mean cross entropy of the next byte (the head's first ``vocab_size``
    columns: prediction head 0) over the positions whose loss_mask is 1, for
    one micro-batch of ``generators/train_packed.py`` rows, one document a
    row (EVA's windows do not know packed segments). What a training cell of
    this model would be held to; none exists, and the program does not train
    such a head yet."""
    lg = reference_logits(params, config, jnp.asarray(batch["tokens"]),
                          jnp.asarray(batch["segment_ids"]),
                          jnp.asarray(batch["position_ids"]))
    lg = lg[..., :config["vocab_size"]]
    logz = jax.nn.logsumexp(lg, axis=-1)
    picked = jnp.take_along_axis(
        lg, jnp.asarray(batch["labels"])[..., None], axis=-1)[..., 0]
    mask = jnp.asarray(batch["loss_mask"], F32)
    return float(jnp.sum((logz - picked) * mask) / jnp.maximum(mask.sum(), 1))
