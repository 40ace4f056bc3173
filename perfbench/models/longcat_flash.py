"""``"model": "longcat_flash"``: LongCat-Flash-Chat as its ``config.json``
publishes it (the configuration file keeps the source's own keys), built as
the program's ``models/gpt.py`` model, with its plain reference and its
counts. What a model module gives the runners is listed in
``models/gpt_dense.py``.

The reference (``reference_logits``) is the published forward pass written
out in ``jax.numpy``, float32, matmuls at precision "highest". RMS(x; g) =
x / sqrt(mean(x^2) + eps) * g. A layer is the shortcut-connected double
layer (norms n1..n4, attentions A1, A2, dense SwiGLUs F1, F2, the MoE M)::

    x1 = x  + A1(RMS(x;  n1))
    h  =      RMS(x1; n2)
    m  = M(h)                        # the shortcut: added at the end
    x2 = x1 + F1(h)
    x3 = x2 + A2(RMS(x2; n3))
    x4 = x3 + F2(RMS(x3; n4))
    out = x4 + m

Latent attention with a query latent, UNABSORBED (s_q = sqrt(hidden /
q_lora_rank), s_kv = sqrt(hidden / kv_lora_rank))::

    c_q = RMS(u W_qa; g_q);  [q_n | q_r] = s_q (c_q W_qb)   per head
    [c | k_r] = u W_kva;     c' = s_kv RMS(c; g_kv)
    [k_n | v] = c' W_kvb     per head
    score = (q_n k_n + RoPE(q_r) RoPE(k_r)) / sqrt(nope + rope), causal softmax

with the one roped key broadcast to every head, RoPE theta from the file
over the roped columns, no YaRN. The MoE with zero-compute experts (E
published computing experts, Z identity experts behind them, bias b)::

    p = softmax(float32(h) W_r)           # over E + Z
    S = top-k indices of (p + b)          # selection sees the bias
    w_e = gamma p_e for e in S            # weights do not; not renormalised
    M(h) = sum_{e in S, e held} w_e SwiGLU_e(h) + (sum_{e in S, e >= E} w_e) h

THE SHARE. The program's parameter tree holds the experts ``first ..
first + count`` of the E published ones (the file's ``expert_share``); the
reference, like the program, routes over all E + Z, adds the held experts'
terms and the identity term, and leaves the other experts' terms out. That
partial result goes on to the next layer. The vocabulary is the slice the
file states: ids, logits and argmax run over its rows. No cache, no
kernels, no absorbed form, no ``ragged_dot``: one held expert at a time
over ALL tokens, with weight 0 for the tokens that did not choose it. It
reads the program's own tree a matrix group at a time (one attention
sublayer, one dense FFN, one expert), each upcast as it is used, so that it
fits beside the bf16 weights.

Departures from the published model, all of layout, none of mathematics:
- rope rotates the pairs (i, i + d/2) of the roped columns, the layout of
  this repository's ``ops/rotary.py``; the published code rotates its
  interleaved columns (2i, 2i+1), a fixed permutation of ``q_b``'s and
  ``kv_a``'s roped columns, which random weights do not see;
- gate and up projections are one ``fc1`` matrix ``[gate | up]`` (the tree's
  layout), ``kv_a_proj_with_mqa`` is ``kv_down`` ``[latent | rope key]``;
- the four norms are ``first.ln1``, ``first.ln2``, ``second.ln1``,
  ``second.ln2`` of the tree's two halves.
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

REHEARSAL = {"num_layers": 2, "hidden_size": 64, "num_attention_heads": 4,
             "ffn_hidden_size": 96, "expert_ffn_hidden_size": 48,
             "n_routed_experts": 4, "zero_expert_num": 4, "moe_topk": 3,
             "q_lora_rank": 32, "kv_lora_rank": 16, "qk_nope_head_dim": 16,
             "qk_rope_head_dim": 8, "v_head_dim": 16, "vocab_size": 512,
             "max_position_embeddings": 512,
             "published": {"num_layers": 2, "n_routed_experts": 8,
                           "vocab_size": 4096},
             "expert_share": {"first": 0}}


def _share(config: dict):
    """(published computing experts, first held, held here)."""
    return (config["published"]["n_routed_experts"],
            config["expert_share"]["first"], config["n_routed_experts"])


def kv_bytes_per_token(config: dict, dtype: str) -> int:
    """The scaled latent and the one roped key of BOTH attention sublayers
    of every layer, in `dtype`: two planes a layer."""
    return (2 * config["num_layers"]
            * (config["kv_lora_rank"] + config["qk_rope_head_dim"])
            * jnp.dtype(DTYPES[dtype]).itemsize)


def params_per_token(config: dict) -> float:
    """Matrix parameters a token meets in a layer here: two attention
    sublayers, two dense FFNs, the router, and its top-k's share of the held
    experts (topk * held / router width of them on average)."""
    h, nh = config["hidden_size"], config["num_attention_heads"]
    nope, rope = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    dv, klat, qlat = (config["v_head_dim"], config["kv_lora_rank"],
                      config["q_lora_rank"])
    experts, _, held = _share(config)
    width = experts + config["zero_expert_num"]
    attn = (h * qlat + qlat * nh * (nope + rope) + h * (klat + rope)
            + klat * nh * (nope + dv) + nh * dv * h)
    dense = 3 * h * config["ffn_hidden_size"]
    picks_here = config["moe_topk"] * held / width
    return (2 * attn + 2 * dense + h * width
            + picks_here * 3 * h * config["expert_ffn_hidden_size"])


def flops_per_token(config: dict, seq_len: int) -> float:
    """Forward and backward matmul operations per token (3 x 2 x the
    parameters a token meets, plus causal attention over `seq_len` in both
    sublayers), the yardstick an ``mfu`` reader would use; no cell of this
    model trains."""
    n, nh = config["num_layers"], config["num_attention_heads"]
    params = (n * params_per_token(config)
              + config["hidden_size"] * config["vocab_size"])
    scores = (2 * n * nh * (config["qk_nope_head_dim"]
                            + config["qk_rope_head_dim"]
                            + config["v_head_dim"]) * seq_len / 2)
    return 6.0 * (params + scores)


def model_config(config: dict, params_dtype: str, **extra):
    """The program's TransformerConfig for a configuration file that keeps
    the source's keys. Everything not named stays at the program's default.
    A program that lacks a field this model needs (the commit before the
    one that added it) fails here, at once and in words."""
    from megatronapp_tpu.config.transformer_config import (
        ActivationKind, NormKind, PositionEmbeddingKind, TransformerConfig,
    )
    if (config["attention_method"] != "MLA" or config["attention_bias"]
            or config["zero_expert_type"] != "identity"
            or not (config["mla_scale_q_lora"]
                    and config["mla_scale_kv_lora"])):
        raise SystemExit("perfbench: models/longcat_flash.py builds the "
                         "LongCat-Flash form only (MLA with both scale "
                         "corrections, identity zero-compute experts, no "
                         "biases)")
    experts, first, held = _share(config)
    try:
        return TransformerConfig(
            num_layers=config["num_layers"],
            hidden_size=config["hidden_size"],
            num_attention_heads=config["num_attention_heads"],
            ffn_hidden_size=config["ffn_hidden_size"],
            vocab_size=config["vocab_size"],
            vocab_slice_of=config["published"]["vocab_size"],
            max_position_embeddings=config["max_position_embeddings"],
            normalization=NormKind.rmsnorm,
            layernorm_epsilon=config["rms_norm_eps"],
            activation=ActivationKind.swiglu, add_bias_linear=False,
            untie_embeddings_and_output_weights=True,
            position_embedding=PositionEmbeddingKind.rope,
            rotary_base=float(config["rope_theta"]),
            multi_latent_attention=True,
            q_lora_rank=config["q_lora_rank"],
            kv_lora_rank=config["kv_lora_rank"],
            qk_head_dim=config["qk_nope_head_dim"],
            qk_pos_emb_head_dim=config["qk_rope_head_dim"],
            v_head_dim=config["v_head_dim"],
            mla_scale_q_lora=True, mla_scale_kv_lora=True,
            num_moe_experts=experts,
            moe_zero_experts=config["zero_expert_num"],
            moe_router_topk=config["moe_topk"],
            moe_ffn_hidden_size=config["expert_ffn_hidden_size"],
            moe_router_norm_topk_prob=False,
            moe_routed_scaling_factor=float(config["routed_scaling_factor"]),
            moe_router_selection_bias=True,
            moe_experts_held=(first, held),
            moe_shortcut_double_layer=True,
            params_dtype=DTYPES[params_dtype], **extra)
    except TypeError as e:
        raise SystemExit(
            "perfbench: this program cannot build LongCat-Flash (the "
            "shortcut-connected double layer, zero-compute experts, an "
            f"expert share, the latent scale corrections): {e}")


# ---- the plain reference ---------------------------------------------------

def _rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * weight


def _rope(x, cos, sin):
    """x [B,S,heads,d], cos/sin [B,S,d/2]: rotate the pairs (i, i + d/2)."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    c, s = cos[:, :, None, :], sin[:, :, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def _row(tree, i):
    """Layer i of a stacked tree, float32; i is traced, so one program
    serves every layer (a Python index would compile a slice a layer)."""
    return jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False)
        .astype(F32), tree)


def attention(u, at, cos, sin, segment_ids, dims, eps, scales=(True, True)):
    """A(u) for one sublayer's float32 parameters `at`. dims = (hidden,
    heads, nope, rope, dv, qlat, klat); `scales` drops a scale correction
    (the tests' control: a program that left one out)."""
    hidden, heads, nope, rope, dv, qlat, klat = dims
    b, s, _ = u.shape
    s_q = (hidden / qlat) ** 0.5 if scales[0] else 1.0
    s_kv = (hidden / klat) ** 0.5 if scales[1] else 1.0
    c_q = _rms_norm(u @ at["q_down"], at["q_ln_scale"], eps)
    q = (s_q * (c_q @ at["q_up"])).reshape(b, s, heads, nope + rope)
    q_nope, q_pe = q[..., :nope], q[..., nope:]
    ckv = u @ at["kv_down"]
    c = s_kv * _rms_norm(ckv[..., :klat], at["kv_ln_scale"], eps)
    k_pe = ckv[..., klat:]
    kv = (c @ at["kv_up"]).reshape(b, s, heads, nope + dv)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    q_pe = _rope(q_pe, cos, sin)
    k_pe = jnp.broadcast_to(_rope(k_pe[:, :, None, :], cos, sin),
                            (b, s, heads, rope))
    q = jnp.concatenate([q_nope, q_pe], axis=-1)
    k = jnp.concatenate([k_nope, k_pe], axis=-1)
    pos = jnp.arange(s)
    allowed = (pos[:, None] >= pos[None, :])[None]
    allowed = allowed & (segment_ids[:, :, None] == segment_ids[:, None, :])
    scale = (nope + rope) ** -0.5

    def one_head(qkv):          # one head at a time: [S, S] scores, not 64
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


def moe_terms(h, mp, route):
    """(the held experts' term, the identity term) of M(h) for one layer's
    parameters `mp` (router float32; the held experts' kernels in their own
    type, each upcast as it is used). route = (published experts E, first
    held, top_k, gamma). One held expert at a time over ALL tokens, with
    weight 0 for the tokens that did not choose it."""
    experts, first, top_k, gamma = route
    b, s, hid = h.shape
    flat = h.reshape(b * s, hid)
    probs = jax.nn.softmax(flat @ mp["router_kernel"].astype(F32), axis=-1)
    _, picked = jax.lax.top_k(probs + mp["router_bias"].astype(F32), top_k)
    w = gamma * jnp.take_along_axis(probs, picked, axis=-1)
    weights = jnp.sum(jax.nn.one_hot(picked, probs.shape[-1], dtype=F32)
                      * w[..., None], axis=1)                   # [T, E + Z]
    held = mp["fc1_kernel"].shape[0]

    def one_expert(acc, xs):
        fc1, fc2, w_e = xs
        return acc + _swiglu(flat, fc1.astype(F32),
                             fc2.astype(F32)) * w_e[:, None], None

    routed, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(flat),
        (mp["fc1_kernel"], mp["fc2_kernel"],
         weights[:, first:first + held].T))
    identity = jnp.sum(weights[:, experts:], axis=-1, keepdims=True) * flat
    return routed.reshape(b, s, hid), identity.reshape(b, s, hid)


# One program a sublayer, so that a pass holds one group of float32
# matrices at a time (the largest: a dense FFN's pair, 0.9 GB).
@functools.partial(jax.jit, static_argnames=("dims", "eps", "scales"))
def _attend(x, half, i, cos, sin, segment_ids, dims, eps, scales):
    """x + A(RMS(x; ln1)) for half-layer stack `half`, layer i."""
    at = _row(half["attention"], i)
    ln = _row(half["ln1_scale"], i)
    return x + attention(_rms_norm(x, ln, eps), at, cos, sin, segment_ids,
                         dims, eps, scales)


@functools.partial(jax.jit, static_argnames=("eps",))
def _normed(x, ln_stack, i, eps):
    return _rms_norm(x, _row(ln_stack, i), eps)


@jax.jit
def _dense(x, h, mlp_stack, i):
    """x + F(h)."""
    mlp = _row(mlp_stack, i)
    return x + _swiglu(h, mlp["fc1_kernel"], mlp["fc2_kernel"])


@functools.partial(jax.jit, static_argnames=("route",))
def _moe(h, moe_stack, i, route):
    mp = jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False),
        moe_stack)
    routed, identity = moe_terms(h, mp, route)
    return routed + identity


@functools.partial(jax.jit, static_argnames=("eps", "size"))
def _head(x, scale, output, start, eps: float, size: int):
    x = jax.lax.dynamic_slice_in_dim(x, start, size, axis=1)
    return _rms_norm(x, scale.astype(F32), eps) @ output.astype(F32)


def reference_logits(params, config: dict, tokens, segment_ids, position_ids,
                     rows=None, scales=(True, True)):
    """tokens/segment_ids/position_ids [B,S] -> logits float32 over the
    vocabulary slice, [B,S,V], or [B,size,V] for the positions
    start..start+size when `rows` = (start, size). `config` is a
    configuration file's top level; `params` the program's tree, which holds
    the share of the experts the file states."""
    rope = config["qk_rope_head_dim"]
    dims = (config["hidden_size"], config["num_attention_heads"],
            config["qk_nope_head_dim"], rope, config["v_head_dim"],
            config["q_lora_rank"], config["kv_lora_rank"])
    eps = config["rms_norm_eps"]
    experts, first, _ = _share(config)
    route = (experts, first, config["moe_topk"],
             float(config["routed_scaling_factor"]))
    attend = functools.partial(_attend, dims=dims, eps=eps, scales=scales)
    with jax.default_matmul_precision("highest"):
        inv_freq = 1.0 / float(config["rope_theta"]) ** (
            jnp.arange(0, rope, 2, dtype=F32) / rope)
        angles = position_ids.astype(F32)[..., None] * inv_freq
        cos, sin = jnp.cos(angles), jnp.sin(angles)
        x = jnp.take(params["embedding"]["word"], tokens, axis=0).astype(F32)
        one, two = params["block"]["first"], params["block"]["second"]
        for layer in range(jax.tree.leaves(one)[0].shape[0]):
            i = jnp.int32(layer)
            x = attend(x, one, i, cos, sin, segment_ids)
            h = _normed(x, one["ln2_scale"], i, eps=eps)
            m = _moe(h, one["moe"], i, route=route)
            x = _dense(x, h, one["mlp"], i)
            x = attend(x, two, i, cos, sin, segment_ids)
            x = _dense(x, _normed(x, two["ln2_scale"], i, eps=eps),
                       two["mlp"], i)
            x = x + m
        start, size = rows if rows is not None else (0, x.shape[1])
        return _head(x, params["final_ln_scale"], params["output"],
                     jnp.int32(start), eps=eps, size=size)
