"""Kimi delta attention: the fourth kind of a layer's first half, beside
attention.py, ssm.py and shortconv.py (Kimi Linear, arXiv:2510.26692; the
open `fla` layer `KimiDeltaAttention`; HF `solar_open2`'s linear-attention
layers). With u the layer's normed input, `heads` heads of K key channels
and V value columns (both 128 as published):

    [q~ | k~ | v] = silu(conv_k(u W_qkv))       # causal, depthwise, no bias
    q = q~ / |q~| K^-1/2 ;  k = k~ / |k~|       # a head, 1e-6 under the root
    g = -exp(A_log[h]) softplus(u W_f1 W_f2 + dt_bias)   # [heads, K], <= 0
    b = 2 sigmoid(u W_b)                        # a head, in (0, 2)
    S' = diag(exp(g_t)) S_{t-1}                 # a decay a KEY CHANNEL
    S_t = S' + b_t k_t (v_t - S'^T k_t)^T       # the delta rule
    o_t = S_t^T q_t
    out = (RMS_head(o_t; w) * sigmoid(u W_g1 W_g2 + b_g)) W_o

The state a head is S [K, V] float32. The factor 2 on b puts the eigenvalue
of I - b k k^T along k into (-1, 1) (`kda_allow_neg_eigval`). The update
READS the state before it writes it (S'^T k), which no state-space mixer
here does: `ssm.ssd_chunked` and `ssm_update` cannot be bent into it.

The state lives where the state-space mixers' does, as h [B, K, E] float32,
E = heads x V minor (h[c, head * V + j] = S[head][c, j]), and the
convolution's last k-1 inputs over q, k and v side by side ([B, k-1, 3E]).

A whole sequence (training, a prefill call) is the CHUNKED pass
(`kda_chunked`): chunks of `chunk` positions one after the other, the state
carried between them. With G_i the running sum of g inside a chunk, S_0 the
state that came in and U the chunk's "pseudo values" (u_i = b_i (v_i -
S'_i^T k_i)):

    A_ij = b_i sum_c k_i[c] k_j[c] exp(G_i[c] - G_j[c])      (j < i)
    (I + A) U = b (V - (K e^G) S_0)
    B_ij = sum_c q_i[c] k_j[c] exp(G_i[c] - G_j[c])          (j <= i)
    O = (Q e^G) S_0 + B U
    S_C = e^{G_C} S_0 + (K e^{G_C - G})^T U

UNDER STRONG DECAY (A up to 16, softplus near 1) a chunk's G passes -1,000,
so the textbook factors (K e^G)(K e^-G)^T overflow float32. Every decay here
is formed as exp(G_i - G_j) with i >= j, never above 1: a chunk is cut into
sub-chunks of SUB (16) positions; a block of A or B BELOW the diagonal is
(K_I e^{G_I - r_I})(K_J e^{r_I - G_J})^T with r_I the G of sub-chunk I's
first position (both exponents <= 0: an underflow to 0 is the right answer,
the true product is smaller still), and a block ON the diagonal takes its
[SUB, SUB, K] decays directly. The unit-lower-triangular system is solved by
forward substitution (each diagonal block inverted row by row in float32,
then block after block), never by powers of A, which cancel.

A decode step is one Pallas call that updates the running slots' states in
place (ops/pallas/kda_update.py).

Param leaves: qkv_kernel [H, 3E], conv_kernel [k, 3E], f_down [H, R], f_up
[R, E], dt_bias [E], A_log [heads], beta_kernel [H, heads], g_down [H, R],
g_up [R, E], g_bias [E], norm_scale [V], out_kernel [E, H]; the rank R is
the head's width V. 137,740,480 a layer at the published sizes.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from megatronapp_tpu.config.transformer_config import TransformerConfig
from megatronapp_tpu.ops.normalization import rms_norm
from megatronapp_tpu.transformer.ssm import (
    _causal_conv, _init_dt_bias, _last_inputs,
)

SUB = 16            # positions a sub-chunk of the chunked pass
HIGHEST = jax.lax.Precision.HIGHEST


class KdaDims(NamedTuple):
    heads: int
    key_dim: int        # K: a head's key channels (the state's rows)
    value_dim: int      # V: a head's value columns
    conv_kernel: int
    rank: int           # of the decay's and the output gate's projections
    chunk: int

    @property
    def inner(self) -> int:
        return self.heads * self.value_dim


def kda_dims(cfg: TransformerConfig) -> KdaDims:
    if cfg.ssm_state_dim != cfg.ssm_head_dim:
        raise NotImplementedError(
            "Kimi delta attention is written for heads whose key channels "
            f"(ssm_state_dim={cfg.ssm_state_dim}) and value columns "
            f"(ssm_head_dim={cfg.ssm_head_dim}) are as many, as published")
    return KdaDims(cfg.kda_heads, cfg.ssm_state_dim, cfg.ssm_head_dim,
                   cfg.ssm_conv_kernel, cfg.ssm_head_dim, cfg.ssm_chunk_size)


def init_kda_params(rng, cfg: TransformerConfig, out_std):
    """std for the matrices, the taps too; A = -(1..16) uniform a head and
    softplus(dt_bias) log-uniform in [1e-3, 1e-1] a channel (the
    state-space mixers' draws), g_bias 0, the head norm's scale 1."""
    dims = kda_dims(cfg)
    h, e, r = cfg.hidden_size, dims.inner, dims.rank
    keys = jax.random.split(rng, 10)
    std, dt = cfg.init_method_std, cfg.params_dtype

    def normal(key, shape, scale=std):
        return jax.random.normal(key, shape, dt) * scale

    p = {
        "qkv_kernel": normal(keys[0], (h, 3 * e)),
        "conv_kernel": normal(keys[1], (dims.conv_kernel, 3 * e)),
        "f_down": normal(keys[2], (h, r)),
        "f_up": normal(keys[3], (r, e)),
        "dt_bias": _init_dt_bias(keys[4], (e,), dt),
        "A_log": jnp.log(jax.random.uniform(
            keys[5], (dims.heads,), jnp.float32, 1.0, 16.0)).astype(dt),
        "beta_kernel": normal(keys[6], (h, dims.heads)),
        "g_down": normal(keys[7], (h, r)),
        "g_up": normal(keys[8], (r, e)),
        "g_bias": jnp.zeros((e,), dt),
        "norm_scale": jnp.ones((dims.value_dim,), dt),
        "out_kernel": normal(keys[9], (e, h), out_std),
    }
    ax = {
        "qkv_kernel": ("embed", "mlp"), "conv_kernel": (None, "mlp"),
        "f_down": ("embed", None), "f_up": (None, "mlp"),
        "dt_bias": ("mlp",), "A_log": (None,),
        "beta_kernel": ("embed", None), "g_down": ("embed", None),
        "g_up": (None, "mlp"), "g_bias": ("mlp",), "norm_scale": (None,),
        "out_kernel": ("mlp", "embed"),
    }
    return p, ax


def _unit_lower_inverse(lower):
    """(I + L)^-1 for L [..., m, m] STRICTLY lower triangular, by forward
    substitution a row: row i of the inverse is e_i - L[i, :] X, which reads
    the rows above it alone. Elementwise float32, m - 1 steps."""
    m = lower.shape[-1]
    eye = jnp.eye(m, dtype=lower.dtype)
    x = jnp.broadcast_to(eye, lower.shape)
    for i in range(1, m):
        row = eye[i] - jnp.sum(lower[..., i, :, None] * x, axis=-2)
        x = x.at[..., i, :].set(row)
    return x


def kda_chunked(q, k, v, g, beta, chunk: int, s0=None):
    """The gated delta rule over a whole sequence as matrix products a
    chunk (the module's text). q, k [B,S,heads,K] and v [B,S,heads,V] in the
    compute type; g [B,S,heads,K] float32 (log decays, <= 0); beta
    [B,S,heads] float32; s0 [B,heads,K,V] float32 or None (zeros) ->
    (o [B,S,heads,V] float32, S after the last position [B,heads,K,V]
    float32). A position whose g and beta are 0 leaves the state as it was
    (so does the padding of the last chunk). The products take their
    operands in q's type and add up in float32; the triangular solve stays
    float32."""
    bsz, s, heads, kd = q.shape
    vd = v.shape[-1]
    f32, cd = jnp.float32, q.dtype
    c = -(-min(chunk, s) // SUB) * SUB      # whole sub-chunks
    n = c // SUB
    chunks = -(-s // c)

    def split(t):               # [B,S,heads,...] -> [chunks,B,heads,c,...]
        t = jnp.pad(t, ((0, 0), (0, chunks * c - s))
                    + ((0, 0),) * (t.ndim - 2))
        t = t.reshape((bsz, chunks, c) + t.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(t, 3, 2), 1, 0)

    at = jnp.arange(c)
    # [n, c]: the positions before sub-chunk I
    before = at[None, :] < (jnp.arange(n) * SUB)[:, None]
    tri = at[:SUB, None] >= at[None, :SUB]      # i >= j inside a sub-chunk
    strict = jnp.tril(jnp.ones((c, c), bool), -1)
    block_eye = jnp.eye(n, dtype=f32)

    def on_diagonal(blocks):    # [B,heads,n,SUB,SUB] -> [B,heads,c,c]
        full = blocks[:, :, :, :, None, :] * block_eye[:, None, :, None]
        return full.reshape(bsz, heads, c, c)

    def step(state, xs):
        q_c, k_c, v_c, g_c, b_c = xs
        cum = jnp.cumsum(g_c, axis=2)                       # [B,h,c,K]
        cums = cum.reshape(bsz, heads, n, SUB, kd)
        ref = cums[:, :, :, 0]                              # [B,h,n,K]
        qf, kf = q_c.astype(f32), k_c.astype(f32)
        # below the diagonal: (X_I e^{G_I - r_I}) (K_J e^{r_I - G_J})^T
        left = jnp.exp(cums - ref[:, :, :, None])           # [B,h,n,SUB,K]
        right = jnp.exp(jnp.where(
            before[None, None, :, :, None],
            ref[:, :, :, None] - cum[:, :, None], -jnp.inf))  # [B,h,n,c,K]
        k_right = (kf[:, :, None] * right).astype(cd)
        ks = kf.reshape(bsz, heads, n, SUB, kd)
        qs = qf.reshape(bsz, heads, n, SUB, kd)

        def below(xs_):
            return jnp.einsum(
                "bhnik,bhnjk->bhnij", (xs_ * left).astype(cd), k_right,
                preferred_element_type=f32).reshape(bsz, heads, c, c)

        # on the diagonal: the decays themselves, [SUB, SUB, K]
        decay = jnp.exp(jnp.where(
            tri[..., None],
            cums[:, :, :, :, None] - cums[:, :, :, None, :], -jnp.inf))

        def diagonal(xs_):
            return on_diagonal(jnp.einsum("bhnik,bhnjk,bhnijk->bhnij",
                                          xs_, ks, decay))

        a = jnp.where(strict, below(ks) + diagonal(ks), 0.0) \
            * b_c[..., None]                                # [B,h,c,c]
        qk = below(qs) + diagonal(qs)       # j <= i; 0 above the diagonal
        e_cum = jnp.exp(cum)
        # what came in: (K e^G) S_0 and (Q e^G) S_0
        k_in = jnp.einsum("bhck,bhkv->bhcv", (kf * e_cum).astype(cd),
                          state.astype(cd), preferred_element_type=f32)
        q_in = jnp.einsum("bhck,bhkv->bhcv", (qf * e_cum).astype(cd),
                          state.astype(cd), preferred_element_type=f32)
        rhs = b_c[..., None] * (v_c.astype(f32) - k_in)     # [B,h,c,V]
        # (I + A) U = rhs, block after block
        inv = _unit_lower_inverse(
            a.reshape(bsz, heads, n, SUB, n, SUB)[
                :, :, jnp.arange(n), :, jnp.arange(n)])     # [n,B,h,SUB,SUB]
        u_blocks = []
        for i in range(n):
            r = rhs[:, :, i * SUB:(i + 1) * SUB]
            if i:
                r = r - jnp.einsum(
                    "bhij,bhjv->bhiv", a[:, :, i * SUB:(i + 1) * SUB,
                                         :i * SUB],
                    jnp.concatenate(u_blocks, axis=2), precision=HIGHEST)
            u_blocks.append(jnp.einsum("bhij,bhjv->bhiv", inv[i], r,
                                       precision=HIGHEST))
        u = jnp.concatenate(u_blocks, axis=2)               # [B,h,c,V]
        o = q_in + jnp.einsum("bhij,bhjv->bhiv", qk.astype(cd), u.astype(cd),
                              preferred_element_type=f32)
        last = cum[:, :, -1:]                               # [B,h,1,K]
        state = jnp.exp(last[:, :, 0])[..., None] * state + jnp.einsum(
            "bhck,bhcv->bhkv", (kf * jnp.exp(last - cum)).astype(cd),
            u.astype(cd), preferred_element_type=f32)
        return state, o

    if s0 is None:
        s0 = jnp.zeros((bsz, heads, kd, vd), f32)
    state, o = jax.lax.scan(step, s0, tuple(map(split, (q, k, v, g, beta))))
    # [chunks,B,heads,c,V] -> [B,S,heads,V]
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 1), 2, 3).reshape(
        bsz, chunks * c, heads, vd)[:, :s]
    return o, state


def _plain_update(h, q, k, v, alpha, beta):
    from megatronapp_tpu.ops.pallas.kda_update import kda_update_reference
    return kda_update_reference(h, q, k, v, alpha, beta)


def _to_heads(h, heads: int):       # [B,K,E] -> [B,heads,K,V]
    b, kd, e = h.shape
    return jnp.swapaxes(h.reshape(b, kd, heads, e // heads), 1, 2)


def _to_pool(s):                    # [B,heads,K,V] -> [B,K,E]
    b, heads, kd, vd = s.shape
    return jnp.swapaxes(s, 1, 2).reshape(b, kd, heads * vd)


def kda_forward(p, x, cfg: TransformerConfig, state=None, counts=None,
                update=_plain_update):
    """x [B,S,H] -> (out [B,S,H], (conv_tail [B,k-1,3E], h [B,K,E])).

    state: the (conv_tail, h) a sequence arrives with; None is a sequence's
    start (zeros). counts [B]: row b's first counts[b] positions are real
    and the rest padding, which neither moves the state (its g and beta are
    0) nor enters the new tail. update(h, q, k, v, alpha, beta) -> (o, h'):
    how one token (S == 1 on a given state) advances h; the paged engine
    passes its in-place kernel, whose h is the whole pool (state[1] goes to
    it as it came)."""
    dims = kda_dims(cfg)
    bsz, s, _ = x.shape
    heads, kd, vd, e = dims.heads, dims.key_dim, dims.value_dim, dims.inner
    f32, cd = jnp.float32, cfg.compute_dtype
    u = x.astype(cd)
    tail, h0 = state if state is not None else (None, None)
    padded, qkv = _causal_conv(u @ p["qkv_kernel"].astype(cd), tail, p,
                               dims.conv_kernel)
    q, k, v = (t.reshape(bsz, s, heads, -1)
               for t in jnp.split(qkv, [heads * kd, 2 * heads * kd], axis=-1))
    q = q * jax.lax.rsqrt(jnp.sum(q * q, -1, keepdims=True) + 1e-6) \
        * kd ** -0.5
    k = k * jax.lax.rsqrt(jnp.sum(k * k, -1, keepdims=True) + 1e-6)
    g = -jnp.exp(p["A_log"].astype(f32))[:, None] * jax.nn.softplus((
        (u @ p["f_down"].astype(cd)) @ p["f_up"].astype(cd)).astype(f32)
        + p["dt_bias"].astype(f32)).reshape(bsz, s, heads, kd)
    beta = 2.0 * jax.nn.sigmoid(
        (u @ p["beta_kernel"].astype(cd)).astype(f32))     # [B,S,heads]
    if counts is not None:
        real = jnp.arange(s)[None, :] < counts[:, None]
        g = jnp.where(real[..., None, None], g, 0.0)
        beta = jnp.where(real[..., None], beta, 0.0)
    if s == 1 and h0 is not None:
        o, h_new = update(h0, q[:, 0], k[:, 0], v[:, 0], jnp.exp(g[:, 0]),
                          beta[:, 0])
        o = o.reshape(bsz, 1, heads, vd)
    else:
        with jax.named_scope("kda_chunk"):
            o, s_new = kda_chunked(
                q.astype(cd), k.astype(cd), v.astype(cd), g, beta, dims.chunk,
                None if h0 is None else _to_heads(h0, heads))
            h_new = _to_pool(s_new)
    with jax.named_scope("kda_gate_norm"):
        gate = jax.nn.sigmoid((
            (u @ p["g_down"].astype(cd)) @ p["g_up"].astype(cd)).astype(f32)
            + p["g_bias"].astype(f32))
        y = rms_norm(o.astype(f32), p["norm_scale"], cfg.layernorm_epsilon)
        y = (y.reshape(bsz, s, e) * gate).astype(cd)
    out = y @ p["out_kernel"].astype(cd)
    return out, (_last_inputs(padded, s, counts, dims.conv_kernel), h_new)


def kda_paged_forward(p, x, cfg: TransformerConfig, state, rows=None,
                      starts=None, counts=None, active=None):
    """The mixer inside a paged serving step: `ssm.ssm_paged_forward`'s
    arguments and results on the same two pools (state = (S pool [L, slots,
    K, E] f32, tail pool [L, slots, (k-1) * 3E], this layer's plane)). A
    decode round (counts None) shifts the tails in XLA and advances S in the
    kda_update kernel, in place; a prefill call (counts given) runs the
    chunked pass on each row from its slot's state (zeros where starts[b]
    == 0) and writes the state back."""
    from megatronapp_tpu.ops.pallas.kda_update import kda_update
    pool, conv, index = state
    dims = kda_dims(cfg)
    bsz = x.shape[0]
    index = jnp.asarray(index, jnp.int32)
    if active is None:
        active = jnp.ones((bsz,), bool)
    zero = jnp.int32(0)
    taps = dims.conv_kernel - 1
    c = conv.shape[2] // taps
    if counts is None:
        if rows is not None or bsz != pool.shape[1] or x.shape[1] != 1:
            raise ValueError("a decode round advances every slot by one "
                             "token: x is [slots, 1, H]")
        # lane slices and a stack, not a reshape (ssm_paged_forward)
        flat = jax.lax.dynamic_index_in_dim(conv, index, 0, keepdims=False)
        tail = jnp.stack([flat[:, i * c:(i + 1) * c] for i in range(taps)],
                         axis=1)

        def update(pool, q, k, v, alpha, beta):
            with jax.named_scope("kda_update"):
                return kda_update(pool, index, q, k, v, alpha, beta, active)

        out, (new_tail, pool) = kda_forward(p, x, cfg, state=(tail, pool),
                                            update=update)
        new_tail = jnp.where(active[:, None, None],
                             new_tail.astype(conv.dtype), tail)
        conv = jax.lax.dynamic_update_slice(
            conv, jnp.concatenate([new_tail[:, i] for i in range(taps)],
                                  axis=-1)[None], (index, zero, zero))
        return out, (pool, conv)

    if rows is None:
        rows = jnp.arange(bsz, dtype=jnp.int32)

    def h_of(b):
        return jax.lax.dynamic_slice(
            pool, (index, rows[b], zero, zero), (1, 1) + pool.shape[2:])[0]

    def tail_of(b):
        return jax.lax.dynamic_slice(
            conv, (index, rows[b], zero), (1, 1, taps * c)).reshape(
                1, taps, c)

    fresh = (starts == 0)[:, None, None]
    h0 = jnp.concatenate([h_of(b) for b in range(bsz)])
    tail = jnp.concatenate([tail_of(b) for b in range(bsz)])
    out, (new_tail, h_new) = kda_forward(
        p, x, cfg, counts=counts,
        state=(jnp.where(fresh, 0, tail), jnp.where(fresh, 0.0, h0)))
    keep = active[:, None, None]
    h_new = jnp.where(keep, h_new, h0)
    new_tail = jnp.where(keep, new_tail.astype(conv.dtype), tail)
    for b in range(bsz):
        pool = jax.lax.dynamic_update_slice(
            pool, h_new[b][None, None], (index, rows[b], zero, zero))
        conv = jax.lax.dynamic_update_slice(
            conv, new_tail[b].reshape(1, 1, taps * c),
            (index, rows[b], zero))
    return out, (pool, conv)
