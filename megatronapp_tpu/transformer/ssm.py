"""Selective-state-space mixer (Mamba-1): the third kind of a layer's first
half, beside attention.py and mla.py.

Parity with /root/reference/megatron/core/ssm/mamba_mixer.py and HF
`modeling_jamba.JambaMambaMixer`: in_proj -> (u, z); causal depthwise
conv1d over the last k positions; silu; data-dependent dt, B, C (Jamba:
each through an RMS norm of its own); the recurrence

    h_t = exp(dt_t * A) * h_{t-1} + (dt_t * B_t) * u_t ;  y_t = C_t . h_t + D * u_t

over a diagonal A; gate by silu(z); out_proj. The reference leans on Triton
kernels for the scan; here a whole sequence (training, a prefill chunk) is
a `lax.associative_scan` a block of positions (the first-order recurrence
is associative, so XLA lowers it to a log-depth parallel scan), the blocks
in sequence, and a decode step is one
Pallas call that updates the state in place (ops/pallas/ssm_update.py).

The state is h [B, N, E] float32, E (the expanded width) minor: E is a
whole number of 128-lane vregs where N (16) would be padded eightfold. The
parameters keep the published layout (A_log [E, N]).

Param leaves: in_kernel [H, 2E], conv_kernel [k, E], conv_bias [E],
x_proj [E, R + 2N], dt_proj [R, E], dt_bias [E], A_log [E, N], D [E],
out_kernel [E, H]; with ssm_inner_norms dt_ln_scale [R], b_ln_scale [N],
c_ln_scale [N]. The convolution has a bias and the two projections none:
every model here says so (HF mamba_conv_bias true, mamba_proj_bias false),
so they are no switches.
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from megatronapp_tpu.config.transformer_config import TransformerConfig
from megatronapp_tpu.ops.normalization import rms_norm


class SsmDims(NamedTuple):
    """The mixer's sizes and its one switch (TransformerConfig's ssm_*
    fields; models/mamba.py builds one from its MambaConfig)."""
    state_dim: int = 16
    conv_kernel: int = 4
    expand: int = 2
    dt_rank: Optional[int] = None
    inner_norms: bool = False

    def rank(self, hidden: int) -> int:
        return self.dt_rank or max(hidden // 16, 1)


def ssm_dims(cfg: TransformerConfig) -> SsmDims:
    return SsmDims(cfg.ssm_state_dim, cfg.ssm_conv_kernel, cfg.ssm_expand,
                   cfg.ssm_dt_rank, cfg.ssm_inner_norms)


def init_ssm_params(rng, cfg: TransformerConfig, dims: SsmDims,
                    out_std=None):
    h = cfg.hidden_size
    e = dims.expand * h
    n = dims.state_dim
    dt_rank = dims.rank(h)
    keys = jax.random.split(rng, 6)
    std = cfg.init_method_std
    if out_std is None:
        out_std = std / jnp.sqrt(2.0 * cfg.num_layers)
    p = {
        "in_kernel": jax.random.normal(keys[0], (h, 2 * e),
                                       cfg.params_dtype) * std,
        "conv_kernel": jax.random.normal(
            keys[1], (dims.conv_kernel, e), cfg.params_dtype) * std,
        "conv_bias": jnp.zeros((e,), cfg.params_dtype),
        # x → (Δ_rank, B, C)
        "x_proj": jax.random.normal(keys[2], (e, dt_rank + 2 * n),
                                    cfg.params_dtype) * std,
        "dt_proj": jax.random.normal(keys[3], (dt_rank, e),
                                     cfg.params_dtype) * std,
        # softplus(dt_bias) initialized in [1e-3, 1e-1] (reference dt init).
        "dt_bias": jnp.log(jnp.expm1(jnp.exp(jax.random.uniform(
            keys[4], (e,), jnp.float32,
            jnp.log(1e-3), jnp.log(1e-1))))).astype(cfg.params_dtype),
        # A negative-real diagonal, initialized -[1..N] per channel.
        "A_log": jnp.log(jnp.tile(jnp.arange(1, n + 1, dtype=jnp.float32),
                                  (e, 1))).astype(cfg.params_dtype),
        "D": jnp.ones((e,), cfg.params_dtype),
        "out_kernel": jax.random.normal(
            keys[5], (e, h), cfg.params_dtype) * out_std,
    }
    ax = {
        "in_kernel": ("embed", "mlp"), "conv_kernel": (None, "mlp"),
        "conv_bias": ("mlp",),
        "x_proj": ("mlp", None), "dt_proj": (None, "mlp"),
        "dt_bias": ("mlp",), "A_log": ("mlp", None), "D": ("mlp",),
        "out_kernel": ("mlp", "embed"),
    }
    if dims.inner_norms:
        for name, width in (("dt_ln_scale", dt_rank), ("b_ln_scale", n),
                            ("c_ln_scale", n)):
            p[name] = jnp.ones((width,), cfg.params_dtype)
            ax[name] = (None,)
    return p, ax


# Positions one parallel scan holds. A longer sequence is scanned block
# after block, the state carried between them: lax.associative_scan makes
# log2(S) passes over [S, N, E] float32 operands, and what it costs a
# position depends on where XLA keeps them. Read off a v5e at [., 16, 5120]
# (21 MB an operand at 64 positions): one scan costs 0.05 ms a position of a
# 26-layer prefill call up to 64 positions and 0.3 ms from 96 on; compiled
# for that chip, a [1, 256] call in blocks of 64 (or 32) holds 20 MB of
# temporaries in HBM and one in blocks of 128 holds 120 MB; on the chip,
# blocks of 32 and of 64 serve alike (PERF.md section 6, PR 35).
SCAN_BLOCK = 64


def _scan_block(u, dt, a_t, b, c, d, h0=None):
    """selective_scan over one block: a parallel associative scan."""
    a = jnp.exp(dt[:, :, None, :] * a_t[None, None])          # [B,S,N,E]
    x = dt[:, :, None, :] * b[..., None] * u[:, :, None, :]   # [B,S,N,E]
    if h0 is not None:
        x = x.at[:, 0].add(a[:, 0] * h0)

    def combine(left, right):
        a_l, x_l = left
        a_r, x_r = right
        return a_l * a_r, a_r * x_l + x_r

    _, h = jax.lax.associative_scan(combine, (a, x), axis=1)
    y = jnp.einsum("bsne,bsn->bse", h, c) + u * d[None, None]
    return y, h[:, -1]


def selective_scan(u, dt, a_t, b, c, d, h0=None):
    """u, dt [B,S,E]; a_t [N,E] (A transposed); b, c [B,S,N]; d [E];
    h0 [B,N,E] or None (zeros) → (y [B,S,E], h_S [B,N,E]).

    A parallel associative scan over blocks of SCAN_BLOCK positions, the
    blocks one after the other (S up to a block: one scan, no loop). A
    position whose dt is 0 leaves the state as it was (exp(0) = 1 and a
    zero input): so does the padding of the last block."""
    bsz, s, e = u.shape
    if s <= SCAN_BLOCK:
        return _scan_block(u, dt, a_t, b, c, d, h0)
    blocks = -(-s // SCAN_BLOCK)

    def split(t):                           # [B,S,.] → [blocks,B,block,.]
        t = jnp.pad(t, ((0, 0), (0, blocks * SCAN_BLOCK - s), (0, 0)))
        return jnp.swapaxes(
            t.reshape(bsz, blocks, SCAN_BLOCK, t.shape[-1]), 0, 1)

    def step(h, xs):
        y, h = _scan_block(*xs[:2], a_t, *xs[2:], d, h)
        return h, y

    if h0 is None:
        h0 = jnp.zeros((bsz,) + a_t.shape,
                       jnp.result_type(u, dt, a_t, b))
    h, y = jax.lax.scan(step, h0, tuple(map(split, (u, dt, b, c))))
    return jnp.swapaxes(y, 0, 1).reshape(bsz, -1, e)[:, :s], h


def _plain_update(h, dt, u, b, c, a_t, d):
    from megatronapp_tpu.ops.pallas.ssm_update import ssm_update_reference
    return ssm_update_reference(h, dt, u, b, c, a_t, d)


def ssm_forward(p, x, cfg: TransformerConfig, dims: SsmDims, state=None,
                counts=None, update=_plain_update):
    """x [B,S,H] → (out [B,S,H], (conv_tail [B,k-1,E], h [B,N,E])).

    state: the (conv_tail, h) a sequence arrives with; None is a sequence's
    start (zeros). conv_tail holds the last k-1 inputs of the convolution.
    counts [B]: row b's first counts[b] positions are real and the rest
    padding, which neither advances h (its dt is 0) nor enters the new
    tail: that is the last k-1 REAL inputs, taken across the chunk's edge
    from the old tail where the count is under k-1.
    update(h, dt, u, b, c, a_t, d) → (y, h'): how one token (S == 1 on a
    given state) advances h; the paged engine passes its in-place kernel,
    whose h is the whole pool (state[1] goes to it as it came)."""
    bsz, s, hidden = x.shape
    n = dims.state_dim
    dt_rank = dims.rank(hidden)
    k = dims.conv_kernel
    f32 = jnp.float32
    cd = cfg.compute_dtype
    u_raw, z = jnp.split(x.astype(cd) @ p["in_kernel"].astype(cd), 2,
                         axis=-1)

    # Causal depthwise conv along seq, over the tail and the new inputs.
    tail, h0 = state if state is not None else (None, None)
    if tail is None:
        u_pad = jnp.pad(u_raw, ((0, 0), (k - 1, 0), (0, 0)))
    else:
        u_pad = jnp.concatenate([tail.astype(u_raw.dtype), u_raw], axis=1)
    # k shifted products summed in float32, elementwise: as a dot_general
    # (one contraction of length k a channel) XLA:TPU lays the operands
    # out batch-minor and relayouts the tails on the way in and out.
    taps = p["conv_kernel"].astype(f32)
    u = sum(u_pad[:, i:i + s].astype(f32) * taps[i] for i in range(k))
    u = jax.nn.silu(u + p["conv_bias"].astype(f32)).astype(cd)

    proj = u @ p["x_proj"].astype(u.dtype)  # [B,S,dt_rank+2N]
    dt_r, b_, c_ = jnp.split(proj, [dt_rank, dt_rank + n], axis=-1)
    if "dt_ln_scale" in p:
        eps = cfg.layernorm_epsilon
        dt_r = rms_norm(dt_r, p["dt_ln_scale"], eps)
        b_ = rms_norm(b_, p["b_ln_scale"], eps)
        c_ = rms_norm(c_, p["c_ln_scale"], eps)
    dt = jax.nn.softplus(
        dt_r.astype(f32) @ p["dt_proj"].astype(f32)
        + p["dt_bias"].astype(f32))
    if counts is not None:
        dt = jnp.where(jnp.arange(s)[None, :, None] < counts[:, None, None],
                       dt, 0.0)
    a_t = -jnp.exp(p["A_log"].astype(f32)).T
    d = p["D"].astype(f32)
    if s == 1 and h0 is not None:
        y, h_new = update(h0, dt[:, 0], u[:, 0].astype(f32),
                          b_[:, 0].astype(f32), c_[:, 0].astype(f32), a_t, d)
        y = y[:, None]
    else:
        y, h_new = selective_scan(u.astype(f32), dt, a_t, b_.astype(f32),
                                  c_.astype(f32), d, h0)
    y = y.astype(cd) * jax.nn.silu(z)
    out = y @ p["out_kernel"].astype(cd)
    if counts is None:
        new_tail = u_pad[:, s:]
    else:
        new_tail = jnp.take_along_axis(
            u_pad, (counts[:, None] + jnp.arange(k - 1)[None, :])[..., None],
            axis=1)
    return out, (new_tail, h_new)


def ssm_paged_forward(p, x, cfg: TransformerConfig, state, rows=None,
                      starts=None, counts=None, active=None):
    """The mixer inside a paged serving step (inference/dynamic_engine.py).

    state = (ssm [L, slots, N, E] f32, conv [L, slots, (k-1) * E], index):
    the engine's stacked state pools and this layer's plane of them (a
    slot's k-1 convolution inputs side by side in one row, so that the two
    minor dims tile whole and a slot's tail is one row to slice). x's
    row b belongs to slot rows[b] (None: row b is slot b, a decode round
    over every slot). Returns (out, (ssm, conv)): the pools that came in,
    plane `index` of the active rows' slots advanced, in place where the
    caller's copies are dead (the layer loop's carry).

    A decode round (counts None, one token a row) reads the convolution's
    tails and shifts them in XLA and advances h in the ssm_update kernel.
    A prefill chunk (counts given) scans each row from its slot's state
    (zeros where starts[b] == 0: the row begins a sequence, whatever the
    slot held) up to counts[b] and writes the state back."""
    from megatronapp_tpu.ops.pallas.ssm_update import ssm_update
    ssm, conv, index = state
    dims = ssm_dims(cfg)
    bsz = x.shape[0]
    index = jnp.asarray(index, jnp.int32)
    if active is None:
        active = jnp.ones((bsz,), bool)
    zero = jnp.int32(0)
    e = ssm.shape[3]
    taps = conv.shape[2] // e
    if counts is None:
        if rows is not None or bsz != ssm.shape[1] or x.shape[1] != 1:
            raise ValueError("a decode round advances every slot by one "
                             "token: x is [slots, 1, H]")
        # Lane slices and a stack, not a reshape: [slots, taps * E] ->
        # [slots, taps, E] is no bitcast on a TPU, and XLA would rather
        # relayout the whole pool around the step than the plane.
        flat = jax.lax.dynamic_index_in_dim(conv, index, 0, keepdims=False)
        tail = jnp.stack([flat[:, i * e:(i + 1) * e] for i in range(taps)],
                         axis=1)

        def update(pool, dt, u, b, c, a_t, d):
            return ssm_update(pool, index, dt, u, b, c, a_t, d, active)

        # The state given is the whole pool: ssm_update reads and writes
        # this layer's plane of it and hands the pool back.
        out, (new_tail, ssm) = ssm_forward(p, x, cfg, dims,
                                           state=(tail, ssm), update=update)
        new_tail = jnp.where(active[:, None, None], new_tail.astype(
            conv.dtype), tail)
        conv = jax.lax.dynamic_update_slice(
            conv, jnp.concatenate([new_tail[:, i] for i in range(taps)],
                                  axis=-1)[None], (index, zero, zero))
        return out, (ssm, conv)

    if rows is None:
        rows = jnp.arange(bsz, dtype=jnp.int32)

    def h_of(b):
        return jax.lax.dynamic_slice(
            ssm, (index, rows[b], zero, zero), (1, 1) + ssm.shape[2:])[0]

    def tail_of(b):
        return jax.lax.dynamic_slice(
            conv, (index, rows[b], zero), (1, 1, taps * e)).reshape(
                1, taps, e)

    fresh = (starts == 0)[:, None, None]
    h0 = jnp.concatenate([h_of(b) for b in range(bsz)])
    tail = jnp.concatenate([tail_of(b) for b in range(bsz)])
    out, (new_tail, h_new) = ssm_forward(
        p, x, cfg, dims, counts=counts,
        state=(jnp.where(fresh, 0, tail), jnp.where(fresh, 0.0, h0)))
    keep = active[:, None, None]
    h_new = jnp.where(keep, h_new, h0)
    new_tail = jnp.where(keep, new_tail.astype(conv.dtype), tail)
    for b in range(bsz):
        ssm = jax.lax.dynamic_update_slice(
            ssm, h_new[b][None, None], (index, rows[b], zero, zero))
        conv = jax.lax.dynamic_update_slice(
            conv, new_tail[b].reshape(1, 1, taps * e),
            (index, rows[b], zero))
    return out, (ssm, conv)
