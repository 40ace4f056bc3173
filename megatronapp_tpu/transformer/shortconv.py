"""Gated short convolution (LFM2): the fourth kind of a layer's first half,
beside attention.py, mla.py and ssm.py.

Parity with HF `modeling_lfm2.Lfm2ShortConv` (`lfm2` / `lfm2_moe`,
layer_types "conv"): for the normed input u [S, H]

    [B | C | z] = u W_in                  # H -> 3H, split in that order
    v_t = B_t * z_t
    c_t = sum_j w_j * v_{t-(k-1-j)}       # depthwise, causal, k taps, v_{<0} = 0
    out_t = (C_t * c_t) W_out             # H -> H

No bias anywhere (HF conv_bias false), no nonlinearity and no recurrence: a
sequence's whole state is the convolution's last k-1 gated inputs v (the
published cache keeps k columns and never reads the oldest). The taps are
k shifted products summed in float32, elementwise, as ssm.py's convolution
is and for its reason; everything else runs in the compute type. A decode
step is the same expression at S = 1 over the cached columns: plain XLA ops.

Param leaves: in_kernel [H, 3H], conv_kernel [k, H] (row j multiplies the
input k-1-j positions back), out_kernel [H, H].
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from megatronapp_tpu.config.transformer_config import TransformerConfig


def init_shortconv_params(rng, cfg: TransformerConfig, out_std=None):
    h, k = cfg.hidden_size, cfg.shortconv_kernel
    keys = jax.random.split(rng, 3)
    std = cfg.init_method_std
    if out_std is None:
        out_std = std / jnp.sqrt(2.0 * cfg.num_layers)
    p = {
        "in_kernel": jax.random.normal(keys[0], (h, 3 * h),
                                       cfg.params_dtype) * std,
        # std 1/sqrt(k): the scale of the published layer's default
        # initialiser at fan-in k, which keeps c at v's scale.
        "conv_kernel": jax.random.normal(
            keys[1], (k, h), cfg.params_dtype) * (1.0 / jnp.sqrt(float(k))),
        "out_kernel": jax.random.normal(keys[2], (h, h),
                                        cfg.params_dtype) * out_std,
    }
    ax = {"in_kernel": ("embed", "mlp"), "conv_kernel": (None, "embed"),
          "out_kernel": ("mlp", "embed")}
    return p, ax


def shortconv_forward(p, x, cfg: TransformerConfig, tail=None, counts=None,
                      segment_ids=None):
    """x [B,S,H] -> (out [B,S,H], new tail [B,k-1,H]).

    tail: the k-1 gated inputs a sequence arrives with; None is a
    sequence's start (zeros). counts [B]: row b's first counts[b] positions
    are real and the rest padding, which does not enter the new tail: that
    is the last k-1 REAL inputs, taken across the chunk's edge from the old
    tail where the count is under k-1. segment_ids [B,S] (whole packed
    sequences, no tail): a tap never reads another segment."""
    s = x.shape[1]
    k = p["conv_kernel"].shape[0]
    f32, cd = jnp.float32, cfg.compute_dtype
    b_, c_, z = jnp.split(x.astype(cd) @ p["in_kernel"].astype(cd), 3,
                          axis=-1)
    v = b_ * z
    if tail is None:
        v_pad = jnp.pad(v, ((0, 0), (k - 1, 0), (0, 0)))
    else:
        v_pad = jnp.concatenate([tail.astype(v.dtype), v], axis=1)
    taps = p["conv_kernel"].astype(f32)
    conv = 0.0
    for i in range(k):
        term = v_pad[:, i:i + s].astype(f32) * taps[i]
        back = k - 1 - i
        if segment_ids is not None and back:
            seg_back = jnp.pad(segment_ids, ((0, 0), (back, 0)),
                               constant_values=-1)[:, :s]
            term = jnp.where((seg_back == segment_ids)[..., None], term, 0.0)
        conv = conv + term
    y = (c_.astype(f32) * conv).astype(cd)
    out = y @ p["out_kernel"].astype(cd)
    if counts is None:
        new_tail = v_pad[:, s:]
    else:
        new_tail = jnp.take_along_axis(
            v_pad, (counts[:, None] + jnp.arange(k - 1)[None, :])[..., None],
            axis=1)
    return out, new_tail


def shortconv_paged_forward(p, x, cfg: TransformerConfig, state, rows=None,
                            starts=None, counts=None, active=None):
    """The mixer inside a paged serving step (inference/dynamic_engine.py).

    state = (conv [L, slots, (k-1) * H], index): the engine's stacked tail
    pool (paged_cache.py: a slot's k-1 columns side by side in one row, as
    the state-space tenant keeps its tail) and this layer's plane of it.
    x's row b belongs to slot rows[b] (None: row b is slot b, a decode round
    over every slot). Returns (out, (conv,)): the pool that came in, plane
    `index` of the active rows' slots advanced, in place where the caller's
    copy is dead (the layer loop's carry).

    A decode round (counts None, one token a row) shifts every active
    slot's columns by one. A prefill chunk (counts given) starts each row
    from its slot's columns (zeros where starts[b] == 0: the row begins a
    sequence, whatever the slot held) and writes back the last k-1 real
    inputs up to counts[b]."""
    conv, index = state
    bsz = x.shape[0]
    index = jnp.asarray(index, jnp.int32)
    if active is None:
        active = jnp.ones((bsz,), bool)
    zero = jnp.int32(0)
    h = x.shape[-1]
    cols = conv.shape[2] // h
    if counts is None:
        if rows is not None or bsz != conv.shape[1] or x.shape[1] != 1:
            raise ValueError("a decode round advances every slot by one "
                             "token: x is [slots, 1, H]")
        # Lane slices and a stack, not a reshape (ssm.ssm_paged_forward).
        flat = jax.lax.dynamic_index_in_dim(conv, index, 0, keepdims=False)
        tail = jnp.stack([flat[:, i * h:(i + 1) * h] for i in range(cols)],
                         axis=1)
        out, new_tail = shortconv_forward(p, x, cfg, tail=tail)
        new_tail = jnp.where(active[:, None, None],
                             new_tail.astype(conv.dtype), tail)
        conv = jax.lax.dynamic_update_slice(
            conv, jnp.concatenate([new_tail[:, i] for i in range(cols)],
                                  axis=-1)[None], (index, zero, zero))
        return out, (conv,)

    if rows is None:
        rows = jnp.arange(bsz, dtype=jnp.int32)
    tail = jnp.concatenate([
        jax.lax.dynamic_slice(conv, (index, rows[b], zero),
                              (1, 1, cols * h)).reshape(1, cols, h)
        for b in range(bsz)])
    out, new_tail = shortconv_forward(
        p, x, cfg, counts=counts,
        tail=jnp.where((starts == 0)[:, None, None], 0, tail))
    new_tail = jnp.where(active[:, None, None], new_tail.astype(conv.dtype),
                         tail)
    for b in range(bsz):
        conv = jax.lax.dynamic_update_slice(
            conv, new_tail[b].reshape(1, 1, cols * h),
            (index, rows[b], zero))
    return out, (conv,)
