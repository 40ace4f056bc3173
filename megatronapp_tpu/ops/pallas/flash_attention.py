"""Pallas TPU flash attention (forward + custom-VJP backward).

The reference gets fused attention from TransformerEngine/Apex CUDA kernels
(SURVEY §2.7 native-code inventory: "Pallas flash attention" is the TPU
replacement obligation). This kernel:

- blockwise online-softmax forward, O(S) memory (no [Sq,Skv] materialized),
  fp32 accumulators, bf16 matmul inputs on the MXU;
- causal masking with whole-block skip for fully-masked tiles;
- a sliding window (`window` keys a query, inside its segment): the grid of
  a window layer's kernels holds only the tiles its band can touch
  (`_BandGrid`), under names of their own (`flash_window_*`);
- GQA: KV heads indexed as h // group via BlockSpec index maps, no repeat;
- custom VJP with two backward kernels (dq; dk/dv), log-sum-exp residuals —
  the FlashAttention-2 recipe;
- runs in interpret mode on CPU (tests) and compiled on TPU.

Layout: [B, H, S, D] per-head-contiguous (callers reshape from [B,S,H,D]).
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _force_straight() -> bool:
    """FLASH_STRAIGHT_ORIENTATION=1 pins the straight-orientation
    kernels even for D<128 — the measurement knob for A/Bing the
    transposed orientation on real hardware (tools/bench_profile.py)."""
    import os
    return os.environ.get("FLASH_STRAIGHT_ORIENTATION") == "1"


def _cdiv(a, b):
    return (a + b - 1) // b



def _mask_rows(x, start, limit):
    """Zero rows >= limit. Padding may be NaN (interpret mode pads with NaN),
    so this must be a select, not a multiply (NaN*0 == NaN)."""
    idx = start + jax.lax.broadcasted_iota(jnp.int32, (x.shape[0], 1), 0)
    return jnp.where(idx < limit, x, jnp.zeros_like(x))

def _valid_mask(q_start, k_start, block_q, block_kv, seq_q, seq_kv,
                causal, bounded, qs_ref, ks_ref, window=0):
    """[bq, bkv] validity mask with only the statically-needed terms:
    bounds checks when the sequence doesn't divide the block, the causal
    triangle, a sliding window's band (the query at row i sees the keys
    i - window < j <= i), and packed-segment equality."""
    rows = q_start + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_kv), 0)
    cols = k_start + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_kv), 1)
    if bounded:
        valid = (rows < seq_q) & (cols < seq_kv)
        if causal:
            valid = valid & (rows >= cols)
    else:
        valid = rows >= cols if causal else jnp.ones(
            (block_q, block_kv), jnp.bool_)
    if window:
        valid = valid & (rows - cols < window)
    if qs_ref is not None:
        # Packed sequences: attend within-segment only (segment ids
        # [bq,1] vs [1,bkv] broadcast to the score block).
        valid = valid & (qs_ref[0] == ks_ref[0])
    return valid


def _dispatch_tiles(compute, causal, edge_mask, q_start, k_start,
                    block_q, block_kv, window=0, live=None):
    """Shared tile dispatch for all three kernels: skip tiles entirely
    above the causal diagonal, and route interior tiles (strictly below
    the diagonal, in-bounds, no segment ids) to compute(masked=False) —
    skipping the iota/compare/select chain on [bq, bkv] is the kernels'
    main VPU saving. A window layer's grid (`_BandGrid`) holds no tile
    outside the band but the steps a short row of tiles leaves over, on
    which `live` is false; its interior tiles lie inside the band too."""
    if window:
        if edge_mask:
            pl.when(live)(lambda: compute(True))
        else:
            interior = ((q_start >= k_start + block_kv)
                        & (q_start + block_q - 1 - k_start < window))
            pl.when(live & interior)(lambda: compute(False))
            pl.when(live & jnp.logical_not(interior))(
                lambda: compute(True))
    elif causal:
        if edge_mask:
            @pl.when(q_start + block_q - 1 >= k_start)
            def _():
                compute(True)
        else:
            interior = q_start >= k_start + block_kv

            @pl.when(interior)
            def _():
                compute(False)

            @pl.when(jnp.logical_not(interior)
                     & (q_start + block_q - 1 >= k_start))
            def _():
                compute(True)
    else:
        compute(edge_mask)



class _BandGrid(NamedTuple):
    """The tiles that a causal band of `window` keys can touch, as the window
    kernels' grids walk them: a q tile's kv tiles (forward and dq:
    ``kv_tile``) and a kv tile's q tiles (dkv: ``q_tile``), a row of at most
    ``kv_steps`` / ``q_steps`` of them. Both take the grid's (row, step) and
    answer (tile, live): the tile's index, held at the row's last tile on the
    steps that a shorter row leaves over (so no new copy starts), where
    `live` is false. Tiles outside the band are in no grid: they cost no
    step, where a masked tile would cost a whole one."""
    window: int
    block_q: int
    block_kv: int
    nq: int
    nk: int

    def kv_range(self, iq, xp=jnp):
        q_start = iq * self.block_q
        return (xp.maximum(q_start - (self.window - 1), 0) // self.block_kv,
                xp.minimum((q_start + self.block_q - 1) // self.block_kv,
                           self.nk - 1))

    def q_range(self, ik, xp=jnp):
        k_start = ik * self.block_kv
        return (xp.minimum(k_start // self.block_q, self.nq - 1),
                xp.minimum((k_start + self.block_kv + self.window - 2)
                           // self.block_q, self.nq - 1))

    @property
    def kv_steps(self) -> int:
        lo, hi = self.kv_range(np.arange(self.nq), np)
        return int(np.max(hi - lo)) + 1

    @property
    def q_steps(self) -> int:
        lo, hi = self.q_range(np.arange(self.nk), np)
        return int(np.max(hi - lo)) + 1

    def kv_tile(self, iq, step):
        lo, hi = self.kv_range(iq)
        return jnp.minimum(lo + step, hi), lo + step <= hi

    def q_tile(self, ik, step):
        lo, hi = self.q_range(ik)
        return jnp.minimum(lo + step, hi), lo + step <= hi


# ---------------------------------------------------------------------------
# BlockSpec builders shared by all kernels. Every kernel runs on a
# (b, h, major, minor) grid where (major, minor) is (iq, ik) for
# q-major kernels (forward, dq) and (ik, iq) for kv-major ones (dkv);
# `q_major` picks which grid slot indexes the q blocks. Segment-id and
# lse/delta specs come in straight ([bq,1] columns) and transposed
# ([1,bq] lane rows) orientations.
# ---------------------------------------------------------------------------


def _spec_q(block_q, d, q_major):
    if q_major:
        return pl.BlockSpec((1, 1, block_q, d),
                            lambda b_, h_, iq, ik: (b_, h_, iq, 0))
    return pl.BlockSpec((1, 1, block_q, d),
                        lambda b_, h_, ik, iq: (b_, h_, iq, 0))


def _spec_kv(block_kv, d, group, q_major):
    if q_major:
        return pl.BlockSpec(
            (1, 1, block_kv, d),
            lambda b_, h_, iq, ik, g_=group: (b_, h_ // g_, ik, 0))
    return pl.BlockSpec(
        (1, 1, block_kv, d),
        lambda b_, h_, ik, iq, g_=group: (b_, h_ // g_, ik, 0))


def _spec_segs(block_q, block_kv, q_major, transposed):
    """(q_segs, kv_segs) specs. Straight orientation reads q ids as a
    [bq, 1] column from [B,Sq,1] and kv ids as a [1, bkv] row from
    [B,1,Skv]; the transposed kernels read q ids as a [1, bq] row and kv
    ids as a [bkv, 1] column (callers swap the arrays to match)."""
    if transposed:
        q_shape, q_idx = (1, 1, block_q), (lambda b_, m, n: (b_, 0, m))
        k_shape, k_idx = (1, block_kv, 1), (lambda b_, m, n: (b_, n, 0))
    else:
        q_shape, q_idx = (1, block_q, 1), (lambda b_, m, n: (b_, m, 0))
        k_shape, k_idx = (1, 1, block_kv), (lambda b_, m, n: (b_, 0, n))
    iq_of = (lambda mj, mn: mj) if q_major else (lambda mj, mn: mn)
    ik_of = (lambda mj, mn: mn) if q_major else (lambda mj, mn: mj)
    return [
        pl.BlockSpec(q_shape,
                     lambda b_, h_, mj, mn: q_idx(b_, iq_of(mj, mn),
                                                  ik_of(mj, mn))),
        pl.BlockSpec(k_shape,
                     lambda b_, h_, mj, mn: k_idx(b_, iq_of(mj, mn),
                                                  ik_of(mj, mn))),
    ]


def _spec_qcol(block_q, q_major):
    """[bq, 1] per-q-row scalars (straight-orientation lse/delta)."""
    if q_major:
        return pl.BlockSpec((1, 1, block_q, 1),
                            lambda b_, h_, iq, ik: (b_, h_, iq, 0))
    return pl.BlockSpec((1, 1, block_q, 1),
                        lambda b_, h_, ik, iq: (b_, h_, iq, 0))


def _spec_qrow(block_q, q_major):
    """[1, bq] lane-row scalars (transposed-orientation lse/delta)."""
    if q_major:
        return pl.BlockSpec((1, 1, 1, block_q),
                            lambda b_, h_, iq, ik: (b_, h_, 0, iq))
    return pl.BlockSpec((1, 1, 1, block_q),
                        lambda b_, h_, ik, iq: (b_, h_, 0, iq))


def _band_specs(band: _BandGrid, d, group, q_major, has_segs):
    """The window kernels' BlockSpecs by operand, on a (b, h, row, step)
    grid whose (row, step) the band turns into (iq, ik): rows are q tiles
    and steps their kv tiles (`q_major`: forward, dq), or the other way
    round (dkv). Straight orientation only."""
    if q_major:
        iq_of = lambda row, step: row                       # noqa: E731
        ik_of = lambda row, step: band.kv_tile(row, step)[0]  # noqa: E731
    else:
        iq_of = lambda row, step: band.q_tile(row, step)[0]   # noqa: E731
        ik_of = lambda row, step: row                       # noqa: E731
    bq, bkv = band.block_q, band.block_kv
    specs = {
        "q": pl.BlockSpec((1, 1, bq, d), lambda b_, h_, r, t: (
            b_, h_, iq_of(r, t), 0)),
        "kv": pl.BlockSpec((1, 1, bkv, d), lambda b_, h_, r, t: (
            b_, h_ // group, ik_of(r, t), 0)),
        "dkv": pl.BlockSpec((1, 1, bkv, d), lambda b_, h_, r, t: (
            b_, h_, ik_of(r, t), 0)),
        "qcol": pl.BlockSpec((1, 1, bq, 1), lambda b_, h_, r, t: (
            b_, h_, iq_of(r, t), 0)),
    }
    specs["segs"] = [
        pl.BlockSpec((1, bq, 1), lambda b_, h_, r, t: (b_, iq_of(r, t), 0)),
        pl.BlockSpec((1, 1, bkv), lambda b_, h_, r, t: (b_, 0, ik_of(r, t))),
    ] if has_segs else []
    return specs


# ---------------------------------------------------------------------------
# Forward kernel
# ---------------------------------------------------------------------------

def _fwd_kernel(*refs, scale, causal, block_q, block_kv,
                num_kv, seq_q, seq_kv, has_segs, bounded, band=None):
    """`band` (a window layer): the grid's last axis walks the band's
    `num_kv` steps of q tile iq, not all the kv tiles."""
    if has_segs:
        (q_ref, k_ref, v_ref, qs_ref, ks_ref,
         o_ref, lse_ref, acc, m_scr, l_scr) = refs
    else:
        q_ref, k_ref, v_ref, o_ref, lse_ref, acc, m_scr, l_scr = refs
        qs_ref = ks_ref = None
    iq = pl.program_id(2)
    step = pl.program_id(3)
    window = band.window if band else 0
    ik, live = band.kv_tile(iq, step) if band else (step, None)

    @pl.when(step == 0)
    def _init():
        acc[:] = jnp.zeros_like(acc)
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)

    q_start = iq * block_q
    k_start = ik * block_kv

    def compute(masked):
        q = q_ref[0, 0].astype(jnp.float32) * scale   # [bq, D]
        k = k_ref[0, 0]                               # [bkv, D]
        v = v_ref[0, 0]                               # [bkv, D]
        if bounded:
            q = _mask_rows(q, q_start, seq_q)
            k = _mask_rows(k, k_start, seq_kv)
            v = _mask_rows(v, k_start, seq_kv)
        s = jax.lax.dot_general(
            q.astype(k.dtype), k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)       # [bq, bkv]
        if masked:
            valid = _valid_mask(q_start, k_start, block_q, block_kv,
                                seq_q, seq_kv, causal, bounded,
                                qs_ref, ks_ref, window)
            s = jnp.where(valid, s, _NEG_INF)

        m_prev = m_scr[:, 0]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        m_safe = jnp.maximum(m_new, _NEG_INF / 2)
        p = jnp.exp(s - m_safe[:, None])
        if masked:
            p = jnp.where(valid, p, 0.0)
        corr = jnp.exp(jnp.minimum(m_prev - m_new, 0.0))
        corr = jnp.where(m_prev <= _NEG_INF / 2, 0.0, corr)
        l_scr[:, 0] = l_scr[:, 0] * corr + jnp.sum(p, axis=1)
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        acc[:] = acc[:] * corr[:, None] + pv
        m_scr[:, 0] = m_new

    _dispatch_tiles(compute, causal, bounded or has_segs, q_start, k_start,
                    block_q, block_kv, window, live)

    @pl.when(step == num_kv - 1)
    def _finalize():
        l = l_scr[:, 0]
        o_ref[0, 0] = (acc[:] / jnp.maximum(l, 1e-20)[:, None]).astype(
            o_ref.dtype)
        m = m_scr[:, 0]
        lse = jnp.where(
            l > 0, jnp.maximum(m, _NEG_INF / 2) + jnp.log(
                jnp.maximum(l, 1e-20)), _NEG_INF)
        lse_ref[0, 0] = lse[:, None]


def _fwd_kernel_t(*refs, scale, causal, block_q, block_kv,
                  num_kv, seq_q, seq_kv, has_segs, bounded):
    """Forward in transposed orientation for D < 128: scores as
    s^T = k·q^T [bkv, bq], accumulator o^T [D, bq] filled by
    (p·v)^T = v^T·p — full-width contraction (bkv) and output (bq) dims
    where the straight orientation's p@v has only D output lanes. The
    online-softmax running max/sum live as [1, bq] lane rows; reductions
    run over sublanes (axis 0)."""
    if has_segs:
        (q_ref, k_ref, v_ref, qs_ref, ks_ref,
         ot_ref, lse_ref, acc, m_scr, l_scr) = refs
    else:
        q_ref, k_ref, v_ref, ot_ref, lse_ref, acc, m_scr, l_scr = refs
        qs_ref = ks_ref = None
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        acc[:] = jnp.zeros_like(acc)
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)

    q_start = iq * block_q
    k_start = ik * block_kv

    def compute(masked):
        q = q_ref[0, 0].astype(jnp.float32) * scale   # [bq, D]
        k = k_ref[0, 0]                               # [bkv, D]
        v = v_ref[0, 0]
        if bounded:
            q = _mask_rows(q, q_start, seq_q)
            k = _mask_rows(k, k_start, seq_kv)
            v = _mask_rows(v, k_start, seq_kv)
        st = jax.lax.dot_general(                     # k·q^T = s^T
            k, q.astype(k.dtype), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)       # [bkv, bq]
        if masked:
            valid = _valid_mask_t(q_start, k_start, block_q, block_kv,
                                  seq_q, seq_kv, causal, bounded,
                                  qs_ref, ks_ref)
            st = jnp.where(valid, st, _NEG_INF)

        m_prev = m_scr[0]                             # [bq]
        m_new = jnp.maximum(m_prev, jnp.max(st, axis=0))
        m_safe = jnp.maximum(m_new, _NEG_INF / 2)
        p = jnp.exp(st - m_safe[None, :])             # [bkv, bq]
        if masked:
            p = jnp.where(valid, p, 0.0)
        corr = jnp.exp(jnp.minimum(m_prev - m_new, 0.0))
        corr = jnp.where(m_prev <= _NEG_INF / 2, 0.0, corr)
        l_scr[0] = l_scr[0] * corr + jnp.sum(p, axis=0)
        pvt = jax.lax.dot_general(                    # v^T·p = (p·v)^T
            v, p.astype(v.dtype), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)       # [D, bq]
        acc[:] = acc[:] * corr[None, :] + pvt
        m_scr[0] = m_new

    _dispatch_tiles(compute, causal, bounded or has_segs, q_start, k_start,
                    block_q, block_kv)

    @pl.when(ik == num_kv - 1)
    def _finalize():
        l = l_scr[0]
        ot_ref[0, 0] = (acc[:] / jnp.maximum(l, 1e-20)[None, :]).astype(
            ot_ref.dtype)
        m = m_scr[0]
        lse = jnp.where(
            l > 0, jnp.maximum(m, _NEG_INF / 2) + jnp.log(
                jnp.maximum(l, 1e-20)), _NEG_INF)
        lse_ref[0, 0] = lse[None, :]


def _flash_forward_t(q, k, v, scale, causal, block_q, block_kv, nq, nk,
                     bounded, group, segs):
    """D<128 forward: transposed-orientation kernel; output comes out as
    [B,H,D,Sq] and is swapped back here, lse as [B,H,1,Sq] rows."""
    b, h, sq, d = q.shape
    skv = k.shape[2]
    has_segs = segs is not None

    kernel = functools.partial(
        _fwd_kernel_t, scale=scale, causal=causal, block_q=block_q,
        block_kv=block_kv, num_kv=nk, seq_q=sq, seq_kv=skv,
        has_segs=has_segs, bounded=bounded)

    in_specs = [_spec_q(block_q, d, q_major=True),
                _spec_kv(block_kv, d, group, q_major=True),
                _spec_kv(block_kv, d, group, q_major=True)]
    inputs = [q, k, v]
    if has_segs:
        q_segs, kv_segs = segs                # [B,Sq,1] / [B,1,Skv]
        qs_row = jnp.swapaxes(q_segs, 1, 2)   # [B,1,Sq]
        ks_col = jnp.swapaxes(kv_segs, 1, 2)  # [B,Skv,1]
        in_specs += _spec_segs(block_q, block_kv, q_major=True,
                               transposed=True)
        inputs += [qs_row, ks_col]

    ot, lse_row = pl.pallas_call(
        kernel,
        grid=(b, h, nq, nk),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, 1, d, block_q),
                         lambda b_, h_, iq, ik: (b_, h_, 0, iq)),
            pl.BlockSpec((1, 1, 1, block_q),
                         lambda b_, h_, iq, ik: (b_, h_, 0, iq)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, d, sq), q.dtype),
            jax.ShapeDtypeStruct((b, h, 1, sq), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((d, block_q), jnp.float32),
            pltpu.VMEM((1, block_q), jnp.float32),
            pltpu.VMEM((1, block_q), jnp.float32),
        ],
        interpret=_interpret(),
        name="flash_fwd_t",
    )(*inputs)
    return jnp.swapaxes(ot, -1, -2), lse_row[:, :, 0, :]


def _flash_forward_window(q, k, v, scale, band: _BandGrid, bounded, group,
                          segs):
    """A window layer's forward: the straight kernel on the band's grid."""
    b, h, sq, d = q.shape
    specs = _band_specs(band, d, group, True, segs is not None)
    out, lse = pl.pallas_call(
        functools.partial(
            _fwd_kernel, scale=scale, causal=True, block_q=band.block_q,
            block_kv=band.block_kv, num_kv=band.kv_steps, seq_q=sq,
            seq_kv=k.shape[2], has_segs=segs is not None, bounded=bounded,
            band=band),
        grid=(b, h, band.nq, band.kv_steps),
        in_specs=[specs["q"], specs["kv"], specs["kv"]] + specs["segs"],
        out_specs=[specs["q"], specs["qcol"]],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, sq, d), q.dtype),
            jax.ShapeDtypeStruct((b, h, sq, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((band.block_q, d), jnp.float32),
            pltpu.VMEM((band.block_q, 1), jnp.float32),
            pltpu.VMEM((band.block_q, 1), jnp.float32),
        ],
        interpret=_interpret(),
        name="flash_window_fwd",
    )(q, k, v, *(segs or ()))
    return out, lse[..., 0]


def _flash_forward(q, k, v, scale, causal, block_q, block_kv, segs=None,
                   window=0):
    b, h, sq, d = q.shape
    hkv, skv = k.shape[1], k.shape[2]
    group = h // hkv
    block_q = min(block_q, sq)
    block_kv = min(block_kv, skv)
    nq = _cdiv(sq, block_q)
    nk = _cdiv(skv, block_kv)

    bounded = (sq % block_q != 0) or (skv % block_kv != 0)
    if window:
        return _flash_forward_window(
            q, k, v, scale, _BandGrid(window, block_q, block_kv, nq, nk),
            bounded, group, segs)
    if d < 128 and not _force_straight():
        return _flash_forward_t(q, k, v, scale, causal, block_q, block_kv,
                                nq, nk, bounded, group, segs)

    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, block_q=block_q,
        block_kv=block_kv, num_kv=nk, seq_q=sq, seq_kv=skv,
        has_segs=segs is not None, bounded=bounded)

    in_specs = [_spec_q(block_q, d, q_major=True),
                _spec_kv(block_kv, d, group, q_major=True),
                _spec_kv(block_kv, d, group, q_major=True)]
    inputs = [q, k, v]
    if segs is not None:
        q_segs, kv_segs = segs  # [B,Sq,1] / [B,1,Skv] int32
        in_specs += _spec_segs(block_q, block_kv, q_major=True,
                               transposed=False)
        inputs += [q_segs, kv_segs]

    out, lse = pl.pallas_call(
        kernel,
        grid=(b, h, nq, nk),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda b_, h_, iq, ik: (b_, h_, iq, 0)),
            pl.BlockSpec((1, 1, block_q, 1),
                         lambda b_, h_, iq, ik: (b_, h_, iq, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, sq, d), q.dtype),
            jax.ShapeDtypeStruct((b, h, sq, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        interpret=_interpret(),
        name="flash_fwd",
    )(*inputs)
    return out, lse[..., 0]


# ---------------------------------------------------------------------------
# Backward kernels
#
# Two orientations. For D >= 128 the straightforward one: accumulators
# [block, D] and the output-producing matmuls (dq = ds@k, dk = ds^T@q,
# dv = p^T@do) have N = D output lanes. At D = 64 that leaves half the
# MXU's 128 output columns (and half of every 128-lane vreg row of the
# accumulator) idle — PERF.md's main backward-kernel lever. The
# transposed orientation used when D < 128 computes dq^T = k^T·ds^T,
# dk^T = q^T·ds, dv^T = do^T·p instead: contraction and output dims are
# both the 512-wide sequence blocks (full MXU), the [D, block]
# accumulators fill whole vregs, and only the D-contracted score matmuls
# (s, dp) keep the intrinsic K=D underfill. Outputs land as [B,H,D,S]
# and are swapped back outside (one XLA transpose, O(bytes)).
# ---------------------------------------------------------------------------


def _valid_mask_t(q_start, k_start, block_q, block_kv, seq_q, seq_kv,
                  causal, bounded, qs_ref, ks_ref):
    """Transposed-orientation [bkv, bq] validity mask (rows = kv
    positions, cols = q positions) for the dq^T kernel."""
    rows = k_start + jax.lax.broadcasted_iota(
        jnp.int32, (block_kv, block_q), 0)
    cols = q_start + jax.lax.broadcasted_iota(
        jnp.int32, (block_kv, block_q), 1)
    if bounded:
        valid = (rows < seq_kv) & (cols < seq_q)
        if causal:
            valid = valid & (cols >= rows)
    else:
        valid = cols >= rows if causal else jnp.ones(
            (block_kv, block_q), jnp.bool_)
    if qs_ref is not None:
        # qs_ref[0]: [1, bq] lane row; ks_ref[0]: [bkv, 1] column.
        valid = valid & (ks_ref[0] == qs_ref[0])
    return valid


def _bwd_dq_kernel_t(*refs, scale, causal, block_q, block_kv, num_kv,
                     seq_q, seq_kv, has_segs, bounded):
    """dq in transposed orientation: scores as s^T = k·q^T [bkv, bq],
    accumulator dq^T [D, bq], final matmul k^T·ds^T with full-width
    contraction (bkv) and output (bq) dims."""
    if has_segs:
        (q_ref, k_ref, v_ref, qs_ref, ks_ref, do_ref, lse_ref, delta_ref,
         dqt_ref, dqt_acc) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         dqt_ref, dqt_acc) = refs
        qs_ref = ks_ref = None
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        dqt_acc[:] = jnp.zeros_like(dqt_acc)

    q_start = iq * block_q
    k_start = ik * block_kv

    def compute(masked):
        q = q_ref[0, 0].astype(jnp.float32) * scale   # [bq, D]
        k = k_ref[0, 0]                               # [bkv, D]
        v = v_ref[0, 0]
        if bounded:
            k = _mask_rows(k, k_start, seq_kv)
            v = _mask_rows(v, k_start, seq_kv)
        do = do_ref[0, 0].astype(jnp.float32)         # [bq, D]
        lse = lse_ref[0, 0]                           # [1, bq]
        delta = delta_ref[0, 0]                       # [1, bq]

        st = jax.lax.dot_general(                     # k·q^T = s^T
            k, q.astype(k.dtype), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)       # [bkv, bq]
        dpt = jax.lax.dot_general(                    # v·do^T = dp^T
            v, do.astype(v.dtype), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)       # [bkv, bq]
        if masked:
            valid = _valid_mask_t(q_start, k_start, block_q, block_kv,
                                  seq_q, seq_kv, causal, bounded,
                                  qs_ref, ks_ref)
            pt = jnp.where(valid, jnp.exp(st - lse), 0.0)
            dst = jnp.where(valid, pt * (dpt - delta), 0.0)
        else:
            pt = jnp.exp(st - lse)
            dst = pt * (dpt - delta)
        dqt_acc[:] += jax.lax.dot_general(            # k^T·ds^T = dq^T
            k, dst.astype(k.dtype), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [D, bq]

    _dispatch_tiles(compute, causal, bounded or has_segs, q_start, k_start,
                    block_q, block_kv)

    @pl.when(ik == num_kv - 1)
    def _finalize():
        dqt_ref[0, 0] = dqt_acc[:].astype(dqt_ref.dtype)


def _bwd_dkv_kernel_t(*refs, scale, causal,
                      block_q, block_kv, num_q, seq_q, seq_kv, has_segs,
                      bounded):
    """dk/dv in transposed orientation: scores stay [bq, bkv] (so the
    standard mask applies), but the accumulating matmuls contract over
    bq with D-row outputs: dv^T = do^T·p, dk^T = q^T·ds — full-width
    contraction and output dims, [D, bkv] accumulators."""
    if has_segs:
        (q_ref, k_ref, v_ref, qs_ref, ks_ref, do_ref, lse_ref, delta_ref,
         dkt_ref, dvt_ref, dkt_acc, dvt_acc) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         dkt_ref, dvt_ref, dkt_acc, dvt_acc) = refs
        qs_ref = ks_ref = None
    ik = pl.program_id(2)
    iq = pl.program_id(3)

    @pl.when(iq == 0)
    def _init():
        dkt_acc[:] = jnp.zeros_like(dkt_acc)
        dvt_acc[:] = jnp.zeros_like(dvt_acc)

    q_start = iq * block_q
    k_start = ik * block_kv

    def compute(masked):
        q = q_ref[0, 0].astype(jnp.float32) * scale
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        do = do_ref[0, 0].astype(jnp.float32)
        if bounded:
            q = _mask_rows(q, q_start, seq_q)
            k = _mask_rows(k, k_start, seq_kv)
            v = _mask_rows(v, k_start, seq_kv)
            do = _mask_rows(do, q_start, seq_q)
        lse = lse_ref[0, 0][:, 0]
        delta = delta_ref[0, 0][:, 0]

        s = jax.lax.dot_general(q.astype(k.dtype), k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do.astype(v.dtype), v,
                                 (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        if masked:
            valid = _valid_mask(q_start, k_start, block_q, block_kv,
                                seq_q, seq_kv, causal, bounded,
                                qs_ref, ks_ref)
            p = jnp.where(valid, jnp.exp(s - lse[:, None]), 0.0)
            ds = jnp.where(valid, p * (dp - delta[:, None]), 0.0)
        else:
            p = jnp.exp(s - lse[:, None])          # [bq, bkv]
            ds = p * (dp - delta[:, None])         # [bq, bkv]
        # dv^T += do^T @ p   (contract bq; [D, bkv])
        dvt_acc[:] += jax.lax.dot_general(
            do.astype(v.dtype), p.astype(v.dtype),
            (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        # dk^T += q^T @ ds (q already has scale folded in)
        dkt_acc[:] += jax.lax.dot_general(
            q.astype(k.dtype), ds.astype(k.dtype),
            (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    _dispatch_tiles(compute, causal, bounded or has_segs, q_start, k_start,
                    block_q, block_kv)

    @pl.when(iq == num_q - 1)
    def _finalize():
        dkt_ref[0, 0] = dkt_acc[:].astype(dkt_ref.dtype)
        dvt_ref[0, 0] = dvt_acc[:].astype(dvt_ref.dtype)

# ---------------------------------------------------------------------------
# Head-fold backward kernels (PERF.md lever 1, ISSUE 11): at D = 64 the
# straight kernels' [block, D] refs/accumulators fill only half of every
# 128-lane vreg row. Folding a PAIR of q heads into the trailing block
# dim ([B, H, S, D] → [B, H/2, S, 2D]) makes every q/do load, the dq /
# dk / dv accumulators, and the gradient stores full 128-lane rows, and
# halves the grid's head extent (half the per-tile dispatch overhead).
# The score matmuls stay per-head (two D-contracted dots per tile — the
# intrinsic K = D underfill is untouched, same as the transposed
# orientation). GQA: a pair must share its kv head, so eligibility is
# group even (pair inside one group) or group == 1 with hkv even (kv
# folds alongside q). Opt-in via flash_attention(head_fold=True) /
# --flash-head-fold; grad parity vs the unfolded kernels is pinned
# ≤ 1e-5 in tests/test_kernel_gen.py. No on-chip A/B yet (ROADMAP S2).
# ---------------------------------------------------------------------------


def _fold_heads(x):
    """[B, H, S, D] → [B, H/2, S, 2D] (head pair side by side in the
    trailing dim)."""
    b, h, s, d = x.shape
    return jnp.swapaxes(x.reshape(b, h // 2, 2, s, d), 2, 3).reshape(
        b, h // 2, s, 2 * d)


def _unfold_heads(x):
    """Inverse of _fold_heads."""
    b, hp, s, d2 = x.shape
    d = d2 // 2
    return jnp.swapaxes(x.reshape(b, hp, s, 2, d), 2, 3).reshape(
        b, 2 * hp, s, d)


def _fold_rows(x):
    """[B, H, S] per-row scalars (lse/delta) → [B, H/2, S, 2]."""
    b, h, s = x.shape
    return jnp.transpose(x.reshape(b, h // 2, 2, s), (0, 1, 3, 2))


def _bwd_dq_kernel_fold(*refs, scale, causal, block_q, block_kv, num_kv,
                        seq_q, seq_kv, bounded, kv_folded, d):
    """dq with a folded head pair: q/do/lse/delta/dq refs carry both
    heads ([bq, 2D] / [bq, 2]); the two per-head score chains share one
    [bq, bkv] validity mask and accumulate into the [bq, 2D] dq rows."""
    (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
     dq_ref, dq_acc) = refs
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    q_start = iq * block_q
    k_start = ik * block_kv

    def compute(masked):
        valid = None
        if masked:
            valid = _valid_mask(q_start, k_start, block_q, block_kv,
                                seq_q, seq_kv, causal, bounded,
                                None, None)
        for half in (0, 1):
            sl = slice(half * d, (half + 1) * d)
            q = q_ref[0, 0][:, sl].astype(jnp.float32) * scale
            do = do_ref[0, 0][:, sl].astype(jnp.float32)
            k = k_ref[0, 0][:, sl] if kv_folded else k_ref[0, 0]
            v = v_ref[0, 0][:, sl] if kv_folded else v_ref[0, 0]
            if bounded:
                k = _mask_rows(k, k_start, seq_kv)
                v = _mask_rows(v, k_start, seq_kv)
            lse = lse_ref[0, 0][:, half]
            delta = delta_ref[0, 0][:, half]

            s = jax.lax.dot_general(
                q.astype(k.dtype), k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            dp = jax.lax.dot_general(
                do.astype(v.dtype), v, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            if masked:
                p = jnp.where(valid, jnp.exp(s - lse[:, None]), 0.0)
                ds = jnp.where(valid, p * (dp - delta[:, None]), 0.0)
            else:
                p = jnp.exp(s - lse[:, None])
                ds = p * (dp - delta[:, None])
            dq_acc[:, sl] += jax.lax.dot_general(
                ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32) * scale

    _dispatch_tiles(compute, causal, bounded, q_start, k_start,
                    block_q, block_kv)

    @pl.when(ik == num_kv - 1)
    def _finalize():
        dq_ref[0, 0] = dq_acc[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel_fold(*refs, scale, causal, block_q, block_kv, num_q,
                         seq_q, seq_kv, bounded, kv_folded, d):
    """dk/dv with a folded q-head pair. kv_folded (MHA, hkv even): the
    kv pair folds alongside and the accumulators are [bkv, 2D]. Shared
    kv head (GQA, group even): both halves accumulate into one [bkv, D]
    dk/dv — the in-kernel half of the group reduction the caller
    finishes over pairs."""
    (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
     dk_ref, dv_ref, dk_acc, dv_acc) = refs
    ik = pl.program_id(2)
    iq = pl.program_id(3)

    @pl.when(iq == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    q_start = iq * block_q
    k_start = ik * block_kv

    def compute(masked):
        valid = None
        if masked:
            valid = _valid_mask(q_start, k_start, block_q, block_kv,
                                seq_q, seq_kv, causal, bounded,
                                None, None)
        for half in (0, 1):
            sl = slice(half * d, (half + 1) * d)
            acc_sl = sl if kv_folded else slice(None)
            q = q_ref[0, 0][:, sl].astype(jnp.float32) * scale
            do = do_ref[0, 0][:, sl].astype(jnp.float32)
            k = k_ref[0, 0][:, sl] if kv_folded else k_ref[0, 0]
            v = v_ref[0, 0][:, sl] if kv_folded else v_ref[0, 0]
            if bounded:
                q = _mask_rows(q, q_start, seq_q)
                k = _mask_rows(k, k_start, seq_kv)
                v = _mask_rows(v, k_start, seq_kv)
                do = _mask_rows(do, q_start, seq_q)
            lse = lse_ref[0, 0][:, half]
            delta = delta_ref[0, 0][:, half]

            s = jax.lax.dot_general(
                q.astype(k.dtype), k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            dp = jax.lax.dot_general(
                do.astype(v.dtype), v, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            if masked:
                s = jnp.where(valid, s, _NEG_INF)
                p = jnp.where(valid, jnp.exp(s - lse[:, None]), 0.0)
                ds = jnp.where(valid, p * (dp - delta[:, None]), 0.0)
            else:
                p = jnp.exp(s - lse[:, None])          # [bq, bkv]
                ds = p * (dp - delta[:, None])         # [bq, bkv]
            # dv += p^T @ do ; dk += ds^T @ q (scale already in q)
            dv_acc[:, acc_sl] += jax.lax.dot_general(
                p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            dk_acc[:, acc_sl] += jax.lax.dot_general(
                ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

    _dispatch_tiles(compute, causal, bounded, q_start, k_start,
                    block_q, block_kv)

    @pl.when(iq == num_q - 1)
    def _finalize():
        dk_ref[0, 0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[:].astype(dv_ref.dtype)


def head_fold_eligible(h: int, hkv: int, d: int, segs=None) -> bool:
    """May the backward fold head pairs? 2D must fit the 128-lane vreg
    row, the q heads must pair evenly, every pair must share one kv head
    (group even) or fold its kv pair alongside (MHA, hkv even), and
    packed segments keep the unfolded kernels (their id specs are
    per-head-agnostic but the folded kernels don't thread them)."""
    group = h // hkv
    if segs is not None or 2 * d > 128 or h % 2:
        return False
    return (group % 2 == 0) or (group == 1 and hkv % 2 == 0)


def _flash_backward_fold(q, k, v, g, lse, delta, scale, causal,
                         block_q, block_kv, nq, nk, bounded, group):
    """Head-fold backward dispatch: fold pairs outside (one O(bytes)
    transpose per operand), run the folded kernels, unfold the
    gradients. GQA (group even) reduces dk/dv over pairs-per-group."""
    b, h, sq, d = q.shape
    hkv, skv = k.shape[1], k.shape[2]
    kv_folded = group == 1

    qf = _fold_heads(q)
    dof = _fold_heads(g)
    lsef = _fold_rows(lse)
    deltaf = _fold_rows(delta)
    if kv_folded:
        kf, vf = _fold_heads(k), _fold_heads(v)
        kv_dim = 2 * d
        kv_idx_q = lambda b_, h_, iq, ik: (b_, h_, ik, 0)  # noqa: E731
        kv_idx_k = lambda b_, h_, ik, iq: (b_, h_, ik, 0)  # noqa: E731
    else:
        kf, vf = k, v
        kv_dim = d
        kv_idx_q = (lambda b_, h_, iq, ik,
                    g_=group: (b_, (2 * h_) // g_, ik, 0))
        kv_idx_k = (lambda b_, h_, ik, iq,
                    g_=group: (b_, (2 * h_) // g_, ik, 0))

    qp_spec = pl.BlockSpec((1, 1, block_q, 2 * d),
                           lambda b_, h_, iq, ik: (b_, h_, iq, 0))
    row_spec = pl.BlockSpec((1, 1, block_q, 2),
                            lambda b_, h_, iq, ik: (b_, h_, iq, 0))

    dqf = pl.pallas_call(
        functools.partial(_bwd_dq_kernel_fold, scale=scale, causal=causal,
                          block_q=block_q, block_kv=block_kv, num_kv=nk,
                          seq_q=sq, seq_kv=skv, bounded=bounded,
                          kv_folded=kv_folded, d=d),
        grid=(b, h // 2, nq, nk),
        in_specs=[qp_spec,
                  pl.BlockSpec((1, 1, block_kv, kv_dim), kv_idx_q),
                  pl.BlockSpec((1, 1, block_kv, kv_dim), kv_idx_q),
                  qp_spec, row_spec, row_spec],
        out_specs=qp_spec,
        out_shape=jax.ShapeDtypeStruct((b, h // 2, sq, 2 * d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, 2 * d), jnp.float32)],
        interpret=_interpret(),
        name="flash_bwd_dq_fold",
    )(qf, kf, vf, dof, lsef, deltaf)

    qp_spec_k = pl.BlockSpec((1, 1, block_q, 2 * d),
                             lambda b_, h_, ik, iq: (b_, h_, iq, 0))
    row_spec_k = pl.BlockSpec((1, 1, block_q, 2),
                              lambda b_, h_, ik, iq: (b_, h_, iq, 0))
    dkv_out_spec = pl.BlockSpec((1, 1, block_kv, kv_dim),
                                lambda b_, h_, ik, iq: (b_, h_, ik, 0))
    dkf, dvf = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel_fold, scale=scale,
                          causal=causal, block_q=block_q,
                          block_kv=block_kv, num_q=nq, seq_q=sq,
                          seq_kv=skv, bounded=bounded,
                          kv_folded=kv_folded, d=d),
        grid=(b, h // 2, nk, nq),
        in_specs=[qp_spec_k,
                  pl.BlockSpec((1, 1, block_kv, kv_dim), kv_idx_k),
                  pl.BlockSpec((1, 1, block_kv, kv_dim), kv_idx_k),
                  qp_spec_k, row_spec_k, row_spec_k],
        out_specs=[dkv_out_spec, dkv_out_spec],
        out_shape=[
            jax.ShapeDtypeStruct((b, h // 2, skv, kv_dim), k.dtype),
            jax.ShapeDtypeStruct((b, h // 2, skv, kv_dim), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_kv, kv_dim), jnp.float32),
            pltpu.VMEM((block_kv, kv_dim), jnp.float32),
        ],
        interpret=_interpret(),
        name="flash_bwd_dkv_fold",
    )(qf, kf, vf, dof, lsef, deltaf)

    dq = _unfold_heads(dqf)
    if kv_folded:
        dk, dv = _unfold_heads(dkf), _unfold_heads(dvf)
    else:
        # Each pair already summed its two halves into the shared kv
        # head; finish the GQA reduction over the group's pairs.
        dk = dkf.reshape(b, hkv, group // 2, skv, d).sum(axis=2)
        dv = dvf.reshape(b, hkv, group // 2, skv, d).sum(axis=2)
    return dq, dk.astype(k.dtype), dv.astype(v.dtype)


def _bwd_dq_kernel(*refs, scale, causal, block_q, block_kv, num_kv,
                   seq_q, seq_kv, has_segs, bounded, band=None):
    if has_segs:
        (q_ref, k_ref, v_ref, qs_ref, ks_ref, do_ref, lse_ref, delta_ref,
         dq_ref, dq_acc) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         dq_ref, dq_acc) = refs
        qs_ref = ks_ref = None
    iq = pl.program_id(2)
    step = pl.program_id(3)
    window = band.window if band else 0
    ik, live = band.kv_tile(iq, step) if band else (step, None)

    @pl.when(step == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    q_start = iq * block_q
    k_start = ik * block_kv

    def compute(masked):
        q = q_ref[0, 0].astype(jnp.float32) * scale
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        if bounded:
            k = _mask_rows(k, k_start, seq_kv)
            v = _mask_rows(v, k_start, seq_kv)
        do = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0][:, 0]
        delta = delta_ref[0, 0][:, 0]

        s = jax.lax.dot_general(q.astype(k.dtype), k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do.astype(v.dtype), v,
                                 (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        if masked:
            valid = _valid_mask(q_start, k_start, block_q, block_kv,
                                seq_q, seq_kv, causal, bounded,
                                qs_ref, ks_ref, window)
            s = jnp.where(valid, s, _NEG_INF)
            p = jnp.where(valid, jnp.exp(s - lse[:, None]), 0.0)
            ds = jnp.where(valid, p * (dp - delta[:, None]), 0.0)
        else:
            p = jnp.exp(s - lse[:, None])
            ds = p * (dp - delta[:, None])
        dq_acc[:] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale

    _dispatch_tiles(compute, causal, bounded or has_segs, q_start, k_start,
                    block_q, block_kv, window, live)

    @pl.when(step == num_kv - 1)
    def _finalize():
        dq_ref[0, 0] = dq_acc[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(*refs, scale, causal,
                    block_q, block_kv, num_q, seq_q, seq_kv, has_segs,
                    bounded, band=None):
    """`band` (a window layer): the grid's last axis walks the band's
    `num_q` steps of kv tile ik, not all the q tiles."""
    if has_segs:
        (q_ref, k_ref, v_ref, qs_ref, ks_ref, do_ref, lse_ref, delta_ref,
         dk_ref, dv_ref, dk_acc, dv_acc) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         dk_ref, dv_ref, dk_acc, dv_acc) = refs
        qs_ref = ks_ref = None
    ik = pl.program_id(2)
    step = pl.program_id(3)
    window = band.window if band else 0
    iq, live = band.q_tile(ik, step) if band else (step, None)

    @pl.when(step == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    q_start = iq * block_q
    k_start = ik * block_kv

    def compute(masked):
        q = q_ref[0, 0].astype(jnp.float32) * scale
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        do = do_ref[0, 0].astype(jnp.float32)
        if bounded:
            q = _mask_rows(q, q_start, seq_q)
            k = _mask_rows(k, k_start, seq_kv)
            v = _mask_rows(v, k_start, seq_kv)
            do = _mask_rows(do, q_start, seq_q)
        lse = lse_ref[0, 0][:, 0]
        delta = delta_ref[0, 0][:, 0]

        s = jax.lax.dot_general(q.astype(k.dtype), k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do.astype(v.dtype), v,
                                 (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        if masked:
            valid = _valid_mask(q_start, k_start, block_q, block_kv,
                                seq_q, seq_kv, causal, bounded,
                                qs_ref, ks_ref, window)
            s = jnp.where(valid, s, _NEG_INF)
            p = jnp.where(valid, jnp.exp(s - lse[:, None]), 0.0)
            ds = jnp.where(valid, p * (dp - delta[:, None]), 0.0)
        else:
            p = jnp.exp(s - lse[:, None])          # [bq, bkv]
            ds = p * (dp - delta[:, None])         # [bq, bkv]
        # dv += p^T @ do
        dv_acc[:] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        # dk += ds^T @ q * scale (q already has scale folded in → use raw q)
        dk_acc[:] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    _dispatch_tiles(compute, causal, bounded or has_segs, q_start, k_start,
                    block_q, block_kv, window, live)

    @pl.when(step == num_q - 1)
    def _finalize():
        dk_ref[0, 0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[:].astype(dv_ref.dtype)


def _flash_backward_window(q, k, v, g, lse, delta, scale, band: _BandGrid,
                           bounded, group, segs):
    """A window layer's backward: the straight dq and dkv kernels on the
    band's two grids; dk/dv come out a query head and are summed over a
    key/value head's group outside, as in `_flash_backward`."""
    b, h, sq, d = q.shape
    hkv, skv = k.shape[1], k.shape[2]
    has_segs = segs is not None
    static = dict(scale=scale, causal=True, block_q=band.block_q,
                  block_kv=band.block_kv, seq_q=sq, seq_kv=skv,
                  has_segs=has_segs, bounded=bounded, band=band)
    inputs = (q, k, v, *(segs or ()), g, lse[..., None], delta[..., None])

    def in_specs(specs):
        return ([specs["q"], specs["kv"], specs["kv"]] + specs["segs"]
                + [specs["q"], specs["qcol"], specs["qcol"]])

    specs = _band_specs(band, d, group, True, has_segs)
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, num_kv=band.kv_steps, **static),
        grid=(b, h, band.nq, band.kv_steps),
        in_specs=in_specs(specs),
        out_specs=specs["q"],
        out_shape=jax.ShapeDtypeStruct((b, h, sq, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((band.block_q, d), jnp.float32)],
        interpret=_interpret(),
        name="flash_window_bwd_dq",
    )(*inputs)

    specs = _band_specs(band, d, group, False, has_segs)
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, num_q=band.q_steps, **static),
        grid=(b, h, band.nk, band.q_steps),
        in_specs=in_specs(specs),
        out_specs=[specs["dkv"], specs["dkv"]],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, skv, d), k.dtype),
            jax.ShapeDtypeStruct((b, h, skv, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((band.block_kv, d), jnp.float32),
            pltpu.VMEM((band.block_kv, d), jnp.float32),
        ],
        interpret=_interpret(),
        name="flash_window_bwd_dkv",
    )(*inputs)
    if group > 1:
        dk = dk.reshape(b, hkv, group, skv, d).sum(axis=2)
        dv = dv.reshape(b, hkv, group, skv, d).sum(axis=2)
    return dq, dk.astype(k.dtype), dv.astype(v.dtype)


def _flash_backward(res, g, scale, causal, block_q, block_kv, segs=None,
                    head_fold: bool = False, window=0):
    q, k, v, out, lse = res
    b, h, sq, d = q.shape
    hkv, skv = k.shape[1], k.shape[2]
    group = h // hkv
    block_q = min(block_q, sq)
    block_kv = min(block_kv, skv)
    nq = _cdiv(sq, block_q)
    nk = _cdiv(skv, block_kv)
    bounded = (sq % block_q != 0) or (skv % block_kv != 0)

    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)  # [B,H,Sq]
    if window:
        return _flash_backward_window(
            q, k, v, g, lse, delta, scale,
            _BandGrid(window, block_q, block_kv, nq, nk), bounded, group,
            segs)
    if head_fold and head_fold_eligible(h, hkv, d, segs):
        return _flash_backward_fold(
            q, k, v, g, lse, delta, scale, causal, block_q, block_kv,
            nq, nk, bounded, group)
    if d < 128 and not _force_straight():
        return _flash_backward_t(
            q, k, v, g, lse, delta, scale, causal, block_q, block_kv,
            nq, nk, bounded, group, segs)
    lse4 = lse[..., None]
    delta4 = delta[..., None]

    dq_in_specs = [_spec_q(block_q, d, q_major=True),
                   _spec_kv(block_kv, d, group, q_major=True),
                   _spec_kv(block_kv, d, group, q_major=True)]
    dq_inputs = [q, k, v]
    if segs is not None:
        q_segs, kv_segs = segs
        dq_in_specs += _spec_segs(block_q, block_kv, q_major=True,
                                  transposed=False)
        dq_inputs += [q_segs, kv_segs]
    dq_in_specs += [_spec_q(block_q, d, q_major=True),
                    _spec_qcol(block_q, q_major=True),
                    _spec_qcol(block_q, q_major=True)]

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_kv=block_kv, num_kv=nk,
                          seq_q=sq, seq_kv=skv, has_segs=segs is not None,
                          bounded=bounded),
        grid=(b, h, nq, nk),
        in_specs=dq_in_specs,
        out_specs=pl.BlockSpec((1, 1, block_q, d),
                               lambda b_, h_, iq, ik: (b_, h_, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, sq, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=_interpret(),
        name="flash_bwd_dq",
    )(*dq_inputs, g, lse4, delta4)

    # dk/dv computed at q-head granularity [B, H, Skv, D]; grouped heads are
    # reduced outside (GQA) — simple and correct; a fused variant can
    # accumulate in-kernel later.
    dkv_in_specs = [_spec_q(block_q, d, q_major=False),
                    _spec_kv(block_kv, d, group, q_major=False),
                    _spec_kv(block_kv, d, group, q_major=False)]
    dkv_inputs = [q, k, v]
    if segs is not None:
        q_segs, kv_segs = segs
        dkv_in_specs += _spec_segs(block_q, block_kv, q_major=False,
                                   transposed=False)
        dkv_inputs += [q_segs, kv_segs]
    dkv_in_specs += [_spec_q(block_q, d, q_major=False),
                     _spec_qcol(block_q, q_major=False),
                     _spec_qcol(block_q, q_major=False)]

    dk_full, dv_full = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_kv=block_kv, num_q=nq,
                          seq_q=sq, seq_kv=skv, has_segs=segs is not None,
                          bounded=bounded),
        grid=(b, h, nk, nq),
        in_specs=dkv_in_specs,
        out_specs=[
            pl.BlockSpec((1, 1, block_kv, d),
                         lambda b_, h_, ik, iq: (b_, h_, ik, 0)),
            pl.BlockSpec((1, 1, block_kv, d),
                         lambda b_, h_, ik, iq: (b_, h_, ik, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, skv, d), k.dtype),
            jax.ShapeDtypeStruct((b, h, skv, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_kv, d), jnp.float32),
            pltpu.VMEM((block_kv, d), jnp.float32),
        ],
        interpret=_interpret(),
        name="flash_bwd_dkv",
    )(*dkv_inputs, g, lse4, delta4)

    if group > 1:
        dk = dk_full.reshape(b, hkv, group, skv, d).sum(axis=2)
        dv = dv_full.reshape(b, hkv, group, skv, d).sum(axis=2)
    else:
        dk, dv = dk_full, dv_full
    return dq, dk.astype(k.dtype), dv.astype(v.dtype)


def _flash_backward_t(q, k, v, g, lse, delta, scale, causal,
                      block_q, block_kv, nq, nk, bounded, group, segs):
    """D<128 backward: transposed-orientation kernels (full MXU lanes —
    see the orientation note above). Gradients come out as [B,H,D,S] and
    are swapped back here."""
    b, h, sq, d = q.shape
    hkv, skv = k.shape[1], k.shape[2]
    has_segs = segs is not None

    # lse/delta as [B,H,1,Sq] lane rows for the dq^T kernel.
    lse_row = lse[:, :, None, :]
    delta_row = delta[:, :, None, :]

    dq_in_specs = [_spec_q(block_q, d, q_major=True),
                   _spec_kv(block_kv, d, group, q_major=True),
                   _spec_kv(block_kv, d, group, q_major=True)]
    dq_inputs = [q, k, v]
    if has_segs:
        q_segs, kv_segs = segs              # [B,Sq,1] / [B,1,Skv]
        qs_row = jnp.swapaxes(q_segs, 1, 2)   # [B,1,Sq]
        ks_col = jnp.swapaxes(kv_segs, 1, 2)  # [B,Skv,1]
        dq_in_specs += _spec_segs(block_q, block_kv, q_major=True,
                                  transposed=True)
        dq_inputs += [qs_row, ks_col]
    dq_in_specs += [_spec_q(block_q, d, q_major=True),
                    _spec_qrow(block_q, q_major=True),
                    _spec_qrow(block_q, q_major=True)]

    dqt = pl.pallas_call(
        functools.partial(_bwd_dq_kernel_t, scale=scale, causal=causal,
                          block_q=block_q, block_kv=block_kv, num_kv=nk,
                          seq_q=sq, seq_kv=skv, has_segs=has_segs,
                          bounded=bounded),
        grid=(b, h, nq, nk),
        in_specs=dq_in_specs,
        out_specs=pl.BlockSpec((1, 1, d, block_q),
                               lambda b_, h_, iq, ik: (b_, h_, 0, iq)),
        out_shape=jax.ShapeDtypeStruct((b, h, d, sq), q.dtype),
        scratch_shapes=[pltpu.VMEM((d, block_q), jnp.float32)],
        interpret=_interpret(),
        name="flash_bwd_dq_t",
    )(*dq_inputs, g, lse_row, delta_row)

    lse4 = lse[..., None]
    delta4 = delta[..., None]
    dkv_in_specs = [_spec_q(block_q, d, q_major=False),
                    _spec_kv(block_kv, d, group, q_major=False),
                    _spec_kv(block_kv, d, group, q_major=False)]
    dkv_inputs = [q, k, v]
    if has_segs:
        q_segs, kv_segs = segs
        dkv_in_specs += _spec_segs(block_q, block_kv, q_major=False,
                                   transposed=False)
        dkv_inputs += [q_segs, kv_segs]
    dkv_in_specs += [_spec_q(block_q, d, q_major=False),
                     _spec_qcol(block_q, q_major=False),
                     _spec_qcol(block_q, q_major=False)]

    dkt_full, dvt_full = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel_t, scale=scale, causal=causal,
                          block_q=block_q, block_kv=block_kv, num_q=nq,
                          seq_q=sq, seq_kv=skv, has_segs=has_segs,
                          bounded=bounded),
        grid=(b, h, nk, nq),
        in_specs=dkv_in_specs,
        out_specs=[
            pl.BlockSpec((1, 1, d, block_kv),
                         lambda b_, h_, ik, iq: (b_, h_, 0, ik)),
            pl.BlockSpec((1, 1, d, block_kv),
                         lambda b_, h_, ik, iq: (b_, h_, 0, ik)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, d, skv), k.dtype),
            jax.ShapeDtypeStruct((b, h, d, skv), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((d, block_kv), jnp.float32),
            pltpu.VMEM((d, block_kv), jnp.float32),
        ],
        interpret=_interpret(),
        name="flash_bwd_dkv_t",
    )(*dkv_inputs, g, lse4, delta4)

    dq = jnp.swapaxes(dqt, -1, -2)
    if group > 1:
        dk = jnp.swapaxes(
            dkt_full.reshape(b, hkv, group, d, skv).sum(axis=2), -1, -2)
        dv = jnp.swapaxes(
            dvt_full.reshape(b, hkv, group, d, skv).sum(axis=2), -1, -2)
    else:
        dk = jnp.swapaxes(dkt_full, -1, -2)
        dv = jnp.swapaxes(dvt_full, -1, -2)
    return dq, dk.astype(k.dtype), dv.astype(v.dtype)


# ---------------------------------------------------------------------------
# Public API
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_attention_bhsd(q, k, v, scale, causal, block_q, block_kv,
                          head_fold=False):
    out, _ = _flash_forward(q, k, v, scale, causal, block_q, block_kv)
    return out


def _fwd_rule(q, k, v, scale, causal, block_q, block_kv, head_fold):
    out, lse = _flash_forward(q, k, v, scale, causal, block_q, block_kv)
    return out, (q, k, v, out, lse)


def _bwd_rule(scale, causal, block_q, block_kv, head_fold, res, g):
    return _flash_backward(res, g, scale, causal, block_q, block_kv,
                           head_fold=head_fold)


_flash_attention_bhsd.defvjp(_fwd_rule, _bwd_rule)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _flash_attention_seg_bhsd(q, k, v, q_segs, kv_segs, scale, causal,
                              block_q, block_kv):
    out, _ = _flash_forward(q, k, v, scale, causal, block_q, block_kv,
                            segs=(q_segs, kv_segs))
    return out


def _seg_fwd_rule(q, k, v, q_segs, kv_segs, scale, causal, block_q,
                  block_kv):
    out, lse = _flash_forward(q, k, v, scale, causal, block_q, block_kv,
                              segs=(q_segs, kv_segs))
    return out, (q, k, v, out, lse, q_segs, kv_segs)


def _seg_bwd_rule(scale, causal, block_q, block_kv, res, g):
    q, k, v, out, lse, q_segs, kv_segs = res
    dq, dk, dv = _flash_backward((q, k, v, out, lse), g, scale, causal,
                                 block_q, block_kv, segs=(q_segs, kv_segs))
    # Integer segment ids take float0 cotangents.
    import numpy as np
    f0 = jax.dtypes.float0
    return (dq, dk, dv, np.zeros(q_segs.shape, f0),
            np.zeros(kv_segs.shape, f0))


_flash_attention_seg_bhsd.defvjp(_seg_fwd_rule, _seg_bwd_rule)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _flash_window_bhsd(q, k, v, segs, scale, window, block_q, block_kv):
    """A sliding-window layer: causal, `window` keys a query; `segs` is
    (q_segs, kv_segs) of a packed batch or None."""
    return _flash_forward(q, k, v, scale, True, block_q, block_kv, segs,
                          window)[0]


def _window_fwd_rule(q, k, v, segs, scale, window, block_q, block_kv):
    out, lse = _flash_forward(q, k, v, scale, True, block_q, block_kv, segs,
                              window)
    return out, (q, k, v, out, lse, segs)


def _window_bwd_rule(scale, window, block_q, block_kv, res, g):
    q, k, v, out, lse, segs = res
    dq, dk, dv = _flash_backward((q, k, v, out, lse), g, scale, True,
                                 block_q, block_kv, segs=segs, window=window)
    d_segs = None if segs is None else tuple(
        np.zeros(x.shape, jax.dtypes.float0) for x in segs)
    return dq, dk, dv, d_segs


_flash_window_bhsd.defvjp(_window_fwd_rule, _window_bwd_rule)


class AttentionChoice(NamedTuple):
    """What `choose_attention` decided for one call: the implementation
    ('pallas' or 'reference'), the flash kernels' tiles, and the term that
    decided, for the line `transformer/attention.py` prints once a shape."""
    impl: str
    block_q: int
    block_kv: int
    why: str


# What `choose_attention` rests on (tools/bench_profile.py's sweep on a TPU
# v5e; the tables are in PERF.md, PR 37): forward + backward under selective
# recomputation, bf16, heads of 64 / 80 / 128, S 512..2048, causal and
# bidirectional, packed and not. XLA's dense attention keeps the float32
# [B, H, S, S] scores of a call on the chip while they fit its fast memory
# and then beats the kernels (by 10-40% at 64 MiB of scores, level at 108
# MiB); from 128 MiB on it streams them through HBM about eleven times a
# layer and the kernels are 1.5-4 times faster. What decides is the bytes,
# whichever of batch, heads and S they come from. At S 2048, where the
# kernels have always run, they are level with dense below 128 MiB (0.94 to
# 1.24 times its speed) and 2-4 times faster from there.
_MEASURED_SEQ = (512, 2048)
_MEASURED_HEAD_DIM = (64, 128)
_DENSE_SCORES_SPILL = 128 << 20
# Beside what was measured, the second reason to take the kernels that the
# rule before this function had: dense float32 scores and probabilities
# past 1 GB on a device, whatever the dtype, the heads and S.
_DENSE_BYTES_MAX = 1 << 30


def flash_tiles(seq: int, block_q: Optional[int] = None,
                block_kv: Optional[int] = None, window: int = 0) -> tuple:
    """(block_q, block_kv) of the three flash kernels: the caller's where it
    names them (clamped to S), else one tile a sequence up to S 1024 and
    512 x 512 past it; a window layer whose band is shorter than the
    sequence takes 512 x 512 at every S, since one tile a sequence would
    skip nothing (not measured below S 2048). Measured
    (PERF.md, PR 37): at S 1024 one 1024 x 1024 tile is 20-30% faster than
    four 512 x 512 of which causal skipping drops one (no rescaling between
    key/value tiles, a quarter of the grid steps) and every smaller tile is
    slower still; at S 2048 tiles of 1024 gain under 10% and Mosaic refuses
    them with segments at batch 4 (VMEM), so the four-chip cell keeps its
    512 x 512. A compile for a described v5e takes one tile a sequence at
    every S <= 1024, D <= 128, batch 1..16, packed or not, grouped or not."""
    chosen = seq if seq <= 1024 and not 0 < window < seq else 512
    return min(block_q or chosen, seq), min(block_kv or chosen, seq)


def choose_attention(*, impl: str, batch: int, seq: int, heads: int,
                     head_dim: int, dtype, segments: bool, backend: str,
                     block_q: Optional[int] = None,
                     block_kv: Optional[int] = None,
                     window: int = 0) -> AttentionChoice:
    """The attention implementation and the flash tiles for one call, from
    what the call can see: `impl` as configured ('auto', 'pallas',
    'reference'), batch and query heads ON ONE DEVICE, S, D, the compute
    dtype, packed segments or not, and the backend. Pure: no device, no
    configuration, no environment is read.

    'auto' on a TPU takes the Pallas kernels from S 2048 on, as it always
    has; below, where the call's dense scores were measured not to stay on
    the chip (bf16, heads of 64..128, S from 512: the constants above) or
    would pass 1 GB with their probabilities; and keeps XLA's dense
    attention everywhere else: nobody measured float32 compute (the kernels
    feed the MXU bf16), other head sizes, or S under 512. Other backends
    keep XLA's dense attention. Explicit tiles are honoured; unset ones come
    from `flash_tiles`. A sliding-window layer (`window` > 0) is chosen for
    by the same terms: XLA's dense attention under a band mask holds the
    same [B, heads, S, S] scores whatever the window, and the kernels' band
    (`flash_window_*`) only does less."""
    def choice(impl_, why):
        return AttentionChoice(
            impl_, *flash_tiles(seq, block_q, block_kv, window), why)

    shape = (f"S={seq} D={head_dim}" + (f" window {window}" if window else "")
             + (" segments" if segments else ""))
    if impl != "auto":
        return choice(impl, shape if impl == "pallas" else impl)
    if backend != "tpu":
        return choice("reference", f"auto: backend {backend}")
    if seq >= _MEASURED_SEQ[1]:
        return choice("pallas", shape)
    scores = 4 * batch * heads * seq * seq
    mib = f"{scores / 2**20:.0f} MiB of scores"
    if 2 * scores > _DENSE_BYTES_MAX:
        return choice("pallas", f"{shape}, dense scores over 1 GB")
    if not (jnp.dtype(dtype) == jnp.bfloat16 and seq >= _MEASURED_SEQ[0]
            and _MEASURED_HEAD_DIM[0] <= head_dim <= _MEASURED_HEAD_DIM[1]):
        return choice("reference", f"auto: {shape} {jnp.dtype(dtype).name} "
                                   "not measured")
    if scores >= _DENSE_SCORES_SPILL:
        return choice("pallas", f"{shape}, {mib}")
    return choice("reference", f"auto: {mib} stay on the chip")


def flash_attention(q, k, v, causal: bool = True,
                    softmax_scale: Optional[float] = None,
                    block_q: Optional[int] = None,
                    block_kv: Optional[int] = None,
                    segment_ids: Optional[jnp.ndarray] = None,
                    head_fold: bool = False, window: int = 0):
    """Flash attention on [B, S, H, D] tensors (GQA-aware).

    Returns [B, Sq, H, D]. Drop-in for ops.attention.dot_product_attention's
    causal/bidirectional paths. Tiles left unset come from `flash_tiles`.

    segment_ids: optional [B, S] int packing map — attention is restricted
    to within-segment (packed sequences, reference THD/packed_seq_params
    semantics) with the same O(S) memory profile; segment masking composes
    with the causal block-skip.

    head_fold: fold q-head pairs into the trailing block dim in the
    BACKWARD kernels (D=64 → full 128-lane rows; PERF.md lever 1,
    --flash-head-fold). Silently keeps the standard kernels when
    ineligible (head_fold_eligible: 2D > 128, odd head counts, packed
    segments). Forward math is unchanged; grads parity-pinned ≤ 1e-5.

    window: > 0 makes the layer a sliding-window one (causal only): the
    query at position i of a segment sees the keys i - window < j <= i of
    that segment. Its three kernels (`flash_window_fwd`, `_bwd_dq`,
    `_bwd_dkv`; straight orientation at every D) run grids that hold the
    band's tiles alone; with window 0 nothing of this is traced.
    """
    b, sq, h, d = q.shape
    if softmax_scale is None:
        softmax_scale = 1.0 / (d ** 0.5)
    block_q, block_kv = flash_tiles(sq, block_q, block_kv, window)
    qt = jnp.swapaxes(q, 1, 2)   # [B,H,S,D]
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    if window:
        if not causal:
            raise ValueError("a sliding window is causal")
        segs = None
        if segment_ids is not None:
            segs = segment_ids.astype(jnp.int32)
            segs = (segs[:, :, None], segs[:, None, :])
        out = _flash_window_bhsd(qt, kt, vt, segs, float(softmax_scale),
                                 int(window), block_q, block_kv)
    elif segment_ids is None:
        out = _flash_attention_bhsd(qt, kt, vt, float(softmax_scale),
                                    causal, block_q, block_kv,
                                    bool(head_fold))
    else:
        segs = segment_ids.astype(jnp.int32)
        out = _flash_attention_seg_bhsd(
            qt, kt, vt, segs[:, :, None], segs[:, None, :],
            float(softmax_scale), causal, block_q, block_kv)
    return jnp.swapaxes(out, 1, 2)
