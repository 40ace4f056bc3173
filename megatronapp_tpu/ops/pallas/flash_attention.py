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
- packed documents (`segment_ids`): a call of more than one tile a sequence
  is handed, by scalar prefetch, every tile's range of document ids
  (`segment_tile_table`). A tile whose query ids and key ids cannot be equal
  is neither computed nor copied (the index maps hold the row's last live
  tile, as they do above the causal diagonal of such a call), and a tile
  inside one document under the diagonal takes the unmasked path; exact for
  any ids, the same numbers as masking every tile;
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


def _dispatch_tiles(compute, causal, mask_all, q_start, k_start,
                    block_q, block_kv, window=0, live=None, docs=None):
    """Shared tile dispatch for all three kernels: skip tiles entirely
    above the causal diagonal, and route interior tiles (strictly below
    the diagonal, in-bounds) to compute(masked=False), which skips the
    iota/compare/select chain on [bq, bkv] (1.4-3.6% of a kernel's time at
    D 128 and 512 x 512 on a v5e with 28% of the tiles unmasked: PR 58).
    A window layer's grid (`_BandGrid`) holds no tile outside the band but
    the steps a short row of tiles leaves over, on which `live` is false;
    its interior tiles lie inside the band too.

    `docs` (a packed call that was handed its table, `segment_tile_table`)
    is (meet, one) of this tile: whether the query tile's range of document
    ids meets the key tile's, and whether both are one and the same id. A
    tile that does not meet is not computed (no pair has equal ids), and an
    interior tile of one document needs no mask. `mask_all`: every tile
    takes the mask (ragged tiles, or segment ids without a table)."""
    meet, one = docs if docs is not None else (None, None)

    def both(*terms):
        terms = [t for t in terms if t is not None]
        return functools.reduce(lambda a, b: a & b, terms) if terms else None

    def when(condition, masked):
        if condition is None:
            compute(masked)
        else:
            pl.when(condition)(lambda: compute(masked))

    def on_or_under_the_diagonal():
        if causal and not window:
            return q_start + block_q - 1 >= k_start

    if mask_all:
        when(both(on_or_under_the_diagonal(), live, meet), True)
        return
    if window:
        interior = ((q_start >= k_start + block_kv)
                    & (q_start + block_q - 1 - k_start < window))
    elif causal:
        interior = q_start >= k_start + block_kv
    else:
        interior = None
    interior = both(interior, one)
    when(both(interior, live), False)
    if interior is not None:
        when(both(jnp.logical_not(interior), on_or_under_the_diagonal(),
                  live, meet), True)


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
# The documents' table of a packed call. `segment_ids` restricts attention
# to pairs of equal id, so a tile whose query ids and key ids cannot be
# equal has nothing to compute. The table holds, a (sequence, tile), the
# range [min, max] of the tile's ids and the run [lo, hi] of the other
# side's tiles that the tile can meet inside the grid's causal or band
# part; the kernels and their index maps read it from SMEM (scalar
# prefetch). It is exact for any ids: sorted ids (packed documents) make a
# row's live tiles one run, unsorted ones only skip less.
# ---------------------------------------------------------------------------

_TABLE_COLUMNS = 4      # a tile's (min id, max id, lo, hi)


def _tile_ranges(ids, block):
    """[B, S] ids -> ([B, n], [B, n]): the least and the largest id of
    every tile of `block` positions (a ragged last tile counts its real
    positions alone)."""
    b, s = ids.shape
    n = _cdiv(s, block)
    pad = ((0, 0), (0, n * block - s))
    big = jnp.iinfo(jnp.int32)
    return (jnp.pad(ids, pad, constant_values=big.max)
            .reshape(b, n, block).min(-1),
            jnp.pad(ids, pad, constant_values=big.min)
            .reshape(b, n, block).max(-1))


def _grid_part(nq, nk, block_q, block_kv, causal, window):
    """bool [nq, nk] (numpy, static): the tiles that the kernels' grids
    compute whatever the documents are — the band's tiles of a window
    layer, the tiles on and under the diagonal of a causal one, else all."""
    iq, ik = np.arange(nq)[:, None], np.arange(nk)[None, :]
    if window:
        lo, hi = _BandGrid(window, block_q, block_kv, nq, nk).kv_range(iq, np)
        return (lo <= ik) & (ik <= hi)
    if causal:
        return iq * block_q + block_q - 1 >= ik * block_kv
    return np.ones((nq, nk), bool)


def segment_tile_table(q_ids, kv_ids, block_q, block_kv, causal=True,
                       window=0):
    """The table of one packed call, from its [B, Sq] and [B, Skv] int32
    document ids and its tiles: (q_table [B, nq, 4], kv_table [B, nk, 4],
    tiles, computed). A row of q_table is a q tile's (min id, max id, first
    and last kv tile it is live with), a row of kv_table the same of a kv
    tile over the q tiles. A tile pair is live where it lies in the grid's
    causal or band part (`_grid_part`) and the two ranges of ids meet;
    `tiles` (a Python int) counts the part over the batch and `computed`
    (int32 scalar) the live pairs: what the kernels compute of it. A tile
    with no live partner holds lo > hi clamped to a valid index; nothing
    is computed for it either way."""
    q_min, q_max = _tile_ranges(q_ids, block_q)
    k_min, k_max = _tile_ranges(kv_ids, block_kv)
    nq, nk = q_min.shape[1], k_min.shape[1]
    part = _grid_part(nq, nk, block_q, block_kv, causal, window)
    live = ((q_min[:, :, None] <= k_max[:, None, :])
            & (k_min[:, None, :] <= q_max[:, :, None]) & part)

    def run(live, n, axis):
        at = jnp.arange(n, dtype=jnp.int32).reshape(
            (1, 1, n) if axis == 2 else (1, n, 1))
        lo = jnp.min(jnp.where(live, at, n - 1), axis=axis)
        return lo, jnp.maximum(jnp.max(jnp.where(live, at, 0), axis=axis),
                               lo)

    q_table = jnp.stack([q_min, q_max, *run(live, nk, 2)], axis=-1)
    kv_table = jnp.stack([k_min, k_max, *run(live, nq, 1)], axis=-1)
    return (q_table.astype(jnp.int32), kv_table.astype(jnp.int32),
            int(part.sum()) * q_ids.shape[0], jnp.sum(live, dtype=jnp.int32))


def segment_tile_counts(segment_ids, block_q=None, block_kv=None,
                        causal=True, window=0):
    """(tiles, computed) of `flash_attention(..., segment_ids=segment_ids)`
    with the same tiles and window: the tile pairs of its grids' causal or
    band part over the batch, and how many of them its kernels compute
    (`segment_tile_table`; a call of one tile a sequence is handed no table
    and computes its one tile, which is what the table says of it too)."""
    block_q, block_kv = flash_tiles(segment_ids.shape[1], block_q, block_kv,
                                    window)
    ids = segment_ids.astype(jnp.int32)
    return segment_tile_table(ids, ids, block_q, block_kv, causal,
                              window)[2:]


# ---------------------------------------------------------------------------
# How a kernel's grid walks the tiles, and the BlockSpecs that follow it.
# Every kernel runs on a (b, h, row, step) grid. Rows are q tiles and steps
# their kv tiles in the q-major kernels (forward, dq), the other way round
# in the kv-major ones (dkv). Segment-id and lse/delta specs come in straight
# ([bq,1] columns) and transposed ([1,bq] lane rows) orientations.
# ---------------------------------------------------------------------------


class _Walk(NamedTuple):
    """One kernel's walk over a call's tiles. `band` (a window layer): a
    row's steps are its band's tiles alone, else all the tiles of the other
    side. `tabled` (a packed call of more than one tile): the kernel and
    its index maps are handed the documents' table by scalar prefetch,
    flat: q_table [B * nq * 4] and kv_table [B * nk * 4]."""
    block_q: int
    block_kv: int
    nq: int
    nk: int
    q_major: bool
    band: Optional[_BandGrid] = None
    tabled: bool = False

    @property
    def window(self) -> int:
        return self.band.window if self.band else 0

    @property
    def rows(self) -> int:
        return self.nq if self.q_major else self.nk

    @property
    def steps(self) -> int:
        if self.band:
            return self.band.kv_steps if self.q_major else self.band.q_steps
        return self.nk if self.q_major else self.nq

    def tile(self, row, step):
        """(iq, ik, live) that a grid step stands for; `live` is None but
        on a band's grid."""
        if self.band is None:
            other, live = step, None
        elif self.q_major:
            other, live = self.band.kv_tile(row, step)
        else:
            other, live = self.band.q_tile(row, step)
        return (row, other, live) if self.q_major else (other, row, live)

    def copied(self, b, row, step, tables):
        """(iq, ik) whose blocks a grid step holds: the step's own tile,
        held inside the run [lo, hi] of the row's live tiles where there is
        a table, so a dead step (another document's tile, one above the
        causal diagonal) starts no copy."""
        iq, ik, _ = self.tile(row, step)
        if tables:
            q_table, kv_table = tables
            if self.q_major:
                at = (b * self.nq + iq) * _TABLE_COLUMNS
                ik = jnp.minimum(jnp.maximum(ik, q_table[at + 2]),
                                 q_table[at + 3])
            else:
                at = (b * self.nk + ik) * _TABLE_COLUMNS
                iq = jnp.minimum(jnp.maximum(iq, kv_table[at + 2]),
                                 kv_table[at + 3])
        return iq, ik

    def docs(self, b, iq, ik, tables):
        """(meet, one) of tile pair (iq, ik) for `_dispatch_tiles`."""
        q_table, kv_table = tables
        at_q = (b * self.nq + iq) * _TABLE_COLUMNS
        at_k = (b * self.nk + ik) * _TABLE_COLUMNS
        q_min, q_max = q_table[at_q], q_table[at_q + 1]
        k_min, k_max = kv_table[at_k], kv_table[at_k + 1]
        return ((q_min <= k_max) & (k_min <= q_max),
                (q_min == q_max) & (k_min == k_max) & (q_min == k_min))

    def enter(self, refs):
        """A kernel's first lines: (refs without the table, step, iq, ik,
        live, docs) of this grid step."""
        tables, refs = (refs[:2], refs[2:]) if self.tabled else (None, refs)
        row = pl.program_id(2)
        step = pl.program_id(3)
        iq, ik, live = self.tile(row, step)
        docs = self.docs(pl.program_id(0), iq, ik, tables) if tables else None
        return refs, step, iq, ik, live, docs

    def specs(self, d, group):
        """BlockSpecs by operand. Blocks: q [bq, d], kv [bkv, d] of the
        group's key/value head, dkv [bkv, d] a query head; qcol / qrow the
        [bq, 1] columns and [1, bq] lane rows of lse and delta; q_t / dkv_t
        the transposed kernels' [d, bq] and [d, bkv] outputs; segs / segs_t
        the (q ids, kv ids) of the straight ([bq, 1], [1, bkv]) and the
        transposed ([1, bq], [bkv, 1]) kernels."""
        bq, bkv = self.block_q, self.block_kv

        def spec(shape, index):
            return pl.BlockSpec(shape, lambda b_, h_, r, t, *tables: index(
                b_, h_, *self.copied(b_, r, t, tables)))

        return {
            "q": spec((1, 1, bq, d), lambda b_, h_, iq, ik: (b_, h_, iq, 0)),
            "kv": spec((1, 1, bkv, d),
                       lambda b_, h_, iq, ik: (b_, h_ // group, ik, 0)),
            "dkv": spec((1, 1, bkv, d),
                        lambda b_, h_, iq, ik: (b_, h_, ik, 0)),
            "qcol": spec((1, 1, bq, 1),
                         lambda b_, h_, iq, ik: (b_, h_, iq, 0)),
            "qrow": spec((1, 1, 1, bq),
                         lambda b_, h_, iq, ik: (b_, h_, 0, iq)),
            "q_t": spec((1, 1, d, bq),
                        lambda b_, h_, iq, ik: (b_, h_, 0, iq)),
            "dkv_t": spec((1, 1, d, bkv),
                          lambda b_, h_, iq, ik: (b_, h_, 0, ik)),
            "segs": [
                spec((1, bq, 1), lambda b_, h_, iq, ik: (b_, iq, 0)),
                spec((1, 1, bkv), lambda b_, h_, iq, ik: (b_, 0, ik))],
            "segs_t": [
                spec((1, 1, bq), lambda b_, h_, iq, ik: (b_, 0, iq)),
                spec((1, bkv, 1), lambda b_, h_, iq, ik: (b_, ik, 0))],
        }

    def call(self, kernel, b, h, tables, in_specs, out_specs, out_shape,
             scratch_shapes, name):
        """The pallas_call of `kernel` on this walk's grid, to be applied
        to (*tables, *inputs)."""
        grid = dict(grid=(b, h, self.rows, self.steps), in_specs=in_specs,
                    out_specs=out_specs, scratch_shapes=scratch_shapes)
        if self.tabled:
            grid = dict(grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=len(tables), **grid))
        return pl.pallas_call(kernel, out_shape=out_shape,
                              interpret=_interpret(), name=name, **grid)


def _walk_of(block_q, block_kv, nq, nk, q_major, window, tables) -> _Walk:
    return _Walk(block_q, block_kv, nq, nk, q_major,
                 _BandGrid(window, block_q, block_kv, nq, nk) if window
                 else None, bool(tables))


def _doc_tables(segs, block_q, block_kv, nq, nk, causal, window):
    """() for a call that is handed no table (no segment ids, or one tile a
    sequence: nothing to skip, and the kernels stay the ones they were),
    else the call's (q_table, kv_table), flat for SMEM."""
    if segs is None or nq * nk == 1:
        return ()
    q_segs, kv_segs = segs                  # [B,Sq,1] / [B,1,Skv]
    q_table, kv_table, _, _ = segment_tile_table(
        q_segs[:, :, 0], kv_segs[:, 0, :], block_q, block_kv, causal, window)
    return q_table.reshape(-1), kv_table.reshape(-1)


# ---------------------------------------------------------------------------
# Forward kernel
# ---------------------------------------------------------------------------

def _fwd_kernel(*refs, scale, causal, walk: _Walk, seq_q, seq_kv, has_segs,
                bounded):
    """The grid's last axis walks `walk.steps` kv tiles of q tile iq: all of
    them, or a window layer's band alone."""
    refs, step, iq, ik, live, docs = walk.enter(refs)
    if has_segs:
        (q_ref, k_ref, v_ref, qs_ref, ks_ref,
         o_ref, lse_ref, acc, m_scr, l_scr) = refs
    else:
        q_ref, k_ref, v_ref, o_ref, lse_ref, acc, m_scr, l_scr = refs
        qs_ref = ks_ref = None
    block_q, block_kv = walk.block_q, walk.block_kv
    window = walk.window

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

    _dispatch_tiles(compute, causal, bounded or (has_segs and docs is None),
                    q_start, k_start, block_q, block_kv, window, live, docs)

    @pl.when(step == walk.steps - 1)
    def _finalize():
        l = l_scr[:, 0]
        o_ref[0, 0] = (acc[:] / jnp.maximum(l, 1e-20)[:, None]).astype(
            o_ref.dtype)
        m = m_scr[:, 0]
        lse = jnp.where(
            l > 0, jnp.maximum(m, _NEG_INF / 2) + jnp.log(
                jnp.maximum(l, 1e-20)), _NEG_INF)
        lse_ref[0, 0] = lse[:, None]


def _fwd_kernel_t(*refs, scale, causal, walk: _Walk, seq_q, seq_kv,
                  has_segs, bounded):
    """Forward in transposed orientation for D < 128: scores as
    s^T = k·q^T [bkv, bq], accumulator o^T [D, bq] filled by
    (p·v)^T = v^T·p — full-width contraction (bkv) and output (bq) dims
    where the straight orientation's p@v has only D output lanes. The
    online-softmax running max/sum live as [1, bq] lane rows; reductions
    run over sublanes (axis 0)."""
    refs, step, iq, ik, _, docs = walk.enter(refs)
    if has_segs:
        (q_ref, k_ref, v_ref, qs_ref, ks_ref,
         ot_ref, lse_ref, acc, m_scr, l_scr) = refs
    else:
        q_ref, k_ref, v_ref, ot_ref, lse_ref, acc, m_scr, l_scr = refs
        qs_ref = ks_ref = None
    block_q, block_kv = walk.block_q, walk.block_kv

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

    _dispatch_tiles(compute, causal, bounded or (has_segs and docs is None),
                    q_start, k_start, block_q, block_kv, docs=docs)

    @pl.when(step == walk.steps - 1)
    def _finalize():
        l = l_scr[0]
        ot_ref[0, 0] = (acc[:] / jnp.maximum(l, 1e-20)[None, :]).astype(
            ot_ref.dtype)
        m = m_scr[0]
        lse = jnp.where(
            l > 0, jnp.maximum(m, _NEG_INF / 2) + jnp.log(
                jnp.maximum(l, 1e-20)), _NEG_INF)
        lse_ref[0, 0] = lse[None, :]


def _flash_forward(q, k, v, scale, causal, block_q, block_kv, segs=None,
                   window=0):
    """One forward kernel on one walk: a window layer's band under its own
    name (straight orientation at every D), else the transposed-orientation
    kernel for D < 128 (output [B,H,D,Sq] and lse [B,H,1,Sq] rows, swapped
    back here) and the straight one from there."""
    b, h, sq, d = q.shape
    hkv, skv = k.shape[1], k.shape[2]
    group = h // hkv
    block_q = min(block_q, sq)
    block_kv = min(block_kv, skv)
    nq = _cdiv(sq, block_q)
    nk = _cdiv(skv, block_kv)
    bounded = (sq % block_q != 0) or (skv % block_kv != 0)
    has_segs = segs is not None
    transposed = not window and d < 128 and not _force_straight()

    tables = _doc_tables(segs, block_q, block_kv, nq, nk, causal, window)
    walk = _walk_of(block_q, block_kv, nq, nk, True, window, tables)
    specs = walk.specs(d, group)
    inputs = [q, k, v]
    in_specs = [specs["q"], specs["kv"], specs["kv"]]
    if has_segs:
        q_segs, kv_segs = segs                # [B,Sq,1] / [B,1,Skv] int32
        if transposed:                        # [B,1,Sq] / [B,Skv,1]
            q_segs, kv_segs = (jnp.swapaxes(x, 1, 2) for x in segs)
        inputs += [q_segs, kv_segs]
        in_specs += specs["segs_t" if transposed else "segs"]
    static = dict(scale=scale, causal=causal, walk=walk,
                  seq_q=sq, seq_kv=skv, has_segs=has_segs, bounded=bounded)

    if transposed:
        ot, lse_row = walk.call(
            functools.partial(_fwd_kernel_t, **static), b, h, tables,
            in_specs, [specs["q_t"], specs["qrow"]],
            [jax.ShapeDtypeStruct((b, h, d, sq), q.dtype),
             jax.ShapeDtypeStruct((b, h, 1, sq), jnp.float32)],
            [pltpu.VMEM((d, block_q), jnp.float32),
             pltpu.VMEM((1, block_q), jnp.float32),
             pltpu.VMEM((1, block_q), jnp.float32)],
            "flash_fwd_t")(*tables, *inputs)
        return jnp.swapaxes(ot, -1, -2), lse_row[:, :, 0, :]
    out, lse = walk.call(
        functools.partial(_fwd_kernel, **static), b, h, tables,
        in_specs, [specs["q"], specs["qcol"]],
        [jax.ShapeDtypeStruct((b, h, sq, d), q.dtype),
         jax.ShapeDtypeStruct((b, h, sq, 1), jnp.float32)],
        [pltpu.VMEM((block_q, d), jnp.float32),
         pltpu.VMEM((block_q, 1), jnp.float32),
         pltpu.VMEM((block_q, 1), jnp.float32)],
        "flash_window_fwd" if window else "flash_fwd")(*tables, *inputs)
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


def _bwd_dq_kernel_t(*refs, scale, causal, walk: _Walk, seq_q, seq_kv,
                     has_segs, bounded):
    """dq in transposed orientation: scores as s^T = k·q^T [bkv, bq],
    accumulator dq^T [D, bq], final matmul k^T·ds^T with full-width
    contraction (bkv) and output (bq) dims."""
    refs, step, iq, ik, _, docs = walk.enter(refs)
    if has_segs:
        (q_ref, k_ref, v_ref, qs_ref, ks_ref, do_ref, lse_ref, delta_ref,
         dqt_ref, dqt_acc) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         dqt_ref, dqt_acc) = refs
        qs_ref = ks_ref = None
    block_q, block_kv = walk.block_q, walk.block_kv

    @pl.when(step == 0)
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

    _dispatch_tiles(compute, causal, bounded or (has_segs and docs is None),
                    q_start, k_start, block_q, block_kv, docs=docs)

    @pl.when(step == walk.steps - 1)
    def _finalize():
        dqt_ref[0, 0] = dqt_acc[:].astype(dqt_ref.dtype)


def _bwd_dkv_kernel_t(*refs, scale, causal, walk: _Walk, seq_q, seq_kv,
                      has_segs, bounded):
    """dk/dv in transposed orientation: scores stay [bq, bkv] (so the
    standard mask applies), but the accumulating matmuls contract over
    bq with D-row outputs: dv^T = do^T·p, dk^T = q^T·ds — full-width
    contraction and output dims, [D, bkv] accumulators."""
    refs, step, iq, ik, _, docs = walk.enter(refs)
    if has_segs:
        (q_ref, k_ref, v_ref, qs_ref, ks_ref, do_ref, lse_ref, delta_ref,
         dkt_ref, dvt_ref, dkt_acc, dvt_acc) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         dkt_ref, dvt_ref, dkt_acc, dvt_acc) = refs
        qs_ref = ks_ref = None
    block_q, block_kv = walk.block_q, walk.block_kv

    @pl.when(step == 0)
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

    _dispatch_tiles(compute, causal, bounded or (has_segs and docs is None),
                    q_start, k_start, block_q, block_kv, docs=docs)

    @pl.when(step == walk.steps - 1)
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


def _bwd_dq_kernel(*refs, scale, causal, walk: _Walk, seq_q, seq_kv,
                   has_segs, bounded):
    refs, step, iq, ik, live, docs = walk.enter(refs)
    if has_segs:
        (q_ref, k_ref, v_ref, qs_ref, ks_ref, do_ref, lse_ref, delta_ref,
         dq_ref, dq_acc) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         dq_ref, dq_acc) = refs
        qs_ref = ks_ref = None
    block_q, block_kv = walk.block_q, walk.block_kv
    window = walk.window

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

    _dispatch_tiles(compute, causal, bounded or (has_segs and docs is None),
                    q_start, k_start, block_q, block_kv, window, live, docs)

    @pl.when(step == walk.steps - 1)
    def _finalize():
        dq_ref[0, 0] = dq_acc[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(*refs, scale, causal, walk: _Walk, seq_q, seq_kv,
                    has_segs, bounded):
    """The grid's last axis walks `walk.steps` q tiles of kv tile ik: all
    of them, or a window layer's band alone."""
    refs, step, iq, ik, live, docs = walk.enter(refs)
    if has_segs:
        (q_ref, k_ref, v_ref, qs_ref, ks_ref, do_ref, lse_ref, delta_ref,
         dk_ref, dv_ref, dk_acc, dv_acc) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         dk_ref, dv_ref, dk_acc, dv_acc) = refs
        qs_ref = ks_ref = None
    block_q, block_kv = walk.block_q, walk.block_kv
    window = walk.window

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

    _dispatch_tiles(compute, causal, bounded or (has_segs and docs is None),
                    q_start, k_start, block_q, block_kv, window, live, docs)

    @pl.when(step == walk.steps - 1)
    def _finalize():
        dk_ref[0, 0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[:].astype(dv_ref.dtype)


def _flash_backward(res, g, scale, causal, block_q, block_kv, segs=None,
                    head_fold: bool = False, window=0):
    """dq on the forward's walk and dk/dv on the kv-major one: a window
    layer's straight kernels on its band's two grids, else the transposed-
    orientation kernels for D < 128 (full MXU lanes — see the orientation
    note above; gradients come out [B,H,D,S] and are swapped back here) and
    the straight ones from there. dk/dv are computed at q-head granularity
    and a key/value head's group is summed outside (GQA) — simple and
    correct; a fused variant can accumulate in-kernel later."""
    q, k, v, out, lse = res
    b, h, sq, d = q.shape
    hkv, skv = k.shape[1], k.shape[2]
    group = h // hkv
    block_q = min(block_q, sq)
    block_kv = min(block_kv, skv)
    nq = _cdiv(sq, block_q)
    nk = _cdiv(skv, block_kv)
    bounded = (sq % block_q != 0) or (skv % block_kv != 0)
    has_segs = segs is not None

    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)  # [B,H,Sq]
    if not window and head_fold and head_fold_eligible(h, hkv, d, segs):
        return _flash_backward_fold(
            q, k, v, g, lse, delta, scale, causal, block_q, block_kv,
            nq, nk, bounded, group)
    transposed = not window and d < 128 and not _force_straight()
    family = "flash_window_bwd" if window else "flash_bwd"
    suffix = "_t" if transposed else ""

    tables = _doc_tables(segs, block_q, block_kv, nq, nk, causal, window)
    static = dict(scale=scale, causal=causal, seq_q=sq, seq_kv=skv,
                  has_segs=has_segs, bounded=bounded)

    def columns():                          # [B,H,Sq,1]
        return [lse[..., None], delta[..., None]]

    # dq. The transposed kernel reads the ids as a [1, bq] lane row and a
    # [bkv, 1] column, lse and delta as [B,H,1,Sq] lane rows.
    walk = _walk_of(block_q, block_kv, nq, nk, True, window, tables)
    specs = walk.specs(d, group)
    ids = list(segs or ())
    if transposed:
        per_row = [lse[:, :, None, :], delta[:, :, None, :]]
        ids = [jnp.swapaxes(x, 1, 2) for x in ids]
    else:
        per_row = columns()
    row_spec = specs["qrow" if transposed else "qcol"]
    dq = walk.call(
        functools.partial(_bwd_dq_kernel_t if transposed else _bwd_dq_kernel,
                          walk=walk, **static), b, h, tables,
        [specs["q"], specs["kv"], specs["kv"]]
        + (specs["segs_t" if transposed else "segs"] if has_segs else [])
        + [specs["q"], row_spec, row_spec],
        specs["q_t" if transposed else "q"],
        jax.ShapeDtypeStruct((b, h, d, sq) if transposed else (b, h, sq, d),
                             q.dtype),
        [pltpu.VMEM((d, block_q) if transposed else (block_q, d),
                    jnp.float32)],
        f"{family}_dq{suffix}")(*tables, q, k, v, *ids, g, *per_row)

    # dk/dv: scores stay [bq, bkv] in both orientations, so the ids, lse
    # and delta are the straight ones.
    if transposed:
        per_row = columns()
    walk = _walk_of(block_q, block_kv, nq, nk, False, window, tables)
    specs = walk.specs(d, group)
    out_spec = specs["dkv_t" if transposed else "dkv"]
    shape = (b, h, d, skv) if transposed else (b, h, skv, d)
    acc = pltpu.VMEM((d, block_kv) if transposed else (block_kv, d),
                     jnp.float32)
    dk, dv = walk.call(
        functools.partial(
            _bwd_dkv_kernel_t if transposed else _bwd_dkv_kernel,
            walk=walk, **static), b, h, tables,
        [specs["q"], specs["kv"], specs["kv"]]
        + (specs["segs"] if has_segs else [])
        + [specs["q"], specs["qcol"], specs["qcol"]],
        [out_spec, out_spec],
        [jax.ShapeDtypeStruct(shape, k.dtype),
         jax.ShapeDtypeStruct(shape, v.dtype)],
        [acc, acc],
        f"{family}_dkv{suffix}")(*tables, q, k, v, *(segs or ()), g,
                                 *per_row)

    def swapped_back(x):
        return jnp.swapaxes(x, -1, -2) if transposed else x

    def of_kv_head(x):
        if group > 1:
            x = x.reshape((b, hkv, group) + x.shape[2:]).sum(axis=2)
        return swapped_back(x)

    dq, dk, dv = swapped_back(dq), of_kv_head(dk), of_kv_head(dv)
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
    every S <= 1024, D <= 128, batch 1..16, packed or not, grouped or not.
    What a packed call skips follows from the tiles: at 512 x 512 the tiles
    whose documents cannot meet are not computed (46.5% of a causal layer's
    at S 8192 over documents of median 1,500, PR 58); one tile a sequence
    has nothing to skip and is handed no table, so a packed S 1024 call
    still masks its whole tile (ROADMAP S5a's open half)."""
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
    with the causal block-skip, and where a sequence is more than one tile
    the tiles whose documents cannot meet are skipped too
    (`segment_tile_table`; any ids, sorted ones skip most).

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
