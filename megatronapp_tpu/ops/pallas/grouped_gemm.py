"""The experts' grouped GEMM: one Pallas call that streams every touched
expert's matrix once, in tiles chosen from the call's shapes.

    y[rows of group g] = x[rows of group g] @ w[g]        g = 0 .. E-1

x [M, K] holds the rows sorted by group, `group_sizes` [E] says how many each
group has; rows behind the last group belong to none, cost no grid step and
their output rows hold whatever was there. w is one layer's [E, K, N] or,
with `layer`, the whole [L, E, K, N] stack of a scanned model, read where
it lies: the stack is viewed as [L·E, K, N] (merging leading dimensions
moves nothing), stays in HBM, and the kernel copies block (layer·E + group,
all of K, a column tile) from it, the layer id scalar prefetched. Nothing
of a layer's shape is sliced or copied.

The grid is (column tiles of N, visits). A visit is a pair (row tile,
group) whose rows overlap: a row tile that several groups share is visited
once a group, consecutively, and each visit stores its own group's rows
(the others keep what the tile's earlier visits wrote); a group that spans
several row tiles is visited once a tile, consecutively, under one weight
block [K, tn], whole in K. The kernel double-buffers the weight blocks
itself: when a group's first visit begins it starts the copy of the NEXT
group's block (or the next column tile's first) and then waits for its
own, so a block has all of a group's visits to arrive in. (A BlockSpec
operand is fetched one grid step ahead: a block that stays put over
several visits would arrive under the last of them alone, and the visits
before it would compute with no copy in flight.) Every touched expert's
matrix therefore crosses HBM -> VMEM once a call whatever the tiles, in
N / tn copies of K x tn elements. Empty groups have no visit; the visits
are counted in the call (a traced grid bound), so the rows behind the
groups have none either. An output element is one float32 accumulation over
all of K, rounded once to the rows' dtype, as `lax.ragged_dot`'s is.

`choose_gemm_tiles` picks (tm, K, tn) from what a call can see: its rows,
K, N, the dtype and Mosaic's VMEM. The rule and the kernel times it was
settled on are in PERF.md section 6 (PR 43).
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from megatronapp_tpu.ops.pallas import kernel_gen

# The most one weight block [K, tn] may hold: the kernel keeps two (one
# computing, one arriving), so a call's VMEM is a little over twice this.
# Blocks of 2 and 4 MiB read 1-4% slower at K 2048, and 15% where N / 128
# has no divisor between (2816 = 22 x 128: 256 columns or 1408).
WEIGHT_BLOCK_BYTES = 8 * 2**20
# What Mosaic may take for a call at most (a v5e's VMEM is 128 MiB, its
# default scoped limit 16): the chooser's blocks stay far below it.
VMEM_LIMIT_BYTES = 96 * 2**20
# The row tile. A weight block waits in VMEM while its group's row tiles
# pass and the next block arrives meanwhile, so a taller tile buys nothing
# and computes more rows of other groups: 16 to 64 rows read the same in a
# decode round, 64 the least in a prefill call (PERF.md section 6, PR 43).
ROW_TILE = 64

_announced: set = set()


class GemmTiles(NamedTuple):
    m: int
    k: int
    n: int


def _sublanes(dtype) -> int:
    """Rows of one vreg tile: 8 of float32, 16 of bfloat16, 32 of int8."""
    return 8 * max(1, 4 // jnp.dtype(dtype).itemsize)


def vmem_bytes(tiles: GemmTiles, dtype) -> int:
    """What a call's blocks take in VMEM: rows, weights and result twice
    (the pipeline's two buffers) and the float32 product once."""
    b = jnp.dtype(dtype).itemsize
    tm, k, tn = tiles
    return 2 * b * (tm * k + k * tn + tm * tn) + 4 * tm * tn


def vmem_limit(tiles: GemmTiles, dtype) -> int:
    """What a call asks Mosaic for: its blocks and 4 MiB of room, no more,
    since XLA keeps for its own use what the step's kernels do not ask."""
    return min(VMEM_LIMIT_BYTES, vmem_bytes(tiles, dtype) + 4 * 2**20)


def choose_gemm_tiles(m: int, e: int, k: int, n: int, dtype) -> GemmTiles:
    """(tm, tk, tn) for x [m, k] against e groups of [k, n]. Pure: shapes
    and dtype in, tiles out; no device, flag or environment is read.

    tk is K: a whole-K weight block stays put while a group's row tiles
    pass, which is what reads an expert once (module docstring). tn is the
    widest whole number of 128 lanes that divides N with the block inside
    WEIGHT_BLOCK_BYTES (N itself where N is no multiple of 128). tm is
    ROW_TILE, or the rows rounded up to the dtype's sublanes where they are
    fewer."""
    sub = _sublanes(dtype)
    b = jnp.dtype(dtype).itemsize
    if n % 128:
        tn = n
    else:
        lanes = n // 128
        fits = [d for d in range(1, lanes + 1)
                if lanes % d == 0 and k * 128 * d * b <= WEIGHT_BLOCK_BYTES]
        tn = 128 * max(fits, default=1)
    return GemmTiles(min(ROW_TILE, kernel_gen._padded(m, sub)), k, tn)


def _visits(group_sizes, tm: int, tiles_m: int):
    """The call's (row tile, group) pairs in grid order, from the group
    sizes: offsets [E + 1] (a group's first row; offsets[E] the rows in
    groups), group_ids and tile_ids [tiles_m + E - 1] (padded with the last
    pair), next [E] (the next group that has rows, -1 behind the last) and
    how many pairs there are."""
    e = group_sizes.shape[0]
    ends = jnp.cumsum(group_sizes)
    starts = ends - group_sizes
    first = starts // tm
    spans = jnp.where(group_sizes > 0, (ends - 1) // tm - first + 1, 0)
    upto = jnp.cumsum(spans)                  # visits of groups 0..g
    count = upto[-1]
    i = jnp.arange(tiles_m + e - 1, dtype=jnp.int32)
    i = jnp.minimum(i, jnp.maximum(count - 1, 0))
    gid = jnp.sum(upto[None, :] <= i[:, None], axis=1).astype(jnp.int32)
    gid = jnp.minimum(gid, e - 1)
    tid = jnp.take(first, gid) + i - (jnp.take(upto, gid)
                                      - jnp.take(spans, gid))
    offsets = jnp.pad(ends, (1, 0))
    g = jnp.arange(e, dtype=jnp.int32)
    later = (g[None, :] > g[:, None]) & (group_sizes[None, :] > 0)
    nxt = jnp.min(jnp.where(later, g[None, :], e), axis=1)
    nxt = jnp.where(nxt == e, -1, nxt)
    return (offsets.astype(jnp.int32), gid, tid.astype(jnp.int32),
            nxt.astype(jnp.int32), count.astype(jnp.int32))


def _announce(m, k, e, n, tiles: GemmTiles, interpreted: bool) -> None:
    """Print, once per distinct call shape in this process, that the
    grouped GEMM took the Pallas kernel, and its tiles."""
    line = (f"grouped gemm: [{m} x {k}] x {e} groups of [{k}, {n}] -> "
            f"pallas, tiles ({tiles.m}, {tiles.k}, {tiles.n}), an expert "
            f"read once ({'interpreted' if interpreted else 'compiled'})")
    if line not in _announced:
        _announced.add(line)
        print(line, flush=True)


def grouped_gemm(x: jnp.ndarray, w: jnp.ndarray, group_sizes: jnp.ndarray,
                 layer=None, tiles: Optional[GemmTiles] = None
                 ) -> jnp.ndarray:
    """x [M, K] rows sorted by group; w [E, K, N], or [L, E, K, N] with
    `layer` (an int32 scalar, traced or not) naming the layer to read;
    group_sizes [E] int32, their sum at most M. Returns [M, N] in x's
    dtype; rows behind the last group are not written."""
    m, k = x.shape
    if layer is None:
        w = w[None]
        layer = 0
    e, n = w.shape[1], w.shape[3]
    assert w.shape[2] == k and group_sizes.shape == (e,), (
        x.shape, w.shape, group_sizes.shape)
    assert w.dtype == x.dtype, (w.dtype, x.dtype)
    if tiles is None:
        tiles = choose_gemm_tiles(m, e, k, n, x.dtype)
    tm, tk, tn = tiles
    assert tk == k and n % tn == 0, "a weight block is whole in K and in N"
    interpreted = kernel_gen._interpret()
    _announce(m, k, e, n, tiles, interpreted)

    rows = kernel_gen._padded(m, tm)
    if rows != m:
        x = jnp.pad(x, ((0, rows - m), (0, 0)))
    offsets, gid, tid, nxt, count = _visits(group_sizes.astype(jnp.int32),
                                            tm, rows // tm)
    lid = jnp.asarray(layer, jnp.int32).reshape(1)

    stack = w.reshape((-1, k, n))
    cols = n // tn
    # An N that is no whole number of lanes (1856 = 14.5 x 128) under a K
    # that is one: the chip keeps such a matrix with K minor (N would be
    # padded), which is the transposed matrix [N, K] in rows. The kernel
    # reads it as that (the swap moves nothing there; a row-major operand
    # would be a copy of the whole stack a call, and Mosaic slices no
    # dimension that is padded), one column tile, an expert's whole matrix
    # a block, and contracts both operands' last dimension.
    by_rows = bool(n % 128) and not k % 128
    if by_rows:
        stack = jnp.swapaxes(stack, 1, 2)

    def kernel(lid_ref, off_ref, gid_ref, tid_ref, nxt_ref, x_ref, w_hbm,
               o_ref, w_buf, sem, slot_ref):
        j, v = pl.program_id(0), pl.program_id(1)
        g = gid_ref[v]

        def block(group, col, slot):
            at = ((lid_ref[0] * e + group,) if by_rows else
                  (lid_ref[0] * e + group, slice(None), pl.ds(col * tn, tn)))
            return pltpu.make_async_copy(
                w_hbm.at[at], w_buf.at[slot], sem.at[slot])

        # A weight block is fetched when its group's first visit of a
        # column tile begins, one block ahead: the next group's block (or
        # the next column tile's first) starts now and has all of this
        # group's visits to arrive in.
        opens = (v == 0) | (gid_ref[jnp.maximum(v - 1, 0)] != g)
        very_first = (j == 0) & (v == 0)

        @pl.when(very_first)
        def _():
            slot_ref[0] = 0
            block(g, j, 0).start()

        @pl.when(opens & ~very_first)
        def _():
            slot_ref[0] = 1 - slot_ref[0]

        slot = slot_ref[0]

        @pl.when(opens)
        def _():
            nxt = nxt_ref[g]

            @pl.when(nxt >= 0)
            def _():
                block(nxt, j, 1 - slot).start()

            @pl.when((nxt < 0) & (j + 1 < cols))
            def _():
                block(gid_ref[0], j + 1, 1 - slot).start()

            block(g, j, slot).wait()

        row = tid_ref[v] * tm + jax.lax.broadcasted_iota(
            jnp.int32, (tm, tn), 0)
        mine = (row >= off_ref[g]) & (row < off_ref[g + 1])
        y = jax.lax.dot_general(
            x_ref[...], w_buf[slot],
            (((1,), (1 if by_rows else 0,)), ((), ())),
            preferred_element_type=jnp.float32)
        o_ref[...] = jnp.where(mine, y, o_ref[...].astype(jnp.float32)
                               ).astype(o_ref.dtype)

    def x_block(j, v, lid_, off, gid_, tid_, nxt_):
        return (tid_[v], 0)

    def o_block(j, v, lid_, off, gid_, tid_, nxt_):
        return (tid_[v], j)

    b = x.dtype.itemsize
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(cols, count),
        in_specs=[pl.BlockSpec((tm, k), x_block),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((tm, tn), o_block),
        scratch_shapes=[pltpu.VMEM((2, n, k) if by_rows else (2, k, tn),
                                   x.dtype),
                        pltpu.SemaphoreType.DMA((2,)),
                        pltpu.SMEM((1,), jnp.int32)],
    )
    y = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((rows, n), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=vmem_limit(tiles, x.dtype)),
        cost_estimate=pl.CostEstimate(
            flops=2 * m * k * n, transcendentals=0,
            bytes_accessed=b * (e * k * n + cols * m * k + m * n)),
        interpret=interpreted,
        name="grouped_gemm",
    )(lid, offsets, gid, tid, nxt, x, stack)
    return y if rows == m else y[:m]
