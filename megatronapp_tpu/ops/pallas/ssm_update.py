"""The decode step of a selective-state-space layer: one Pallas call that
updates the running slots' recurrent state in place.

A state-space layer carries, per sequence, h [N, E] float32 (N the state
size, E the expanded width; E is the minor dimension, a whole number of
128-lane vregs). A decode round advances every running slot by one token:

    h' = exp(dt * A) * h + (dt * B) * u ;  y = sum_n h'[n] * C[n] + D * u

Mamba-1 (N 16) has an A [N, E]; Mamba-2 (N 128; transformer/ssm.py keeps a
head's matrix state as the head's columns of h) one scalar a head, which
comes as ONE row [1, E] with dt and D broadcast over a head's columns alike:
the exponential is then taken a column and not an element. Where a slot's
plane is larger than BLOCK_BYTES (Mamba-2's [128, 8192] is 4 MiB; in, out
and double buffering of it would be the v5e's whole scoped VMEM) the grid
has a second dimension over tiles of E: the columns are independent.

The states of all the model's state-space layers live in one stacked pool
[L, slots, N, E] (inference/paged_cache.py) which rides the engine's layer
loop as a carry. The kernel reads the layer's plane through the
scalar-prefetched layer id and writes h' over h (input_output_aliases), so
a step holds one copy of the pool and touches, a layer, the planes of the
slots that run: the rows are sorted running-first and their count bounds
the grid, so an inactive slot's state is neither read nor written and its
y is 0.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from megatronapp_tpu.ops.pallas import kernel_gen


def ssm_update_reference(h, dt, u, b, c, a_t, d):
    """The plain update, [rows, N, E] at once: (y [rows, E], h'). b, c
    [rows, N], or [rows, G, N]: G groups of E / G columns, each reading its
    own."""
    if b.ndim == 3:     # [rows, G, N] -> [rows, N, E], a group's columns
        width = h.shape[-1] // b.shape[1]
        b, c = (jnp.repeat(jnp.swapaxes(t, 1, 2), width, axis=2)
                for t in (b, c))
    else:
        b, c = b[:, :, None], c[:, :, None]
    h = jnp.exp(dt[:, None, :] * a_t[None]) * h \
        + (dt[:, None, :] * b) * u[:, None, :]
    y = jnp.sum(h * c, axis=1) + u * d[None]
    return y, h


# The largest block of a slot's plane a grid step holds (Jamba's whole plane
# [16, 5120] is 320 KiB: one tile, the kernel as it was).
BLOCK_BYTES = 1 << 20


def _tile(n: int, e: int) -> int:
    """Columns of E a grid step takes: all of them where the plane fits
    BLOCK_BYTES, else the largest multiple of 128 that divides E and
    fits."""
    if n * e * 4 <= BLOCK_BYTES or e % 128:
        return e
    return max((t for t in range(128, e, 128)
                if e % t == 0 and n * t * 4 <= BLOCK_BYTES), default=128)


def ssm_update(pool: jnp.ndarray, layer, dt: jnp.ndarray, u: jnp.ndarray,
               b: jnp.ndarray, c: jnp.ndarray, a_t: jnp.ndarray,
               d: jnp.ndarray, active: jnp.ndarray):
    """pool [L, slots, N, E] f32; layer int32 scalar; dt, u [slots, E]
    f32; b, c [slots, N] f32, or [slots, G, N] where the columns of E fall
    into G groups that each read a B and C of their own (Mamba-2 with
    ssm_groups: a tile of E then lies inside one group, and its b and c
    block is that group's); a_t [N, E] f32 (A transposed) or [1, E] (one A
    for every n); d [E] f32;
    active [slots] bool. Returns (y [slots, E] f32, pool): the pool is the
    buffer that came in wherever the caller's copy of it is dead (a
    donated argument, a loop carry), with plane `layer` of the active
    slots advanced one token."""
    slots, n, e = pool.shape[1:]
    layer = jnp.asarray(layer, jnp.int32).reshape(1)
    order = jnp.argsort(~active, stable=True).astype(jnp.int32)

    def kernel(lid_ref, row_ref, dt_ref, u_ref, b_ref, c_ref, a_ref, d_ref,
               h_ref, y0_ref, y_ref, h_out_ref):
        del lid_ref, row_ref, y0_ref
        dt_, u_ = dt_ref[...], u_ref[...]                      # [1, E]
        h = jnp.exp(dt_ * a_ref[...]) * h_ref[...] \
            + (dt_ * b_ref[...]) * u_                          # [N, E]
        h_out_ref[...] = h
        y_ref[...] = jnp.sum(h * c_ref[...], axis=0, keepdims=True) \
            + u_ * d_ref[...]

    # The grid: the running rows, and where a plane is tiled the tiles of E
    # (ids = (i,) or (i, j), then the two prefetched scalars).
    groups = b.shape[1] if b.ndim == 3 else 1
    # with groups, the tile of a plane [N, E / G]: no tile crosses a group
    te = _tile(n, e // groups)
    tiled = te < e

    def col(ids):
        return ids[1] if tiled else 0

    def row(*ids):
        return (ids[-1][ids[0]], 0, col(ids))

    def tall_row(*ids):
        return (ids[-1][ids[0]], 0, 0)

    def fixed(*ids):
        return (0, col(ids))

    def plane(*ids):
        return (ids[-2][0], ids[-1][ids[0]], 0, col(ids))

    # A row's vectors come as [slots, 1, E] and [slots, N, 1]: blocks whose
    # last two dims are the array's own (or whole lane tiles of E), which
    # Mosaic takes whole.
    wide = pl.BlockSpec((None, 1, te), row)
    if groups > 1:      # [slots, G, N, 1]: the block of the tile's group
        tall = pl.BlockSpec(
            (None, None, n, 1),
            lambda *ids: (ids[-1][ids[0]], ids[1] * te // (e // groups),
                          0, 0))
    else:
        tall = pl.BlockSpec((None, n, 1), tall_row)
    state = pl.BlockSpec((None, None, n, te), plane)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(jnp.sum(active, dtype=jnp.int32),) + (
            (e // te,) if tiled else ()),
        in_specs=[wide, wide, tall, tall,
                  pl.BlockSpec((a_t.shape[0], te), fixed),
                  pl.BlockSpec((1, te), fixed), state, wide],
        out_specs=[wide, state],
    )
    y, pool = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((slots, 1, e), jnp.float32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        input_output_aliases={8: 1, 9: 0},
        interpret=kernel_gen._interpret(),
        name="ssm_update",
    )(layer, order, dt[:, None, :], u[:, None, :], b[..., None],
      c[..., None], a_t, d[None, :], pool,
      jnp.zeros((slots, 1, e), jnp.float32))
    return y[:, 0], pool
