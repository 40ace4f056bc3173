"""The decode step of a Kimi delta attention layer (transformer/kda.py): one
Pallas call that updates the running slots' matrix states in place.

A head's state is S [K, V] float32 (K key channels, V value columns, both
128 as published), kept as the head's V columns of the slot's plane h [K, E]
(E = heads x V; `ssm_update.py`'s pool, [L, slots, K, E]). A decode round
advances every running slot by one token:

    S' = diag(a) S ;  S'' = S' + b k (v - S'^T k)^T ;  o = S''^T q

with a in (0, 1)^K a KEY CHANNEL (the tile is decayed by row), b in (0, 2).
The update reads the decayed state (S'^T k) before its rank-1 correction,
so `ssm_update`'s body, whose y needs only h', cannot do it: here a head's
[K, V] tile is decayed, contracted with k, corrected and contracted with q
in ONE visit: one read and one write of the plane (4 MiB a slot a layer at
64 heads), `heads_step` heads (16: 1 MiB) a grid step.

As in `ssm_update`: the kernel reads the layer's plane through the
scalar-prefetched layer id and writes S'' over S (input_output_aliases);
the rows are sorted running-first and their count bounds the grid, so an
inactive slot's state is neither read nor written and its o is 0.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from megatronapp_tpu.ops.pallas import kernel_gen

# Bytes of a slot's plane a grid step holds (in, out, each double-buffered:
# 4 MiB of VMEM at 1 MiB).
BLOCK_BYTES = 1 << 20


def kda_update_reference(h, q, k, v, alpha, beta):
    """The plain update, all rows at once. h [rows, K, E] f32; q, k, alpha
    [rows, heads, K]; v [rows, heads, V]; beta [rows, heads] ->
    (o [rows, E], h')."""
    rows, kd, e = h.shape
    heads = q.shape[1]
    s = jnp.swapaxes(h.reshape(rows, kd, heads, e // heads), 1, 2)
    s = alpha[..., None] * s                                # [rows,h,K,V]
    u = beta[..., None] * (v - jnp.einsum("rhkv,rhk->rhv", s, k))
    s = s + k[..., None] * u[:, :, None, :]
    o = jnp.einsum("rhkv,rhk->rhv", s, q)
    return (o.reshape(rows, e),
            jnp.swapaxes(s, 1, 2).reshape(rows, kd, e))


def heads_step(kd: int, vd: int, heads: int) -> int:
    """Heads a grid step takes: the most that divide `heads` and whose
    tiles fit BLOCK_BYTES (at least one)."""
    return max((t for t in range(1, heads + 1)
                if heads % t == 0 and t * kd * vd * 4 <= BLOCK_BYTES),
               default=1)


def kda_update(pool: jnp.ndarray, layer, q: jnp.ndarray, k: jnp.ndarray,
               v: jnp.ndarray, alpha: jnp.ndarray, beta: jnp.ndarray,
               active: jnp.ndarray):
    """pool [L, slots, K, E] f32; layer int32 scalar; q, k, alpha
    [slots, heads, K] f32; v [slots, heads, V] f32; beta [slots, heads]
    f32; active [slots] bool. Returns (o [slots, E] f32, pool): the pool is
    the buffer that came in wherever the caller's copy of it is dead (a
    donated argument, a loop carry), with plane `layer` of the active slots
    advanced one token."""
    slots, kd, e = pool.shape[1:]
    heads, vd = v.shape[1:]
    t = heads_step(kd, vd, heads)
    tiles, te = heads // t, t * vd
    layer = jnp.asarray(layer, jnp.int32).reshape(1)
    order = jnp.argsort(~active, stable=True).astype(jnp.int32)

    # A row's vectors over the key channels come as columns [K, .] (a tile
    # is decayed and contracted along its rows): q, k and alpha of a grid
    # step's t heads side by side, [slots, tiles, K, 3t]; those over the
    # value columns as rows [2, E]: v, and beta spread over a head's columns.
    def tall_of(x):             # [slots, heads, K] -> [slots, tiles, K, t]
        return jnp.swapaxes(x.reshape(slots, tiles, t, kd), 2, 3)

    tall = jnp.concatenate([tall_of(q), tall_of(k), tall_of(alpha)], axis=-1)
    wide = jnp.stack([v.reshape(slots, e),
                      jnp.repeat(beta, vd, axis=-1)], axis=1)

    def kernel(lid_ref, row_ref, tall_ref, wide_ref, h_ref, o0_ref, o_ref,
               h_out_ref):
        del lid_ref, row_ref, o0_ref
        for i in range(t):
            cols = slice(i * vd, (i + 1) * vd)
            q_, k_, a_ = (tall_ref[:, j * t + i:j * t + i + 1]
                          for j in range(3))                    # [K, 1]
            s = a_ * h_ref[:, cols]                             # [K, V]
            u = wide_ref[1:2, cols] * (wide_ref[0:1, cols] - jnp.sum(
                s * k_, axis=0, keepdims=True))                 # [1, V]
            s = s + k_ * u
            h_out_ref[:, cols] = s
            o_ref[:, cols] = jnp.sum(s * q_, axis=0, keepdims=True)

    def row(i, j, lid, rows):
        del lid
        return rows[i], 0, j

    def tall_row(i, j, lid, rows):
        del lid
        return rows[i], j, 0, 0

    def plane(i, j, lid, rows):
        return lid[0], rows[i], 0, j

    out_row = pl.BlockSpec((None, 1, te), row)
    state = pl.BlockSpec((None, None, kd, te), plane)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(jnp.sum(active, dtype=jnp.int32), tiles),
        in_specs=[pl.BlockSpec((None, None, kd, 3 * t), tall_row),
                  pl.BlockSpec((None, 2, te), row), state, out_row],
        out_specs=[out_row, state],
    )
    o, pool = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((slots, 1, e), jnp.float32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        input_output_aliases={4: 1, 5: 0},
        interpret=kernel_gen._interpret(),
        name="kda_update",
    )(layer, order, tall, wide, pool, jnp.zeros((slots, 1, e), jnp.float32))
    return o[:, 0], pool
