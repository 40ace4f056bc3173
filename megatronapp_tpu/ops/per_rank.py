"""A dense layer whose weight gradient stays a data-parallel rank's own.

Under GSPMD a weight is one value, replicated over dp, and the batch is
split over dp: the weight-gradient product ``x^T dy`` contracts over the
batch, so the partitioner has to finish the sum over dp where the product is
made — in every layer of every micro-batch, a synchronous all-reduce of the
layer's whole gradient (PERF.md, PR 42). There is no way to call a value
"unreduced over dp" on this mesh, but there is a way to make the sum a
BATCH dimension: the train step hands the layer one copy of the kernel a
rank, ``[R, K, N]`` with ``R`` split over dp (each chip holds the one copy
it always held), and the product is batched over ``R``. Its gradient is then
``[R, K, N]`` too, a rank's own rows in a rank's own slice, and the sum over
``R`` is made once, behind the accumulation scan, in fp32
(training/train_step.py).

Nothing else changes: the activations keep the batch split over dp that
sharding propagation starts from, no region is manual, the loss is the
global one. A kernel without the extra axis takes the plain product, to the
instruction.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

# The batched product's result, by name: `dots_with_no_batch_dims_saveable`
# (transformer/block.py's selective recomputation) does not see it as the
# matmul output it is.
RANK_DENSE_OUT = "rank_dense_out"


def dense(x: jnp.ndarray, w: jnp.ndarray) -> jnp.ndarray:
    """``x [B, ..., K] @ w``: ``w [K, N]`` the plain product; ``w [R, K, N]``
    one copy a data-parallel rank, rows ``r·B/R … (r+1)·B/R`` of ``x`` (the
    rows rank ``r`` holds) against copy ``r``."""
    if w.ndim == 2:
        return x @ w
    r, b = w.shape[0], x.shape[0]
    xr = x.reshape(r, b // r, *x.shape[1:])
    y = jnp.einsum("rb...k,rkn->rb...n", xr, w)
    return checkpoint_name(y.reshape(*x.shape[:-1], w.shape[-1]),
                           RANK_DENSE_OUT)


def take(table: jnp.ndarray, ids: jnp.ndarray) -> jnp.ndarray:
    """``jnp.take(table, ids, axis=0)``: ``table [V, H]`` plain; ``table
    [R, V, H]`` one copy a data-parallel rank, rows ``r·B/R … (r+1)·B/R`` of
    ``ids [B, ...]`` looked up in copy ``r``."""
    if table.ndim == 2:
        return jnp.take(table, ids, axis=0)
    r, b = table.shape[0], ids.shape[0]
    out = jax.vmap(lambda t, i: jnp.take(t, i, axis=0))(
        table, ids.reshape(r, b // r, *ids.shape[1:]))
    return out.reshape(*ids.shape, table.shape[-1])
