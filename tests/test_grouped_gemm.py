"""The experts' Pallas grouped GEMM (ops/pallas/grouped_gemm.py) on the CPU,
interpreted, against ``lax.ragged_dot``; its visit list against a replay by
hand; and the tiles `choose_gemm_tiles` gives the three MoE serving cells."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from megatronapp_tpu.ops.pallas import grouped_gemm as gg
from megatronapp_tpu.ops.pallas.grouped_gemm import (
    GemmTiles, choose_gemm_tiles, grouped_gemm,
)
from megatronapp_tpu.utils.dispatch import stack_slices


def _operands(m, e, k, n, dtype, layers=None, seed=0):
    kx, kw = jax.random.split(jax.random.PRNGKey(seed))
    x = jax.random.normal(kx, (m, k), jnp.float32).astype(dtype)
    shape = (e, k, n) if layers is None else (layers, e, k, n)
    w = (jax.random.normal(kw, shape, jnp.float32) / np.sqrt(k)).astype(dtype)
    return x, w


def _close(got, want, dtype):
    """Equal to a rounding of `dtype`: both sides are one float32
    accumulation over K rounded once, summed in another order."""
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    ulp = 2.0 ** -8 if dtype == jnp.bfloat16 else 2.0 ** -19
    assert np.abs(want).max() > 1.0
    assert np.abs(got - want).max() <= ulp * np.abs(want).max()


# (rows, groups, K, N, sizes, tiles): the shapes' regimes at sizes the
# interpreter runs in seconds. tiles None: the chooser's.
CASES = {
    # decode: three rows a group, a third of the groups empty
    "3-rows-a-group": (48, 24, 256, 256, [3, 3, 0] * 5 + [6, 0, 0] * 3,
                       None),
    # decode: twelve rows a group (the assist cell's), groups 2 and 5 empty
    "12-rows-a-group": (96, 8, 128, 384, [12, 20, 0, 12, 16, 0, 12, 24],
                        GemmTiles(16, 128, 128)),
    # K no multiple of 512 (DeepSeek-V2-Lite's fc2), whole in one block
    "K-1408": (32, 4, 1408, 256, [5, 0, 20, 7], None),
    # K 6144 (LongCat's fc1)
    "K-6144": (32, 4, 6144, 128, [1, 30, 0, 1], None),
    # a prefill buffer, skewed: group 1 spans five row tiles, group 3 is
    # one row, group 4 starts inside a tile and ends inside the next
    "prefill-skewed": (256, 6, 128, 256, [30, 150, 0, 1, 40, 35],
                       GemmTiles(32, 128, 128)),
    # rows no multiple of the row tile, columns no multiple of 128
    "ragged-edges": (50, 8, 64, 96, [3, 0, 5, 10, 0, 0, 22, 10],
                     GemmTiles(16, 64, 96)),
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["fp32", "bf16"])
@pytest.mark.parametrize("case", list(CASES))
def test_equals_ragged_dot(case, dtype):
    m, e, k, n, sizes, tiles = CASES[case]
    assert sum(sizes) == m and len(sizes) == e
    x, w = _operands(m, e, k, n, dtype)
    sizes = jnp.asarray(sizes, jnp.int32)
    got = jax.jit(lambda *a: grouped_gemm(*a, tiles=tiles))(x, w, sizes)
    assert got.shape == (m, n) and got.dtype == dtype
    _close(got, jax.lax.ragged_dot(x, w, sizes), dtype)


@pytest.mark.parametrize("tm", [16, 64])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["fp32", "bf16"])
def test_rows_behind_the_last_group(dtype, tm):
    """The held-experts case: the groups cover the first 21 of 128 rows.
    The grouped rows equal ragged_dot's; a row tile wholly behind the
    groups is no visit, so NaNs there (and in the group-less rows of the
    shared tile) reach nothing."""
    m, e, k, n = 128, 4, 128, 256
    x, w = _operands(m, e, k, n, dtype)
    sizes = jnp.asarray([8, 0, 12, 1], jnp.int32)
    x = x.at[21:].set(jnp.nan)
    got = jax.jit(lambda *a: grouped_gemm(
        *a, tiles=GemmTiles(tm, k, 128)))(x, w, sizes)
    want = jax.lax.ragged_dot(x.at[21:].set(0), w, sizes)
    _close(got[:21], want[:21], dtype)
    _, _, tid, _, count = gg._visits(sizes, tm, m // tm)
    assert int(count) == (4 if tm == 16 else 3)
    assert int(jnp.max(tid)) == (1 if tm == 16 else 0)


@pytest.mark.parametrize("layer", range(3))
def test_a_layer_of_the_stack_in_place(layer):
    """Layer l of an [L, E, K, N] stack, named by a traced index, gives the
    sliced layer's call bit for bit, and the call's weight operand is the
    whole stack: no equation of the traced program has a layer's shape."""
    m, e, k, n = 64, 8, 128, 256
    x, stack = _operands(m, e, k, n, jnp.bfloat16, layers=3)
    sizes = jnp.asarray([10, 0, 3, 20, 1, 0, 0, 30], jnp.int32)
    named = jax.jit(lambda l: grouped_gemm(x, stack, sizes, layer=l))
    got = np.asarray(named(jnp.int32(layer)).astype(jnp.float32))
    want = np.asarray(grouped_gemm(x, stack[layer], sizes
                                   ).astype(jnp.float32))
    assert got.tobytes() == want.tobytes()
    other = np.asarray(grouped_gemm(x, stack[(layer + 1) % 3], sizes
                                    ).astype(jnp.float32))
    assert np.abs(got - other).max() > 1.0
    jaxpr = jax.make_jaxpr(lambda l: grouped_gemm(x, stack, sizes, layer=l))(
        jnp.int32(layer)).jaxpr
    call, = [q for q in jaxpr.eqns if q.primitive.name == "pallas_call"]
    assert call.params["name"] == "grouped_gemm"
    assert call.invars[-1].aval.shape == (3 * e, k, n)
    assert stack_slices(jaxpr, [(e, k, n)]) == 0


def _replayed_visits(sizes, tm):
    """(row tile, group) pairs in grid order, by hand."""
    pairs, row = [], 0
    for g, size in enumerate(sizes):
        if size:
            pairs += [(t, g) for t in range(row // tm,
                                            (row + size - 1) // tm + 1)]
        row += size
    return pairs


@pytest.mark.parametrize("tm", [8, 16, 128])
@pytest.mark.parametrize("sizes", [
    [3, 3, 0, 3, 3, 0, 6, 0, 3], [0, 0, 0, 0], [0, 0, 37, 0], [16, 16, 16],
    [1] * 40, [100, 1, 0, 0, 91], [0, 5, 0, 0, 0, 1]],
    ids=["decode", "none", "one", "aligned", "ones", "skewed", "held"])
def test_visits(sizes, tm):
    """A visit a (row tile, group) pair whose rows overlap, groups in
    order, an empty group none; the list is padded with its last pair. A
    group's `next` is the next group that has rows (the block the kernel
    fetches ahead), -1 behind the last."""
    rows = -(-max(sum(sizes), 1) // tm) * tm + tm    # and a tile behind
    offsets, gid, tid, nxt, count = gg._visits(
        jnp.asarray(sizes, jnp.int32), tm, rows // tm)
    want = _replayed_visits(sizes, tm)
    assert int(count) == len(want) <= rows // tm + len(sizes) - 1
    assert gid.shape == tid.shape == (rows // tm + len(sizes) - 1,)
    got = list(zip(np.asarray(tid).tolist(), np.asarray(gid).tolist()))
    assert got[:len(want)] == want
    assert not want or all(p == want[-1] for p in got[len(want):])
    assert np.asarray(offsets).tolist() == [0] + np.cumsum(sizes).tolist()
    held = [g for g, size in enumerate(sizes) if size]
    after = dict(zip(held, held[1:] + [-1]))
    assert [n for g, n in enumerate(np.asarray(nxt).tolist())
            if g in after] == [after[g] for g in held]


H = 2**20
# (rows, groups, K, N) of the two GEMMs of the three MoE serving cells
# (perfbench/configs), a decode round and a prefill call each, and the
# tiles the chooser gives them.
CELL_SHAPES = {
    "deepseek-v2-lite.decode.fc1": ((192, 64, 2048, 2816), (64, 1408)),
    "deepseek-v2-lite.decode.fc2": ((192, 64, 1408, 2048), (64, 2048)),
    "deepseek-v2-lite.prefill.fc1": ((6144, 64, 2048, 2816), (64, 1408)),
    "deepseek-v2-lite.prefill.fc2": ((6144, 64, 1408, 2048), (64, 2048)),
    "lfm2-24b-a2b.decode.fc1": ((768, 64, 2048, 3072), (64, 1536)),
    "lfm2-24b-a2b.decode.fc2": ((768, 64, 1536, 2048), (64, 2048)),
    "lfm2-24b-a2b.prefill.fc1": ((8192, 64, 2048, 3072), (64, 1536)),
    "lfm2-24b-a2b.prefill.fc2": ((8192, 64, 1536, 2048), (64, 2048)),
    "longcat-flash-chat.decode.fc1": ((768, 16, 6144, 4096), (64, 512)),
    "longcat-flash-chat.decode.fc2": ((768, 16, 2048, 6144), (64, 2048)),
    "longcat-flash-chat.prefill.fc1": ((6144, 16, 6144, 4096), (64, 512)),
    "longcat-flash-chat.prefill.fc2": ((6144, 16, 2048, 6144), (64, 2048)),
}


@pytest.mark.parametrize("cell", list(CELL_SHAPES))
def test_the_chooser_at_the_cells(cell):
    """An expert's matrix is read once in every cell: its block is whole in
    K (so it stays put while the expert's row tiles pass) and a whole
    number of blocks covers N; a block is a DMA of 4 to 8 MiB; the call's
    buffers fit Mosaic's VMEM with room to spare."""
    (m, e, k, n), (tm, tn) = CELL_SHAPES[cell]
    tiles = choose_gemm_tiles(m, e, k, n, jnp.bfloat16)
    assert tiles == GemmTiles(tm, k, tn)
    assert tiles.k == k and n % tiles.n == 0 and tiles.n % 128 == 0
    assert m % tiles.m == 0 and tiles.m % 16 == 0
    assert 4 * H <= k * tiles.n * 2 <= gg.WEIGHT_BLOCK_BYTES
    assert gg.vmem_bytes(tiles, jnp.bfloat16) <= gg.VMEM_LIMIT_BYTES // 2


@pytest.mark.parametrize("shape,dtype,want", [
    ((48, 8, 32, 170), jnp.float32, (48, 32, 170)),     # tests/test_moe.py
    ((4, 8, 64, 128), jnp.bfloat16, (16, 64, 128)),     # rows under a tile
    ((4, 8, 64, 128), jnp.float32, (8, 64, 128)),
    ((40, 4, 256, 1024), jnp.bfloat16, (48, 256, 1024)),
    ((192, 64, 65536, 2048), jnp.bfloat16, (64, 65536, 128)),
])
def test_the_chooser_off_the_cells(shape, dtype, want):
    """Columns that are no multiple of 128 lanes are one block; rows fewer
    than a tile round up to the dtype's sublanes; a block never falls under
    128 lanes however long K."""
    assert choose_gemm_tiles(*shape, dtype) == GemmTiles(*want)


def test_announced_once_a_shape(capsys):
    gg._announced.clear()
    x, w = _operands(32, 4, 128, 256, jnp.bfloat16)
    sizes = jnp.asarray([8, 8, 8, 8], jnp.int32)
    for _ in range(2):
        grouped_gemm(x, w, sizes)
    out = capsys.readouterr().out
    assert out.count("grouped gemm:") == 1
    assert ("grouped gemm: [32 x 128] x 4 groups of [128, 256] -> pallas, "
            "tiles (32, 128, 256), an expert read once (interpreted)") in out
