"""The flash kernels' window term (`flash_window_*`, interpret mode on the
CPU) against XLA's dense attention under a band mask: forward and both
gradients, packed and not, grouped key/value heads, at a window and a length
that are no multiples of the tile; which tiles the band's grids hold; and
that with `window` 0 the traced kernels are the parent commit's."""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from megatronapp_tpu.ops.pallas import flash_attention as fa


def _dense(q, k, v, window, segment_ids=None):
    """[B,S,H,D] float32 oracle: softmax over the keys i - window < j <= i
    of the query's segment."""
    b, s, h, d = q.shape
    group = h // k.shape[2]
    k, v = (jnp.repeat(x, group, axis=2) for x in (k, v))
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(d)
    at = jnp.arange(s)
    ok = (at[:, None] >= at[None, :])
    if window:
        ok &= at[:, None] - at[None, :] < window
    ok = ok[None, None]
    if segment_ids is not None:
        ok = ok & (segment_ids[:, None, :, None]
                   == segment_ids[:, None, None, :])
    probs = jax.nn.softmax(jnp.where(ok, scores, -1e30), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def _inputs(s, h=4, hkv=2, d=16, b=2, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(keys[0], (b, s, h, d), jnp.float32)
    k = jax.random.normal(keys[1], (b, s, hkv, d), jnp.float32)
    v = jax.random.normal(keys[2], (b, s, hkv, d), jnp.float32)
    w = jax.random.normal(keys[3], (b, s, h, d), jnp.float32)
    cuts = np.sort(np.asarray(jax.random.choice(
        keys[3], s - 1, (b, 3), replace=False)) + 1, axis=1)
    segs = jnp.asarray((np.arange(s)[None, :, None]
                        >= cuts[:, None, :]).sum(-1), jnp.int32)
    return q, k, v, w, segs


@pytest.mark.parametrize("packed", [False, True], ids=["plain", "packed"])
@pytest.mark.parametrize("s,window,tile", [
    (80, 24, 32),       # window and length no multiples of the tile
    (96, 32, 32),       # both multiples: whole tiles fall off the band
    (64, 100, 16),      # a window longer than the sequence: plain causal
], ids=["ragged", "aligned", "long-window"])
def test_the_window_kernels_match_the_band_mask(s, window, tile, packed):
    q, k, v, w, segs = _inputs(s)
    segs = segs if packed else None

    def mine(q, k, v):
        return jnp.sum(w * fa.flash_attention(
            q, k, v, block_q=tile, block_kv=tile, segment_ids=segs,
            window=window))

    def oracle(q, k, v):
        return jnp.sum(w * _dense(q, k, v, window, segs))

    out = fa.flash_attention(q, k, v, block_q=tile, block_kv=tile,
                             segment_ids=segs, window=window)
    np.testing.assert_allclose(out, _dense(q, k, v, window, segs),
                               atol=2e-5, rtol=2e-5)
    for got, want in zip(jax.jit(jax.grad(mine, (0, 1, 2)))(q, k, v),
                         jax.jit(jax.grad(oracle, (0, 1, 2)))(q, k, v)):
        np.testing.assert_allclose(got, want, atol=5e-5, rtol=5e-5)


def test_a_band_off_by_one_is_seen():
    q, k, v, _, segs = _inputs(80)
    out = fa.flash_attention(q, k, v, block_q=32, block_kv=32,
                             segment_ids=segs, window=24)
    for wrong in (23, 25):
        assert float(jnp.max(jnp.abs(
            out - _dense(q, k, v, wrong, segs)))) > 1e-3


def test_unequal_tiles_and_ungrouped_heads():
    q, k, v, w, segs = _inputs(96, h=2, hkv=2, d=32, b=1, seed=3)
    for bq, bkv in ((16, 48), (48, 16)):
        def mine(q, k, v):
            return jnp.sum(w * fa.flash_attention(
                q, k, v, block_q=bq, block_kv=bkv, segment_ids=segs,
                window=40))
        got = jax.jit(jax.grad(mine, (0, 1, 2)))(q, k, v)
        want = jax.jit(jax.grad(lambda q, k, v: jnp.sum(
            w * _dense(q, k, v, 40, segs)), (0, 1, 2)))(q, k, v)
        for g, x in zip(got, want):
            np.testing.assert_allclose(g, x, atol=5e-5, rtol=5e-5)


@pytest.mark.parametrize("window,bq,bkv,s,kv_steps,q_steps", [
    (1024, 512, 512, 8192, 3, 3),    # the cell's layers: 3 of 16 tiles a row
    (1024, 1024, 1024, 8192, 2, 2),
    (24, 32, 32, 80, 2, 2),
    (512, 512, 512, 4096, 2, 2),
    (513, 512, 512, 4096, 2, 2),
    (514, 512, 512, 4096, 3, 3),
])
def test_the_band_grid_holds_the_bands_tiles_alone(window, bq, bkv, s,
                                                   kv_steps, q_steps):
    nq, nk = -(-s // bq), -(-s // bkv)
    band = fa._BandGrid(window, bq, bkv, nq, nk)
    assert (band.kv_steps, band.q_steps) == (kv_steps, q_steps)
    at_q, at_k = np.arange(s)[:, None], np.arange(s)[None, :]
    allowed = (at_q >= at_k) & (at_q - at_k < window)
    for iq in range(nq):
        lo, hi = band.kv_range(iq, np)
        cols = np.flatnonzero(allowed[iq * bq:(iq + 1) * bq].any(0)) // bkv
        assert (lo, hi) == (cols.min(), cols.max())
    for ik in range(nk):
        lo, hi = band.q_range(ik, np)
        rows = np.flatnonzero(allowed[:, ik * bkv:(ik + 1) * bkv].any(1)) // bq
        assert (lo, hi) == (rows.min(), rows.max())


# Rows packed like the share-training cell's, at tiles of 32: documents that
# start and end inside tiles, one shorter than a tile, tiles that lie whole
# inside one document (row 0's fourth, under the diagonal: the unmasked
# path), a document that spans the row, and documents that end on tile edges.
PACKED_ROWS = ([20, 70, 5, 100, 61], [256], [64, 32, 160])
# ids that are not sorted, and an id that comes back after another (0, 1, 0):
# a tile's range then only says too much, never too little
UNSORTED_ROWS = ([(0, 100), (1, 60), (0, 96)],
                 [(7, 40), (3, 90), (5, 30), (3, 96)],
                 [(2, 256)])


def _ids(rows):
    return jnp.asarray([np.repeat([i for i, _ in row], [n for _, n in row])
                        for row in rows], jnp.int32)


PACKED = _ids([list(enumerate(row)) for row in PACKED_ROWS])
UNSORTED = _ids(UNSORTED_ROWS)


def _grads_and_out(q, k, v, w, segs, window, tile=32):
    def loss(q, k, v):
        out = fa.flash_attention(q, k, v, block_q=tile, block_kv=tile,
                                 segment_ids=segs, window=window)
        return jnp.sum(w * out), out
    grads, out = jax.grad(loss, (0, 1, 2), has_aux=True)(q, k, v)
    return (out, *grads)


@pytest.mark.parametrize("ids", [PACKED, UNSORTED], ids=["packed", "unsorted"])
@pytest.mark.parametrize("window,d", [(0, 16), (0, 128), (72, 16)],
                         ids=["transposed", "straight", "window"])
def test_the_documents_table_changes_no_number(window, d, ids, monkeypatch):
    """A packed call of several tiles (plain causal in both orientations,
    and a window layer's band): output and the three gradients against the
    float32 oracle within the limits the kernels have always had, and
    against the same kernels handed no table (every tile masked, none
    skipped: the parent's program) to 1e-6."""
    q, k, v, w, _ = _inputs(256, h=2, hkv=1, d=d, b=3, seed=5)

    def oracle(q, k, v):
        out = _dense(q, k, v, window, ids)
        return jnp.sum(w * out), out
    want_grads, want = jax.grad(oracle, (0, 1, 2), has_aux=True)(q, k, v)
    got = _grads_and_out(q, k, v, w, ids, window)
    np.testing.assert_allclose(got[0], want, atol=2e-5, rtol=2e-5)
    for g, x in zip(got[1:], want_grads):
        np.testing.assert_allclose(g, x, atol=5e-5, rtol=5e-5)

    tabled = []
    real = fa._doc_tables
    monkeypatch.setattr(fa, "_doc_tables", lambda *a: tabled.append(
        real(*a)) or ())
    for g, x in zip(_grads_and_out(q, k, v, w, ids, window), got):
        np.testing.assert_allclose(g, x, atol=1e-6, rtol=0)
    # forward, and the backward rule once more: each had a table to drop
    assert len(tabled) == 2 and all(len(t) == 2 for t in tabled)


def test_the_kernels_of_a_window_layer_carry_their_own_names():
    q, k, v, w, segs = _inputs(64)
    text = str(jax.make_jaxpr(jax.grad(lambda q: jnp.sum(
        w * fa.flash_attention(q, k, v, block_q=32, block_kv=32,
                               segment_ids=segs, window=24))))(q))
    for name in ("flash_window_fwd", "flash_window_bwd_dq",
                 "flash_window_bwd_dkv"):
        assert name in text
    # no reader of the dense kernels' families matches them
    assert "flash_fwd" not in text and "flash_bwd" not in text


# sha256 of str(jax.make_jaxpr(grad)) at the parent commit, by
# tests/test_flash_window.py::_window0_jaxprs run there: the three kernels'
# bodies, names, grids and tiles as traced. Unpacked calls at 2 x 2 tiles
# (986eda0, and unchanged at f4cc4df), and packed calls of ONE tile a
# sequence, which are handed no table (f4cc4df). A packed call of several
# tiles is no longer the parent's: its kernels read the documents' table.
PARENT_JAXPR_SHA = {
    "d64": "37d35ad33911b1827e4d97ae5e21b25e8fdd8b61ad3253f92f54e7dcc87edca7",
    "d64-packed-one-tile":
        "8ab9b176b4aacbc243368c7b3779d602ad1af1996dd2d1214722f5c6ba788345",
    "d128":
        "bd7c3970642ec26eef131d53ac120e4c70d47b8fe6090952616cdb76784eb23d",
    "d128-packed-one-tile":
        "28c17d2594e16410e1eff05134b5d66395ce34d8d8bb1177a5b70381e3d2bf41",
}


def _grad_jaxpr(d, tile, segs):
    q, k, v, w, ids = _inputs(256, h=4, hkv=2, d=d, b=1)
    q, k, v = (x.astype(jnp.bfloat16) for x in (q, k, v))
    return jax.make_jaxpr(jax.grad(lambda q, k, v: jnp.sum(
        w * fa.flash_attention(q, k, v, block_q=tile, block_kv=tile,
                               segment_ids=ids if segs else None)),
        (0, 1, 2)))(q, k, v)


def _window0_jaxprs():
    return {name: hashlib.sha256(str(_grad_jaxpr(*case)).encode()).hexdigest()
            for name, case in {
                "d64": (64, 128, False), "d128": (128, 128, False),
                "d64-packed-one-tile": (64, None, True),
                "d128-packed-one-tile": (128, None, True)}.items()}


def test_with_window_0_the_traced_kernels_are_the_parents():
    assert _window0_jaxprs() == PARENT_JAXPR_SHA


def _pallas_calls(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _pallas_calls(sub)


@pytest.mark.parametrize("tile,operands", [(None, 0), (128, 2)],
                         ids=["one-tile", "four-tiles"])
def test_only_a_packed_call_of_several_tiles_is_handed_a_table(tile,
                                                               operands):
    """One tile a sequence has nothing to skip and lowers without a scalar
    prefetch operand; 2 x 2 tiles are handed the two tables."""
    calls = list(_pallas_calls(_grad_jaxpr(64, tile, True).jaxpr))
    assert len(calls) == 3
    assert {c.params["grid_mapping"].num_index_operands
            for c in calls} == {operands}


def test_choose_attention_gives_a_window_layer_the_kernels():
    kw = dict(batch=1, seq=8192, heads=32, head_dim=128, dtype=jnp.bfloat16,
              segments=True, backend="tpu")
    choice = fa.choose_attention(impl="auto", window=1024, **kw)
    assert (choice.impl, choice.block_q, choice.block_kv) == (
        "pallas", 512, 512)
    assert choice.why == "S=8192 D=128 window 1024 segments"
    # with window 0 the answer and its tiles are what they were
    assert fa.choose_attention(impl="auto", **kw) == fa.AttentionChoice(
        "pallas", 512, 512, "S=8192 D=128 segments")
    assert fa.flash_tiles(1024) == (1024, 1024)
    assert fa.flash_tiles(1024, window=256) == (512, 512)
    assert fa.flash_tiles(1024, window=1024) == (1024, 1024)
    assert fa.choose_attention(
        impl="auto", window=1024, **dict(kw, backend="cpu")).impl == (
        "reference")
