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
    for got, want in zip(jax.grad(mine, (0, 1, 2))(q, k, v),
                         jax.grad(oracle, (0, 1, 2))(q, k, v)):
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
        got = jax.grad(mine, (0, 1, 2))(q, k, v)
        want = jax.grad(lambda q, k, v: jnp.sum(
            w * _dense(q, k, v, 40, segs)), (0, 1, 2))(q, k, v)
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


# sha256 of str(jax.make_jaxpr(grad)) at the parent commit (986eda0), by
# tests/test_flash_window.py::_window0_jaxprs run there: the three kernels'
# bodies, names, grids and tiles as traced.
PARENT_JAXPR_SHA = {
    "d64": "37d35ad33911b1827e4d97ae5e21b25e8fdd8b61ad3253f92f54e7dcc87edca7",
    "d64-packed":
        "aa6bbb38821a2c73313b7f0ec6917ebc3abfe8fc6a1db4f80f7e3cdf3d20615b",
    "d128":
        "bd7c3970642ec26eef131d53ac120e4c70d47b8fe6090952616cdb76784eb23d",
    "d128-packed":
        "552dc74edb9a2f83e6b938125822da491774c47ecaa1dc14e54f44e9abbcb6ec",
}


def _window0_jaxprs():
    out = {}
    for name, (d, packed) in {"d64": (64, False), "d64-packed": (64, True),
                              "d128": (128, False),
                              "d128-packed": (128, True)}.items():
        q, k, v, w, segs = _inputs(256, h=4, hkv=2, d=d, b=1)
        q, k, v = (x.astype(jnp.bfloat16) for x in (q, k, v))
        text = str(jax.make_jaxpr(jax.grad(lambda q, k, v: jnp.sum(
            w * fa.flash_attention(q, k, v, block_q=128, block_kv=128,
                                   segment_ids=segs if packed else None)),
            (0, 1, 2)))(q, k, v))
        out[name] = hashlib.sha256(text.encode()).hexdigest()
    return out


def test_with_window_0_the_traced_kernels_are_the_parents():
    assert _window0_jaxprs() == PARENT_JAXPR_SHA


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
