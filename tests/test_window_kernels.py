"""The paged kernels' window term (kernel_gen.PagedSpec.window), interpreted
on the CPU, against paged_attention_reference under a band mask: lengths
below, at and above the window, a window that is no multiple of the block, 6
and 8 query heads a key/value head; every block wholly behind a slot's window
holds NaNs, as a block the allocator gave back to another slot may."""
import numpy as np
import pytest

import jax.numpy as jnp

from megatronapp_tpu.ops.pallas import kernel_gen as kg
from megatronapp_tpu.ops.pallas.paged_attention import (
    paged_attention_multiquery_reference, paged_attention_reference,
)

BS, HKV, D, NB, MB = 4, 2, 16, 160, 14


def _pools(rng):
    k = rng.normal(size=(NB, BS, HKV, D)).astype(np.float32)
    v = rng.normal(size=(NB, BS, HKV, D)).astype(np.float32)
    return k, v


def _poisoned(k, v, table, firsts):
    """The pools with every block before a slot's first visited one NaN."""
    k, v = k.copy(), v.copy()
    for row, first in zip(table, firsts):
        k[row[:first]] = np.nan
        v[row[:first]] = np.nan
    return jnp.asarray(k), jnp.asarray(v)


@pytest.mark.parametrize("group", [3, 4], ids=["group3", "group4"])
@pytest.mark.parametrize("window", [8, 6, 5, 1])
def test_decode_walk_against_the_band_mask(window, group):
    rng = np.random.default_rng(window * 10 + group)
    k, v = _pools(rng)
    # below, at and above the window; a block's edge; a long slot; empty
    lens = np.array([1, 3, window - 1, window, window + 1, 2 * window + 3,
                     17, 40, 52, 0])
    lens = np.maximum(lens, 0)
    b = len(lens)
    table = rng.permutation(NB)[:b * MB].reshape(b, MB).astype(np.int32)
    q = jnp.asarray(rng.normal(size=(b, HKV * group, D)), jnp.float32)
    firsts = np.maximum(lens - window, 0) // BS
    kp, vp = _poisoned(k, v, table, firsts)
    out = kg.paged_attention(q, kp, vp, jnp.asarray(table),
                             jnp.asarray(lens), window=window)
    ref = paged_attention_reference(q, jnp.asarray(k), jnp.asarray(v),
                                    jnp.asarray(table), jnp.asarray(lens),
                                    window=window)
    live = lens > 0
    assert not np.isnan(np.asarray(out)).any()
    np.testing.assert_allclose(np.asarray(out)[live], np.asarray(ref)[live],
                               atol=2e-6, rtol=2e-6)
    assert not np.asarray(out)[~live].any()     # an empty slot: zeros
    # the window term changes what a long slot reads
    full = kg.paged_attention(q, jnp.asarray(k), jnp.asarray(v),
                              jnp.asarray(table), jnp.asarray(lens))
    assert np.abs(np.asarray(full - out))[lens > window].max() > 1e-3
    np.testing.assert_allclose(np.asarray(full)[lens <= window],
                               np.asarray(out)[lens <= window], atol=2e-6)


@pytest.mark.parametrize("group", [3, 4], ids=["group3", "group4"])
@pytest.mark.parametrize("window,s_q", [(8, 16), (6, 16), (5, 12), (8, 4)])
def test_ragged_walk_against_the_band_mask(window, s_q, group):
    """A call wider than the window (s_q 16 over a window of 8) and one
    narrower: each query sees its own last `window` keys, the walk starts at
    the FIRST query's oldest key."""
    rng = np.random.default_rng(window * 100 + s_q + group)
    k, v = _pools(rng)
    lens = np.array([1, 3, 8, 9, 17, 40, 0, 48, 29])
    q_lens = np.minimum(np.array([1, 3, 5, 9, s_q, 7, 0, s_q, 2]), s_q)
    q_lens = np.minimum(q_lens, lens)
    b = len(lens)
    table = rng.permutation(NB)[:b * MB].reshape(b, MB).astype(np.int32)
    q = jnp.asarray(rng.normal(size=(b, s_q, HKV * group, D)), jnp.float32)
    firsts = np.maximum(lens - q_lens - (window - 1), 0) // BS
    kp, vp = _poisoned(k, v, table, firsts)
    out = kg.paged_attention(q, kp, vp, jnp.asarray(table),
                             jnp.asarray(lens), q_lens=jnp.asarray(q_lens),
                             window=window)
    ref = paged_attention_multiquery_reference(
        q, jnp.asarray(k), jnp.asarray(v), jnp.asarray(table),
        jnp.asarray(lens), jnp.asarray(q_lens), window=window)
    for i, n in enumerate(q_lens):
        np.testing.assert_allclose(np.asarray(out)[i, :n],
                                   np.asarray(ref)[i, :n],
                                   atol=2e-6, rtol=2e-6)


def test_a_wide_call_cut_into_query_tiles_starts_each_tile_at_its_own_window(
        monkeypatch):
    """kernel_gen._query_tiled gives every tile of a slot its own lengths,
    and the window walk takes its first block from them: tiled == untiled."""
    rng = np.random.default_rng(5)
    k, v = _pools(rng)
    lens, q_lens, s_q, window = np.array([50, 37]), np.array([32, 19]), 32, 6
    table = rng.permutation(NB)[:2 * MB].reshape(2, MB).astype(np.int32)
    q = jnp.asarray(rng.normal(size=(2, s_q, HKV * 4, D)), jnp.float32)
    args = (q, jnp.asarray(k), jnp.asarray(v), jnp.asarray(table),
            jnp.asarray(lens))
    whole = kg.paged_attention(*args, q_lens=jnp.asarray(q_lens),
                               window=window)
    monkeypatch.setattr(kg, "_query_tile", lambda *a, **kw: 8)
    tiled = kg.paged_attention(*args, q_lens=jnp.asarray(q_lens),
                               window=window)
    for i, n in enumerate(q_lens):
        np.testing.assert_allclose(np.asarray(tiled)[i, :n],
                                   np.asarray(whole)[i, :n],
                                   atol=2e-6, rtol=2e-6)


def test_the_walk_never_names_a_block_behind_the_window():
    """_walk_steps with a first block: no step's pages come from a table
    column before it, and the steps cover the rows from there on."""
    table = jnp.arange(3 * 10, dtype=jnp.int32).reshape(3, 10) + 100
    lens = jnp.asarray([37, 5, 0])
    first = kg._window_first(lens, None, 4, 8)
    assert list(np.asarray(first)) == [(37 - 8) // 4, 0, 0]
    total, slot_of, step_of, block_of = kg._walk_steps(table, lens, 4, 2,
                                                       first)
    total = int(total)
    pages = np.asarray(block_of).reshape(-1, 2)[:total]
    slots = np.asarray(slot_of)[:total]
    # slot 0: rows 28..36 in blocks 7, 8, 9 -> two steps of two pages
    assert list(slots) == [0, 0, 1, 2]
    assert pages[0].tolist() == [107, 108] and pages[1, 0] == 109
    assert pages[:2].min() >= 107
    assert pages[2].tolist()[0] == 110 and pages[3].tolist() == [0, 0]


def test_the_window_kernels_carry_names_of_their_own():
    assert kg._paged_name(False, window=True) == "paged_window_decode"
    assert kg._paged_name(True, window=True) == "paged_window_mq"
    for name in ("paged_window_decode", "paged_window_mq"):
        assert "paged_decode" not in name and "paged_mq" not in name
    assert kg._paged_name(False) == "paged_decode"
    assert kg._paged_name(True, "int8") == "paged_mq_int8"


def test_a_window_walk_refuses_what_it_was_not_written_for():
    q = jnp.zeros((1, 4, D))
    pool = jnp.zeros((NB, BS, HKV, D), jnp.int8)
    with pytest.raises(NotImplementedError, match="one device over bf16"):
        kg.paged_attention(q, pool, pool, jnp.zeros((1, MB), jnp.int32),
                           jnp.ones((1,), jnp.int32), window=8,
                           k_scales=jnp.ones((NB, BS, HKV)),
                           v_scales=jnp.ones((NB, BS, HKV)))


@pytest.mark.parametrize("s_q", [0, 12], ids=["decode", "ragged"])
@pytest.mark.parametrize("window", [40, 16])
def test_pages_of_whole_tiles_are_copied_inside_the_window_alone(window, s_q):
    """ISSUE 46: at 8 key/value heads of 128 a page is whole tiles and the
    kernel starts its copies itself. Every pool block but those a slot's
    table names from its window's first block to its length is NaN: the
    blocks behind a window, the entries past a length, the blocks no table
    names. Slots: shorter than the window, a one-step slot, two steps with
    a partial last one before another slot's first, an empty one, a whole
    step."""
    bs, hkv, d, mb, pages = 16, 8, 128, 20, 8
    rng = np.random.default_rng(window + s_q)
    lens = np.array([5, window + 3, pages * bs + window + 7, 0, 60,
                     pages * bs])
    q_lens = np.minimum(np.array([1, 3, s_q, 0, 7, s_q]), lens)
    b = len(lens)
    nb = b * mb + 1
    k = rng.normal(size=(nb, bs, hkv, d)).astype(np.float32)
    v = rng.normal(size=(nb, bs, hkv, d)).astype(np.float32)
    table = (rng.permutation(nb - 1)[:b * mb].reshape(b, mb) + 1).astype(
        np.int32)
    news = q_lens if s_q else np.minimum(lens, 1)
    firsts = np.maximum(lens - news - (window - 1), 0) // bs
    read = np.zeros(nb, bool)
    for row, first, n in zip(table, firsts, lens):
        read[row[first:-(-n // bs)]] = True
    kp, vp = (jnp.asarray(np.where(read[:, None, None, None], x, np.nan))
              for x in (k, v))
    shape = (b, s_q, hkv * 2, d) if s_q else (b, hkv * 2, d)
    q = jnp.asarray(rng.normal(size=shape), jnp.float32)
    args = (jnp.asarray(table), jnp.asarray(lens))
    if s_q:
        out = kg.paged_attention(q, kp, vp, *args,
                                 q_lens=jnp.asarray(q_lens), window=window)
        ref = paged_attention_multiquery_reference(
            q, jnp.asarray(k), jnp.asarray(v), *args, jnp.asarray(q_lens),
            window=window)
        for i, n in enumerate(q_lens):
            np.testing.assert_allclose(np.asarray(out)[i, :n],
                                       np.asarray(ref)[i, :n],
                                       atol=2e-5, rtol=2e-5)
    else:
        out = kg.paged_attention(q, kp, vp, *args, window=window)
        ref = paged_attention_reference(q, jnp.asarray(k), jnp.asarray(v),
                                        *args, window=window)
        live = lens > 0
        assert not np.isnan(np.asarray(out)).any()
        np.testing.assert_allclose(np.asarray(out)[live],
                                   np.asarray(ref)[live],
                                   atol=2e-5, rtol=2e-5)
        assert not np.asarray(out)[~live].any()
