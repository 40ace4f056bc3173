"""Pallas flash attention vs the jnp oracle (interpret mode on CPU).

The reference's fused attention comes from TE/Apex CUDA kernels; this is the
TPU replacement (SURVEY §2.7)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from megatronapp_tpu.config.transformer_config import AttnMaskType
from megatronapp_tpu.ops.attention import dot_product_attention
from megatronapp_tpu.ops.pallas.flash_attention import (
    choose_attention, flash_attention, flash_tiles,
)


def make_qkv(b=2, s=128, h=4, hkv=4, d=32, dtype=jnp.float32):
    q = jax.random.normal(jax.random.PRNGKey(0), (b, s, h, d), dtype)
    k = jax.random.normal(jax.random.PRNGKey(1), (b, s, hkv, d), dtype)
    v = jax.random.normal(jax.random.PRNGKey(2), (b, s, hkv, d), dtype)
    return q, k, v


class TestFlashAttention:
    @pytest.mark.parametrize("causal", [True, False])
    def test_forward_matches(self, causal):
        q, k, v = make_qkv()
        out = flash_attention(q, k, v, causal=causal, block_q=64, block_kv=64)
        ref = dot_product_attention(
            q, k, v, mask_type=(AttnMaskType.causal if causal
                                else AttnMaskType.bidirectional))
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=3e-6)

    def test_gqa_forward(self):
        q, k, v = make_qkv(h=4, hkv=2)
        out = flash_attention(q, k, v, causal=True, block_q=64, block_kv=64)
        ref = dot_product_attention(q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=3e-6)

    def test_uneven_blocks(self):
        # Sequence length not a multiple of the block size exercises the
        # ceiling-division grid.
        q, k, v = make_qkv(s=96)
        out = flash_attention(q, k, v, causal=True, block_q=64, block_kv=64)
        ref = dot_product_attention(q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=3e-6)

    def test_uneven_blocks_grads(self):
        """Backward kernels' padded-row masking: s not a block multiple."""
        q, k, v = make_qkv(s=80, h=2, hkv=2, d=16)

        def loss_f(args):
            return jnp.sum(flash_attention(*args, causal=True, block_q=32,
                                           block_kv=32) ** 2)

        def loss_r(args):
            from megatronapp_tpu.ops.attention import dot_product_attention
            return jnp.sum(dot_product_attention(*args) ** 2)

        gf = jax.grad(loss_f)((q, k, v))
        gr = jax.grad(loss_r)((q, k, v))
        for a, b in zip(gf, gr):
            assert bool(jnp.all(jnp.isfinite(a)))
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=2e-5)

    def test_grads_match(self):
        q, k, v = make_qkv(s=64, h=2, hkv=2, d=16)

        def loss_f(args):
            return jnp.sum(flash_attention(*args, causal=True, block_q=32,
                                           block_kv=32) ** 2)

        def loss_r(args):
            return jnp.sum(dot_product_attention(*args) ** 2)

        gf = jax.grad(loss_f)((q, k, v))
        gr = jax.grad(loss_r)((q, k, v))
        for a, b in zip(gf, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=2e-5)

    def test_model_level_pallas_impl(self, devices8):
        """attention_impl='pallas' through the full model (gating branch in
        attention_forward), single- and multi-device, vs 'reference'."""
        from megatronapp_tpu.config.parallel_config import ParallelConfig
        from megatronapp_tpu.config.transformer_config import TransformerConfig
        from megatronapp_tpu.models.gpt import gpt_loss, init_gpt_params
        from megatronapp_tpu.parallel.mesh import build_mesh

        tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 64), 0, 128)
        losses = {}
        for impl in ("reference", "pallas"):
            cfg = TransformerConfig(
                num_layers=2, hidden_size=64, num_attention_heads=4,
                vocab_size=128, max_position_embeddings=64,
                attention_impl=impl, flash_block_q=32, flash_block_kv=32,
                compute_dtype=jnp.float32)
            p, _ = init_gpt_params(jax.random.PRNGKey(0), cfg)
            # multi-device: dp=2 x tp=2 exercises the shard_map wrapper.
            par = ParallelConfig(tensor_parallel=2)
            ctx = build_mesh(par, devices=devices8[:4])
            with ctx.mesh:
                loss, _ = jax.jit(
                    lambda p, t, c=cfg, x=ctx: gpt_loss(
                        p, t, jnp.roll(t, -1, 1), None, c, ctx=x))(p, tokens)
            losses[impl] = float(loss)
        assert abs(losses["pallas"] - losses["reference"]) < 1e-4, losses

    def test_d64_transposed_bwd_grads(self):
        """D=64 takes the transposed-orientation backward kernels (full
        128-lane MXU fill — PERF.md lever); uneven blocks + GQA compose
        with it."""
        q, k, v = make_qkv(s=160, h=4, hkv=2, d=64)

        def loss_f(args):
            return jnp.sum(flash_attention(*args, causal=True, block_q=64,
                                           block_kv=64) ** 2)

        def loss_r(args):
            return jnp.sum(dot_product_attention(*args) ** 2)

        gf = jax.grad(loss_f)((q, k, v))
        gr = jax.grad(loss_r)((q, k, v))
        for a, b in zip(gf, gr):
            assert bool(jnp.all(jnp.isfinite(a)))
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=5e-5)

    def test_d128_legacy_bwd_grads(self):
        """D=128 keeps the straight-orientation backward kernels (lanes
        already full); pin that path now that every smaller-D test runs
        the transposed one."""
        q, k, v = make_qkv(b=1, s=64, h=2, hkv=2, d=128)

        def loss_f(args):
            return jnp.sum(flash_attention(*args, causal=True, block_q=32,
                                           block_kv=32) ** 2)

        def loss_r(args):
            return jnp.sum(dot_product_attention(*args) ** 2)

        gf = jax.grad(loss_f)((q, k, v))
        gr = jax.grad(loss_r)((q, k, v))
        for a, b in zip(gf, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=5e-5)

    def test_segment_grads(self):
        """Packed-segment backward through both transposed kernels (the
        dq^T kernel needs the transposed [bkv, bq] validity mask)."""
        b, s, h, d = 2, 96, 2, 32
        q, k, v = make_qkv(b=b, s=s, h=h, hkv=h, d=d)
        seg = jnp.concatenate([jnp.zeros((b, 40), jnp.int32),
                               jnp.ones((b, s - 40), jnp.int32)], axis=1)

        def seg_oracle(args):
            qq, kk, vv = args
            scale = 1.0 / (d ** 0.5)
            sc = jnp.einsum("bqhd,bkhd->bhqk", qq, kk) * scale
            mask = (seg[:, None, :, None] == seg[:, None, None, :])
            tri = jnp.tril(jnp.ones((s, s), jnp.bool_))
            mask = mask & tri[None, None]
            sc = jnp.where(mask, sc, -1e30)
            p = jax.nn.softmax(sc, axis=-1)
            return jnp.sum(jnp.einsum("bhqk,bkhd->bqhd", p, vv) ** 2)

        def loss_f(args):
            return jnp.sum(flash_attention(
                *args, causal=True, block_q=32, block_kv=32,
                segment_ids=seg) ** 2)

        gf = jax.grad(loss_f)((q, k, v))
        gr = jax.grad(seg_oracle)((q, k, v))
        for a, b in zip(gf, gr):
            assert bool(jnp.all(jnp.isfinite(a)))
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=5e-5)

    def test_segment_gqa_grads_compose(self):
        """GQA × packed segments through the transposed kernels: the
        grouped-KV BlockSpecs and the transposed segment mask must
        compose (each was tested alone above)."""
        b, s, d = 2, 64, 32
        q, k, v = make_qkv(b=b, s=s, h=4, hkv=2, d=d)
        seg = jnp.concatenate([jnp.zeros((b, 24), jnp.int32),
                               jnp.ones((b, s - 24), jnp.int32)], axis=1)

        def seg_oracle(args):
            qq, kk, vv = args
            kk = jnp.repeat(kk, 2, axis=2)   # GQA: expand KV heads
            vv = jnp.repeat(vv, 2, axis=2)
            scale = 1.0 / (d ** 0.5)
            sc = jnp.einsum("bqhd,bkhd->bhqk", qq, kk) * scale
            mask = (seg[:, None, :, None] == seg[:, None, None, :])
            mask = mask & jnp.tril(jnp.ones((s, s), jnp.bool_))[None, None]
            p = jax.nn.softmax(jnp.where(mask, sc, -1e30), axis=-1)
            return jnp.sum(jnp.einsum("bhqk,bkhd->bqhd", p, vv) ** 2)

        def loss_f(args):
            return jnp.sum(flash_attention(
                *args, causal=True, block_q=32, block_kv=32,
                segment_ids=seg) ** 2)

        gf = jax.grad(loss_f)((q, k, v))
        gr = jax.grad(seg_oracle)((q, k, v))
        for a, b_ in zip(gf, gr):
            assert bool(jnp.all(jnp.isfinite(a)))
            np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                       atol=5e-5)

    def test_gqa_grads(self):
        q, k, v = make_qkv(s=64, h=4, hkv=2, d=16)

        def loss_f(args):
            return jnp.sum(flash_attention(*args, causal=True, block_q=32,
                                           block_kv=32) ** 2)

        def loss_r(args):
            return jnp.sum(dot_product_attention(*args) ** 2)

        gf = jax.grad(loss_f)((q, k, v))
        gr = jax.grad(loss_r)((q, k, v))
        for a, b in zip(gf, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=2e-5)


def _choose(**kw):
    facts = dict(impl="auto", batch=4, seq=1024, heads=16, head_dim=64,
                 dtype=jnp.bfloat16, segments=True, backend="tpu")
    facts.update(kw)
    return choose_attention(**facts)


@pytest.mark.parametrize("facts,impl,tiles", [
    # train.gpt2-medium.packed-1k: micro-batch 4 x 1024, 16 heads of 64:
    # 256 MiB of dense scores, one tile a sequence
    ({}, "pallas", (1024, 1024)),
    ({"segments": False}, "pallas", (1024, 1024)),
    # train.gpt3-2.7b.tp2dp2-2k, one chip's share: 1 x 2048, 16 heads of 80
    ({"batch": 1, "seq": 2048, "head_dim": 80}, "pallas", (512, 512)),
    # the same bytes from another batch and S; 128 MiB is the least measured
    # to leave the chip, 108 MiB the most measured to stay
    ({"batch": 16, "seq": 512, "head_dim": 128}, "pallas", (512, 512)),
    ({"batch": 2}, "pallas", (1024, 1024)),
    ({"batch": 3, "seq": 768}, "reference", None),
    ({"batch": 1}, "reference", None),
    ({"batch": 4, "seq": 512}, "reference", None),
    # from S 2048 on the kernels run whatever the bytes, as they always have
    ({"batch": 1, "heads": 4, "seq": 2048}, "pallas", (512, 512)),
    # nobody measured: short sequences, float32 compute, heads outside
    # 64..128 keep XLA's dense attention under S 2048
    ({"seq": 256, "batch": 64}, "reference", None),
    ({"dtype": jnp.float32}, "reference", None),
    ({"head_dim": 32}, "reference", None),
    ({"head_dim": 256}, "reference", None),
    ({"seq": 2048, "dtype": jnp.float32}, "pallas", (512, 512)),
    ({"seq": 4096, "batch": 1, "heads": 1}, "pallas", (512, 512)),
    # dense scores and probabilities past 1 GB: flash whatever S and dtype
    ({"batch": 128, "seq": 256, "heads": 32}, "pallas", (256, 256)),
    ({"batch": 32, "dtype": jnp.float32}, "pallas", (1024, 1024)),
    # another backend keeps XLA's dense attention
    ({"backend": "cpu"}, "reference", None),
    ({"backend": "gpu", "seq": 4096}, "reference", None),
    # forced either way; explicit tiles are honoured, and clamped to S
    ({"impl": "pallas", "seq": 64, "backend": "cpu"}, "pallas", (64, 64)),
    ({"impl": "reference"}, "reference", None),
    ({"block_q": 128, "block_kv": 2048}, "pallas", (128, 1024)),
    ({"block_kv": 256}, "pallas", (1024, 256)),
])
def test_choose_attention(facts, impl, tiles):
    choice = _choose(**facts)
    assert choice.impl == impl, choice
    if tiles is not None:
        assert (choice.block_q, choice.block_kv) == tiles, choice
    assert choice.why


def test_choose_attention_names_what_decided():
    assert _choose().why == "S=1024 D=64 segments, 256 MiB of scores"
    assert _choose(seq=2048, batch=1).why == "S=2048 D=64 segments"
    assert "64 MiB of scores stay" in _choose(batch=1).why
    assert "1 GB" in _choose(batch=128, seq=256, heads=32).why
    assert "float32" in _choose(dtype=jnp.float32).why
    assert "cpu" in _choose(backend="cpu").why


@pytest.mark.parametrize("seq,tiles", [
    (64, (64, 64)), (768, (768, 768)), (1024, (1024, 1024)),
    (1536, (512, 512)), (2048, (512, 512)), (8192, (512, 512))])
def test_flash_tiles(seq, tiles):
    assert flash_tiles(seq) == tiles


def test_segment_grads_unequal_tiles():
    """Packed-segment gradients through the transposed kernels at UNEQUAL
    tiles (256 x 512 at S 1024, D 64, 2 heads, batch 1): a causal grid whose
    query and key/value tiles differ, with segment edges inside tiles and on
    a tile's edge. (The tiles `flash_tiles` gives this S, one 1024 x 1024,
    run under the same oracle.)"""
    s, h, d = 1024, 2, 64
    q, k, v = make_qkv(b=1, s=s, h=h, hkv=h, d=d)
    seg = jnp.asarray(np.repeat(np.arange(4), [300, 212, 412, 100]))[None]

    def oracle(args):
        qq, kk, vv = args
        sc = jnp.einsum("bqhd,bkhd->bhqk", qq, kk) / (d ** 0.5)
        mask = (seg[:, None, :, None] == seg[:, None, None, :])
        mask = mask & jnp.tril(jnp.ones((s, s), jnp.bool_))[None, None]
        p = jax.nn.softmax(jnp.where(mask, sc, -1e30), axis=-1)
        return jnp.sum(jnp.einsum("bhqk,bkhd->bqhd", p, vv) ** 2)

    gr = jax.grad(oracle)((q, k, v))
    for tiles in ({"block_q": 256, "block_kv": 512}, {}):
        gf = jax.grad(lambda args: jnp.sum(flash_attention(
            *args, causal=True, segment_ids=seg, **tiles) ** 2))((q, k, v))
        for a, b in zip(gf, gr):
            assert bool(jnp.all(jnp.isfinite(a)))
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=5e-5)


# ---------------------------------------------------------------------------
# The documents' table of a packed call (segment_tile_table): which tile
# pairs the kernels compute, against a count from the [S, S] mask itself.
# ---------------------------------------------------------------------------

def _live_by_hand(ids, block_q, block_kv, causal, window):
    """(tiles, meet, allowed) by brute force over the batch: the tile pairs
    that hold a (query, key) of the [S, S] causal or band mask; of them,
    those whose ranges of ids meet (what the table promises to compute);
    and those that hold a pair the masks and the ids allow (what has to
    be computed)."""
    ids = np.asarray(ids)
    b, s = ids.shape
    at_q, at_k = np.arange(s)[:, None], np.arange(s)[None, :]
    part = np.ones((s, s), bool)
    if causal:
        part &= at_q >= at_k
    if window:
        part &= at_q - at_k < window
    nq, nk = -(-s // block_q), -(-s // block_kv)
    tiles = meet = allowed = 0
    for row in ids:
        same = (row[:, None] == row[None, :]) & part
        for iq in range(nq):
            rows = slice(iq * block_q, (iq + 1) * block_q)
            for ik in range(nk):
                cols = slice(ik * block_kv, (ik + 1) * block_kv)
                if not part[rows, cols].any():
                    continue
                tiles += 1
                allowed += bool(same[rows, cols].any())
                meet += bool(row[rows].min() <= row[cols].max()
                             and row[cols].min() <= row[rows].max())
    return tiles, meet, allowed


@pytest.mark.parametrize("causal,window,tiles", [
    (True, 0, (32, 32)), (True, 0, (16, 48)), (True, 72, (32, 32)),
    (True, 40, (48, 16)), (False, 0, (32, 32)), (True, 0, (40, 40)),
], ids=["causal", "causal-unequal", "band", "band-unequal", "bidirectional",
        "ragged"])
@pytest.mark.parametrize("sorted_ids", [True, False],
                         ids=["sorted", "unsorted"])
def test_the_table_counts_what_the_masks_leave(causal, window, tiles,
                                               sorted_ids):
    """`tiles` is the grid's causal or band part, `computed` the pairs whose
    ranges of ids meet (for sorted ids exactly those that hold an allowed
    pair or straddle a diagonal tile's corner; never fewer), and each
    tile's [lo, hi] is the hull of its live partners."""
    from megatronapp_tpu.ops.pallas import flash_attention as fa
    rng = np.random.default_rng(7)
    ids = rng.integers(0, 5, (3, 240))
    if sorted_ids:
        ids = np.sort(ids, axis=1)
    ids[2] = 3                               # one document spans the row
    bq, bkv = tiles
    q_table, kv_table, n, computed = fa.segment_tile_table(
        jnp.asarray(ids, jnp.int32), jnp.asarray(ids, jnp.int32), bq, bkv,
        causal, window)
    want_tiles, meet, allowed = _live_by_hand(ids, bq, bkv, causal, window)
    assert (n, int(computed)) == (want_tiles, meet)
    assert allowed <= meet
    if sorted_ids and not window and bq == bkv and causal:
        # ranges that meet share an id, and on or under the diagonal a
        # shared id of sorted rows is an allowed pair
        assert allowed == meet
    q_table, kv_table = np.asarray(q_table), np.asarray(kv_table)
    part = fa._grid_part(q_table.shape[1], kv_table.shape[1], bq, bkv,
                         causal, window)
    for b in range(ids.shape[0]):
        live = ((q_table[b, :, None, 0] <= kv_table[b, None, :, 1])
                & (kv_table[b, None, :, 0] <= q_table[b, :, None, 1]) & part)
        for iq, row in enumerate(live):
            at = np.flatnonzero(row)
            assert tuple(q_table[b, iq, 2:]) == (at.min(), at.max())
        for ik, col in enumerate(live.T):
            at = np.flatnonzero(col)
            assert tuple(kv_table[b, ik, 2:]) == (at.min(), at.max())


def test_the_share_training_cells_rows_leave_half_the_full_layers_tiles():
    """ISSUE 58's count: over 48 rows of the cell's own generator (seed 0,
    six batches) at 512 x 512, 53.5% of the full layer's causal tiles and
    94.3% of the window layers' band tiles hold a query and a key whose
    documents can meet."""
    import itertools
    import json
    import os
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, root)
    try:
        from perfbench.generators import train_packed
    finally:
        sys.path.remove(root)
    from megatronapp_tpu.ops.pallas.flash_attention import (
        segment_tile_counts,
    )
    with open(os.path.join(root, "perfbench/traffic/packed-8k.json")) as f:
        job = json.load(f)
    ids = jnp.asarray(np.concatenate([
        batch["segment_ids"] for batch in itertools.islice(
            train_packed.batches(job, 0, 24576, 8192), 6)]))
    assert ids.shape == (48, 8192)
    tiles, computed = segment_tile_counts(ids)           # 512 x 512
    assert (tiles, int(computed)) == (6528, 3491)        # 53.5%
    tiles, computed = segment_tile_counts(ids, window=1024)
    assert (tiles, int(computed)) == (2160, 2037)        # 94.3%
    # one tile a sequence is handed no table: all of it is computed
    tiles, computed = segment_tile_counts(ids[:, :1024])
    assert (tiles, int(computed)) == (48, 48)
