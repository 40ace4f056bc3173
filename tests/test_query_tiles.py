"""ISSUE 35: the ragged paged kernels cut a call's queries into query tiles
(ops/pallas/kernel_gen.py: `query_rows_per_step`, `_query_tiled`), and the
engine's streams are the dense oracle's whatever the width of its prefill
call. A file of its own beside tests/test_kernel_gen.py, whose helpers it
uses: pytest-xdist hands out whole files, and that one is the longest."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import test_kernel_gen as base
from megatronapp_tpu.config.parallel_config import ParallelConfig
from megatronapp_tpu.ops.pallas import kernel_gen
from megatronapp_tpu.ops.pallas.kernel_gen import paged_attention_latent
from megatronapp_tpu.parallel.mesh import build_mesh


class TestQueryTiles:
    """ISSUE 35: a ragged call whose slots bring more queries than a step
    of the walk can hold (a prefill call as wide as its weight stream pays
    for) is cut into query tiles by the entry points, each a row of the
    walk with its own kv_len and q_len over the slot's table row. Against
    the gather-everything oracles, and against the same call in one
    tile."""

    S_Q = 40
    # (cached rows, real queries) a slot: a full call over a long context;
    # one that ends inside the second tile, with no context before it; one
    # that ends on a tile's edge; one query; one inside the first tile; a
    # slot that holds nothing
    SLOTS = [(200 + 40, 40), (21, 21), (150 + 16, 16), (1, 1), (97, 7),
             (0, 0)]

    @pytest.fixture
    def tiles(self, monkeypatch):
        """A VMEM budget under which a step holds 16 to 24 of these
        queries (the real `query_rows_per_step` picks); -> the tiles the
        calls under it were cut into."""
        seen = []
        cut = kernel_gen._query_tiled

        def spy(tile, *a):
            seen.append(tile)
            return cut(tile, *a)

        monkeypatch.setattr(kernel_gen, "_query_vmem_budget",
                            lambda *a: 400_000)
        monkeypatch.setattr(kernel_gen, "_query_tiled", spy)
        return seen

    def _case(self, body, pool):
        lens, q_lens = zip(*self.SLOTS)
        return base._walk_case(body, list(lens), pool=pool, s_q=self.S_Q,
                          q_lens=list(q_lens))

    @pytest.mark.parametrize("pool", ["fp32", "bf16", "int8"])
    @pytest.mark.parametrize("body", ["paged_mq", "paged_mq_latent"])
    def test_tiled_call_matches_the_oracle(self, tiles, body, pool):
        out, ref = self._case(body, pool)
        assert tiles and 8 <= tiles[-1] < self.S_Q and tiles[-1] % 8 == 0
        assert bool(jnp.all(out[-1] == 0.0))       # the slot with no row
        base._assert_close(out, ref, **base.TestWalk()._tol(body, pool))

    @pytest.mark.parametrize("body,key_tile", [
        ("paged_mq", None), ("paged_mq_latent", 128),
        ("paged_mq_latent", None)])
    def test_tiles_change_no_bit(self, monkeypatch, body, key_tile):
        """A query row folds the same key tiles in the same order whatever
        tile of queries it sits in (the tiles past its own position are
        masked whole and leave max, sum and accumulator as they were): the
        tiled call's real rows equal the one-tile call's bit for bit. At
        the latent family's own key tile of 256 a product's contraction is
        long enough for XLA:CPU's matrix product to block it by the
        operand's rows, which differ between the two calls: equal to
        float32's rounding there, bit for bit at 128."""
        if key_tile:
            monkeypatch.setattr(kernel_gen, "LATENT_KEY_TILE", key_tile)
        whole, _ = self._case(body, "fp32")
        monkeypatch.setattr(kernel_gen, "_query_vmem_budget",
                            lambda *a: 400_000)
        tiled, _ = self._case(body, "fp32")
        if body == "paged_mq_latent" and key_tile is None:
            base._assert_close(tiled, whole, atol=4e-6, rtol=4e-6)
        else:
            assert bool(jnp.all(tiled == whole))

    def test_a_verify_call_is_not_cut(self, monkeypatch):
        """Speculative verify's [B, k + 1] queries fit one tile under the
        real budget: `_query_tiled` hands the call back as it came, so the
        kernel that runs is the parent's, operand for operand."""
        cuts = []
        cut = kernel_gen._query_tiled
        monkeypatch.setattr(
            kernel_gen, "_query_tiled",
            lambda tile, *a: cuts.append((tile, a, cut(tile, *a)))
            or cuts[-1][2])
        out, ref = base._walk_case("paged_mq", [40, 9, 130, 5], s_q=5)
        base._assert_close(out, ref, **base.TestWalk.TOL)
        (tile, (tbl, kv, ql, queries), got), = cuts
        assert tile == 5
        assert got[0] is tbl and got[1] is kv and got[2] is ql
        assert got[3] is queries

    def test_query_rows_per_step_follows_the_shapes(self):
        """The tile the serving cells' prefill calls get on a v5e (16 MiB
        of scoped VMEM), from shapes alone; Mosaic takes each
        (tests/test_chip_compile.py)."""
        bf16 = jnp.bfloat16

        def sds(*shape, dtype=bf16):
            return jax.ShapeDtypeStruct(shape, dtype)

        def tile(s_q, hq, hkv, d, nb, mb):
            pools, by_pools = kernel_gen._call_pools(
                [sds(2, nb, 16, hkv, d), sds(2, nb, 16, hkv, d)],
                [None, None], mb)
            return kernel_gen._query_tile([sds(1, s_q, hq, d)], d, pools,
                                          by_pools)

        # dense GPT-3 2.7B (32 heads of 80, padded to 128 lanes) and
        # EvaByte (32 of 128): 112 KB a query beside 4 MiB of page buffers
        assert tile(256, 32, 32, 80, 896, 128) == 64
        assert tile(256, 32, 32, 128, 3584, 192) == 64
        assert tile(32, 32, 32, 128, 3584, 192) == 32     # fits whole
        # 20 query heads over one key/value head: the pages are small, and
        # the hybrid cell's call of 256 is two tiles
        assert tile(256, 20, 1, 128, 16384, 128) == 128
        assert tile(64, 20, 1, 128, 16384, 128) == 64
        # MLA, 512 + 64 latent columns: the accumulator and the output
        # block are the latent sum's, 512 float32 columns a head (ISSUE
        # 39), 200 KB a query at 16 heads and 800 KB at 64, beside a key
        # tile of 256. 16 heads: 512 are eight tiles of 64, the MoE cell's
        # call of 1,024 sixteen; 64 heads: the agent cell's call of 512 is
        # 32 tiles of 16 (tests/test_chip_compile.py: Mosaic takes twice
        # those)
        def latent_tile(s_q, nq, planes, nb):
            pools, by_pools = kernel_gen._call_pools(
                [sds(planes, nb, 16, 512), sds(planes, nb, 16, 64)],
                [None, None], 256, kernel_gen.LATENT_KEY_TILE)
            assert by_pools["pages"] == 16
            return kernel_gen._query_tile(
                [sds(1, s_q, nq, 512), sds(1, s_q, nq, 64)], 512, pools,
                by_pools, jnp.float32)

        assert latent_tile(512, 16, 9, 8192) == 64
        assert latent_tile(1024, 16, 9, 8192) == 64
        assert latent_tile(512, 64, 8, 16384) == 16
        assert latent_tile(32, 16, 9, 8192) == 32         # fits whole
        # equal tiles, multiples of 8, never under 8
        assert kernel_gen.query_rows_per_step(100, 1000, 64_000) == 56
        assert kernel_gen.query_rows_per_step(100, 1000, 1_000) == 8
        assert kernel_gen.query_rows_per_step(100, 1000, -5) == 8

    @pytest.mark.parametrize("quant", [False, True])
    def test_tp2_ragged_latent_queries_are_tiled(self, devices8,
                                                 monkeypatch, quant):
        """The tp placement's two kernels hold a row's whole query block as
        the walk does, so a call wider than a step holds (a prefill call
        of the engine's width) is cut into query tiles before the
        shard_map too: q_lens that fill the call, end inside its second
        tile and are 1, under a VMEM budget that holds 8 queries."""
        rng = np.random.default_rng(25)
        s_q = 20
        scale = base.TestLatentKernelPins.SCALE
        (q_lat, q_pe, lat, pe, w_v, tbl, lens, ls,
         ps) = base._mk_latent_inputs(rng, 3, s_q, 4, 32, 8, 16, 8, 6, quant,
                                      jnp.float32)
        lens = jnp.maximum(lens, s_q)
        qlens = jnp.asarray([s_q, 11, 1], jnp.int32)
        ref = paged_attention_latent(q_lat, q_pe, lat, pe, tbl, lens,
                                     w_v, q_lens=qlens,
                                     softmax_scale=scale,
                                     lat_scales=ls, pe_scales=ps)
        tiles = []
        cut = kernel_gen._query_tiled
        monkeypatch.setattr(
            kernel_gen, "_query_tiled",
            lambda tile, *a: tiles.append(tile) or cut(tile, *a))
        monkeypatch.setattr(kernel_gen, "_latent_tp_row_vmem_bytes",
                            lambda *a: kernel_gen.VMEM_SCOPE // 8)
        ctx = build_mesh(ParallelConfig(tensor_parallel=2),
                         devices=jax.devices()[:2])
        tp = paged_attention_latent(q_lat, q_pe, lat, pe, tbl, lens,
                                    w_v, q_lens=qlens,
                                    softmax_scale=scale,
                                    lat_scales=ls, pe_scales=ps,
                                    mesh=ctx.mesh)
        assert tiles == [8]
        real = (np.arange(s_q)[None, :] < np.asarray(qlens)[:, None])
        np.testing.assert_allclose(np.asarray(ref)[real],
                                   np.asarray(tp)[real],
                                   atol=2e-5, rtol=2e-5)


class TestLatentSum:
    """ISSUE 39: the latent bodies add p · latent to a [rows, klat]
    accumulator and `paged_attention_latent` expands the normalised sum
    through kv_up's value columns once a query row. Against the dense
    oracle (gather, re-expand every row through kv_up, plain softmax) at
    DeepSeek-V2-Lite's and LongCat's head counts: slots with no cached row,
    one row, a partial last tile, a tile's edge, two tiles and a row."""

    LENS = [0, 1, 200, 128, 257]

    @pytest.mark.parametrize("pool", ["bf16", "int8", "fp8"])
    @pytest.mark.parametrize("heads", [16, 64])
    @pytest.mark.parametrize("body", ["paged_decode_latent",
                                      "paged_mq_latent"])
    def test_walk_matches_the_dense_oracle(self, body, heads, pool):
        out, ref = base._walk_case(body, self.LENS, pool=pool, heads=heads)
        assert out.shape[-2:] == (heads, 16)       # [.., nq, dv]
        assert bool(jnp.all(out[0] == 0.0))        # the slot with no row
        base._assert_close(out[1:], ref[1:],
                           **base.TestWalk()._tol(body, pool))

    @pytest.mark.parametrize("heads", [16, 64])
    def test_decode_is_a_one_query_ragged_call(self, heads):
        """One template, two points: the ragged body at one query a slot
        walks, folds and expands as the decode body does, bit for bit."""
        rng = np.random.default_rng(39)
        q_lat, q_pe, lat, pe, w_v, tbl, lens, _, _ = base._mk_latent_inputs(
            rng, 3, 0, heads, 32, 8, 16, 16, 20, False, jnp.float32)
        scale = base.TestLatentKernelPins.SCALE
        dec = paged_attention_latent(q_lat, q_pe, lat, pe, tbl, lens, w_v,
                                     softmax_scale=scale)
        mq = paged_attention_latent(q_lat[:, None], q_pe[:, None], lat, pe,
                                    tbl, lens, w_v,
                                    q_lens=jnp.ones((3,), jnp.int32),
                                    softmax_scale=scale)
        assert bool(jnp.all(dec == mq[:, 0]))

    def test_the_kernel_holds_no_value_columns(self):
        """The walk's operands are the queries and the pages: `w_v` is no
        operand of it, its output is the latent sum in float32, and the one
        product with `w_v` comes after it."""
        rng = np.random.default_rng(40)
        q_lat, q_pe, lat, pe, w_v, tbl, lens, _, _ = base._mk_latent_inputs(
            rng, 2, 0, 16, 32, 8, 16, 16, 4, False, jnp.bfloat16)
        jaxpr = jax.make_jaxpr(lambda *a: paged_attention_latent(
            *a, softmax_scale=0.2))(q_lat, q_pe, lat, pe, tbl, lens, w_v)
        call, = (e for e in jaxpr.eqns if e.primitive.name == "pallas_call")
        w_v_var = jaxpr.jaxpr.invars[-1]
        assert w_v_var not in call.invars
        assert [v.aval.shape for v in call.outvars] == [(2, 16, 32)]
        assert call.outvars[0].aval.dtype == jnp.float32
        after = jaxpr.eqns[jaxpr.eqns.index(call) + 1:]
        assert sum(e.primitive.name == "dot_general" for e in after) == 1
        assert jaxpr.out_avals[0].shape == (2, 16, 16)
        assert jaxpr.out_avals[0].dtype == jnp.bfloat16


class TestPrefillWidths:
    @pytest.mark.parametrize("width", [32, "a v5e's", 7],
                             ids=["32", "chosen-for-a-v5e", "odd-7"])
    def test_streams_at_any_prefill_width(self, monkeypatch, width):
        """ISSUE 35: greedy streams are the dense oracle's whatever the
        width of the prefill call: 32 (what every engine had; here under a
        VMEM budget so small that the ragged kernel cuts each call into
        query tiles of 8), the width the engine chooses for these shapes
        on a v5e (max_seq_len: a call holds a whole prompt) and 7, which
        divides no prompt. An init wide enough that a stream is not one
        token repeated."""
        from megatronapp_tpu.inference.dynamic_engine import (
            choose_prefill_width,
        )
        cfg = base._engine_cfg(init_method_std=0.2)
        params, prompts = base._engine_case(cfg, seed=35)
        prompts = [np.random.default_rng(36).integers(
            0, cfg.vocab_size, 45).astype(np.int32), prompts[2]]
        if width == 32:
            monkeypatch.setattr(kernel_gen, "_query_vmem_budget",
                                lambda *a: 100_000)
        elif width != 7:
            width = choose_prefill_width(cfg, params, 64, 8,
                                         device_kind="TPU v5 lite")
            assert width == 64      # the engine's max_seq_len: one call
        out, eng = base._stream(cfg, params, prompts, max_new=5,
                           prefill_chunk=width)
        eng.pool.audit()
        pre = eng.stats_snapshot()["prefill"]
        assert pre["width"] == width and pre["tokens"] == 45 + 17
        assert pre["calls"] == sum(-(-len(p) // width) for p in prompts)
        assert eng.mq_traces == 1
        streams = [toks[len(p):] for p, toks in zip(prompts, out)]
        assert any(len(set(st)) > 2 for st in streams), streams
        for p, toks in zip(prompts, out):
            assert toks == base._greedy_oracle(params, cfg, p, 5)


class TestOwnCopies:
    """ISSUE 46: where a pool's page is whole tiles (a row of 8 x 128 and
    more), the walk keeps the pool in HBM and starts the copies of a step's
    pages itself, a step ahead, into its own two buffers; a page that a
    slot's partial last step lacks is not copied and its rows are blanked.
    A pool whose page is no whole tiles stays the pipeline's blocked
    operands (every other test of the walk runs those). Widths here are
    the smallest that are whole tiles; every pool block that no slot's
    table names within its length, and every table entry past a length,
    is NaN, so a copy that should not exist fails the comparison."""

    # (query heads, key/value heads, head dim) | (latent, roped-key columns)
    WIDTHS = {
        "dense": (16, 8, 128),
        "gqa-8-of-48": (48, 8, 128),
        # the latent plane copied by the kernel, the roped keys blocked
        "latent": (128, 8),
        "latent-both": (128, 128),
    }
    # a slot with no row, a one-step slot, a partial last step followed by
    # another slot's first step, and a whole step; one slot of one step
    # (a grid of 1); whole steps only
    SLOTS = {"mixed": [0, 5, 8 * 16 + 3, 40, 8 * 16],
             "one-step": [37],
             "whole": [8 * 16, 16 * 16]}

    def _case(self, body, widths, lens, pool="fp32", **kw):
        dirty, _ = base._walk_case(body, lens, nan_past=True, pool=pool,
                                   widths=self.WIDTHS[widths], **kw)
        clean, ref = base._walk_case(body, lens, pool=pool,
                                     widths=self.WIDTHS[widths], **kw)
        assert bool(jnp.all(jnp.isfinite(dirty)))
        assert bool(jnp.all(dirty == clean))
        # a slot that holds nothing gets zeros (the oracles average its
        # table's garbage): compare the others
        rows = np.asarray([n > 0 for n in lens])
        assert bool(jnp.all(clean[~rows] == 0.0))
        return clean[rows], ref[rows]

    @pytest.mark.parametrize("slots", list(SLOTS))
    @pytest.mark.parametrize("body,widths", [
        ("paged_decode", "dense"), ("paged_decode", "gqa-8-of-48"),
        ("paged_mq", "dense"), ("paged_decode_latent", "latent"),
        ("paged_decode_latent", "latent-both"),
        ("paged_mq_latent", "latent-both")])
    def test_against_the_oracle_and_nan_pages(self, body, widths, slots):
        out, ref = self._case(body, widths, self.SLOTS[slots])
        base._assert_close(out, ref, **base.TestWalk.TOL)

    @pytest.mark.parametrize("pool", ["bf16", "int8", "fp8"])
    @pytest.mark.parametrize("body,widths", [
        ("paged_decode", "dense"), ("paged_mq_latent", "latent-both")])
    def test_pools_of_other_types(self, body, widths, pool):
        """int8 and fp8 pages are copied by the kernel where they are whole
        tiles of their type (a latent page's rows are the sublanes: 32 rows
        a block); their fp32 scale pages ([bs, heads], [bs]) never are, and
        stay blocked operands of the same call."""
        bs = 32 if "latent" in widths and pool != "bf16" else 16
        lens = [n * bs // 16 for n in self.SLOTS["mixed"]]
        out, ref = self._case(body, widths, lens, pool=pool, bs=bs)
        # bf16 pages under 128 + 128 columns: the oracle rounds elsewhere
        tol = (dict(atol=0.2, rtol=5e-2) if (body, pool)
               == ("paged_mq_latent", "bf16")
               else base.TestWalk()._tol(body, pool))
        if bs == 32:
            # twice the rows a page: float32 sums in another order
            tol = dict(atol=5e-5, rtol=5e-5)
        base._assert_close(out, ref, **tol)

    @pytest.mark.parametrize("body,widths", [
        ("paged_mq", "dense"), ("paged_mq_latent", "latent")])
    def test_tiled_queries(self, monkeypatch, body, widths):
        """A ragged call cut into query tiles: every tile is a row of the
        walk over its slot's table row, so one slot's last step is
        followed by the same slot's first."""
        monkeypatch.setattr(kernel_gen, "_query_tile", lambda *a, **kw: 8)
        lens = [200 + 20, 21, 0]
        out, ref = self._case(body, widths, lens, s_q=20,
                              q_lens=[20, 21 - 8, 0])
        base._assert_close(out, ref, **base.TestWalk.TOL)

    def test_which_pools_the_kernel_copies(self):
        def pool(*shape, dtype=jnp.bfloat16):
            return jax.ShapeDtypeStruct(shape, dtype)

        is_tiles = kernel_gen._page_is_tiles
        # the byte and code cells' pages, the latent plane
        assert is_tiles(pool(2, 64, 16, 32, 128))
        assert is_tiles(pool(2, 64, 16, 8, 128))
        assert is_tiles(pool(2, 64, 16, 512))
        # D 80 and D 64, one key/value head, the roped keys' 64 columns,
        # scale pages
        assert not is_tiles(pool(2, 64, 16, 32, 80))
        assert not is_tiles(pool(2, 64, 16, 8, 64))
        assert not is_tiles(pool(2, 64, 16, 1, 128))
        assert not is_tiles(pool(2, 64, 16, 64))
        assert not is_tiles(pool(2, 64, 16, 32, dtype=jnp.float32))
        assert not is_tiles(pool(2, 64, 16, dtype=jnp.float32))
        # the tile follows the dtype. Pages of heads are cut out along an
        # untiled dim, whatever their type (both lower for a v5e:
        # test_chip_compile.py); a latent page's rows are the sublanes, 16
        # of bf16 and 32 of int8 or fp8 a tile
        for quant in (jnp.int8, jnp.float8_e4m3fn):
            assert is_tiles(pool(2, 64, 16, 8, 128, dtype=quant))
            assert is_tiles(pool(2, 64, 16, 32, 128, dtype=quant))
            assert not is_tiles(pool(2, 64, 16, 512, dtype=quant))
            assert is_tiles(pool(2, 64, 32, 512, dtype=quant))
        assert not is_tiles(pool(2, 64, 8, 512))
        # and the keys a step folds: 256 where the kernel copies pages of
        # 8 heads of 128 (the code cell), 128 where a step of 256 would
        # hold 4 MiB (32 heads: the byte cell) or the pipeline brings the
        # pages (8 heads of 64, one head: the assist and hybrid cells keep
        # the kernels and the bits they had)
        tile = kernel_gen.default_kv_tile(None)

        def keys(hkv, d):
            pools = [pool(2, 64, 16, hkv, d)] * 2
            return kernel_gen.dense_key_tile(
                16, kernel_gen._pages_vmem_bytes(pools, tile),
                [is_tiles(p) for p in pools])

        assert keys(8, 128) == kernel_gen.WIDE_KEY_TILE == 256
        assert keys(32, 128) == kernel_gen.KEY_TILE == 128
        assert keys(8, 64) == keys(1, 128) == keys(32, 80) == 128
