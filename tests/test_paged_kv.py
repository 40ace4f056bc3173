"""Paged KV-cache serving subsystem tests (ISSUE 3).

Covers the three layers: the block-pool allocator (eviction order,
refcounted prefix sharing, copy-on-write, rollback), the ragged
paged-attention Pallas kernel (CPU interpret mode, parity vs the jnp
reference to <= 1e-5 incl. GQA and ragged lengths), and the engine/server
integration (paged-vs-dense greedy parity for GQA and MLA, prefix-cache
hits asserted via refcounts, preemption-and-resume, batched fold_in
sampling reproducibility, continuous batching through the server driver,
MegaScope reset_compilation hook-toggle smoke)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from megatronapp_tpu.config.transformer_config import TransformerConfig
from megatronapp_tpu.inference.dynamic_engine import DynamicInferenceEngine
from megatronapp_tpu.inference.engine import SamplingParams
from megatronapp_tpu.inference.paged_cache import PagedKVCache, cdiv
from megatronapp_tpu.models.gpt import init_gpt_params


def _gqa_cfg():
    return TransformerConfig(
        num_layers=2, hidden_size=64, num_attention_heads=4,
        num_query_groups=2, vocab_size=128, max_position_embeddings=64,
        compute_dtype=jnp.float32, remat_policy="none")


def _mla_cfg():
    return TransformerConfig(
        num_layers=2, hidden_size=64, num_attention_heads=4,
        vocab_size=128, max_position_embeddings=64,
        multi_latent_attention=True, kv_lora_rank=32, qk_head_dim=16,
        qk_pos_emb_head_dim=8, v_head_dim=16,
        compute_dtype=jnp.float32, remat_policy="none")


from jitted import greedy_oracle as _greedy_oracle  # noqa: E402


class TestPagedAttentionKernel:
    @pytest.mark.parametrize("hq,hkv,d,bs", [(4, 2, 16, 4), (8, 8, 8, 8),
                                             (6, 2, 32, 16), (4, 1, 8, 4)])
    def test_kernel_matches_reference(self, hq, hkv, d, bs):
        """Ragged paged decode == jnp reference to fp32 epsilon across
        GQA groupings, block sizes, and lengths that don't divide the
        block."""
        from megatronapp_tpu.ops.pallas.paged_attention import (
            paged_attention_decode, paged_attention_reference,
        )
        b, mb = 3, 4
        nb = b * mb
        rng = np.random.default_rng(hq * 100 + bs)
        q = jnp.asarray(rng.normal(size=(b, hq, d)), jnp.float32)
        kp = jnp.asarray(rng.normal(size=(nb, bs, hkv, d)), jnp.float32)
        vp = jnp.asarray(rng.normal(size=(nb, bs, hkv, d)), jnp.float32)
        table = jnp.asarray(
            rng.permutation(nb)[:b * mb].reshape(b, mb), jnp.int32)
        lens = jnp.asarray([1, bs + 1, mb * bs], jnp.int32)
        out = paged_attention_decode(q, kp, vp, table, lens)
        ref = paged_attention_reference(q, kp, vp, table, lens)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5, rtol=1e-5)

    def test_kernel_matches_dense_attention(self):
        """Paged decode over a scattered page layout == dense softmax
        attention over the contiguous equivalent (<= 1e-5)."""
        from megatronapp_tpu.ops.pallas.paged_attention import (
            paged_attention_decode,
        )
        b, hq, hkv, d, bs, mb = 2, 4, 2, 16, 4, 3
        nb = b * mb
        rng = np.random.default_rng(0)
        table = rng.permutation(nb).reshape(b, mb)
        lens = np.asarray([5, 11], np.int32)
        kd = rng.normal(size=(b, mb * bs, hkv, d)).astype(np.float32)
        vd = rng.normal(size=(b, mb * bs, hkv, d)).astype(np.float32)
        q = rng.normal(size=(b, hq, d)).astype(np.float32)
        kp = np.zeros((nb, bs, hkv, d), np.float32)
        vp = np.zeros((nb, bs, hkv, d), np.float32)
        for i in range(b):
            for j in range(mb):
                kp[table[i, j]] = kd[i, j * bs:(j + 1) * bs]
                vp[table[i, j]] = vd[i, j * bs:(j + 1) * bs]
        out = paged_attention_decode(
            jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
            jnp.asarray(table, jnp.int32), jnp.asarray(lens))
        # dense per-row oracle
        group = hq // hkv
        for i in range(b):
            kk = np.repeat(kd[i, :lens[i]], group, axis=1)  # [S,Hq,D]
            vv = np.repeat(vd[i, :lens[i]], group, axis=1)
            s = np.einsum("hd,shd->hs", q[i], kk) / np.sqrt(d)
            p = np.exp(s - s.max(-1, keepdims=True))
            p /= p.sum(-1, keepdims=True)
            o = np.einsum("hs,shd->hd", p, vv)
            np.testing.assert_allclose(np.asarray(out[i]), o, atol=1e-5)


# (name, one row's dims, dtype): K/V rows are whole tiled planes and go
# through the Pallas writer; scale rows, MLA latent rows and latent scale
# rows are part of a tile and go through per-row dynamic_update_slice.
_POOL_KINDS = [("bf16-kv", (2, 8), jnp.bfloat16),
               ("int8-kv", (2, 8), jnp.int8),
               ("kv-scales", (2,), jnp.float32),
               ("latent", (16,), jnp.bfloat16),
               ("latent-scales", (), jnp.float32)]


@pytest.mark.parametrize("kind,row,dtype", _POOL_KINDS,
                         ids=[k[0] for k in _POOL_KINDS])
class TestPoolWriter:
    """kernel_gen.paged_append (through append_token_pages /
    append_chunk_pages with `layer`) against what the writers did before
    it: `pages.at[blocks, offs].set(vals, mode="drop")` on one layer's
    slice, an out-of-range block id for every row that must not land."""
    L, NB, BS, LAYER = 3, 6, 4, 1

    def _setup(self, row, dtype):
        rng = np.random.default_rng(7)

        def draw(shape):
            x = rng.integers(-100, 100, size=shape)
            return jnp.asarray(x, jnp.float32).astype(dtype)

        pool = draw((self.L, self.NB, self.BS) + row)
        # Slot 2 is inactive and its table still names block 4, which slot
        # 0 owns now (freed and handed on): nothing of slot 2 may land.
        table = jnp.asarray([[4, 1], [3, 0], [4, 5]], jnp.int32)
        return pool, table, draw

    def _old(self, pool, vals, table, starts, counts, active):
        """The deleted scatter, on the one layer."""
        b, s = vals.shape[:2]
        pos = starts[:, None] + jnp.arange(s)[None, :]
        blocks = jnp.take_along_axis(
            table, jnp.clip(pos // self.BS, 0, table.shape[1] - 1), axis=1)
        valid = (jnp.arange(s)[None, :] < counts[:, None]) & active[:, None]
        blocks = jnp.where(valid, blocks, self.NB)
        flat = lambda x: x.reshape((b * s,) + x.shape[2:])  # noqa: E731
        layer = pool[self.LAYER].at[flat(blocks), flat(pos % self.BS)].set(
            flat(vals), mode="drop")
        return pool.at[self.LAYER].set(layer)

    def test_ragged_chunk_drops_what_the_scatter_dropped(self, kind, row,
                                                         dtype):
        from megatronapp_tpu.ops.pallas.paged_attention import (
            append_chunk_pages,
        )
        pool, table, draw = self._setup(row, dtype)
        vals = draw((3, 3) + row)
        starts = jnp.asarray([2, 5, 1], jnp.int32)     # slot 0 crosses a block
        counts = jnp.asarray([3, 1, 3], jnp.int32)     # slot 1: two padding rows
        active = jnp.asarray([True, True, False])
        new = jax.jit(append_chunk_pages)(pool, vals, table, starts, counts,
                                          active, jnp.int32(self.LAYER))
        want = self._old(pool, vals, table, starts, counts, active)
        np.testing.assert_array_equal(np.asarray(new.astype(jnp.float32)),
                                      np.asarray(want.astype(jnp.float32)))
        got = np.asarray(new.astype(jnp.float32))
        was = np.asarray(pool.astype(jnp.float32))
        # the other layers, and every row of the layer that no valid
        # position names, are as they were: 4 rows landed, no more
        np.testing.assert_array_equal(got[[0, 2]], was[[0, 2]])
        changed = (got[1] != was[1]).reshape(self.NB, self.BS, -1).any(-1)
        assert changed.sum() <= 4 and not changed[[2, 3, 5]].any()
        # block 4 holds slot 0's rows (positions 2, 3), none of slot 2's
        np.testing.assert_array_equal(
            got[1, 4, 2:4], np.asarray(vals[0, :2].astype(jnp.float32)))
        np.testing.assert_array_equal(got[1, 4, :2], was[1, 4, :2])

    def test_one_token_form_and_no_valid_row(self, kind, row, dtype):
        from megatronapp_tpu.ops.pallas.paged_attention import (
            append_chunk_pages, append_token_pages,
        )
        pool, table, draw = self._setup(row, dtype)
        vals = draw((3, 1) + row)
        starts = jnp.asarray([0, 3, 5], jnp.int32)
        active = jnp.asarray([True, True, False])
        lid = jnp.int32(self.LAYER)
        one = jnp.ones(3, jnp.int32)
        a = append_chunk_pages(pool, vals, table, starts, one, active, lid)
        b = append_token_pages(pool, vals[:, 0], table, starts, active, lid)
        want = self._old(pool, vals, table, starts, one, active)
        for got in (a, b):
            np.testing.assert_array_equal(
                np.asarray(got.astype(jnp.float32)),
                np.asarray(want.astype(jnp.float32)))
        # a round in which no slot is active writes nothing at all
        idle = append_token_pages(pool, vals[:, 0], table, starts,
                                  jnp.zeros(3, bool), lid)
        np.testing.assert_array_equal(np.asarray(idle.astype(jnp.float32)),
                                      np.asarray(pool.astype(jnp.float32)))


class TestBlockPool:
    def _pool(self, num_blocks=8, block_size=4, max_batch=2):
        return PagedKVCache(_gqa_cfg(), max_batch, 32,
                            num_blocks=num_blocks, block_size=block_size)

    def test_admit_release_roundtrip(self):
        pool = self._pool()
        toks = np.arange(10, dtype=np.int32)
        plan = pool.admit(0, toks)
        assert len(plan.blocks) == cdiv(10, 4) == 3
        assert pool.blocks_in_use() == 3
        assert all(pool.refcount(b) == 1 for b in plan.blocks)
        pool.release(0, toks, 10)
        assert pool.blocks_in_use() == 0
        # Full blocks stay hittable (LRU), the partial one went free.
        assert pool.available_blocks() == 8

    def test_prefix_sharing_refcounts(self):
        pool = self._pool()
        toks = np.arange(12, dtype=np.int32)      # 3 full blocks
        a = pool.admit(0, toks)
        pool.register_prefix(0, toks, 12)
        b = pool.admit(1, toks)                   # full hit -> CoW last
        assert b.cached_tokens == 11 and b.cow
        assert b.blocks[:2] == a.blocks[:2]       # shared
        assert b.blocks[2] != a.blocks[2]         # copy-on-write
        assert pool.refcount(a.blocks[0]) == 2
        assert pool.refcount(a.blocks[2]) == 1    # CoW did not share it
        assert pool.stats["cow_copies"] == 1

    def test_partial_prefix_hit(self):
        pool = self._pool(num_blocks=12)
        toks = np.arange(12, dtype=np.int32)
        pool.admit(0, toks)
        pool.register_prefix(0, toks, 12)
        # Same first 8 tokens, divergent tail: 2 shared + fresh.
        other = np.concatenate([toks[:8], np.asarray([99, 98], np.int32)])
        plan = pool.admit(1, other)
        assert plan.cached_tokens == 8 and not plan.cow
        assert pool.refcount(plan.blocks[0]) == 2

    def test_lru_eviction_order(self):
        pool = self._pool(num_blocks=4, block_size=4, max_batch=4)
        freed = []
        for slot, base in enumerate((0, 100, 200)):
            toks = np.arange(base, base + 4, dtype=np.int32)
            plan = pool.admit(slot, toks)
            pool.release(slot, toks, 4)
            freed.append(plan.blocks[0])
        # 3 hashed rc0 blocks on the LRU + 1 free; a 2-block admit takes
        # the free block then evicts the OLDEST released block.
        plan = pool.admit(0, np.arange(300, 308, dtype=np.int32))
        assert freed[0] in plan.blocks
        assert freed[1] not in plan.blocks and freed[2] not in plan.blocks
        assert pool.stats["evictions"] == 1
        # The evicted block's hash is gone: re-admitting its tokens misses.
        pool.release(0, np.arange(300, 308, dtype=np.int32), 8)
        miss = pool.admit(1, np.arange(0, 4, dtype=np.int32))
        assert miss.cached_tokens == 0

    def test_admit_rolls_back_on_exhaustion(self):
        pool = self._pool(num_blocks=3, block_size=4, max_batch=2)
        toks = np.arange(8, dtype=np.int32)
        assert pool.admit(0, toks) is not None     # 2 blocks
        before = pool.available_blocks()
        assert pool.admit(1, np.arange(50, 58, dtype=np.int32)) \
            is None                                # needs 2, has 1
        assert pool.available_blocks() == before   # rolled back
        assert pool.ensure_capacity(0, 8)          # growth still works
        assert not pool.ensure_capacity(0, 12)     # now exhausted


class TestDecodeLogitsParity:
    @pytest.mark.parametrize("mla", [False, True])
    def test_paged_decode_logits_match_dense(self, mla):
        """One decode step over IDENTICAL cache content: paged logits ==
        dense logits to <= 1e-5 on a mixed-length batch (GQA + MLA)."""
        from megatronapp_tpu.inference.dynamic_engine import (
            _paged_decode_step,
        )
        from megatronapp_tpu.inference.engine import init_kv_cache
        from megatronapp_tpu.inference.speculative import _decode_step
        cfg = _mla_cfg() if mla else _gqa_cfg()
        params, _ = init_gpt_params(jax.random.PRNGKey(11), cfg)
        b, s_max, bs = 3, 32, 8
        mb = s_max // bs
        lengths = np.asarray([5, 17, 26], np.int32)
        rng = np.random.default_rng(4)

        dense = tuple(
            jnp.asarray(rng.normal(size=c.shape).astype(np.float32))
            for c in init_kv_cache(cfg, b, s_max))
        nb = b * mb + 1
        table = np.zeros((b, mb), np.int32)
        table[:, :] = (1 + np.arange(b * mb)).reshape(b, mb)  # block 0 free
        pages = []
        for c in dense:                       # c: [L, B, Smax, ...]
            p = np.zeros((c.shape[0], nb, bs) + c.shape[3:], np.float32)
            for i in range(b):
                for j in range(mb):
                    p[:, table[i, j]] = np.asarray(
                        c[:, i, j * bs:(j + 1) * bs])
            pages.append(jnp.asarray(p))
        pages = tuple(pages)

        tokens = jnp.asarray(rng.integers(0, 128, (b, 1)), jnp.int32)
        lens = jnp.asarray(lengths)
        active = jnp.ones((b,), bool)
        d_logits, _ = _decode_step(params, tokens, dense, lens, active,
                                   cfg)
        p_logits, _, _ = _paged_decode_step(
            params, tokens, pages, jnp.asarray(table), lens, active, cfg,
            s_max)
        np.testing.assert_allclose(np.asarray(d_logits),
                                   np.asarray(p_logits),
                                   atol=1e-5, rtol=1e-5)


class TestPagedEngineParity:
    def test_paged_matches_dense_and_oracle_gqa(self):
        cfg = _gqa_cfg()
        params, _ = init_gpt_params(jax.random.PRNGKey(7), cfg)
        rng = np.random.default_rng(0)
        prompts = [rng.integers(0, 128, n).astype(np.int32)
                   for n in (5, 9, 13, 3)]
        eng = DynamicInferenceEngine(
            params, cfg, max_batch=2, max_seq_len=48,
            prefill_buckets=(16, 32), paged=True, block_size=8)
        ids = [eng.add_request(p, 6, SamplingParams(greedy=True))
               for p in prompts]
        res = eng.run_to_completion()
        for p, rid in zip(prompts, ids):
            assert res[rid].tolist() == _greedy_oracle(params, cfg, p, 6)

    def test_paged_matches_oracle_mla(self):
        cfg = _mla_cfg()
        params, _ = init_gpt_params(jax.random.PRNGKey(7), cfg)
        rng = np.random.default_rng(1)
        prompts = [rng.integers(0, 128, n).astype(np.int32)
                   for n in (5, 9, 3)]
        eng = DynamicInferenceEngine(
            params, cfg, max_batch=2, max_seq_len=48,
            prefill_buckets=(16,), paged=True, block_size=8)
        ids = [eng.add_request(p, 5, SamplingParams(greedy=True))
               for p in prompts]
        res = eng.run_to_completion()
        for p, rid in zip(prompts, ids):
            assert res[rid].tolist() == _greedy_oracle(params, cfg, p, 5)


class TestPrefixCacheEngine:
    def test_shared_prefix_skips_prefill_and_cow(self):
        """Followers of a shared prompt prefix reuse its blocks (refcount
        > 1, prefill_tokens counts only the computed tail) and a
        full-block-aligned hit goes through copy-on-write — outputs stay
        oracle-exact."""
        cfg = _gqa_cfg()
        params, _ = init_gpt_params(jax.random.PRNGKey(3), cfg)
        rng = np.random.default_rng(2)
        shared = rng.integers(0, 128, 16).astype(np.int32)   # 2 blocks
        pa = np.concatenate([shared,
                             rng.integers(0, 128, 3).astype(np.int32)])
        pb = np.concatenate([shared,
                             rng.integers(0, 128, 5).astype(np.int32)])
        pc = shared.copy()                                   # full hit
        eng = DynamicInferenceEngine(
            params, cfg, max_batch=3, max_seq_len=64,
            prefill_buckets=(32,), paged=True, block_size=8)
        ra = eng.add_request(pa, 4, SamplingParams(greedy=True))
        eng.step()                      # admit A, register its prefix
        rb = eng.add_request(pb, 4, SamplingParams(greedy=True))
        rc = eng.add_request(pc, 4, SamplingParams(greedy=True))
        eng.step()                      # admit B + C against A's blocks
        blocks_a = eng.pool.slot_blocks(0)
        assert eng.pool.refcount(blocks_a[0]) == 3           # A + B + C
        assert eng.pool.refcount(blocks_a[1]) == 2           # A + B (C CoW)
        assert eng.pool.stats["cow_copies"] == 1
        # B hit 16, C hit 15 (CoW recomputes the last token only).
        assert eng.pool.stats["prefix_hit_tokens"] == 31
        assert eng.pool.stats["prefill_tokens"] == (
            len(pa) + (len(pb) - 16) + 1)
        res = eng.run_to_completion()
        for p, rid in zip((pa, pb, pc), (ra, rb, rc)):
            assert res[rid].tolist() == _greedy_oracle(params, cfg, p, 4)

    def test_retired_blocks_stay_warm(self):
        """A finished request's full blocks remain hittable until evicted:
        a follow-up with the same prompt prefix-hits them."""
        cfg = _gqa_cfg()
        params, _ = init_gpt_params(jax.random.PRNGKey(3), cfg)
        prompt = np.arange(10, 26, dtype=np.int32) % 128     # 2 blocks
        eng = DynamicInferenceEngine(
            params, cfg, max_batch=1, max_seq_len=64,
            prefill_buckets=(32,), paged=True, block_size=8)
        r1 = eng.add_request(prompt, 3, SamplingParams(greedy=True))
        eng.run_to_completion()
        hits_before = eng.pool.stats["prefix_hit_tokens"]
        r2 = eng.add_request(prompt, 3, SamplingParams(greedy=True))
        res = eng.run_to_completion()
        assert eng.pool.stats["prefix_hit_tokens"] > hits_before
        assert res[r2].tolist() == _greedy_oracle(params, cfg, prompt, 3)


class TestPreemption:
    def test_preempt_and_resume_matches_oracle(self):
        """An undersized pool forces preemption mid-decode; the preempted
        request resumes (re-prefilling prompt+generated, usually re-
        hitting its own cached blocks) and both outputs stay exact."""
        cfg = _gqa_cfg()
        params, _ = init_gpt_params(jax.random.PRNGKey(3), cfg)
        rng = np.random.default_rng(5)
        p1 = rng.integers(0, 128, 12).astype(np.int32)
        p2 = rng.integers(0, 128, 14).astype(np.int32)
        eng = DynamicInferenceEngine(
            params, cfg, max_batch=2, max_seq_len=64,
            prefill_buckets=(32,), paged=True, block_size=8,
            num_blocks=5)       # both fit to start, not to finish
        r1 = eng.add_request(p1, 10, SamplingParams(greedy=True))
        r2 = eng.add_request(p2, 10, SamplingParams(greedy=True))
        res = eng.run_to_completion()
        assert eng.pool.stats["preemptions"] >= 1
        assert res[r1].tolist() == _greedy_oracle(params, cfg, p1, 10)
        assert res[r2].tolist() == _greedy_oracle(params, cfg, p2, 10)

    def test_lowest_priority_is_preempted(self):
        """The victim is the highest (priority, request_id) runner."""
        cfg = _gqa_cfg()
        params, _ = init_gpt_params(jax.random.PRNGKey(3), cfg)
        rng = np.random.default_rng(6)
        p1 = rng.integers(0, 128, 12).astype(np.int32)
        p2 = rng.integers(0, 128, 12).astype(np.int32)
        eng = DynamicInferenceEngine(
            params, cfg, max_batch=2, max_seq_len=64,
            prefill_buckets=(32,), paged=True, block_size=8, num_blocks=4)
        # r1 is LOW priority (larger number), r2 high.
        r1 = eng.add_request(p1, 8, SamplingParams(greedy=True),
                             priority=5)
        r2 = eng.add_request(p2, 8, SamplingParams(greedy=True),
                             priority=0)
        preempted = []
        while eng.has_work:
            preempted += eng.step()["preempted"]
        assert preempted and preempted[0] == r1
        assert r2 not in preempted


class TestSamplingRNG:
    def test_fold_in_keys_fix_additive_collisions(self):
        """The old additive scheme seed + step*7919 + rid collides for
        (rid, step) vs (rid + 7919, step - 1); fold_in chains don't."""
        from megatronapp_tpu.inference.dynamic_engine import _request_keys
        seeds = jnp.asarray([0, 0], jnp.int32)
        rids = jnp.asarray([0, 7919], jnp.int32)
        steps = jnp.asarray([1, 0], jnp.int32)
        keys = np.asarray(_request_keys(seeds, rids, steps))
        assert not np.array_equal(keys[0], keys[1])

    def test_sampler_streams_bitwise_reproducible(self):
        """The seeded sampler is bitwise-deterministic and
        batch-composition independent on FIXED logits: each row's draw
        depends only on (seed, rid, step) — not on which other rows
        share the batch, their order, or the batch size. This is the RNG
        half of the old end-to-end seeded-stream test, pinned at the
        boundary where determinism actually holds (see
        test_seeded_runs_reproducible for why the engine half is
        greedy)."""
        from megatronapp_tpu.inference.dynamic_engine import _sample_batched
        rng = np.random.default_rng(11)
        logits = jnp.asarray(rng.normal(size=(3, 128)), jnp.float32)
        seeds = jnp.asarray([123, 123, 7], jnp.int32)
        rids = jnp.asarray([0, 1, 2], jnp.int32)
        steps = jnp.asarray([0, 4, 2], jnp.int32)
        temps = jnp.full((3,), 0.8, jnp.float32)
        top_ks = jnp.full((3,), 20, jnp.int32)
        top_ps = jnp.zeros((3,), jnp.float32)
        greedys = jnp.zeros((3,), bool)

        def sample(order):
            o = jnp.asarray(order)
            out = _sample_batched(logits[o], seeds[o], rids[o], steps[o],
                                  temps, top_ks, top_ps, greedys)
            return np.asarray(out)[np.argsort(order)].tolist()

        base = sample([0, 1, 2])
        assert base == sample([0, 1, 2])       # reproducible
        assert base == sample([2, 0, 1])       # row-order independent
        # Batch-size independence: each row alone draws the same token.
        for i in range(3):
            solo = _sample_batched(
                logits[i:i + 1], seeds[i:i + 1], rids[i:i + 1],
                steps[i:i + 1], temps[:1], top_ks[:1], top_ps[:1],
                greedys[:1])
            assert int(solo[0]) == base[i]
        # Same (seed, step), different rid → distinct draw (the fold_in
        # chain separates requests sharing a seed).
        same = jnp.asarray([5, 5], jnp.int32)
        two = _sample_batched(
            jnp.tile(logits[:1], (2, 1)), same,
            jnp.asarray([0, 1], jnp.int32), jnp.zeros((2,), jnp.int32),
            temps[:2], top_ks[:2], top_ps[:2], greedys[:2])
        assert int(two[0]) != int(two[1])

    def test_seeded_runs_reproducible(self):
        """Same request params → identical streams across engine runs
        and fresh engines, independent of batch composition.

        Streams are compared GREEDY. The historical flake here compared
        sampled streams end-to-end, which couples the test to bitwise
        logit stability across FRESH COMPILES of the step function — and
        this XLA:CPU build does not provide that under load (measured:
        rare single-token flips at Gumbel near-ties, same config, same
        seed). No sampler-side tie-break can absorb that: for any
        quantization grid the flip probability stays proportional to the
        logit jitter (a jittered value near a grid boundary still
        crosses it). Greedy streams only flip when the top-2 logit gap
        is below the jitter (~1e-6 vs O(0.1) gaps here), and the seeded
        RNG chain itself is pinned bitwise on fixed logits by
        test_sampler_streams_bitwise_reproducible."""
        cfg = _gqa_cfg()
        params, _ = init_gpt_params(jax.random.PRNGKey(3), cfg)
        rng = np.random.default_rng(7)
        prompts = [rng.integers(0, 128, n).astype(np.int32)
                   for n in (5, 9)]
        greedy = SamplingParams(greedy=True)

        def make(max_batch):
            return DynamicInferenceEngine(
                params, cfg, max_batch=max_batch, max_seq_len=48,
                prefill_buckets=(16,), paged=True, block_size=8)

        def run(eng):
            ids = [eng.add_request(p, 5, greedy) for p in prompts]
            res = eng.run_to_completion()
            return [res[r].tolist() for r in ids]

        paged = make(2)
        a = run(paged)
        assert a == run(paged)             # engine fully resets between runs
        assert a == run(make(1))           # batch-composition independent
        assert a == run(make(2))           # a fresh engine
        assert a == [_greedy_oracle(params, cfg, p, 5) for p in prompts]
        # Same prompt+seed but different request ids → distinct sampled
        # streams (an inequality — robust to logit jitter).
        sampling = SamplingParams(temperature=0.8, top_k=20, seed=123)
        i1 = paged.add_request(prompts[0], 5, sampling)
        i2 = paged.add_request(prompts[0], 5, sampling)
        res = paged.run_to_completion()
        assert res[i1].tolist() != res[i2].tolist()


class TestAbortRecovery:
    def test_abort_all_reclaims_pool(self):
        """Server error recovery (driver stepper exception path): every
        block returns to the pool and fresh admissions still work —
        clearing slots without releasing would trip the
        slot-still-holds-blocks assert and leak capacity forever."""
        cfg = _gqa_cfg()
        params, _ = init_gpt_params(jax.random.PRNGKey(3), cfg)
        rng = np.random.default_rng(8)
        eng = DynamicInferenceEngine(
            params, cfg, max_batch=2, max_seq_len=48,
            prefill_buckets=(16,), paged=True, block_size=8)
        for n in (9, 12, 5):
            eng.add_request(rng.integers(0, 128, n).astype(np.int32), 6,
                            SamplingParams(greedy=True))
        eng.step()                       # two running, one queued
        assert eng.pool.blocks_in_use() > 0
        eng.abort_all()
        assert not eng.has_work
        assert eng.pool.blocks_in_use() == 0
        assert not eng.requests
        # The pool is healthy: a fresh request admits and completes.
        p = rng.integers(0, 128, 7).astype(np.int32)
        rid = eng.add_request(p, 3, SamplingParams(greedy=True))
        res = eng.run_to_completion()
        assert res[rid].tolist() == _greedy_oracle(params, cfg, p, 3)


class TestGuards:
    def test_empty_prompt_rejected(self):
        cfg = _gqa_cfg()
        params, _ = init_gpt_params(jax.random.PRNGKey(3), cfg)
        eng = DynamicInferenceEngine(params, cfg, max_batch=1,
                                     max_seq_len=32)
        with pytest.raises(ValueError, match="empty prompt"):
            eng.add_request(np.asarray([], np.int32), 4)

    def test_request_larger_than_pool_rejected(self):
        cfg = _gqa_cfg()
        params, _ = init_gpt_params(jax.random.PRNGKey(3), cfg)
        eng = DynamicInferenceEngine(
            params, cfg, max_batch=1, max_seq_len=64, paged=True,
            block_size=8, num_blocks=2)
        with pytest.raises(ValueError, match="blocks"):
            eng.add_request(np.arange(20, dtype=np.int32), 10)

    def test_too_long_rejected(self):
        cfg = _gqa_cfg()
        params, _ = init_gpt_params(jax.random.PRNGKey(3), cfg)
        eng = DynamicInferenceEngine(params, cfg, max_batch=1,
                                     max_seq_len=16, paged=True,
                                     block_size=8)
        with pytest.raises(ValueError, match="max_seq_len"):
            eng.add_request(np.arange(12, dtype=np.int32), 8)


class TestMegaScopeCompat:
    def test_reset_compilation_rebuilds_paged_jits(self):
        """Hook toggles re-trace the PAGED jits too: captures appear
        after activate+reset and stop after deactivate+reset (stale
        traces would keep streaming or never stream)."""
        from megatronapp_tpu.scope.tensor_tracer import get_tensor_tracer
        cfg = _gqa_cfg()
        params, _ = init_gpt_params(jax.random.PRNGKey(3), cfg)
        eng = DynamicInferenceEngine(
            params, cfg, max_batch=1, max_seq_len=32,
            prefill_buckets=(16,), paged=True, block_size=8)
        rid = eng.add_request(np.arange(5, dtype=np.int32), 6,
                              SamplingParams(greedy=True))
        eng.step()                       # admit + compile hook-free jits
        old_decode = eng._decode
        captured = []
        tt = get_tensor_tracer()
        tt.set_flags_from_config({"QKV_mat_mul": [0]})
        tt.activate(lambda site, lid, arr: captured.append((site, lid)),
                    pixels=4)
        try:
            eng.reset_compilation()
            assert eng._decode is not old_decode
            eng.step()
            jax.effects_barrier()
            assert any(site == "qkv_q" for site, _ in captured)
        finally:
            tt.deactivate()
            tt.clear_records()
        eng.reset_compilation()
        captured.clear()
        while eng.has_work:
            eng.step()
        jax.effects_barrier()
        assert not captured              # hooks really off after reset


class TestServerContinuousBatching:
    def test_driver_batches_concurrent_requests(self):
        """Two submissions from different 'connections' decode in the
        SAME batch (driver max_active == 2) and both complete with
        oracle-exact streams."""
        import time

        from megatronapp_tpu.data.tokenizers import NullTokenizer
        from megatronapp_tpu.inference.server import DynamicBatchingDriver
        cfg = _gqa_cfg()
        params, _ = init_gpt_params(jax.random.PRNGKey(3), cfg)
        eng = DynamicInferenceEngine(
            params, cfg, tokenizer=NullTokenizer(128), max_batch=2,
            max_seq_len=48, prefill_buckets=(16,), paged=True,
            block_size=8)
        driver = DynamicBatchingDriver(eng)
        streams = {1: [], 2: []}
        p1 = np.asarray([1, 2, 3], np.int32)
        p2 = np.asarray([4, 5, 6, 7], np.int32)
        r1, d1 = driver.submit(p1, 6, SamplingParams(greedy=True),
                               token_cb=lambda r, t: streams[1].append(t))
        r2, d2 = driver.submit(p2, 6, SamplingParams(greedy=True),
                               token_cb=lambda r, t: streams[2].append(t))
        assert d1.wait(timeout=120) and d2.wait(timeout=120)
        time.sleep(0.05)                 # let the last dispatch land
        assert driver.max_active == 2    # truly batched, not serialized
        t1 = driver.result_tokens(r1)
        t2 = driver.result_tokens(r2)
        assert t1.tolist() == _greedy_oracle(params, cfg, p1, 6)
        assert t2.tolist() == _greedy_oracle(params, cfg, p2, 6)
        assert streams[1] == t1[len(p1):].tolist()
        assert streams[2] == t2[len(p2):].tolist()

    def test_rest_api_on_paged_dynamic_engine(self):
        """PUT /api served by the continuous-batching driver (multi-
        prompt request batches through one engine)."""
        import asyncio

        from aiohttp.test_utils import TestClient
        from aiohttp.test_utils import TestServer as ATestServer

        from megatronapp_tpu.data.tokenizers import NullTokenizer
        from megatronapp_tpu.inference.server import TextGenerationServer
        cfg = _gqa_cfg()
        params, _ = init_gpt_params(jax.random.PRNGKey(3), cfg)
        eng = DynamicInferenceEngine(
            params, cfg, tokenizer=NullTokenizer(128), max_batch=2,
            max_seq_len=48, prefill_buckets=(16,), paged=True,
            block_size=8)
        srv = TextGenerationServer(eng)
        assert srv._driver is not None

        async def run():
            client = TestClient(ATestServer(srv.build_app()))
            await client.start_server()
            resp = await client.put("/api", json={
                "prompts": ["1 2 3", "4 5"], "tokens_to_generate": 3,
                "greedy": True})
            assert resp.status == 200
            data = await resp.json()
            assert len(data["text"]) == 2
            assert data["text"][0].startswith("1 2 3")
            assert data["text"][1].startswith("4 5")
            await client.close()

        asyncio.run(run())


class TestWsOnDynamicEngine:
    def test_ws_streams_through_driver_and_viz_errors(self):
        """WS on --engine dynamic: tokens stream per step through the
        shared stepper, done carries the text, and a visualization
        request gets a clean error frame (viz needs the static engine)."""
        import asyncio

        from aiohttp.test_utils import TestClient
        from aiohttp.test_utils import TestServer as ATestServer

        from megatronapp_tpu.data.tokenizers import NullTokenizer
        from megatronapp_tpu.inference.server import TextGenerationServer
        cfg = _gqa_cfg()
        params, _ = init_gpt_params(jax.random.PRNGKey(3), cfg)
        eng = DynamicInferenceEngine(
            params, cfg, tokenizer=NullTokenizer(128), max_batch=2,
            max_seq_len=48, prefill_buckets=(16,), paged=True,
            block_size=8)
        srv = TextGenerationServer(eng)

        async def run():
            client = TestClient(ATestServer(srv.build_app()))
            await client.start_server()
            ws = await client.ws_connect("/ws")
            await ws.send_json({"prompt": "1 2 3",
                                "tokens_to_generate": 3, "greedy": True})
            tokens, done = [], None
            while True:
                msg = await ws.receive_json(timeout=120)
                if msg["type"] == "token":
                    tokens.append(msg)
                elif msg["type"] == "done":
                    done = msg
                    break
            assert len(tokens) == 3
            assert [t["step"] for t in tokens] == [0, 1, 2]
            assert done["text"]
            await ws.send_json({"prompt": "1", "tokens_to_generate": 1,
                                "visualization": {"MLP1": [0]}})
            msg = await ws.receive_json(timeout=60)
            assert msg["type"] == "error"
            assert "static" in msg["message"]
            # The connection survives the error frame.
            await ws.send_json({"prompt": "2 3",
                                "tokens_to_generate": 1, "greedy": True})
            while True:
                msg = await ws.receive_json(timeout=120)
                if msg["type"] == "done":
                    break
            await ws.close()
            await client.close()

        asyncio.run(run())
