"""Speculative decoding subsystem tests (ISSUE 4).

Covers the four layers: the multi-query ragged paged-attention kernel
(parity <= 1e-5 vs the jnp reference incl. GQA and ragged q_len mixes,
bitwise-equal to the single-query decode kernel at q_len == 1), the
exact rejection-sampling verifier (Monte-Carlo distribution
preservation for point-mass and full-q proposals; adversarial drafts
rejected without corrupting greedy streams), the proposer
implementations (n-gram lookup, MTP self-draft, draft model with
catch-up), and the engine integration (greedy bit-identity to plain
decode for all three proposers at K in {1, 2, 4}, sampled
reproducibility, chunked-prefill trace counting, preemption+rollback
refcount audits, the server's GET /stats endpoint, and the tier-1
2-round speculate+verify smoke)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from megatronapp_tpu.config.transformer_config import TransformerConfig
from megatronapp_tpu.inference.dynamic_engine import DynamicInferenceEngine
from megatronapp_tpu.inference.engine import SamplingParams
from megatronapp_tpu.models.gpt import init_gpt_params


def _cfg(mtp=False):
    return TransformerConfig(
        num_layers=2, hidden_size=64, num_attention_heads=4,
        num_query_groups=2, vocab_size=128, max_position_embeddings=96,
        compute_dtype=jnp.float32, remat_policy="none",
        mtp_num_layers=(2 if mtp else None))


@pytest.fixture(scope="module")
def model():
    cfg = _cfg(mtp=True)
    params, _ = init_gpt_params(jax.random.PRNGKey(7), cfg)
    return params, cfg


from jitted import greedy_oracle as _greedy_oracle  # noqa: E402


def _prompts(n=4):
    rng = np.random.default_rng(0)
    return [rng.integers(0, 128, ln).astype(np.int32)
            for ln in (5, 9, 13, 3)][:n]


def _run_engine(params, cfg, prompts, max_new=6, spec=None, k=4,
                sampling=None, audit=False, **kw):
    eng = DynamicInferenceEngine(
        params, cfg, max_batch=2, max_seq_len=64,
        prefill_buckets=(16, 32), paged=True, block_size=8,
        spec_method=spec, spec_k=k, prefill_chunk=8, **kw)
    ids = [eng.add_request(p, max_new,
                           sampling or SamplingParams(greedy=True))
           for p in prompts]
    if audit:
        while eng.has_work:
            eng.step()
            eng.pool.audit()
        res = {r.request_id: r for r in eng.requests.values()}
        return [res[i].tokens.tolist() for i in ids], eng
    res = eng.run_to_completion()
    eng.pool.audit()
    return [res[i].tolist() for i in ids], eng


class TestMultiQueryKernel:
    @pytest.mark.parametrize("hq,hkv,d,bs", [(4, 2, 16, 4), (8, 8, 8, 8),
                                             (6, 2, 32, 16), (4, 1, 8, 4)])
    def test_matches_reference_ragged(self, hq, hkv, d, bs):
        """Multi-query kernel == jnp reference to <= 1e-5 across GQA
        groupings with a RAGGED q_len mix in one batch."""
        from megatronapp_tpu.ops.pallas.paged_attention import (
            paged_attention_multiquery, paged_attention_multiquery_reference,
        )
        b, mb, sq = 3, 4, 5
        nb = b * mb
        rng = np.random.default_rng(hq * 100 + bs)
        q = jnp.asarray(rng.normal(size=(b, sq, hq, d)), jnp.float32)
        kp = jnp.asarray(rng.normal(size=(nb, bs, hkv, d)), jnp.float32)
        vp = jnp.asarray(rng.normal(size=(nb, bs, hkv, d)), jnp.float32)
        table = jnp.asarray(rng.permutation(nb).reshape(b, mb), jnp.int32)
        q_lens = jnp.asarray([1, 3, sq], jnp.int32)
        kv_lens = jnp.maximum(jnp.asarray([2, bs + 2, mb * bs], jnp.int32),
                              q_lens)
        out = paged_attention_multiquery(q, kp, vp, table, kv_lens, q_lens)
        ref = paged_attention_multiquery_reference(q, kp, vp, table,
                                                   kv_lens, q_lens)
        for i in range(b):
            ql = int(q_lens[i])
            np.testing.assert_allclose(
                np.asarray(out[i, :ql]), np.asarray(ref[i, :ql]),
                atol=1e-5, rtol=1e-5)

    def test_qlen1_bitwise_matches_decode_kernel(self):
        """At q_len == 1 the multi-query kernel reduces to the decode
        kernel's exact block/accumulator order — bitwise equal, which is
        what keeps speculative engines' plain rows on the same stream as
        non-speculative engines."""
        from megatronapp_tpu.ops.pallas.paged_attention import (
            paged_attention_decode, paged_attention_multiquery,
        )
        b, hq, hkv, d, bs, mb = 3, 4, 2, 16, 4, 4
        nb = b * mb
        rng = np.random.default_rng(0)
        q = jnp.asarray(rng.normal(size=(b, 1, hq, d)), jnp.float32)
        kp = jnp.asarray(rng.normal(size=(nb, bs, hkv, d)), jnp.float32)
        vp = jnp.asarray(rng.normal(size=(nb, bs, hkv, d)), jnp.float32)
        table = jnp.asarray(rng.permutation(nb).reshape(b, mb), jnp.int32)
        lens = jnp.asarray([1, bs + 1, mb * bs], jnp.int32)
        out = paged_attention_multiquery(q, kp, vp, table, lens,
                                         jnp.ones((b,), jnp.int32))
        dec = paged_attention_decode(q[:, 0], kp, vp, table, lens)
        np.testing.assert_array_equal(np.asarray(out[:, 0]),
                                      np.asarray(dec))

    def test_append_chunk_matches_token_append(self):
        """append_chunk_pages at counts == 1 == append_token_pages, and a
        ragged chunk lands each row at starts[b] + i with padding/
        inactive rows dropped."""
        from megatronapp_tpu.ops.pallas.paged_attention import (
            append_chunk_pages, append_token_pages,
        )
        rng = np.random.default_rng(1)
        pages = jnp.asarray(rng.normal(size=(6, 4, 2, 8)), jnp.float32)
        tbl = jnp.asarray(rng.permutation(6).reshape(3, 2), jnp.int32)
        starts = jnp.asarray([0, 3, 5], jnp.int32)
        act = jnp.asarray([True, True, False])
        vals1 = jnp.asarray(rng.normal(size=(3, 1, 2, 8)), jnp.float32)
        a1 = append_chunk_pages(pages, vals1, tbl, starts,
                                jnp.ones(3, jnp.int32), act)
        a2 = append_token_pages(pages, vals1[:, 0], tbl, starts, act)
        np.testing.assert_array_equal(np.asarray(a1), np.asarray(a2))
        # Ragged: row 0 writes 3 rows from pos 0, row 1 writes 1 row at
        # pos 3, row 2 inactive.
        vals = jnp.asarray(rng.normal(size=(3, 3, 2, 8)), jnp.float32)
        out = np.asarray(append_chunk_pages(
            pages, vals, tbl, starts, jnp.asarray([3, 1, 3], jnp.int32),
            act))
        t = np.asarray(tbl)
        for i in range(3):
            np.testing.assert_array_equal(out[t[0, 0], i],
                                          np.asarray(vals[0, i]))
        np.testing.assert_array_equal(out[t[1, 0], 3],
                                      np.asarray(vals[1, 0]))
        # Row 1's positions 4.. and row 2 entirely: untouched.
        np.testing.assert_array_equal(out[t[2, 1]],
                                      np.asarray(pages[t[2, 1]]))


class TestVerifierMath:
    def _sample_first(self, point_mass, n=12000):
        """Empirical distribution of a round's FIRST emitted token.
        Trials ride the batch dimension (distinct request ids → distinct
        key chains), so the whole Monte-Carlo run is ONE verifier call."""
        from megatronapp_tpu.inference.speculative import (
            build_verify_sampler,
        )
        rng = np.random.default_rng(0)
        v, k = 8, 2
        logits1 = rng.normal(size=(1, k + 1, v)).astype(np.float32)
        logits = jnp.asarray(np.broadcast_to(logits1, (n, k + 1, v)))
        ql = rng.normal(size=(k, v)).astype(np.float32)
        q1 = np.exp(ql) / np.exp(ql).sum(-1, keepdims=True)
        q_probs = jnp.asarray(np.broadcast_to(q1[None], (n, k, v)))
        if point_mass:
            d = rng.integers(0, v, (n, k)).astype(np.int32)
        else:
            # Proposer contract: drafts are sampled from q.
            u = rng.random((n, k))
            d = np.minimum((u[..., None] > np.cumsum(q1, -1)[None])
                           .sum(-1), v - 1).astype(np.int32)
        fn = build_verify_sampler(point_mass=point_mass)
        ones = jnp.zeros((n,), jnp.int32)
        a, out = fn(logits, jnp.asarray(d),
                    jnp.full((n,), k + 1, jnp.int32),
                    None if point_mass else q_probs,
                    ones, jnp.arange(n, dtype=jnp.int32), ones,
                    jnp.full((n,), 0.9, jnp.float32), ones,
                    jnp.zeros((n,), jnp.float32), jnp.zeros((n,), bool))
        a = np.asarray(a)
        out = np.asarray(out)
        first = np.where(a >= 1, d[:, 0], out)
        counts = np.bincount(first, minlength=v).astype(np.float64)
        p = np.asarray(jax.nn.softmax(jnp.asarray(logits1[0, 0]) / 0.9))
        return counts / counts.sum(), p

    @pytest.mark.parametrize("point_mass", [True, False])
    def test_first_token_distribution_preserved(self, point_mass):
        """Rejection sampling is EXACT: the emitted token's distribution
        equals the warped target p regardless of the proposal (total
        variation within Monte-Carlo noise)."""
        emp, p = self._sample_first(point_mass)
        tv = 0.5 * np.abs(emp - p).sum()
        assert tv < 0.03, (tv, emp, p)

    def test_greedy_rows_accept_by_argmax(self):
        from megatronapp_tpu.inference.speculative import (
            build_verify_sampler,
        )
        rng = np.random.default_rng(3)
        v, k = 16, 3
        logits = jnp.asarray(rng.normal(size=(1, k + 1, v)), jnp.float32)
        am = np.asarray(jnp.argmax(logits[0], axis=-1))
        fn = build_verify_sampler(point_mass=True)
        # Drafts follow the argmax chain for 2 positions then diverge.
        d = np.asarray([am[0], am[1], (am[2] + 1) % v], np.int32)
        a, out = fn(logits, jnp.asarray(d[None]),
                    jnp.asarray([k + 1], jnp.int32), None,
                    jnp.asarray([0], jnp.int32), jnp.asarray([0], jnp.int32),
                    jnp.asarray([0], jnp.int32),
                    jnp.asarray([1.0], jnp.float32),
                    jnp.asarray([0], jnp.int32),
                    jnp.asarray([0.0], jnp.float32), jnp.asarray([True]))
        assert int(a[0]) == 2
        assert int(out[0]) == am[2]   # correction = argmax at the break


class TestNGramLookup:
    def test_prompt_lookup_continuation(self):
        from megatronapp_tpu.inference.speculative import _ngram_lookup
        t = np.asarray([5, 6, 7, 8, 1, 2, 5, 6, 7], np.int32)
        # Suffix [5,6,7] matched at position 0 → continuation [8, 1, ...]
        np.testing.assert_array_equal(_ngram_lookup(t, 2, 3, 1), [8, 1])

    def test_no_match_proposes_nothing(self):
        from megatronapp_tpu.inference.speculative import _ngram_lookup
        t = np.asarray([1, 2, 3, 4, 5], np.int32)
        assert len(_ngram_lookup(t, 4, 3, 2)) == 0


class TestGreedyBitIdentity:
    """Acceptance criterion: all three proposers, K in {1, 2, 4},
    bit-identical greedy streams vs non-speculative paged decode."""

    @pytest.fixture(scope="class")
    def baseline(self, model):
        params, cfg = model
        prompts = _prompts()
        plain, _ = _run_engine(params, cfg, prompts, max_new=6)
        for p, out in zip(prompts, plain):
            assert out == _greedy_oracle(params, cfg, p, 6)
        return prompts, plain

    @pytest.mark.parametrize("method", ["ngram", "mtp", "draft"])
    @pytest.mark.parametrize("k", [1, 2, 4])
    def test_bit_identical(self, model, baseline, method, k):
        params, cfg = model
        prompts, plain = baseline
        kw = {}
        if method == "draft":
            # The target doubles as its own draft: exercises the full
            # catch-up/q machinery with high acceptance.
            kw = dict(draft_params=params, draft_cfg=cfg)
        spec, eng = _run_engine(params, cfg, prompts, max_new=6,
                                spec=method, k=k, **kw)
        assert spec == plain
        assert eng.spec_stats["rounds"] > 0


class TestMLASpeculation:
    def test_mla_ngram_bit_identical(self):
        """The multi-token verify path also covers MLA (chunked latent
        append + per-(query, kv) mask over the gathered run) — greedy
        streams stay bit-identical and oracle-exact."""
        cfg = TransformerConfig(
            num_layers=2, hidden_size=64, num_attention_heads=4,
            vocab_size=128, max_position_embeddings=64,
            multi_latent_attention=True, kv_lora_rank=32, qk_head_dim=16,
            qk_pos_emb_head_dim=8, v_head_dim=16,
            compute_dtype=jnp.float32, remat_policy="none")
        params, _ = init_gpt_params(jax.random.PRNGKey(7), cfg)
        rng = np.random.default_rng(1)
        prompts = [rng.integers(0, 128, n).astype(np.int32)
                   for n in (5, 9, 3)]

        def run(spec):
            eng = DynamicInferenceEngine(
                params, cfg, max_batch=2, max_seq_len=48,
                prefill_buckets=(16,), paged=True, block_size=8,
                spec_method=spec, spec_k=3)
            ids = [eng.add_request(p, 5, SamplingParams(greedy=True))
                   for p in prompts]
            res = eng.run_to_completion()
            eng.pool.audit()
            return [res[r].tolist() for r in ids]

        plain = run(None)
        assert run("ngram") == plain
        for p, out in zip(prompts, plain):
            assert out == _greedy_oracle(params, cfg, p, 5)


class TestSampledSpeculation:
    def test_reproducible_and_batch_independent(self, model):
        params, cfg = model
        prompts = _prompts(2)
        sampling = SamplingParams(temperature=0.8, top_k=20, seed=123)

        def run(spec, max_batch):
            eng = DynamicInferenceEngine(
                params, cfg, max_batch=max_batch, max_seq_len=64,
                prefill_buckets=(16,), paged=True, block_size=8,
                spec_method=spec, spec_k=2, prefill_chunk=8)
            ids = [eng.add_request(p, 5, sampling) for p in prompts]
            res = eng.run_to_completion()
            return [res[r].tolist() for r in ids]

        a = run("ngram", 2)
        assert a == run("ngram", 2)     # reproducible
        assert a == run("ngram", 1)     # batch-composition independent

    def test_same_prompt_distinct_streams(self, model):
        params, cfg = model
        prompt = _prompts(1)[0]
        sampling = SamplingParams(temperature=0.8, top_k=20, seed=123)
        eng = DynamicInferenceEngine(
            params, cfg, max_batch=2, max_seq_len=64,
            prefill_buckets=(16,), paged=True, block_size=8,
            spec_method="ngram", spec_k=2, prefill_chunk=8)
        i1 = eng.add_request(prompt, 5, sampling)
        i2 = eng.add_request(prompt, 5, sampling)
        res = eng.run_to_completion()
        assert res[i1].tolist() != res[i2].tolist()


class TestChunkedPrefill:
    def test_one_trace_across_length_and_cache_combinations(self, model):
        """The ROADMAP follow-up: prefill used to retrace per
        (bucket, cached-length) pair; the chunked path traces the
        multi-query step ONCE per chunk shape no matter how prompt
        lengths and prefix-cache hits vary."""
        params, cfg = model
        rng = np.random.default_rng(4)
        shared = rng.integers(0, 128, 16).astype(np.int32)
        prompts = [
            rng.integers(0, 128, 5).astype(np.int32),        # short
            rng.integers(0, 128, 23).astype(np.int32),       # multi-chunk
            np.concatenate([shared,
                            rng.integers(0, 128, 3).astype(np.int32)]),
            np.concatenate([shared,
                            rng.integers(0, 128, 7).astype(np.int32)]),
            shared.copy(),                                    # full hit/CoW
        ]
        eng = DynamicInferenceEngine(
            params, cfg, max_batch=2, max_seq_len=64,
            prefill_buckets=(16, 32), paged=True, block_size=8,
            prefill_chunk=8)
        ids = [eng.add_request(p, 3, SamplingParams(greedy=True))
               for p in prompts]
        res = eng.run_to_completion()
        # One prefill trace ([1, chunk]) + one decode shape at most —
        # the engine never retraced per (length, cached) combination.
        assert eng.mq_traces == 1, eng.mq_traces
        assert eng.pool.stats["prefix_hit_tokens"] > 0   # hits still work
        for p, rid in zip(prompts, ids):
            assert res[rid].tolist() == _greedy_oracle(params, cfg, p, 3)

    def test_spec_engine_two_shapes_total(self, model):
        """A speculative engine adds exactly one more shape (the
        [max_batch, K+1] verify step) — not one per workload mix."""
        params, cfg = model
        prompts = _prompts()
        _, eng = _run_engine(params, cfg, prompts, max_new=6,
                             spec="ngram", k=4)
        assert eng.mq_traces == 2, eng.mq_traces


class TestRollbackAndAudit:
    def test_preempt_midblock_resume_with_spec_no_leak(self, model):
        """Satellite regression: preempting a request mid-block and
        resuming WITH speculation enabled never double-frees or leaks
        the tail block — the pool audit (refcounts == slot references,
        free/LRU/held partition exact) runs after EVERY step."""
        params, cfg = model
        rng = np.random.default_rng(5)
        p1 = rng.integers(0, 128, 12).astype(np.int32)
        p2 = rng.integers(0, 128, 14).astype(np.int32)
        eng = DynamicInferenceEngine(
            params, cfg, max_batch=2, max_seq_len=64,
            prefill_buckets=(32,), paged=True, block_size=8,
            num_blocks=5,     # both fit to start, not to finish
            spec_method="ngram", spec_k=4, prefill_chunk=8)
        r1 = eng.add_request(p1, 10, SamplingParams(greedy=True))
        r2 = eng.add_request(p2, 10, SamplingParams(greedy=True))
        while eng.has_work:
            eng.step()
            eng.pool.audit()
        assert eng.pool.stats["preemptions"] >= 1
        res = {r1: eng.requests[r1].tokens, r2: eng.requests[r2].tokens}
        assert res[r1].tolist() == _greedy_oracle(params, cfg, p1, 10)
        assert res[r2].tolist() == _greedy_oracle(params, cfg, p2, 10)
        # Everything retired: zero blocks held.
        eng.pool.audit()
        assert eng.pool.blocks_in_use() == 0

    def test_rewind_releases_only_private_tail(self, model):
        """Direct rewind semantics: over-granted speculative blocks go
        back to the pool; shared prefix blocks are untouchable."""
        from megatronapp_tpu.inference.paged_cache import PagedKVCache
        pool = PagedKVCache(_cfg(), 2, 32, num_blocks=8, block_size=4)
        toks = np.arange(10, dtype=np.int32)
        plan = pool.admit(0, toks)
        assert len(plan.blocks) == 3
        granted = pool.extend_capacity(0, 10, 4)   # spec tail
        assert granted == 4
        assert len(pool.slot_blocks(0)) == 4       # one extra block
        pool.rewind(0, 11)                          # accepted 1 of 4
        assert len(pool.slot_blocks(0)) == 3
        pool.audit()
        pool.rewind(0, 10)
        assert len(pool.slot_blocks(0)) == 3       # never splits a block
        pool.audit()


class TestStatsEndpoint:
    def test_stats_reports_pool_and_acceptance(self, model):
        import asyncio

        from aiohttp.test_utils import TestClient
        from aiohttp.test_utils import TestServer as ATestServer

        from megatronapp_tpu.data.tokenizers import NullTokenizer
        from megatronapp_tpu.inference.server import TextGenerationServer
        params, cfg = model
        eng = DynamicInferenceEngine(
            params, cfg, tokenizer=NullTokenizer(128), max_batch=2,
            max_seq_len=64, prefill_buckets=(16,), paged=True,
            block_size=8, spec_method="ngram", spec_k=2, prefill_chunk=8)
        srv = TextGenerationServer(eng)

        async def run():
            client = TestClient(ATestServer(srv.build_app()))
            await client.start_server()
            resp = await client.get("/stats")
            assert resp.status == 200
            before = await resp.json()
            assert before["engine"] == "dynamic" and before["paged"]
            assert before["speculative"]["method"] == "ngram"
            resp = await client.put("/api", json={
                "prompts": ["1 2 3 1 2 3 1 2"], "tokens_to_generate": 6,
                "greedy": True})
            assert resp.status == 200
            resp = await client.get("/stats")
            after = await resp.json()
            assert after["pool"]["prefill_tokens"] > 0
            assert after["speculative"]["rounds"] > 0
            assert 0.0 <= after["speculative"]["acceptance_rate"] <= 1.0
            assert after["speculative"]["tokens_per_step"] > 0
            assert after["driver_max_active"] >= 1
            await client.close()

        asyncio.run(run())

    def test_stats_on_static_engine(self, model):
        import asyncio

        from aiohttp.test_utils import TestClient
        from aiohttp.test_utils import TestServer as ATestServer

        from megatronapp_tpu.data.tokenizers import NullTokenizer
        from megatronapp_tpu.inference.engine import StaticInferenceEngine
        from megatronapp_tpu.inference.server import TextGenerationServer
        params, cfg = model
        srv = TextGenerationServer(StaticInferenceEngine(
            params, cfg, tokenizer=NullTokenizer(128), max_seq_len=64))

        async def run():
            client = TestClient(ATestServer(srv.build_app()))
            await client.start_server()
            resp = await client.get("/stats")
            assert resp.status == 200
            assert (await resp.json())["engine"] == "static"
            await client.close()

        asyncio.run(run())


class TestFallbacks:
    def test_mtp_without_heads_falls_back(self):
        cfg = _cfg(mtp=False)
        params, _ = init_gpt_params(jax.random.PRNGKey(7), cfg)
        with pytest.warns(UserWarning, match="falling back"):
            eng = DynamicInferenceEngine(
                params, cfg, max_batch=1, max_seq_len=64, paged=True,
                block_size=8, spec_method="mtp")
        assert eng.spec_method is None and eng.proposer is None
        rid = eng.add_request(np.arange(1, 6, dtype=np.int32), 3,
                              SamplingParams(greedy=True))
        res = eng.run_to_completion()
        assert res[rid].tolist() == _greedy_oracle(
            params, cfg, np.arange(1, 6, dtype=np.int32), 3)

    def test_draft_without_model_falls_back(self, model):
        params, cfg = model
        with pytest.warns(UserWarning, match="falling back"):
            eng = DynamicInferenceEngine(
                params, cfg, max_batch=1, max_seq_len=64, paged=True,
                block_size=8, spec_method="draft")
        assert eng.spec_method is None

    def test_draft_vocab_mismatch_rejected(self, model):
        params, cfg = model
        bad_cfg = TransformerConfig(
            num_layers=1, hidden_size=32, num_attention_heads=2,
            vocab_size=64, max_position_embeddings=96,
            compute_dtype=jnp.float32, remat_policy="none")
        bad_params, _ = init_gpt_params(jax.random.PRNGKey(0), bad_cfg)
        with pytest.raises(ValueError, match="vocab"):
            DynamicInferenceEngine(
                params, cfg, max_batch=1, max_seq_len=64, paged=True,
                block_size=8, spec_method="draft",
                draft_params=bad_params, draft_cfg=bad_cfg)


class TestTier1Smoke:
    def test_two_round_greedy_speculate_verify(self, model):
        """CI gate (satellite 6): import inference/speculative.py and run
        a 2-round greedy speculate+verify smoke — fast-lane only, must
        stay out of tests/slow_manifest.txt."""
        import megatronapp_tpu.inference.speculative  # noqa: F401
        params, cfg = model
        eng = DynamicInferenceEngine(
            params, cfg, max_batch=1, max_seq_len=64,
            prefill_buckets=(16,), paged=True, block_size=8,
            spec_method="ngram", spec_k=2, prefill_chunk=8)
        prompt = np.asarray([3, 4, 5, 3, 4, 5, 3], np.int32)
        rid = eng.add_request(prompt, 8, SamplingParams(greedy=True))
        eng.step()
        eng.step()
        assert eng.spec_stats["rounds"] >= 1
        res = eng.run_to_completion()
        assert res[rid].tolist() == _greedy_oracle(params, cfg, prompt, 8)
        assert eng.spec_stats["accepted"] > 0


class TestBenchmarkSmoke:
    def test_ngram_speedup_on_repetitive_workload(self):
        """Acceptance criterion: >= 1.2x tokens/step for the n-gram
        proposer on a repetitive-prompt CPU workload, with bit-identical
        greedy streams."""
        from tools.spec_decode_benchmark import run
        res = run(n_requests=2, motif_len=8, repeats=3, max_new=16,
                  spec_k=4)
        assert res["ngram"]["parity_ok"]
        assert res["ngram"]["speedup_tokens_per_step"] >= 1.2, res
        assert res["ngram"]["acceptance_rate"] > 0.5
        assert res["mtp"]["parity_ok"]
