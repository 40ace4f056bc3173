"""Quantized serving tests (ISSUE 10): int8 KV-cache pages end-to-end.

Covers the vertical slice layer by layer:

- kernel: quantized ragged decode / multi-query == the quantized jnp
  reference exactly (same dequant math), and within an explicit logits-
  style bound of the unquantized kernels on the same content — GQA,
  ragged lengths, and the tp2 head-sharded placement included;
- pool: int8 pages + per-(row, head) fp32 scales — byte accounting off
  the addressable arrays ((D+4)/2D of bf16), CoW copies scales, audit
  clean through prefix-hit / CoW / preempt-resume round-trips;
- engine: greedy streams on the int8 pool match the bf16-pool streams
  and the dense oracle on the tiny model; dtype-aware /stats fields;
- spec decode: exactness vs plain decode holds ON the int8 pool and the
  acceptance-rate delta vs the bf16 pool is gated (<= 0.05);
- disagg: the prefill→decode handoff ships int8 rows + scales (bytes
  halved vs the same-compute-dtype baseline) with streams identical to
  the colocated int8 engine;
- weights: residentized int8 params are bit-identical to
  dequantize-on-load at matmul entry;
- bench: tools/kv_quant_benchmark.py smoke gate (the tier-1 pin for the
  bench.py extra.kv_quant record): memory ratio <= 0.55, logits bound,
  acceptance delta.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from megatronapp_tpu.config.transformer_config import TransformerConfig
from megatronapp_tpu.inference.dynamic_engine import DynamicInferenceEngine
from megatronapp_tpu.inference.engine import SamplingParams
from megatronapp_tpu.inference.paged_cache import PagedKVCache, cdiv
from megatronapp_tpu.models.gpt import gpt_forward, init_gpt_params
from megatronapp_tpu.ops.pallas.paged_attention import (
    dequantize_pages, paged_attention_decode, paged_attention_multiquery,
    paged_attention_multiquery_reference, paged_attention_reference,
    quantize_kv_rows,
)


def _gqa_cfg():
    return TransformerConfig(
        num_layers=2, hidden_size=64, num_attention_heads=4,
        num_query_groups=2, vocab_size=128, max_position_embeddings=64,
        compute_dtype=jnp.float32, remat_policy="none")


from jitted import greedy_oracle as _greedy_oracle  # noqa: E402


class TestQuantizedKernels:
    @pytest.mark.parametrize("hq,hkv,d,bs", [(4, 2, 16, 4), (8, 8, 8, 8),
                                             (6, 2, 32, 16)])
    def test_decode_matches_quantized_reference(self, hq, hkv, d, bs):
        """In-kernel dequant == dense-dequant jnp reference to fp32
        epsilon across GQA groupings and ragged lengths."""
        b, mb = 3, 4
        nb = b * mb
        rng = np.random.default_rng(hq * 100 + bs)
        q = jnp.asarray(rng.normal(size=(b, hq, d)), jnp.float32)
        kp = jnp.asarray(rng.normal(size=(nb, bs, hkv, d)), jnp.float32)
        vp = jnp.asarray(rng.normal(size=(nb, bs, hkv, d)), jnp.float32)
        kq, ks = quantize_kv_rows(kp)
        vq, vs = quantize_kv_rows(vp)
        assert kq.dtype == jnp.int8 and ks.shape == (nb, bs, hkv)
        table = jnp.asarray(
            rng.permutation(nb).reshape(b, mb), jnp.int32)
        lens = jnp.asarray([1, bs + 1, mb * bs], jnp.int32)
        out = paged_attention_decode(q, kq, vq, table, lens,
                                     k_scales=ks, v_scales=vs)
        ref = paged_attention_reference(q, kq, vq, table, lens,
                                        k_scales=ks, v_scales=vs)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5, rtol=1e-5)

    def test_decode_quantization_error_bounded(self):
        """Quantized vs UNQUANTIZED kernel on the same content: the
        attention-out error from per-row int8 stays within an explicit
        bound (the kernel-level half of the accuracy gate)."""
        b, hq, hkv, d, bs, mb = 2, 4, 2, 32, 8, 3
        nb = b * mb
        rng = np.random.default_rng(0)
        q = jnp.asarray(rng.normal(size=(b, hq, d)), jnp.float32)
        kp = jnp.asarray(rng.normal(size=(nb, bs, hkv, d)), jnp.float32)
        vp = jnp.asarray(rng.normal(size=(nb, bs, hkv, d)), jnp.float32)
        kq, ks = quantize_kv_rows(kp)
        vq, vs = quantize_kv_rows(vp)
        # Round-trip bound: |deq - orig| <= scale/2 per element.
        back = dequantize_pages(kq, ks)
        assert float(jnp.max(jnp.abs(back - kp))) <= float(
            jnp.max(ks)) / 2 + 1e-6
        table = jnp.asarray(rng.permutation(nb).reshape(b, mb), jnp.int32)
        lens = jnp.asarray([7, mb * bs], jnp.int32)
        out_q = paged_attention_decode(q, kq, vq, table, lens,
                                       k_scales=ks, v_scales=vs)
        out_f = paged_attention_decode(q, kp, vp, table, lens)
        err = float(jnp.max(jnp.abs(out_q - out_f)))
        assert err <= 0.05, err

    def test_multiquery_matches_quantized_reference(self):
        """Ragged multi-query (spec verify / chunked prefill) quantized
        path == its jnp reference on the valid rows."""
        b, s_q, hq, hkv, d, bs, mb = 3, 3, 4, 2, 16, 4, 4
        nb = b * mb
        rng = np.random.default_rng(1)
        q = jnp.asarray(rng.normal(size=(b, s_q, hq, d)), jnp.float32)
        kp = jnp.asarray(rng.normal(size=(nb, bs, hkv, d)), jnp.float32)
        vp = jnp.asarray(rng.normal(size=(nb, bs, hkv, d)), jnp.float32)
        kq, ks = quantize_kv_rows(kp)
        vq, vs = quantize_kv_rows(vp)
        table = jnp.asarray(rng.permutation(nb).reshape(b, mb), jnp.int32)
        kv_lens = jnp.asarray([3, bs + 2, mb * bs], jnp.int32)
        q_lens = jnp.asarray([1, 2, 3], jnp.int32)
        out = paged_attention_multiquery(q, kq, vq, table, kv_lens,
                                         q_lens, k_scales=ks, v_scales=vs)
        ref = paged_attention_multiquery_reference(
            q, kq, vq, table, kv_lens, q_lens, k_scales=ks, v_scales=vs)
        for i in range(b):
            n = int(q_lens[i])
            np.testing.assert_allclose(np.asarray(out[i, :n]),
                                       np.asarray(ref[i, :n]),
                                       atol=1e-5, rtol=1e-5)

    def test_tp2_quantized_decode_matches_single_device(self, devices8):
        """Head-sharded quantized decode (scale pools sharded on Hkv
        alongside the int8 pools) == the single-device quantized kernel
        to fp32 epsilon (same tolerance as the bf16-pool tp parity
        pins; the engine-level tp2 test below holds the streams
        bit-identical)."""
        from megatronapp_tpu.config.parallel_config import ParallelConfig
        from megatronapp_tpu.ops.pallas.paged_attention import (
            paged_attention_decode_tp,
        )
        from megatronapp_tpu.parallel.mesh import build_mesh
        ctx = build_mesh(ParallelConfig(tensor_parallel=2),
                         devices=devices8[:2])
        b, hq, hkv, d, bs, mb = 2, 4, 2, 16, 4, 3
        nb = b * mb
        rng = np.random.default_rng(2)
        q = jnp.asarray(rng.normal(size=(b, hq, d)), jnp.float32)
        kp = jnp.asarray(rng.normal(size=(nb, bs, hkv, d)), jnp.float32)
        vp = jnp.asarray(rng.normal(size=(nb, bs, hkv, d)), jnp.float32)
        kq, ks = quantize_kv_rows(kp)
        vq, vs = quantize_kv_rows(vp)
        table = jnp.asarray(rng.permutation(nb).reshape(b, mb), jnp.int32)
        lens = jnp.asarray([5, mb * bs], jnp.int32)
        single = paged_attention_decode(q, kq, vq, table, lens,
                                        k_scales=ks, v_scales=vs)
        sharded = paged_attention_decode_tp(
            q, kq, vq, table, lens, ctx.shard_map_mesh,
            k_scales=ks, v_scales=vs)
        np.testing.assert_allclose(np.asarray(single),
                                   np.asarray(sharded),
                                   atol=1e-5, rtol=1e-5)


class TestQuantizedPool:
    def test_pool_bytes_off_addressable_arrays(self):
        """Byte accounting is dtype-aware and read off the actual
        arrays: int8 data + fp32 scales = (D+4)/(cD) of a compute-dtype
        pool (c = baseline itemsize)."""
        cfg = _gqa_cfg()
        base = PagedKVCache(cfg, 2, 32, num_blocks=8, block_size=4)
        i8 = PagedKVCache(cfg, 2, 32, num_blocks=8, block_size=4,
                          kv_cache_dtype="int8")
        d = cfg.head_dim
        itemsize = base.pages[0].dtype.itemsize
        expect = (d + 4) / (itemsize * d)
        assert i8.pages[0].dtype == jnp.int8
        assert i8.scales[0].dtype == jnp.float32
        ratio = i8.bytes_total / base.bytes_total
        assert abs(ratio - expect) < 1e-6, (ratio, expect)
        assert i8.bytes_per_block * i8.num_blocks == i8.bytes_total

    def test_int8_mla_latent_pool(self):
        """MLA pools quantize since ISSUE 17: int8 latent/pe pools with
        per-row SCALAR scale pools [L, NB, bs] (the rows have no kv-head
        axis)."""
        cfg = TransformerConfig(
            num_layers=2, hidden_size=64, num_attention_heads=4,
            vocab_size=128, max_position_embeddings=64,
            multi_latent_attention=True, kv_lora_rank=32, qk_head_dim=16,
            qk_pos_emb_head_dim=8, v_head_dim=16,
            compute_dtype=jnp.float32, remat_policy="none")
        pool = PagedKVCache(cfg, 2, 32, num_blocks=8, block_size=4,
                            kv_cache_dtype="int8")
        assert pool.quantized
        assert pool.pages[0].dtype == jnp.int8
        assert pool.pages[0].shape == (2, 8, 4, cfg.kv_lora_rank)
        assert pool.pages[1].shape == (2, 8, 4, cfg.qk_pos_emb_head_dim)
        assert pool.scales is not None
        assert all(s.shape == (2, 8, 4) and s.dtype == jnp.float32
                   for s in pool.scales)

    def test_cow_copies_scales_alongside(self):
        """A copy-on-write block copy must carry the scale rows with the
        int8 rows — dequantized content of the private copy equals the
        shared block's."""
        cfg = _gqa_cfg()
        pool = PagedKVCache(cfg, 2, 32, num_blocks=8, block_size=4,
                            kv_cache_dtype="int8")
        rng = np.random.default_rng(0)
        rows = jnp.asarray(rng.normal(size=(
            cfg.num_layers, 4, cfg.num_query_groups, cfg.head_dim)),
            jnp.float32)
        q, s = quantize_kv_rows(rows)
        toks = np.arange(4, dtype=np.int32)
        plan = pool.admit(0, toks)
        blk = plan.blocks[0]
        pool.pages = tuple(p.at[:, blk].set(q) for p in pool.pages)
        pool.scales = tuple(sc.at[:, blk].set(s) for sc in pool.scales)
        pool.release(0, toks, 4)
        plan2 = pool.admit(1, toks)          # full hit → CoW
        assert plan2.cow
        dst = plan2.blocks[-1]
        assert dst != blk
        for p, sc in zip(pool.pages, pool.scales):
            np.testing.assert_array_equal(np.asarray(p[:, dst]),
                                          np.asarray(p[:, blk]))
            np.testing.assert_array_equal(np.asarray(sc[:, dst]),
                                          np.asarray(sc[:, blk]))
        pool.audit()


class TestQuantizedEngine:
    def test_int8_streams_match_baseline_and_oracle(self):
        """Greedy streams on the int8 pool == the baseline-pool streams
        == the dense oracle on the tiny model (mixed lengths, continuous
        batching through chunked prefill)."""
        cfg = _gqa_cfg()
        params, _ = init_gpt_params(jax.random.PRNGKey(7), cfg)
        rng = np.random.default_rng(0)
        prompts = [rng.integers(0, 128, n).astype(np.int32)
                   for n in (5, 9, 13, 3)]

        def run(dtype):
            eng = DynamicInferenceEngine(
                params, cfg, max_batch=2, max_seq_len=48,
                prefill_buckets=(16, 32), paged=True, block_size=8,
                kv_cache_dtype=dtype)
            ids = [eng.add_request(p, 6, SamplingParams(greedy=True))
                   for p in prompts]
            res = eng.run_to_completion()
            eng.pool.audit()
            return [res[r].tolist() for r in ids]

        base, i8 = run("bf16"), run("int8")
        assert base == i8
        for p, out in zip(prompts, i8):
            assert out == _greedy_oracle(params, cfg, p, 6)

    def test_prefix_cache_cow_and_stats_on_int8(self):
        """Prefix-cache hit + CoW semantics are dtype-independent, and
        the /stats pool section reports the actual int8 bytes."""
        cfg = _gqa_cfg()
        params, _ = init_gpt_params(jax.random.PRNGKey(3), cfg)
        rng = np.random.default_rng(2)
        shared = rng.integers(0, 128, 16).astype(np.int32)
        pa = np.concatenate([shared,
                             rng.integers(0, 128, 3).astype(np.int32)])
        pc = shared.copy()                                   # full hit
        eng = DynamicInferenceEngine(
            params, cfg, max_batch=2, max_seq_len=64,
            prefill_buckets=(32,), paged=True, block_size=8,
            kv_cache_dtype="int8")
        ra = eng.add_request(pa, 4, SamplingParams(greedy=True))
        eng.step()
        rc = eng.add_request(pc, 4, SamplingParams(greedy=True))
        eng.step()
        assert eng.pool.stats["cow_copies"] == 1
        assert eng.pool.stats["prefix_hit_tokens"] > 0
        snap = eng.stats_snapshot()["pool"]
        assert snap["kv_cache_dtype"] == "int8"
        assert snap["pool_bytes_total"] == eng.pool.bytes_total
        assert snap["resident_bytes"] == (
            (eng.pool.num_blocks - eng.pool.free_blocks())
            * eng.pool.bytes_per_block)
        res = eng.run_to_completion()
        eng.pool.audit()
        for p, rid in ((pa, ra), (pc, rc)):
            assert res[rid].tolist() == _greedy_oracle(params, cfg, p, 4)

    def test_preempt_resume_on_int8_pool(self):
        """An undersized int8 pool preempts mid-decode; resume re-hits
        the quantized blocks and both streams stay oracle-exact."""
        cfg = _gqa_cfg()
        params, _ = init_gpt_params(jax.random.PRNGKey(3), cfg)
        rng = np.random.default_rng(5)
        p1 = rng.integers(0, 128, 12).astype(np.int32)
        p2 = rng.integers(0, 128, 14).astype(np.int32)
        eng = DynamicInferenceEngine(
            params, cfg, max_batch=2, max_seq_len=64,
            prefill_buckets=(32,), paged=True, block_size=8,
            num_blocks=5, kv_cache_dtype="int8")
        r1 = eng.add_request(p1, 10, SamplingParams(greedy=True))
        r2 = eng.add_request(p2, 10, SamplingParams(greedy=True))
        res = eng.run_to_completion()
        eng.pool.audit()
        assert eng.pool.stats["preemptions"] >= 1
        assert res[r1].tolist() == _greedy_oracle(params, cfg, p1, 10)
        assert res[r2].tolist() == _greedy_oracle(params, cfg, p2, 10)

    def test_tp2_int8_engine_matches_single_device(self, devices8):
        """tp2 serving mesh on an int8 pool (per-shard int8 pools +
        per-shard scale pools): greedy streams bit-identical to the
        single-device int8 engine."""
        from megatronapp_tpu.config.parallel_config import ParallelConfig
        from megatronapp_tpu.parallel.mesh import build_mesh
        cfg = _gqa_cfg()
        params, _ = init_gpt_params(jax.random.PRNGKey(7), cfg)
        rng = np.random.default_rng(9)
        prompts = [rng.integers(0, 128, n).astype(np.int32)
                   for n in (5, 11)]

        def run(ctx):
            eng = DynamicInferenceEngine(
                params, cfg, max_batch=2, max_seq_len=48,
                prefill_buckets=(16,), paged=True, block_size=8,
                kv_cache_dtype="int8", ctx=ctx)
            if ctx is not None:
                assert eng.tp_paged
            ids = [eng.add_request(p, 5, SamplingParams(greedy=True))
                   for p in prompts]
            res = eng.run_to_completion()
            eng.pool.audit()
            return [res[r].tolist() for r in ids]

        ctx = build_mesh(ParallelConfig(tensor_parallel=2),
                         devices=devices8[:2])
        assert run(None) == run(ctx)


class TestQuantizedSpecDecode:
    def test_spec_exact_on_int8_and_acceptance_delta(self):
        """Speculative exactness (greedy == plain decode) holds ON the
        int8 pool, and the acceptance-rate delta vs the bf16 pool is
        within the documented epsilon."""
        cfg = _gqa_cfg()
        params, _ = init_gpt_params(jax.random.PRNGKey(7), cfg)
        rng = np.random.default_rng(3)
        motif = rng.integers(0, 128, 6).astype(np.int32)
        prompt = np.tile(motif, 3)

        def run(dtype, spec):
            eng = DynamicInferenceEngine(
                params, cfg, max_batch=2, max_seq_len=64,
                prefill_buckets=(32,), paged=True, block_size=8,
                spec_method=spec, spec_k=3, prefill_chunk=8,
                kv_cache_dtype=dtype)
            rid = eng.add_request(prompt, 10, SamplingParams(greedy=True))
            res = eng.run_to_completion()
            eng.pool.audit()
            st = eng.spec_stats
            acc = (st["accepted"] / st["proposed"]
                   if st["proposed"] else 0.0)
            return res[rid].tolist(), acc

        plain_i8, _ = run("int8", None)
        spec_i8, acc_i8 = run("int8", "ngram")
        _, acc_bf = run("bf16", "ngram")
        assert spec_i8 == plain_i8
        assert abs(acc_i8 - acc_bf) <= 0.05


class TestQuantizedDisagg:
    def test_handoff_ships_quantized_rows(self, devices8):
        """Disaggregated serving on an int8 pool: streams identical to
        the colocated int8 engine, and the handoff ships (D+4)/(cD) of
        the baseline row bytes (counted off the actual transferred
        arrays)."""
        from megatronapp_tpu.inference.disagg import DisaggServingEngine
        cfg = _gqa_cfg()
        params, _ = init_gpt_params(jax.random.PRNGKey(7), cfg)
        rng = np.random.default_rng(0)
        prompts = [rng.integers(0, 128, n).astype(np.int32)
                   for n in (5, 19, 13)]

        def run(dtype):
            eng = DisaggServingEngine(
                params, cfg, max_batch=2, max_seq_len=64,
                prefill_buckets=(16, 32), block_size=8, prefill_chunk=8,
                kv_cache_dtype=dtype, devices=devices8[:2])
            ids = [eng.add_request(p, 6, SamplingParams(greedy=True))
                   for p in prompts]
            res = eng.run_to_completion()
            eng.pool.audit()
            shipped = eng.stats_snapshot()["disagg"]["handoff"]
            return [res[r].tolist() for r in ids], shipped

        base_toks, base_ship = run("bf16")
        i8_toks, i8_ship = run("int8")
        assert i8_toks == base_toks
        assert i8_ship["kv_cache_dtype"] == "int8"
        d = cfg.head_dim
        itemsize = jnp.dtype(cfg.compute_dtype).itemsize
        expect = (d + 4) / (itemsize * d)
        ratio = (i8_ship["kv_shipped_bytes"]
                 / base_ship["kv_shipped_bytes"])
        assert abs(ratio - expect) < 1e-6, (ratio, expect)

        # Colocated int8 engine produces the same streams (prefill-side
        # quantization == decode-side quantization).
        colo = DynamicInferenceEngine(
            params, cfg, max_batch=2, max_seq_len=64,
            prefill_buckets=(16, 32), paged=True, block_size=8,
            prefill_chunk=8, kv_cache_dtype="int8")
        ids = [colo.add_request(p, 6, SamplingParams(greedy=True))
               for p in prompts]
        res = colo.run_to_completion()
        assert [res[r].tolist() for r in ids] == i8_toks


class TestResidentWeights:
    def test_resident_matches_dequantize_on_load(self):
        """resolve_param at matmul entry == eager dequantize-on-load,
        bit for bit, with the int8 kernels dominating the resident
        bytes."""
        from megatronapp_tpu.inference.quantization import (
            dequantize_params, quantize_params, resident_nbytes,
            residentize_params,
        )
        cfg = _gqa_cfg()
        params, _ = init_gpt_params(jax.random.PRNGKey(7), cfg)
        q, _ = quantize_params(params)
        res = residentize_params(q)
        deq = dequantize_params(q)
        toks = jnp.asarray(np.arange(8)[None], jnp.int32)
        l_res, _ = gpt_forward(res, toks, cfg)
        l_deq, _ = gpt_forward(deq, toks, cfg)
        np.testing.assert_array_equal(np.asarray(l_res),
                                      np.asarray(l_deq))
        assert resident_nbytes(res) < resident_nbytes(params)

    def test_resident_weights_serve_int8_pool(self):
        """The full quantized serving stack — resident int8 weights +
        int8 KV pool — produces the same greedy stream as
        dequantized-weight serving (weight quantization fixed, pool
        dtype varied)."""
        from megatronapp_tpu.inference.quantization import (
            dequantize_params, quantize_params, residentize_params,
        )
        cfg = _gqa_cfg()
        params, _ = init_gpt_params(jax.random.PRNGKey(7), cfg)
        q, _ = quantize_params(params)
        res, deq = residentize_params(q), dequantize_params(q)
        prompt = np.arange(1, 10, dtype=np.int32)

        def run(p, dtype):
            eng = DynamicInferenceEngine(
                p, cfg, max_batch=1, max_seq_len=48,
                prefill_buckets=(16,), paged=True, block_size=8,
                kv_cache_dtype=dtype)
            rid = eng.add_request(prompt, 6, SamplingParams(greedy=True))
            return eng.run_to_completion()[rid].tolist()

        assert run(res, "int8") == run(deq, "int8")


class TestServingArgsValidation:
    def _args(self, **kw):
        import argparse

        from megatronapp_tpu.config.arguments import add_serving_args
        ap = argparse.ArgumentParser()
        add_serving_args(ap)
        argv = []
        for k, v in kw.items():
            flag = "--" + k.replace("_", "-")
            argv += [flag] if v is True else [flag, str(v)]
        return ap.parse_args(argv)

    def test_int8_requires_the_dynamic_engine(self):
        from megatronapp_tpu.config.arguments import validate_serving_args
        args = self._args(engine="static", kv_cache_dtype="int8")
        with pytest.raises(SystemExit, match="requires --engine dynamic"):
            validate_serving_args(args)

    def test_int8_accepted_for_mla_preset(self):
        """int8 + MLA validates since ISSUE 17 (quantized latent pool)."""
        from megatronapp_tpu.config.arguments import validate_serving_args
        args = self._args(engine="dynamic", kv_cache_dtype="int8")
        validate_serving_args(args, multi_latent_attention=True)  # no raise

    def test_quantized_weights_rejected_for_mamba(self):
        from megatronapp_tpu.config.arguments import validate_serving_args
        args = self._args(engine="mamba", quantized_weights=True)
        with pytest.raises(SystemExit, match="gpt engines"):
            validate_serving_args(args)

    def test_valid_combo_passes(self):
        from megatronapp_tpu.config.arguments import validate_serving_args
        args = self._args(engine="dynamic", kv_cache_dtype="int8")
        validate_serving_args(args)          # no raise

    def test_startup_ptq_quantizes_resident_leaves_only(self):
        """resident_only PTQ must not round-trip weights residentize
        would dequantize eagerly. Since ISSUE 13, MoE expert stacks ARE
        resident (moe_forward resolves them at matmul entry), so they
        quantize too; the router stays full precision (top-k selection
        is perturbation-sensitive)."""
        from megatronapp_tpu.inference.quantization import (
            is_quantized_leaf, is_resident_leaf, quantize_params,
            residentize_params,
        )
        cfg = TransformerConfig(
            num_layers=2, hidden_size=64, num_attention_heads=4,
            vocab_size=128, max_position_embeddings=64,
            num_moe_experts=4, moe_router_topk=2,
            compute_dtype=jnp.float32, remat_policy="none")
        params, _ = init_gpt_params(jax.random.PRNGKey(0), cfg)
        q, report = quantize_params(params, resident_only=True)
        assert is_quantized_leaf(q["block"]["attention"]["q_kernel"])
        assert is_quantized_leaf(q["block"]["moe"]["fc1_kernel"])
        assert any("moe" in k for k in report)
        assert not is_quantized_leaf(q["block"]["moe"]["router_kernel"])
        res = residentize_params(q)
        assert is_resident_leaf(res["block"]["moe"]["fc1_kernel"])
        np.testing.assert_array_equal(
            np.asarray(res["block"]["moe"]["router_kernel"]),
            np.asarray(params["block"]["moe"]["router_kernel"]))


class TestBenchmarkSmoke:
    def test_kv_quant_benchmark_gates(self):
        """Tier-1 smoke gate for the bench.py extra.kv_quant record: the
        three acceptance-criteria bounds on a reduced workload —
        resident bytes <= 0.55x, logits parity within the documented
        bound, spec acceptance delta <= eps."""
        from tools.kv_quant_benchmark import run_logits_parity, run_memory_and_decode
        md = run_memory_and_decode(max_batch=2, max_seq_len=64,
                                   block_size=8, max_new=2)
        assert md["memory_ratio"] <= 0.55
        assert md["sessions_at_capacity"]["int8"] > \
            md["sessions_at_capacity"]["bf16"]
        assert md["greedy_match"] or md["first_divergence"] is not None
        lp = run_logits_parity()
        assert lp["within_bound"], lp

    def test_kv_quant_benchmark_spec_gate(self):
        from tools.kv_quant_benchmark import run_spec_acceptance
        sp = run_spec_acceptance(max_new=8, spec_k=3)
        assert sp["within_bound"], sp
        assert sp["int8"]["exact_vs_plain"]
        assert sp["bf16"]["exact_vs_plain"]

