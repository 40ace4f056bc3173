"""fp8 end-to-end tests (ISSUE 13): fp8 KV-cache pages, delayed-scaling
fp8 ring GEMMs, and resident MoE experts.

Layer by layer:

- kernels: fp8 (e4m3) paged decode / multiquery == the fp8 jnp
  references exactly (same dequant math) across {decode, multiquery,
  tp2, fused} × {GQA, MHA} × ragged q_lens, next to the existing int8
  pins in tests/test_kernel_gen.py;
- pool: fp8 pages cost exactly the int8 bytes ((D+4)/cD of the
  compute-dtype pool — at or below the documented 0.53x bf16 ratio),
  and the dtype registry keeps the CLI choices / server validation /
  pool check in lockstep;
- engine: greedy streams on the fp8 pool match the bf16-pool streams
  and the dense oracle; the disagg handoff ships fp8 rows + scales
  through the existing drills;
- training: fp8 ring GEMMs track the bf16 loss curve within the
  documented tolerance on the CPU A/B (tp2), the amax/scale state
  survives checkpoint save → restore bitwise, all three ZeRO-1
  update-comm modes stay mutually equal under fp8, and scale drift is
  exported to /metrics;
- weights: --quantized-weights leaves MoE expert stacks RESIDENT — the
  dequantized-bytes fallback counter reads 0 on an MoE config and the
  streams stay bit-identical to dequantize-on-load.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from megatronapp_tpu.config.parallel_config import ParallelConfig
from megatronapp_tpu.config.training_config import (
    OptimizerConfig, TrainingConfig,
)
from megatronapp_tpu.config.transformer_config import TransformerConfig
from megatronapp_tpu.inference.dynamic_engine import DynamicInferenceEngine
from megatronapp_tpu.inference.engine import SamplingParams
from megatronapp_tpu.inference.paged_cache import (
    KV_CACHE_DTYPES, PagedKVCache, validate_kv_cache_dtype,
)
from megatronapp_tpu.models.gpt import gpt_forward, init_gpt_params
from megatronapp_tpu.ops.pallas.paged_attention import (
    dequantize_pages, paged_attention_decode, paged_attention_multiquery,
    paged_attention_multiquery_reference, paged_attention_reference,
    quantize_kv_rows,
)
from megatronapp_tpu.parallel.mesh import build_mesh
from megatronapp_tpu.training.train import pretrain_gpt
from megatronapp_tpu.utils import metrics as telemetry

FP8 = jnp.float8_e4m3fn

# Documented CPU A/B tolerance for the fp8-vs-bf16 training loss curve
# (tiny model, 6 steps, zero-initialized amax history — step 0 quantizes
# at scale 1.0 before the history warms up). Measured max rel diff
# ~2.2e-3; gated at 4x headroom.
FP8_LOSS_RTOL = 1e-2


def _gqa_cfg(**kw):
    d = dict(num_layers=2, hidden_size=64, num_attention_heads=4,
             num_query_groups=2, vocab_size=128,
             max_position_embeddings=64, compute_dtype=jnp.float32,
             remat_policy="none")
    d.update(kw)
    return TransformerConfig(**d)


from jitted import greedy_oracle as _greedy_oracle  # noqa: E402


# ---------------------------------------------------------------------------
class TestFp8Kernels:
    """Generated fp8 kernels vs the jnp oracles — the dtype-matrix pin
    suite riding the PagedSpec quant-dtype axis."""

    @pytest.mark.parametrize("hq,hkv", [(4, 2), (8, 8)])  # GQA, MHA
    def test_decode_matches_fp8_reference(self, hq, hkv):
        b, d, bs, mb = 3, 16, 4, 4
        nb = b * mb
        rng = np.random.default_rng(hq)
        q = jnp.asarray(rng.normal(size=(b, hq, d)), jnp.float32)
        kp = jnp.asarray(rng.normal(size=(nb, bs, hkv, d)), jnp.float32)
        vp = jnp.asarray(rng.normal(size=(nb, bs, hkv, d)), jnp.float32)
        kq, ks = quantize_kv_rows(kp, dtype=FP8)
        vq, vs = quantize_kv_rows(vp, dtype=FP8)
        assert kq.dtype == FP8 and ks.shape == (nb, bs, hkv)
        table = jnp.asarray(rng.permutation(nb).reshape(b, mb), jnp.int32)
        lens = jnp.asarray([1, bs + 1, mb * bs], jnp.int32)
        out = paged_attention_decode(q, kq, vq, table, lens,
                                     k_scales=ks, v_scales=vs)
        ref = paged_attention_reference(q, kq, vq, table, lens,
                                        k_scales=ks, v_scales=vs)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5, rtol=1e-5)

    @pytest.mark.parametrize("hq,hkv", [(4, 2), (6, 6)])  # GQA, MHA
    def test_multiquery_ragged_matches_fp8_reference(self, hq, hkv):
        b, s_q, d, bs, mb = 3, 3, 16, 4, 4
        nb = b * mb
        rng = np.random.default_rng(1)
        q = jnp.asarray(rng.normal(size=(b, s_q, hq, d)), jnp.float32)
        kp = jnp.asarray(rng.normal(size=(nb, bs, hkv, d)), jnp.float32)
        vp = jnp.asarray(rng.normal(size=(nb, bs, hkv, d)), jnp.float32)
        kq, ks = quantize_kv_rows(kp, dtype=FP8)
        vq, vs = quantize_kv_rows(vp, dtype=FP8)
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

    def test_tp2_fp8_decode_matches_single_device(self, devices8):
        from megatronapp_tpu.ops.pallas.paged_attention import (
            paged_attention_decode_tp,
        )
        ctx = build_mesh(ParallelConfig(tensor_parallel=2),
                         devices=devices8[:2])
        b, hq, hkv, d, bs, mb = 2, 4, 2, 16, 4, 3
        nb = b * mb
        rng = np.random.default_rng(2)
        q = jnp.asarray(rng.normal(size=(b, hq, d)), jnp.float32)
        kp = jnp.asarray(rng.normal(size=(nb, bs, hkv, d)), jnp.float32)
        vp = jnp.asarray(rng.normal(size=(nb, bs, hkv, d)), jnp.float32)
        kq, ks = quantize_kv_rows(kp, dtype=FP8)
        vq, vs = quantize_kv_rows(vp, dtype=FP8)
        table = jnp.asarray(rng.permutation(nb).reshape(b, mb), jnp.int32)
        lens = jnp.asarray([5, mb * bs], jnp.int32)
        single = paged_attention_decode(q, kq, vq, table, lens,
                                        k_scales=ks, v_scales=vs)
        sharded = paged_attention_decode_tp(
            q, kq, vq, table, lens, ctx.shard_map_mesh,
            k_scales=ks, v_scales=vs)
        np.testing.assert_allclose(np.asarray(single), np.asarray(sharded),
                                   atol=1e-5, rtol=1e-5)

    def test_fp8_saturates_instead_of_nan(self):
        """e4m3 overflow is NaN — the quantize path must clip, so a row
        scaled to the range bound round-trips finite."""
        rows = jnp.asarray([[[1e4, -2e4, 3.0, 448.0]]], jnp.float32)
        q, s = quantize_kv_rows(rows, dtype=FP8)
        back = dequantize_pages(q, s)
        assert bool(jnp.all(jnp.isfinite(back)))
        # absmax maps to the e4m3 range bound exactly.
        assert float(jnp.max(jnp.abs(q.astype(jnp.float32)))) <= 448.0

    def test_spec_quant_dtype_axis(self):
        from megatronapp_tpu.ops.pallas.kernel_gen import (
            PagedSpec, default_kv_tile, quant_dtype_of,
        )
        assert quant_dtype_of(jnp.int8) == "int8"
        assert quant_dtype_of(FP8) == "fp8"
        assert quant_dtype_of(jnp.bfloat16) is None
        # 1-byte formats tile (32, 128) on-chip; bf16 (16, 128).
        assert default_kv_tile("fp8") == (32, 128)
        assert default_kv_tile("int8") == (32, 128)
        assert default_kv_tile(None) == (16, 128)
        with pytest.raises(ValueError, match="quant_dtype"):
            PagedSpec(ragged=False, quant_dtype="int4", s_q=1,
                      block_size=8, num_blocks_seq=4, hkv=2, group=2,
                      scale=1.0)
        with pytest.raises(ValueError, match="kv_tile"):
            PagedSpec(ragged=False, quant_dtype="fp8", s_q=1,
                      block_size=8, num_blocks_seq=4, hkv=2, group=2,
                      scale=1.0, kv_tile=(32, 100))


# ---------------------------------------------------------------------------
class TestFp8Pool:
    def test_fp8_bytes_equal_int8_bytes(self):
        """fp8 pool bytes == int8 pool bytes exactly (1-byte pages +
        fp32 scales) — at or below the documented 0.53x bf16 ratio."""
        cfg = _gqa_cfg()
        base = PagedKVCache(cfg, 2, 32, num_blocks=8, block_size=4)
        i8 = PagedKVCache(cfg, 2, 32, num_blocks=8, block_size=4,
                          kv_cache_dtype="int8")
        f8 = PagedKVCache(cfg, 2, 32, num_blocks=8, block_size=4,
                          kv_cache_dtype="fp8")
        assert f8.pages[0].dtype == FP8
        assert f8.scales[0].dtype == jnp.float32
        assert f8.bytes_total == i8.bytes_total
        d = cfg.head_dim
        bf16_bytes = base.bytes_total // base.pages[0].dtype.itemsize * 2
        assert f8.bytes_total / bf16_bytes == (d + 4) / (2 * d)
        # The 0.53x acceptance bound holds at the bench head_dim (64):
        # (64+4)/128 = 0.531 — fp8 exactly matches the int8 ratio.
        cfg64 = _gqa_cfg(hidden_size=128, num_attention_heads=2,
                         num_query_groups=2)
        assert cfg64.head_dim == 64
        b64 = PagedKVCache(cfg64, 2, 32, num_blocks=8, block_size=4)
        f64 = PagedKVCache(cfg64, 2, 32, num_blocks=8, block_size=4,
                           kv_cache_dtype="fp8")
        bf16_bytes64 = (b64.bytes_total
                        // b64.pages[0].dtype.itemsize * 2)
        assert abs(f64.bytes_total / bf16_bytes64 - 0.53125) < 1e-9

    def test_registry_drives_cli_and_pool(self):
        """The CLI choices, the pool check, and the parse-time server
        validation all derive from KV_CACHE_DTYPES — adding a dtype
        cannot leave them disagreeing."""
        import argparse

        from megatronapp_tpu.config.arguments import (
            add_serving_args, validate_serving_args,
        )
        ap = argparse.ArgumentParser()
        add_serving_args(ap)
        action = next(a for a in ap._actions
                      if a.dest == "kv_cache_dtype")
        assert sorted(action.choices) == sorted(KV_CACHE_DTYPES)
        # fp8 under --engine static (the default): refused by --engine.
        args = ap.parse_args(["--kv-cache-dtype", "fp8"])
        with pytest.raises(SystemExit, match="--engine dynamic"):
            validate_serving_args(args)
        validate_serving_args(ap.parse_args(
            ["--kv-cache-dtype", "fp8", "--engine", "dynamic"]))
        # fp8 + MLA validates since ISSUE 17 (quantized latent pool).
        validate_kv_cache_dtype("fp8", mla=True)  # no raise
        with pytest.raises(ValueError, match="one of"):
            validate_kv_cache_dtype("int4")

    def test_fp8_mla_latent_pool(self):
        """fp8 MLA pools quantize since ISSUE 17 (per-row scalar scale
        pools [L, NB, bs], same layout as int8)."""
        cfg = TransformerConfig(
            num_layers=2, hidden_size=64, num_attention_heads=4,
            vocab_size=128, max_position_embeddings=64,
            multi_latent_attention=True, kv_lora_rank=32, qk_head_dim=16,
            qk_pos_emb_head_dim=8, v_head_dim=16,
            compute_dtype=jnp.float32, remat_policy="none")
        pool = PagedKVCache(cfg, 2, 32, num_blocks=8, block_size=4,
                            kv_cache_dtype="fp8")
        assert pool.quantized
        assert pool.pages[0].shape == (2, 8, 4, cfg.kv_lora_rank)
        assert pool.scales is not None
        assert all(s.shape == (2, 8, 4) and s.dtype == jnp.float32
                   for s in pool.scales)


# ---------------------------------------------------------------------------
class TestFp8Engine:
    def test_fp8_streams_match_baseline_and_oracle(self):
        """Greedy streams on the fp8 pool == the baseline-pool streams
        == the dense oracle (mixed lengths, chunked prefill) — the
        token-exactness acceptance gate."""
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

        base, f8 = run("bf16"), run("fp8")
        assert base == f8
        for p, out in zip(prompts, f8):
            assert out == _greedy_oracle(params, cfg, p, 6)

    def test_spec_decode_exact_on_fp8_pool(self):
        cfg = _gqa_cfg()
        params, _ = init_gpt_params(jax.random.PRNGKey(7), cfg)
        rng = np.random.default_rng(3)
        motif = rng.integers(0, 128, 6).astype(np.int32)
        prompt = np.tile(motif, 3)

        def run(spec):
            eng = DynamicInferenceEngine(
                params, cfg, max_batch=2, max_seq_len=64,
                prefill_buckets=(32,), paged=True, block_size=8,
                spec_method=spec, spec_k=3, prefill_chunk=8,
                kv_cache_dtype="fp8")
            rid = eng.add_request(prompt, 10, SamplingParams(greedy=True))
            res = eng.run_to_completion()
            eng.pool.audit()
            return res[rid].tolist()

        assert run("ngram") == run(None)

    def test_disagg_handoff_ships_fp8(self, devices8):
        """The existing handoff drill on an fp8 pool: streams identical
        to the colocated fp8 engine, shipped bytes == the int8 ratio."""
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
        f8_toks, f8_ship = run("fp8")
        assert f8_toks == base_toks
        assert f8_ship["kv_cache_dtype"] == "fp8"
        d = cfg.head_dim
        itemsize = jnp.dtype(cfg.compute_dtype).itemsize
        ratio = (f8_ship["kv_shipped_bytes"]
                 / base_ship["kv_shipped_bytes"])
        assert abs(ratio - (d + 4) / (itemsize * d)) < 1e-6

        colo = DynamicInferenceEngine(
            params, cfg, max_batch=2, max_seq_len=64,
            prefill_buckets=(16, 32), paged=True, block_size=8,
            prefill_chunk=8, kv_cache_dtype="fp8")
        ids = [colo.add_request(p, 6, SamplingParams(greedy=True))
               for p in prompts]
        res = colo.run_to_completion()
        assert [res[r].tolist() for r in ids] == f8_toks


# ---------------------------------------------------------------------------
def _train(devices8, n_dev, fp8, iters=6, par_kw=None, opt_kw=None,
           train_kw=None, model_kw=None):
    model_d = dict(num_layers=2, hidden_size=64, num_attention_heads=4,
                   vocab_size=128, max_position_embeddings=64,
                   compute_dtype=jnp.float32, tp_comm_overlap=True,
                   fp8=fp8, fp8_amax_history_len=4)
    model_d.update(model_kw or {})
    model = TransformerConfig(**model_d)
    par = ParallelConfig(tensor_parallel=2, **(par_kw or {}))
    ctx = build_mesh(par, devices=devices8[:n_dev])
    train_d = dict(micro_batch_size=2, global_batch_size=4,
                   seq_length=32, train_iters=iters, log_interval=1)
    train_d.update(train_kw or {})
    train = TrainingConfig(**train_d)
    opt = OptimizerConfig(lr=1e-3, **(opt_kw or {}))
    return pretrain_gpt(model, par, train, opt, ctx=ctx,
                        log_fn=lambda *_: None), model


class TestFp8Training:
    def test_loss_parity_vs_bf16_tp2(self, devices8):
        """CPU A/B: fp8 ring GEMMs track the unquantized loss curve
        within the documented tolerance, and the amax history fills per
        (layer, site, tensor)."""
        rb, _ = _train(devices8, 2, fp8=False)
        rf, model = _train(devices8, 2, fp8=True)
        lb, lf = rb.losses, rf.losses
        for a, b in zip(lb, lf):
            assert abs(a - b) / abs(a) <= FP8_LOSS_RTOL, (lb, lf)
        f8 = rf.state["fp8"]["block"]
        # Structure: every site's history has the right tensor count and
        # a populated slot-0 amax on every layer.
        from megatronapp_tpu.training.fp8 import SITE_TENSORS
        for (mod, site), n in SITE_TENSORS.items():
            hist = np.asarray(f8[mod][site]["hist"])
            assert hist.shape == (model.num_layers, n, 4)
            assert (hist[:, :, 0] > 0).all(), (mod, site, hist)

    def test_amax_state_survives_save_resume_bitwise(self, devices8,
                                                     tmp_path):
        """state["fp8"] is a first-class train-state member: a durable
        checkpoint round-trips it BITWISE, and a resumed run continues
        from the same history (exact resume)."""
        from megatronapp_tpu.training.checkpointing import (
            CheckpointManager,
        )
        r1, _ = _train(devices8, 2, fp8=True, iters=4,
                       train_kw=dict(save_dir=str(tmp_path),
                                     save_interval=4))
        state = r1.state
        ckpt = CheckpointManager(str(tmp_path))
        restored = ckpt.restore(state)
        ckpt.close()
        assert restored is not None
        for a, b in zip(jax.tree.leaves(state["fp8"]),
                        jax.tree.leaves(restored["fp8"])):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        # Resume → the continued curve tracks an uninterrupted run (the
        # resumed run reports only its post-restore steps 5..8). The
        # tolerance is loose ON PURPOSE: this tp2 + tp_comm_overlap
        # config shows a ~3.5e-3 absolute resume wobble on the BF16
        # BASELINE too (measured; pre-existing, unrelated to fp8 —
        # fp8 runs are bitwise deterministic run-to-run), so the fp8
        # acceptance pin is the BITWISE state round-trip above plus
        # curve tracking here.
        r_full, _ = _train(devices8, 2, fp8=True, iters=8)
        r_res, _ = _train(devices8, 2, fp8=True, iters=8,
                          train_kw=dict(save_dir=str(tmp_path),
                                        save_interval=4))
        assert len(r_res.losses) == 4
        np.testing.assert_allclose(r_res.losses, r_full.losses[4:],
                                   rtol=5e-3)

    def test_comm_modes_equal_under_fp8(self, devices8):
        """All three ZeRO-1 update-comm modes stay mutually equal with
        fp8 on (dp2 x tp2): the fp8 state bypasses the optimizer, so
        the update math is untouched."""
        losses = {}
        for comm in ("gspmd", "ring", "bulk"):
            r, _ = _train(devices8, 4, fp8=True, iters=4,
                          par_kw=dict(data_parallel=2,
                                      distributed_optimizer=True),
                          opt_kw=dict(dist_opt_comm=comm))
            losses[comm] = [float(x) for x in r.losses]
        np.testing.assert_allclose(losses["ring"], losses["gspmd"],
                                   rtol=0, atol=0)
        np.testing.assert_allclose(losses["bulk"], losses["gspmd"],
                                   rtol=0, atol=0)

    def test_skipped_step_keeps_history(self, devices8):
        """A NaN-skipped step must not roll the amax history (nothing
        was observed): drive the fp8 step with a NaN batch directly."""
        from megatronapp_tpu.models.gpt import init_gpt_params
        from megatronapp_tpu.training.fp8 import init_fp8_state
        from megatronapp_tpu.training.optimizer import get_optimizer
        from megatronapp_tpu.training.train import gpt_microbatch_loss
        from megatronapp_tpu.training.train_state import setup_train_state
        from megatronapp_tpu.training.train_step import make_train_step
        model = TransformerConfig(
            num_layers=2, hidden_size=64, num_attention_heads=4,
            vocab_size=128, max_position_embeddings=64,
            compute_dtype=jnp.float32, tp_comm_overlap=True, fp8=True,
            fp8_amax_history_len=4)
        ctx = build_mesh(ParallelConfig(tensor_parallel=2),
                         devices=devices8[:2])
        opt_cfg = OptimizerConfig(lr=1e-3)
        optimizer = get_optimizer(opt_cfg, 4, distributed=True)
        state, shardings, _ = setup_train_state(
            jax.random.PRNGKey(0),
            lambda k: init_gpt_params(k, model), optimizer, ctx,
            fp8_state=init_fp8_state(model))
        step = make_train_step(gpt_microbatch_loss(model, ctx=ctx),
                               optimizer, opt_cfg, ctx, shardings, 4,
                               fp8=True, donate=False)
        batch = {
            "tokens": np.ones((2, 2, 32), np.int32),
            "labels": np.ones((2, 2, 32), np.int32),
            "loss_mask": np.full((2, 2, 32), np.nan, np.float32),
        }
        before = jax.tree.map(np.asarray, jax.device_get(state["fp8"]))
        new_state, metrics = step(state, batch)
        assert int(jax.device_get(metrics["skipped"])) == 1
        after = jax.device_get(new_state["fp8"])
        for a, b in zip(jax.tree.leaves(before), jax.tree.leaves(after)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_metrics_export(self, devices8):
        """Scale-drift observability: per-site scale/amax gauges + the
        history-depth gauge land in the registry."""
        from megatronapp_tpu.training.fp8 import export_fp8_metrics
        telemetry.disable()
        try:
            r, model = _train(devices8, 2, fp8=True, iters=2)
            telemetry.enable()
            export_fp8_metrics(r.state["fp8"], model)
            snap = telemetry.snapshot()
            g = snap["gauges"]
            assert g["fp8_amax_history_len"] == 4
            for name in ("fp8_scale_attention_qkv", "fp8_scale_mlp_fc1",
                         "fp8_amax_attention_out", "fp8_amax_mlp_fc2"):
                assert name in g, sorted(g)
            assert g["fp8_amax_attention_qkv"] > 0
            assert g["fp8_scale_attention_qkv"] > 0
        finally:
            telemetry.disable()

    def test_ineligible_layouts_rejected(self):
        from megatronapp_tpu.training.fp8 import fp8_ineligible_reason
        par_tp2 = ParallelConfig(tensor_parallel=2)
        ok = TransformerConfig(
            num_layers=2, hidden_size=64, num_attention_heads=4,
            vocab_size=128, max_position_embeddings=64,
            tp_comm_overlap=True, fp8=True)
        assert fp8_ineligible_reason(ok, par_tp2) is None
        cases = [
            (dataclasses.replace(ok, tp_comm_overlap=False), par_tp2,
             "tp-comm-overlap"),
            (ok, ParallelConfig(tensor_parallel=1), "tp"),
            (ok, ParallelConfig(tensor_parallel=2, pipeline_parallel=2),
             "pipeline"),
            (dataclasses.replace(ok, num_moe_experts=4), par_tp2, "MoE"),
            (dataclasses.replace(
                ok, multi_latent_attention=True, kv_lora_rank=32,
                qk_head_dim=16, qk_pos_emb_head_dim=8, v_head_dim=16),
             par_tp2, "MLA"),
        ]
        for cfg, par, needle in cases:
            reason = fp8_ineligible_reason(cfg, par)
            assert reason is not None and needle in reason, (needle,
                                                            reason)

    def test_parse_time_validation(self):
        from megatronapp_tpu.config.arguments import (
            build_parser, configs_from_args, parse_args,
        )
        args = parse_args(build_parser(), ["--fp8"])
        with pytest.raises(ValueError, match="tp-comm-overlap"):
            configs_from_args(args)
        args = parse_args(build_parser(), [
            "--fp8", "--tp-comm-overlap",
            "--tensor-model-parallel-size", "2"])
        model, _, _, _ = configs_from_args(args)
        assert model.fp8 and model.fp8_amax_history_len == 16


# ---------------------------------------------------------------------------
class TestResidentMoEExperts:
    def _moe_cfg(self):
        return TransformerConfig(
            num_layers=2, hidden_size=64, num_attention_heads=4,
            vocab_size=128, max_position_embeddings=64,
            num_moe_experts=4, moe_router_topk=2,
            compute_dtype=jnp.float32, remat_policy="none")

    def test_expert_stacks_stay_resident_counter_zero(self):
        """The acceptance gate: --quantized-weights leaves expert
        stacks resident (no dequantized pytree copies) — the
        dequantized-bytes counter reads 0 on an MoE config."""
        from megatronapp_tpu.inference.quantization import (
            is_resident_leaf, quantize_params, residentize_params,
        )
        cfg = self._moe_cfg()
        params, _ = init_gpt_params(jax.random.PRNGKey(0), cfg)
        q, report = quantize_params(params, resident_only=True)
        assert any("moe" in k for k in report)
        telemetry.disable()
        telemetry.enable()
        try:
            res = residentize_params(q)
            assert telemetry.counter_value(
                "quantized_weights_dequantized_bytes") == 0
        finally:
            telemetry.disable()
        assert is_resident_leaf(res["block"]["moe"]["fc1_kernel"])
        assert is_resident_leaf(res["block"]["moe"]["fc2_kernel"])
        # Router stays full precision (top-k selection is perturbation-
        # sensitive).
        assert not is_resident_leaf(res["block"]["moe"]["router_kernel"])

    def test_fallback_counts_bytes_and_logs(self, caplog):
        """A quantized leaf with no resolve-aware consumer (simulated
        regression) counts its dequantized bytes and logs once."""
        import logging

        from megatronapp_tpu.inference.quantization import (
            quantize_leaf, residentize_params,
        )
        tree = {"odd_dense": quantize_leaf(
            jnp.asarray(np.random.default_rng(0).normal(size=(8, 8)),
                        jnp.float32))}
        # "dense" suffix quantizes but has no RESIDENT_KERNELS entry.
        telemetry.disable()
        telemetry.enable()
        try:
            with caplog.at_level(logging.WARNING,
                                 logger="megatronapp_tpu.inference"
                                        ".quantization"):
                residentize_params(tree)
            assert telemetry.counter_value(
                "quantized_weights_dequantized_bytes") == 8 * 8 * 4
        finally:
            telemetry.disable()
        assert any("dequantized eagerly" in r.message
                   for r in caplog.records)

    def test_moe_resident_streams_bitwise(self):
        """Resident MoE serving == dequantize-on-load serving, bit for
        bit, through the dynamic engine."""
        from megatronapp_tpu.inference.quantization import (
            dequantize_params, quantize_params, residentize_params,
        )
        cfg = self._moe_cfg()
        params, _ = init_gpt_params(jax.random.PRNGKey(7), cfg)
        q, _ = quantize_params(params, resident_only=True)
        res, deq = residentize_params(q), dequantize_params(q)
        prompt = np.arange(1, 10, dtype=np.int32)

        def run(p):
            eng = DynamicInferenceEngine(
                p, cfg, max_batch=1, max_seq_len=48,
                prefill_buckets=(16,), paged=True, block_size=8)
            rid = eng.add_request(prompt, 6, SamplingParams(greedy=True))
            return eng.run_to_completion()[rid].tolist()

        assert run(res) == run(deq)

    def test_moe_resident_forward_bitwise(self):
        from megatronapp_tpu.inference.quantization import (
            dequantize_params, quantize_params, residentize_params,
        )
        cfg = self._moe_cfg()
        params, _ = init_gpt_params(jax.random.PRNGKey(7), cfg)
        q, _ = quantize_params(params, resident_only=True)
        toks = jnp.asarray(np.arange(8)[None], jnp.int32)
        l_res, _ = gpt_forward(residentize_params(q), toks, cfg)
        l_deq, _ = gpt_forward(dequantize_params(q), toks, cfg)
        np.testing.assert_array_equal(np.asarray(l_res),
                                      np.asarray(l_deq))


# ---------------------------------------------------------------------------
class TestBenchmarkSmoke:
    def test_fp8_benchmark_gates(self):
        """Tier-1 pin for the bench.py extra.fp8 record: loss-parity
        tolerance, populated histories, ring-permute byte ratio < 1
        (conservative on CPU — the f8 chunks transport as f16 there),
        and the fp8 pool at-or-below-int8 byte gate with greedy
        parity."""
        from tools.fp8_benchmark import run_kv, run_train
        tr = run_train(iters=2)
        assert tr["within_tolerance"], tr
        assert tr["hist_filled"]
        assert tr["ring_permute_ratio"] is not None \
            and tr["ring_permute_ratio"] < 1.0, tr
        kv = run_kv(max_new=2)
        assert kv["fp8_at_or_below_int8"], kv
        assert kv["greedy_match_fp8"], kv
