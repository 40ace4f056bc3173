"""fp8 end-to-end A/B (ISSUE 13): fp8 training GEMMs + fp8 KV pages.

Two measurement groups, both CPU-deterministic (nothing here has run on
the chip, so the evidence is parity pins + byte counts off the compiled
module / addressable arrays, the house pattern):

  train:  fp8-vs-baseline loss curves on a tp2 mesh through the ring
          matmuls (parallel/overlap.py fp8 custom_vjps). Gates: max
          relative loss deviation <= LOSS_RTOL over the run, amax
          histories populated for every (layer, site, tensor), and the
          RING-TRANSPORT byte count parsed from the compiled module's
          collective-permute ops — the deterministic stand-in for the
          on-chip win: the fp8 rings permute 1-byte chunks where the
          baseline moves compute-dtype chunks, so the permute-bytes
          ratio must be < 1. (The raw cost-model bytes-accessed total
          is reported but NOT gated: on CPU the fp8 emulation's
          quantize/upcast intermediates dominate it — on-chip those are
          register casts.)
  kv:     fp8-vs-bf16 KV pools through the dynamic engine. Gates: pool
          bytes ratio at or below the int8 ratio ((D+4)/2D = 0.531 at
          D=64, the acceptance bound 0.53x-class), greedy streams
          token-exact, fp8 disagg handoff byte ratio exact.


  python tools/fp8_benchmark.py --iters 6
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# Documented CPU A/B tolerance for the fp8-vs-bf16 loss curve (tiny
# model, zero-initialized history; measured max rel diff ~2.2e-3).
LOSS_RTOL = 1e-2


def _ensure_devices(n=8):
    flags = os.environ.get("XLA_FLAGS", "")
    if "host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n}").strip()
    os.environ.setdefault("JAX_PLATFORMS", "cpu")


_HLO_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2,
    "f8e4m3fn": 1, "f8e5m2": 1, "s8": 1, "u8": 1, "s32": 4, "u32": 4,
}


def permute_bytes(jitted, *args) -> int:
    """Sum the result bytes of every collective-permute in the OPTIMIZED
    HLO — the deterministic ring-transport accounting (each permute op
    moves its result shape across the tp ring once per execution)."""
    import re
    txt = jitted.lower(*args).compile().as_text()
    total = 0
    # Optimized-HLO line shape: `%name = f16[2,4,16]{2,1,0}
    # collective-permute(...)`. NOTE XLA:CPU lowers the f8 chunk
    # transport to f16 converts (no native f8 collectives) — the CPU
    # ratio is therefore CONSERVATIVE; on-chip the chunks move as
    # 1-byte f8.
    for m in re.finditer(
            r"=\s*(\w+)\[([\d,]*)\]\S*\s+collective-permute\(", txt):
        dt, dims = m.group(1), m.group(2)
        if dt not in _HLO_DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _HLO_DTYPE_BYTES[dt]
    return total


def run_train(iters=6, hist_len=4):
    """fp8-vs-bf16 training A/B on a tp2 CPU mesh: loss parity + amax
    state + compiled bytes-accessed ratio."""
    _ensure_devices()
    import jax
    import jax.numpy as jnp

    from megatronapp_tpu.config.parallel_config import ParallelConfig
    from megatronapp_tpu.config.training_config import (
        OptimizerConfig, TrainingConfig,
    )
    from megatronapp_tpu.config.transformer_config import TransformerConfig
    from megatronapp_tpu.parallel.mesh import build_mesh
    from megatronapp_tpu.training.train import pretrain_gpt

    def one(fp8):
        model = TransformerConfig(
            num_layers=2, hidden_size=64, num_attention_heads=4,
            vocab_size=128, max_position_embeddings=64,
            compute_dtype=jnp.float32, tp_comm_overlap=True, fp8=fp8,
            fp8_amax_history_len=hist_len)
        par = ParallelConfig(tensor_parallel=2)
        ctx = build_mesh(par, devices=jax.devices()[:2])
        train = TrainingConfig(micro_batch_size=2, global_batch_size=4,
                               seq_length=32, train_iters=iters,
                               log_interval=1)
        opt = OptimizerConfig(lr=1e-3)
        res = pretrain_gpt(model, par, train, opt, ctx=ctx,
                           log_fn=lambda *_: None)
        return res, model, ctx

    rb, model_b, _ = one(False)
    rf, model_f, _ = one(True)
    rels = [abs(a - b) / abs(a) for a, b in zip(rb.losses, rf.losses)]

    # Deterministic byte evidence: compile ONE fwd+bwd microbatch step
    # both ways and compare the XLA cost model's bytes-accessed totals —
    # the fp8 ring chunks (and quantized residuals) are 1-byte where the
    # baseline moves 4-byte operands.
    import numpy as np

    from megatronapp_tpu.training.fp8 import init_fp8_state
    from megatronapp_tpu.training.train import gpt_microbatch_loss

    ctx = build_mesh(ParallelConfig(tensor_parallel=2),
                     devices=jax.devices()[:2])
    micro = {
        "tokens": np.ones((2, 32), np.int32),
        "labels": np.ones((2, 32), np.int32),
        "loss_mask": np.ones((2, 32), np.float32),
    }
    params = rb.state["params"]
    fp8_state = init_fp8_state(model_f)

    loss_b = gpt_microbatch_loss(model_b, ctx=ctx)
    loss_f = gpt_microbatch_loss(model_f, ctx=ctx)

    def grad_b(p, m):
        return jax.value_and_grad(lambda p_: loss_b(p_, m)[0])(p)

    def grad_f(pair, m):
        return jax.value_and_grad(
            lambda t: loss_f(t[0], m, fp8=t[1])[0])(pair)

    def bytes_accessed(jitted, *args):
        cost = jitted.lower(*args).compile().cost_analysis()
        cost = cost[0] if isinstance(cost, (list, tuple)) else cost
        return float(cost.get("bytes accessed", 0.0))

    with ctx.mesh:
        bytes_b = bytes_accessed(jax.jit(grad_b), params, micro)
        bytes_f = bytes_accessed(jax.jit(grad_f), (params, fp8_state),
                                 micro)
        pb_b = permute_bytes(jax.jit(grad_b), params, micro)
        pb_f = permute_bytes(jax.jit(grad_f), (params, fp8_state), micro)

    f8 = rf.state["fp8"]["block"]
    hist_filled = all(
        bool((np.asarray(site["hist"])[:, :, 0] > 0).all())
        for mod in f8.values() for site in mod.values())
    return {
        "losses_bf16": [round(float(x), 6) for x in rb.losses],
        "losses_fp8": [round(float(x), 6) for x in rf.losses],
        "max_rel_loss_diff": round(max(rels), 6),
        "loss_rtol": LOSS_RTOL,
        "within_tolerance": max(rels) <= LOSS_RTOL,
        "hist_filled": hist_filled,
        # GATED: ring-transport bytes off the compiled module's
        # collective-permute ops (fp8 chunks are 1-byte).
        "ring_permute_bytes": {"baseline": pb_b, "fp8": pb_f},
        "ring_permute_ratio": (round(pb_f / pb_b, 4) if pb_b else None),
        # Reported, NOT gated: raw cost-model totals (CPU emulation's
        # quantize/upcast intermediates dominate — see module doc).
        "step_bytes_accessed": {"baseline": bytes_b, "fp8": bytes_f},
    }


def run_kv(max_new=6):
    """fp8-vs-bf16 KV pools: byte ratio + greedy stream parity."""
    _ensure_devices()
    import jax
    import jax.numpy as jnp
    import numpy as np

    from megatronapp_tpu.config.transformer_config import TransformerConfig
    from megatronapp_tpu.inference.dynamic_engine import (
        DynamicInferenceEngine,
    )
    from megatronapp_tpu.inference.engine import SamplingParams
    from megatronapp_tpu.models.gpt import init_gpt_params

    # head_dim 64, bf16 baseline pool: the analytic quantized ratio is
    # (D+4)/(2D) = 0.531 — the acceptance bound (same bytes as int8).
    cfg = TransformerConfig(
        num_layers=2, hidden_size=128, num_attention_heads=2,
        num_query_groups=2, vocab_size=128, max_position_embeddings=128,
        compute_dtype=jnp.bfloat16, remat_policy="none")
    params, _ = init_gpt_params(jax.random.PRNGKey(7), cfg)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 128, n).astype(np.int32)
               for n in (9, 17, 30, 5)]

    out = {}
    streams = {}
    for dtype in ("bf16", "fp8", "int8"):
        eng = DynamicInferenceEngine(
            params, cfg, max_batch=4, max_seq_len=96,
            prefill_buckets=(32, 64), block_size=8,
            kv_cache_dtype=dtype)
        ids = [eng.add_request(p, max_new, SamplingParams(greedy=True))
               for p in prompts]
        res = eng.run_to_completion()
        eng.pool.audit()
        streams[dtype] = [res[r].tolist() for r in ids]
        out[dtype] = {"pool_bytes": eng.pool.bytes_total}
    ratio_fp8 = out["fp8"]["pool_bytes"] / out["bf16"]["pool_bytes"]
    ratio_int8 = out["int8"]["pool_bytes"] / out["bf16"]["pool_bytes"]
    return {
        "pool_bytes": {k: v["pool_bytes"] for k, v in out.items()},
        "fp8_ratio_vs_bf16": round(ratio_fp8, 4),
        "int8_ratio_vs_bf16": round(ratio_int8, 4),
        "fp8_at_or_below_int8": ratio_fp8 <= ratio_int8 + 1e-9,
        "greedy_match_fp8": streams["fp8"] == streams["bf16"],
        "greedy_match_int8": streams["int8"] == streams["bf16"],
    }


def run(iters=6, max_new=6):
    return {"train": run_train(iters=iters), "kv": run_kv(max_new)}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--iters", type=int, default=6)
    ap.add_argument("--max-new", type=int, default=6)
    args = ap.parse_args(argv)
    print(json.dumps(run(iters=args.iters, max_new=args.max_new)))


if __name__ == "__main__":
    main()
