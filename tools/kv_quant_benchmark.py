"""A/B microbenchmark: int8 vs bf16 KV-cache pages (ISSUE 10;
inference/paged_cache.py kv_cache_dtype, ops/pallas/paged_attention.py
quantized kernels).

Four measurements, identical requests on both pools:

  memory:   resident pool bytes at IDENTICAL block config, measured off
            the addressable arrays (int8 data + fp32 scales vs bf16
            data). The acceptance gate is ratio <= 0.55 — at D=64 the
            analytic ratio is (D+4)/(2D) = 0.531. Also reports
            sessions-admitted-at-capacity: how many full-length
            sessions fit a FIXED byte budget per dtype.
  decode:   tokens/s on a mixed-length continuous-batching workload +
            greedy stream parity (exact match expected on this model;
            first divergence reported if any).
  parity:   one decode step over IDENTICAL cache content (the bf16
            rows quantized into the int8 pool): max |Δlogit| must stay
            within LOGITS_ATOL — the documented accuracy gate.
  spec:     n-gram speculative decoding on a repetitive workload on
            both pools; acceptance-rate delta gated <= SPEC_ACC_EPS.

Weights ride along: params PTQ-quantized and kept RESIDENT
(inference/quantization.py residentize_params) vs dense — byte ratio
reported.

Runs on CPU out of the box (interpret-mode kernels; the pools are
stored bf16/int8 exactly as on TPU, so the byte accounting is
platform-independent).

  python tools/kv_quant_benchmark.py --max-new 6
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# Documented accuracy gates (README "Quantized serving"): greedy logits
# parity vs the bf16 pool on identical cache content, and the
# speculative acceptance-rate delta on the bench workload.
LOGITS_ATOL = 0.05   # measured ~0.007 on the bench model (PERF.md r14)
SPEC_ACC_EPS = 0.05


def _make_cfg():
    """Bench model: head_dim 64 (hidden 128 / 2 heads) so the analytic
    int8-pool ratio (D+4)/(2D) = 0.531 sits under the 0.55 gate, with a
    genuinely-bf16 baseline pool (compute_dtype bf16)."""
    import jax.numpy as jnp

    from megatronapp_tpu.config.transformer_config import TransformerConfig
    return TransformerConfig(
        num_layers=2, hidden_size=128, num_attention_heads=2,
        num_query_groups=2, vocab_size=128, max_position_embeddings=128,
        compute_dtype=jnp.bfloat16, remat_policy="none")


def _build(cfg, params, kv_dtype, max_batch=4, max_seq_len=96,
           block_size=8, num_blocks=None, **kw):
    from megatronapp_tpu.inference.dynamic_engine import (
        DynamicInferenceEngine,
    )
    return DynamicInferenceEngine(
        params, cfg, max_batch=max_batch, max_seq_len=max_seq_len,
        prefill_buckets=(32, 64), block_size=block_size,
        num_blocks=num_blocks, kv_cache_dtype=kv_dtype, **kw)


def _run_requests(engine, prompts, max_new):
    from megatronapp_tpu.inference.engine import SamplingParams
    ids = [engine.add_request(p, max_new, SamplingParams(greedy=True))
           for p in prompts]
    t0 = time.perf_counter()
    results = engine.run_to_completion()
    dt = time.perf_counter() - t0
    return [results[r].tolist() for r in ids], dt, len(prompts) * max_new


def run_memory_and_decode(max_batch: int = 4, max_seq_len: int = 96,
                          block_size: int = 8, max_new: int = 6):
    """Pool bytes at identical block config + sessions-at-capacity +
    tokens/s + greedy stream parity."""
    import jax
    import numpy as np

    from megatronapp_tpu.inference.paged_cache import cdiv
    from megatronapp_tpu.models.gpt import init_gpt_params

    cfg = _make_cfg()
    params, _ = init_gpt_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    lens = [4, 9, 17, 26, 34, 41, 49, 58]
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in lens]

    bf16 = _build(cfg, params, "bf16", max_batch, max_seq_len, block_size)
    b_toks, b_dt, n_new = _run_requests(bf16, prompts, max_new)
    int8 = _build(cfg, params, "int8", max_batch, max_seq_len, block_size)
    i_toks, i_dt, _ = _run_requests(int8, prompts, max_new)
    int8.pool.audit()

    bf16_bytes = bf16.pool.bytes_total
    int8_bytes = int8.pool.bytes_total
    # Sessions-at-capacity: the bf16 pool's byte budget, refilled with
    # blocks of each dtype; a session = one max-length sequence.
    budget = bf16_bytes
    blocks_per_session = cdiv(max_seq_len, block_size)
    sess = {}
    for name, eng in (("bf16", bf16), ("int8", int8)):
        blocks_in_budget = budget // eng.pool.bytes_per_block
        sess[name] = int(blocks_in_budget // blocks_per_session)

    first_div = None
    for a, b in zip(b_toks, i_toks):
        if a != b:
            first_div = next(i for i, (x, y) in enumerate(zip(a, b))
                             if x != y)
            break
    return {
        "max_batch": max_batch, "max_seq_len": max_seq_len,
        "block_size": block_size, "max_new": max_new,
        "head_dim": cfg.head_dim,
        "bf16_pool_bytes": bf16_bytes,
        "int8_pool_bytes": int8_bytes,
        "memory_ratio": round(int8_bytes / bf16_bytes, 4),
        "bytes_per_block": {"bf16": bf16.pool.bytes_per_block,
                            "int8": int8.pool.bytes_per_block},
        "sessions_at_capacity": sess,
        "bf16_tok_s": round(n_new / b_dt, 1),
        "int8_tok_s": round(n_new / i_dt, 1),
        "greedy_match": b_toks == i_toks,
        "first_divergence": first_div,
    }


def run_logits_parity(block_size: int = 8):
    """One decode step over IDENTICAL cache content: the bf16 pool's
    rows quantized into an int8 pool (+scales), logits compared — the
    documented LOGITS_ATOL gate, isolated from stream effects."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from megatronapp_tpu.inference.dynamic_engine import _paged_decode_step
    from megatronapp_tpu.models.gpt import init_gpt_params
    from megatronapp_tpu.ops.pallas.paged_attention import quantize_kv_rows

    cfg = _make_cfg()
    params, _ = init_gpt_params(jax.random.PRNGKey(1), cfg)
    b, mb, bs = 3, 4, block_size
    nb = b * mb + 1
    rng = np.random.default_rng(4)
    lengths = np.asarray([5, 17, 26], np.int32)
    table = (1 + np.arange(b * mb)).reshape(b, mb).astype(np.int32)

    shape = (cfg.num_layers, nb, bs, cfg.num_query_groups, cfg.head_dim)
    pools, qpools, spools = [], [], []
    for _ in range(2):
        data = rng.normal(scale=0.5, size=shape).astype(np.float32)
        pool = jnp.asarray(data, cfg.compute_dtype)
        q, s = quantize_kv_rows(pool)
        pools.append(pool)
        qpools.append(q)
        spools.append(s)

    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (b, 1)),
                         jnp.int32)
    lens = jnp.asarray(lengths)
    active = jnp.ones((b,), bool)
    tbl = jnp.asarray(table)
    base, _, _ = _paged_decode_step(params, tokens, tuple(pools), tbl, lens,
                                 active, cfg, mb * bs)
    quant, _, _ = _paged_decode_step(params, tokens, tuple(qpools), tbl,
                                  lens, active, cfg, mb * bs,
                                  scales=tuple(spools))
    diff = float(jnp.max(jnp.abs(base.astype(jnp.float32)
                                 - quant.astype(jnp.float32))))
    return {"max_abs_logit_diff": round(diff, 5),
            "logits_atol": LOGITS_ATOL,
            "within_bound": diff <= LOGITS_ATOL}


def run_spec_acceptance(max_new: int = 24, spec_k: int = 4):
    """n-gram speculative decoding A/B: acceptance-rate delta between
    the int8 and bf16 pools gated <= SPEC_ACC_EPS; greedy streams must
    also stay exact vs plain decode WITHIN each pool (the speculative
    exactness invariant is dtype-independent)."""
    import jax
    import numpy as np

    from megatronapp_tpu.models.gpt import init_gpt_params

    cfg = _make_cfg()
    params, _ = init_gpt_params(jax.random.PRNGKey(2), cfg)
    rng = np.random.default_rng(3)
    motifs = [rng.integers(0, cfg.vocab_size, 8).astype(np.int32)
              for _ in range(3)]
    prompts = [np.tile(m, 4) for m in motifs]

    out = {}
    for dtype in ("bf16", "int8"):
        spec = _build(cfg, params, dtype, max_batch=2, max_seq_len=128,
                      spec_method="ngram", spec_k=spec_k,
                      prefill_chunk=16)
        s_toks, _, _ = _run_requests(spec, prompts, max_new)
        plain = _build(cfg, params, dtype, max_batch=2, max_seq_len=128,
                       prefill_chunk=16)
        p_toks, _, _ = _run_requests(plain, prompts, max_new)
        st = spec.spec_stats
        out[dtype] = {
            "acceptance_rate": (round(st["accepted"] / st["proposed"], 4)
                                if st["proposed"] else 0.0),
            "tokens_per_step": (
                round(st["emitted_tokens"] / st["model_steps"], 4)
                if st["model_steps"] else 0.0),
            "exact_vs_plain": s_toks == p_toks,
        }
    delta = abs(out["int8"]["acceptance_rate"]
                - out["bf16"]["acceptance_rate"])
    out["acceptance_delta"] = round(delta, 4)
    out["acceptance_eps"] = SPEC_ACC_EPS
    out["within_bound"] = delta <= SPEC_ACC_EPS
    return out


def run_weight_bytes():
    """Dense vs resident-int8 params bytes (the --quantized-weights
    serving path)."""
    import jax

    from megatronapp_tpu.inference.quantization import (
        quantize_params, residentize_params, resident_nbytes,
    )
    from megatronapp_tpu.models.gpt import init_gpt_params

    cfg = _make_cfg()
    params, _ = init_gpt_params(jax.random.PRNGKey(0), cfg)
    q, _ = quantize_params(params)
    res = residentize_params(q)
    dense = resident_nbytes(params)
    resident = resident_nbytes(res)
    return {"dense_bytes": dense, "resident_int8_bytes": resident,
            "ratio": round(resident / dense, 4)}


def run(**kw):
    """All four measurements; returns a JSON-ready dict."""
    import jax

    md_kw = {k: v for k, v in kw.items()
             if k in ("max_batch", "max_seq_len", "block_size", "max_new")}
    sp_kw = {k: v for k, v in kw.items() if k in ("spec_k",)}
    return {"environment": jax.devices()[0].platform,
            "memory_decode": run_memory_and_decode(**md_kw),
            "logits_parity": run_logits_parity(
                block_size=kw.get("block_size", 8)),
            "spec_acceptance": run_spec_acceptance(**sp_kw),
            "weights": run_weight_bytes()}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--block-size", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=6)
    ap.add_argument("--spec-k", type=int, default=4)
    ap.add_argument("--local", action="store_true",
                    help="force the CPU backend")
    args = ap.parse_args(argv)

    if args.local:
        os.environ["JAX_PLATFORMS"] = "cpu"
    res = run(max_batch=args.max_batch, block_size=args.block_size,
              max_new=args.max_new, spec_k=args.spec_k)
    print(json.dumps(res))
    return 0


if __name__ == "__main__":
    sys.exit(main())
