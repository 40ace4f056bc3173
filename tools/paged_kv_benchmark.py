"""A/B microbenchmark: dense slot-cache vs paged block-pool serving
(ISSUE 3; inference/paged_cache.py, ops/pallas/paged_attention.py,
DynamicInferenceEngine paged=True).

Two workloads, identical requests on both backends (greedy, so outputs
must match token-for-token — asserted):

  decode: mixed prompt lengths through continuous batching. The dense
          backend allocates [L, max_batch, S_max, Hkv, D] regardless of
          actual lengths; the paged backend sizes its block pool to the
          workload's PEAK concurrent demand (+1 block slack per slot) —
          the reported memory ratio is the headline win.
  prefix: N requests sharing one long common prompt prefix. The paged
          backend serves the shared blocks from the refcounted prefix
          cache (prefill_tokens counts only what was actually computed);
          dense recomputes the prefix per request.

Runs on CPU out of the box (the paged-attention kernel runs in Pallas
interpret mode there) and on TPU unchanged. Reports one JSON line.

Note on CPU numbers: interpret-mode Pallas adds per-step overhead the
compiled TPU kernel doesn't have, so CPU decode throughput understates
the paged backend; the memory footprint and prefix-hit numbers are
platform-independent.

  python tools/paged_kv_benchmark.py --max-new 6
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _build(paged: bool, cfg, params, max_batch, max_seq_len, num_blocks,
           block_size, prefix_caching=True):
    from megatronapp_tpu.inference.dynamic_engine import (
        DynamicInferenceEngine,
    )
    return DynamicInferenceEngine(
        params, cfg, max_batch=max_batch, max_seq_len=max_seq_len,
        prefill_buckets=(32, 64), paged=paged, block_size=block_size,
        num_blocks=num_blocks, enable_prefix_caching=prefix_caching)


def _run_requests(engine, prompts, max_new):
    from megatronapp_tpu.inference.engine import SamplingParams
    ids = [engine.add_request(p, max_new, SamplingParams(greedy=True))
           for p in prompts]
    t0 = time.perf_counter()
    results = engine.run_to_completion()
    dt = time.perf_counter() - t0
    toks = [results[r].tolist() for r in ids]
    return toks, dt, len(prompts) * max_new


def _dense_cache_bytes(engine):
    return sum(c.size * c.dtype.itemsize for c in engine.cache)


def _make_cfg():
    import jax.numpy as jnp

    from megatronapp_tpu.config.transformer_config import TransformerConfig
    return TransformerConfig(
        num_layers=2, hidden_size=64, num_attention_heads=4,
        num_query_groups=2, vocab_size=128, max_position_embeddings=96,
        compute_dtype=jnp.float32, remat_policy="none")


def run_decode(max_batch: int = 4, max_seq_len: int = 96,
               block_size: int = 8, max_new: int = 6):
    """Mixed-length continuous batching: throughput + memory A/B."""
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

    # Pool sized to peak demand: the max_batch longest sequences at full
    # length, +1 block of slack each.
    demand = sorted((cdiv(n + max_new, block_size) + 1 for n in lens),
                    reverse=True)
    num_blocks = sum(demand[:max_batch])

    dense = _build(False, cfg, params, max_batch, max_seq_len, None,
                   block_size)
    d_toks, d_dt, n_new = _run_requests(dense, prompts, max_new)
    paged = _build(True, cfg, params, max_batch, max_seq_len, num_blocks,
                   block_size)
    p_toks, p_dt, _ = _run_requests(paged, prompts, max_new)

    dense_bytes = _dense_cache_bytes(dense)
    paged_bytes = paged.pool.bytes_total
    return {
        "max_batch": max_batch, "max_seq_len": max_seq_len,
        "block_size": block_size, "num_blocks": num_blocks,
        "prompt_lens": lens, "max_new": max_new,
        "dense_tok_s": round(n_new / d_dt, 1),
        "paged_tok_s": round(n_new / p_dt, 1),
        "dense_ms": round(d_dt * 1e3, 1), "paged_ms": round(p_dt * 1e3, 1),
        "dense_cache_bytes": dense_bytes,
        "paged_cache_bytes": paged_bytes,
        "memory_ratio": round(paged_bytes / dense_bytes, 4),
        "peak_blocks_in_use": paged.pool.stats["peak_blocks_in_use"],
        "parity_ok": d_toks == p_toks,
    }


def run_prefix(n_requests: int = 6, prefix_len: int = 48,
               suffix_len: int = 5, block_size: int = 8, max_new: int = 4):
    """Shared-prefix workload: prefix-cache hit rate + prefill savings."""
    import jax
    import numpy as np

    from megatronapp_tpu.models.gpt import init_gpt_params

    cfg = _make_cfg()
    params, _ = init_gpt_params(jax.random.PRNGKey(1), cfg)
    rng = np.random.default_rng(1)
    shared = rng.integers(0, cfg.vocab_size, prefix_len).astype(np.int32)
    prompts = [np.concatenate([
        shared, rng.integers(0, cfg.vocab_size, suffix_len).astype(np.int32)
    ]) for _ in range(n_requests)]

    dense = _build(False, cfg, params, 2, 96, None, block_size)
    d_toks, d_dt, _ = _run_requests(dense, prompts, max_new)
    paged = _build(True, cfg, params, 2, 96, None, block_size)
    p_toks, p_dt, _ = _run_requests(paged, prompts, max_new)

    st = paged.pool.stats
    total = st["prefix_hit_tokens"] + st["prefill_tokens"]
    return {
        "n_requests": n_requests, "prefix_len": prefix_len,
        "suffix_len": suffix_len, "block_size": block_size,
        "dense_ms": round(d_dt * 1e3, 1), "paged_ms": round(p_dt * 1e3, 1),
        "prefix_hit_tokens": st["prefix_hit_tokens"],
        "prefill_tokens_computed": st["prefill_tokens"],
        "hit_rate": round(st["prefix_hit_tokens"] / total, 4),
        "cow_copies": st["cow_copies"],
        "parity_ok": d_toks == p_toks,
    }


def run(**kw):
    """Both workloads; returns a JSON-ready dict."""
    import jax

    decode_kw = {k: v for k, v in kw.items()
                 if k in ("max_batch", "max_seq_len", "block_size",
                          "max_new")}
    prefix_kw = {k: v for k, v in kw.items()
                 if k in ("n_requests", "prefix_len", "block_size",
                          "max_new")}
    return {"environment": jax.devices()[0].platform,
            "decode": run_decode(**decode_kw),
            "prefix": run_prefix(**prefix_kw)}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--block-size", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=6)
    ap.add_argument("--n-requests", type=int, default=6)
    ap.add_argument("--prefix-len", type=int, default=48)
    ap.add_argument("--local", action="store_true",
                    help="force the CPU backend")
    args = ap.parse_args(argv)

    if args.local:
        os.environ["JAX_PLATFORMS"] = "cpu"
    res = run(max_batch=args.max_batch, block_size=args.block_size,
              max_new=args.max_new, n_requests=args.n_requests,
              prefix_len=args.prefix_len)
    print(json.dumps(res))
    return 0


if __name__ == "__main__":
    sys.exit(main())
