"""A/B microbenchmark: speculative vs plain decode on the paged engine
(ISSUE 4; inference/speculative.py, `_paged_multiquery_step`).

Greedy workload on a repetitive prompt (a tiled token motif — the
shape of retrieval/code/agent traffic where prompt-lookup wins), run
identically on three engines:

  plain: paged continuous batching, one token per model step.
  ngram: model-free prompt-lookup proposer + exact verification.
  mtp:   self-draft through MTP depth heads (random-init heads here, so
         acceptance is a floor, not a ceiling — included to exercise the
         path end to end).

Greedy speculation is BIT-IDENTICAL to plain decode by construction —
asserted per request. The headline numbers are the n-gram proposer's
acceptance rate and tokens per model step (>= 1.2x plain is the ISSUE 4
acceptance bar on this workload); wall-clock on CPU understates the win
because interpret-mode Pallas dominates, so tokens/step is the
platform-independent metric (each verify step costs ~one decode step on
a real chip — the K+1 queries batch into the same kernel launch).

Reports one JSON line.

  python tools/spec_decode_benchmark.py --max-new 24 --spec-k 4
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _make_cfg(mtp: bool = False):
    import jax.numpy as jnp

    from megatronapp_tpu.config.transformer_config import TransformerConfig
    return TransformerConfig(
        num_layers=2, hidden_size=64, num_attention_heads=4,
        num_query_groups=2, vocab_size=128, max_position_embeddings=256,
        compute_dtype=jnp.float32, remat_policy="none",
        mtp_num_layers=(2 if mtp else None))


def _prompts(vocab: int, n_requests: int, motif_len: int, repeats: int):
    import numpy as np
    rng = np.random.default_rng(0)
    out = []
    for _ in range(n_requests):
        motif = rng.integers(0, vocab, motif_len).astype(np.int32)
        out.append(np.tile(motif, repeats))
    return out


def _run(params, cfg, prompts, max_new, spec_method, spec_k,
         max_batch=2, block_size=8):
    from megatronapp_tpu.inference.dynamic_engine import (
        DynamicInferenceEngine,
    )
    from megatronapp_tpu.inference.engine import SamplingParams
    eng = DynamicInferenceEngine(
        params, cfg, max_batch=max_batch, max_seq_len=256,
        prefill_buckets=(64, 128), block_size=block_size,
        spec_method=spec_method, spec_k=spec_k, prefill_chunk=32)
    ids = [eng.add_request(p, max_new, SamplingParams(greedy=True))
           for p in prompts]
    t0 = time.perf_counter()
    results = eng.run_to_completion()
    dt = time.perf_counter() - t0
    eng.pool.audit()
    toks = [results[r].tolist() for r in ids]
    return toks, dt, eng


def run(n_requests: int = 4, motif_len: int = 12, repeats: int = 4,
        max_new: int = 24, spec_k: int = 4):
    """Plain vs ngram (vs mtp) A/B; returns a JSON-ready dict."""
    import jax

    from megatronapp_tpu.models.gpt import init_gpt_params

    cfg = _make_cfg(mtp=True)
    params, _ = init_gpt_params(jax.random.PRNGKey(0), cfg)
    prompts = _prompts(cfg.vocab_size, n_requests, motif_len, repeats)

    plain_toks, plain_dt, plain_eng = _run(params, cfg, prompts, max_new,
                                           None, spec_k)
    plain_tps = (plain_eng.spec_stats["emitted_tokens"]
                 / max(plain_eng.spec_stats["model_steps"], 1))

    out = {
        "environment": jax.devices()[0].platform,
        "n_requests": n_requests, "motif_len": motif_len,
        "repeats": repeats, "max_new": max_new, "spec_k": spec_k,
        "plain": {"ms": round(plain_dt * 1e3, 1),
                  "tokens_per_step": round(plain_tps, 3),
                  "model_steps": plain_eng.spec_stats["model_steps"]},
    }
    for method in ("ngram", "mtp"):
        toks, dt, eng = _run(params, cfg, prompts, max_new, method, spec_k)
        ss = eng.stats_snapshot()["speculative"]
        out[method] = {
            "ms": round(dt * 1e3, 1),
            "acceptance_rate": ss["acceptance_rate"],
            "tokens_per_step": ss["tokens_per_step"],
            "model_steps": ss["model_steps"],
            "speedup_tokens_per_step": round(
                ss["tokens_per_step"] / plain_tps, 3) if plain_tps else 0.0,
            "parity_ok": toks == plain_toks,
        }
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--n-requests", type=int, default=4)
    ap.add_argument("--motif-len", type=int, default=12)
    ap.add_argument("--repeats", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=24)
    ap.add_argument("--spec-k", type=int, default=4)
    ap.add_argument("--local", action="store_true",
                    help="force the CPU backend")
    args = ap.parse_args(argv)
    if args.local:
        os.environ["JAX_PLATFORMS"] = "cpu"
    res = run(n_requests=args.n_requests, motif_len=args.motif_len,
              repeats=args.repeats, max_new=args.max_new,
              spec_k=args.spec_k)
    print(json.dumps(res))
    return 0


if __name__ == "__main__":
    sys.exit(main())
