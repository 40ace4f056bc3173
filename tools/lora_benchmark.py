"""Multi-tenant batched-LoRA serving A/B (ISSUE 19; inference/lora.py
AdapterCache + the segmented batched-LoRA GEMM in ops/pallas/kernel_gen).

Three gates on one tiny GPT, all CPU-runnable (interpret-mode kernels;
the bank byte accounting is platform-independent):

  batched:  ONE engine decodes a mixed batch of N_ADAPTERS distinct
            adapters together (the segmented kernel DMAs each
            segment's bank slot once per step) vs the SAME engine
            serving the same requests one at a time. Gate:
            batched tokens/s >= 1.5x serial at 8 adapters, with every
            batched greedy stream token-exact vs its serial run.
  bytes:    rank-exact HBM accounting — the cache's per-adapter bytes
            must equal the analytic adapter_nbytes formula AND the sum
            of the factor-array sizes; bank bytes must be exactly
            (max_resident + 1 NULL slot) x adapter bytes.
  zero_b:   B=0 adapters add an exact 0.0 — streams through the LoRA
            path are BITWISE those of an engine with no adapter cache.


  python tools/lora_benchmark.py --adapters 8 --max-new 8
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

SPEEDUP_GATE = 1.5   # batched vs serial tokens/s at 8 adapters


def _make_cfg():
    import jax.numpy as jnp

    from megatronapp_tpu.config.transformer_config import TransformerConfig
    return TransformerConfig(
        num_layers=2, hidden_size=64, num_attention_heads=4,
        num_query_groups=2, vocab_size=128, max_position_embeddings=64,
        compute_dtype=jnp.float32, remat_policy="none")


def _build(params, cfg, cache=None, max_batch=8):
    from megatronapp_tpu.inference.dynamic_engine import (
        DynamicInferenceEngine,
    )
    return DynamicInferenceEngine(
        params, cfg, max_batch=max_batch, max_seq_len=48,
        prefill_buckets=(16,), block_size=8,
        adapter_cache=cache)


def _drain(engine, reqs, max_new, t0=None):
    """Submit (prompt, rid, adapter_id) triples together, run to
    completion; returns ({rid: tokens}, wall_s, tokens)."""
    from megatronapp_tpu.inference.engine import SamplingParams
    t0 = time.perf_counter() if t0 is None else t0
    for prompt, rid, aid in reqs:
        engine.add_request(prompt, max_new, SamplingParams(greedy=True),
                           request_id=rid, adapter_id=aid)
    res = engine.run_to_completion()
    dt = time.perf_counter() - t0
    streams = {rid: res[rid].tolist() for _, rid, _ in reqs}
    return streams, dt, sum(len(s) for s in streams.values())


def run(n_adapters: int = 8, rank: int = 8, max_new: int = 8,
        prompt_len: int = 10, max_resident: int = None):
    import jax
    import numpy as np

    from megatronapp_tpu.inference.lora import (
        AdapterCache, AdapterRegistry, LoraAdapter, adapter_nbytes,
        lora_target_dims,
    )
    from megatronapp_tpu.models.gpt import init_gpt_params

    cfg = _make_cfg()
    params, _ = init_gpt_params(jax.random.PRNGKey(7), cfg)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, prompt_len).astype(
        np.int32) for _ in range(n_adapters)]
    ids = [f"tenant-{i}" for i in range(n_adapters)]
    reg = AdapterRegistry()
    for i, aid in enumerate(ids):
        reg.register(LoraAdapter.random(aid, cfg, rank=rank,
                                        seed=10 + i))
        reg.register(LoraAdapter.random(f"z{i}", cfg, rank=rank,
                                        seed=10 + i, zero_b=True))
    cache = AdapterCache(cfg, reg,
                         max_resident=max_resident or n_adapters,
                         rank=rank)
    eng = _build(params, cfg, cache, max_batch=n_adapters)

    # Warmup: compile prefill + decode (and fault in adapter banks)
    # outside the timed windows.
    _drain(eng, [(prompts[0], 10_000, ids[0])], max_new)
    eng.pop_request(10_000)

    # Serial leg: same engine (same compiled steps), one adapter alone
    # per run — rid minted per leg so the fold_in chain matches the
    # batched leg exactly.
    serial_streams = {}
    t0 = time.perf_counter()
    for i, (p, aid) in enumerate(zip(prompts, ids)):
        s, _, _ = _drain(eng, [(p, i, aid)], max_new, t0=t0)
        eng.pop_request(i)
        serial_streams.update(s)
    serial_dt = time.perf_counter() - t0
    serial_tok = sum(len(s) for s in serial_streams.values())

    # Batched leg: all adapters in ONE mixed batch.
    batched_streams, batched_dt, batched_tok = _drain(
        eng, [(p, i, aid) for i, (p, aid) in
              enumerate(zip(prompts, ids))], max_new)
    cache.audit()
    mixed_match = batched_streams == serial_streams
    serial_tok_s = serial_tok / max(serial_dt, 1e-9)
    batched_tok_s = batched_tok / max(batched_dt, 1e-9)
    speedup = batched_tok_s / max(serial_tok_s, 1e-9)

    # Byte gate: cache bytes must be the analytic rank-exact formula
    # AND the literal sum of factor-array sizes.
    ad = reg.get(ids[0])
    arrays = sum(np.asarray(ad.a[t]).nbytes + np.asarray(ad.b[t]).nbytes
                 for t in lora_target_dims(cfg))
    formula = adapter_nbytes(cfg, rank, cfg.num_layers, 4)
    slots = cache.max_resident + 1
    rank_exact = (cache.adapter_nbytes == formula == arrays
                  and cache.bank_bytes() == slots * formula)

    # Zero-B parity gate: BITWISE unchanged streams vs no cache at all.
    base = _build(params, cfg, None, max_batch=2)
    zb = [(prompts[0], 0, None), (prompts[1], 1, None)]
    want, _, _ = _drain(base, zb, max_new)
    got, _, _ = _drain(eng, [(prompts[0], 20_000, "z0"),
                             (prompts[1], 20_001, "z1")], max_new)
    zero_b_match = (want[0] == got[20_000] and want[1] == got[20_001])

    return {
        "adapters": n_adapters, "rank": rank, "max_new": max_new,
        "serial": {"tokens": serial_tok, "wall_s": round(serial_dt, 3),
                   "tok_s": round(serial_tok_s, 1)},
        "batched": {"tokens": batched_tok,
                    "wall_s": round(batched_dt, 3),
                    "tok_s": round(batched_tok_s, 1)},
        "speedup": round(speedup, 2),
        "within_gate": bool(speedup >= SPEEDUP_GATE
                            and mixed_match and zero_b_match
                            and rank_exact),
        "mixed_matches_serial": bool(mixed_match),
        "zero_b_bitwise": bool(zero_b_match),
        "bytes": {"adapter_bytes": int(cache.adapter_nbytes),
                  "formula_bytes": int(formula),
                  "bank_bytes": int(cache.bank_bytes()),
                  "rank_exact": bool(rank_exact)},
        "cache": cache.stats_snapshot(),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="batched-LoRA serving A/B (ISSUE 19)")
    ap.add_argument("--adapters", type=int, default=8)
    ap.add_argument("--rank", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=8)
    args = ap.parse_args(argv)
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    res = run(n_adapters=args.adapters, rank=args.rank,
              max_new=args.max_new)
    print(json.dumps(res))
    return 0 if res["within_gate"] else 1


if __name__ == "__main__":
    sys.exit(main())
