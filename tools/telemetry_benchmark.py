"""Telemetry-overhead A/B (ISSUE 12): driver soak with the telemetry
spine ON (metrics registry + request-lifecycle ring tracer) vs OFF.

The observability contract is "always-on-able": counters at allocator /
engine / driver sites plus per-request B/E spans must not tax the decode
loop. Two soaks of identical greedy requests through the paged
continuous-batching engine, telemetry off then on (greedy, so the token
streams must match — asserted); the headline is the tokens/s ratio
(gate: >= 0.95), plus the disabled-path microbench (ns per site call —
one dict-truthiness check, the chaos.py bound).

Runs on CPU out of the box; one JSON line.

  python tools/telemetry_benchmark.py --max-new 24
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

RATIO_GATE = 0.95


def _make_cfg():
    import jax.numpy as jnp

    from megatronapp_tpu.config.transformer_config import TransformerConfig
    return TransformerConfig(
        num_layers=2, hidden_size=64, num_attention_heads=4,
        num_query_groups=2, vocab_size=128, max_position_embeddings=96,
        compute_dtype=jnp.float32, remat_policy="none")


def _set_telemetry(on: bool, capacity: int = 16384):
    from megatronapp_tpu.trace.request_trace import get_request_tracer
    from megatronapp_tpu.utils import metrics
    rt = get_request_tracer()
    if on:
        metrics.enable()
        rt.configure(enabled=True, capacity=capacity)
    else:
        metrics.disable()
        rt.configure(enabled=False)
    rt.reset()


def _soak(params, cfg, on: bool, n_requests: int, prompt_len: int,
          max_new: int, repeats: int):
    """One telemetry arm: fresh engine, warmup pass (compiles), then
    `repeats` timed waves of identical greedy requests. Returns
    (tokens_per_sec, first wave's streams)."""
    import numpy as np

    from megatronapp_tpu.inference.dynamic_engine import (
        DynamicInferenceEngine,
    )
    from megatronapp_tpu.inference.engine import SamplingParams
    _set_telemetry(on)
    eng = DynamicInferenceEngine(
        params, cfg, max_batch=4, max_seq_len=96, prefill_buckets=(32,),
        block_size=8)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, prompt_len).astype(np.int32)
               for _ in range(n_requests)]
    # Warmup: compile every jit shape this workload touches.
    wid = eng.add_request(prompts[0], max_new,
                          SamplingParams(greedy=True))
    eng.run_to_completion()

    streams = None
    t0 = time.perf_counter()
    emitted = 0
    for _ in range(repeats):
        ids = [eng.add_request(p, max_new, SamplingParams(greedy=True))
               for p in prompts]
        results = eng.run_to_completion()
        wave = [results[r].tolist() for r in ids]
        if streams is None:
            streams = wave
        emitted += n_requests * max_new
    dt = time.perf_counter() - t0
    del wid
    return emitted / dt, streams


def _disabled_path_ns(iters: int = 200_000) -> float:
    """ns per disabled-registry site call (inc + observe pair) — the
    one-dict-check bound the chaos registry pins too."""
    from megatronapp_tpu.utils import metrics
    metrics.disable()
    t0 = time.perf_counter_ns()
    for _ in range(iters):
        metrics.inc("bench_x")
        metrics.observe("bench_y", 1.0)
    return (time.perf_counter_ns() - t0) / (2 * iters)


def run(n_requests: int = 6, prompt_len: int = 16, max_new: int = 24,
        repeats: int = 3):
    import jax

    cfg = _make_cfg()
    from megatronapp_tpu.models.gpt import init_gpt_params
    params, _ = init_gpt_params(jax.random.PRNGKey(0), cfg)

    tok_s_off, streams_off = _soak(params, cfg, False, n_requests,
                                   prompt_len, max_new, repeats)
    tok_s_on, streams_on = _soak(params, cfg, True, n_requests,
                                 prompt_len, max_new, repeats)
    assert streams_on == streams_off, (
        "telemetry changed the greedy token streams")

    from megatronapp_tpu.trace.request_trace import get_request_tracer
    from megatronapp_tpu.utils import metrics
    snap = metrics.snapshot()
    trace_records = len(get_request_tracer().dump())
    ns_per_call = _disabled_path_ns()
    _set_telemetry(False)

    ratio = tok_s_on / tok_s_off
    return {
        "telemetry": {
            "tokens_per_sec_off": round(tok_s_off, 1),
            "tokens_per_sec_on": round(tok_s_on, 1),
            "ratio_on_over_off": round(ratio, 4),
            "gate": RATIO_GATE,
            "pass": bool(ratio >= RATIO_GATE),
            "streams_match": True,
        },
        "disabled_path_ns_per_call": round(ns_per_call, 1),
        "on_arm_counters": {
            k: v for k, v in snap.get("counters", {}).items()},
        "on_arm_trace_records": trace_records,
        "workload": {
            "n_requests": n_requests, "prompt_len": prompt_len,
            "max_new": max_new, "repeats": repeats,
        },
    }


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--n-requests", type=int, default=6)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=24)
    ap.add_argument("--repeats", type=int, default=3)
    args = ap.parse_args(argv)
    print(json.dumps(run(n_requests=args.n_requests,
                         prompt_len=args.prompt_len,
                         max_new=args.max_new, repeats=args.repeats)))


if __name__ == "__main__":
    main()
