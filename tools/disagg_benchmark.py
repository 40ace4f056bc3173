"""A/B benchmark: colocated vs DISAGGREGATED serving under mixed traffic
(ISSUE 9; inference/disagg.py, prefill/decode sub-meshes with KV handoff
through the shared block pool).

The workload is the one disaggregation exists for: a batch of short
decode-heavy requests streaming tokens, plus one LONG prompt arriving
mid-stream.

  colocated:     one paged DynamicInferenceEngine — admission runs the
                 long prompt's ENTIRE chunked prefill inside the step
                 that admits it, so every short request's next token
                 waits for the whole prefill (the p99 token-interval
                 spike).
  disaggregated: DisaggServingEngine — the long prefill runs chunk by
                 chunk on the prefill sub-mesh, interleaved between
                 decode steps, and enters the decode batch by page-table
                 handoff; the short requests' token intervals stay
                 bounded by one chunk.

Both runs are greedy on identical params/requests, so token streams must
match exactly (asserted: parity_ok). Reported per mode:

  window_p99_ms  p99 short-request token interval over the WINDOW where
                 the long prefill is in flight (submit → its first
                 token) — the headline; disaggregated must be strictly
                 better.
  tokens_per_s   total generated tokens / wall second — disaggregation
                 must hold throughput (same total compute + the
                 per-chunk KV ship, so within ~10% of colocated).

Runs on CPU out of the box (sub-meshes are virtual host devices; the
paged kernels run in Pallas interpret mode). One JSON line.

  python tools/disagg_benchmark.py --long-len 192
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _ensure_devices(n: int):
    """Must run before jax import: virtual host devices for the
    sub-mesh split."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n}").strip()


def _make_cfg(max_seq_len):
    import jax.numpy as jnp

    from megatronapp_tpu.config.transformer_config import TransformerConfig
    return TransformerConfig(
        num_layers=2, hidden_size=64, num_attention_heads=4,
        num_query_groups=2, vocab_size=128,
        max_position_embeddings=max_seq_len,
        compute_dtype=jnp.float32, remat_policy="none")


def _ms(x):
    return None if x is None else round(x * 1e3, 2)


def _pctl(xs, q):
    import numpy as np
    return float(np.percentile(np.asarray(xs), q)) if xs else None


def _drive(eng, short_prompts, long_prompt, short_new, long_new,
           warm_tokens=3):
    """Drive the engine step by step: submit the shorts, decode until
    each has `warm_tokens` tokens, then submit the long prompt and run
    everything to completion. Records each short request's token
    intervals, flagging those that land while the long prefill is in
    flight (the SLO window)."""
    from megatronapp_tpu.inference.engine import SamplingParams
    gp = SamplingParams(greedy=True)
    short_ids = [eng.add_request(p, short_new, gp) for p in short_prompts]
    long_id = None
    last_tok_t = {}
    counts = {rid: 0 for rid in short_ids}
    window = []          # short-request intervals while long in flight
    all_iv = []
    n_tokens = 0
    t_start = time.perf_counter()
    long_submit_t = long_first_tok_t = None
    while eng.has_work or long_id is None:
        ev = eng.step()
        now = time.perf_counter()
        # The window STAYS open for the whole event batch in which the
        # long prompt's first token lands: in the colocated engine that
        # batch is the admission step whose monolithic prefill caused
        # the stall being measured.
        window_open = (long_id is not None and long_first_tok_t is None)
        for rid, _tok in ev["tokens"]:
            n_tokens += 1
            if rid in counts:
                counts[rid] += 1
                if rid in last_tok_t:
                    iv = now - last_tok_t[rid]
                    all_iv.append(iv)
                    if window_open:
                        window.append(iv)
                last_tok_t[rid] = now
            elif rid == long_id and long_first_tok_t is None:
                long_first_tok_t = now
        if long_id is None and all(c >= warm_tokens
                                   for c in counts.values()):
            long_id = eng.add_request(long_prompt, long_new, gp)
            long_submit_t = time.perf_counter()
    wall = time.perf_counter() - t_start
    streams = []
    for rid in short_ids + [long_id]:
        req = eng.requests.get(rid)
        streams.append(None if req is None else req.tokens.tolist())
    return {
        "streams": streams, "window_iv": window, "all_iv": all_iv,
        "wall_s": wall, "tokens": n_tokens,
        "prefill_stall_s": (
            None if long_submit_t is None or long_first_tok_t is None
            else long_first_tok_t - long_submit_t),
    }


def run(n_short: int = 3, short_len: int = 8, short_new: int = 48,
        long_len: int = 192, long_new: int = 4, block_size: int = 16,
        prefill_chunk: int = 16, max_seq_len: int = 256, tp: int = 1):
    """Both modes on identical traffic; returns a JSON-ready dict."""
    import jax
    import numpy as np

    from megatronapp_tpu.inference.disagg import DisaggServingEngine
    from megatronapp_tpu.inference.dynamic_engine import (
        DynamicInferenceEngine,
    )
    from megatronapp_tpu.models.gpt import init_gpt_params

    cfg = _make_cfg(max_seq_len)
    params, _ = init_gpt_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    short_prompts = [rng.integers(0, cfg.vocab_size, short_len)
                     .astype(np.int32) for _ in range(n_short)]
    long_prompt = rng.integers(0, cfg.vocab_size, long_len
                               ).astype(np.int32)
    max_batch = n_short + 1

    def leg(mode):
        # Prefix caching OFF in both legs: the warmup pass must not turn
        # the measured long prefill into a cache hit, and the A/B is
        # about scheduling, not prefix reuse.
        if mode == "colocated":
            eng = DynamicInferenceEngine(
                params, cfg, max_batch=max_batch, max_seq_len=max_seq_len,
                prefill_buckets=(32, max_seq_len),
                block_size=block_size, prefill_chunk=prefill_chunk,
                enable_prefix_caching=False)
        else:
            eng = DisaggServingEngine(
                params, cfg, max_batch=max_batch, max_seq_len=max_seq_len,
                prefill_buckets=(32, max_seq_len), block_size=block_size,
                prefill_chunk=prefill_chunk, prefill_slots=2, tp=tp,
                enable_prefix_caching=False)
        # Warmup: trace every jit both legs will hit mid-measurement
        # (short bucket, long bucket, decode, sampling, handoff
        # write/adopt) — serving systems pre-warm at startup, and a
        # compile landing inside the measured window would A/B the
        # compiler, not the scheduler.
        _drive(eng, short_prompts, long_prompt, 4, 2, warm_tokens=1)
        r = _drive(eng, short_prompts, long_prompt, short_new, long_new)
        eng.pool.audit()
        out = {
            "window_p50_ms": _ms(_pctl(r["window_iv"], 50)),
            "window_p99_ms": _ms(_pctl(r["window_iv"], 99)),
            "window_max_ms": _ms(max(r["window_iv"])
                                 if r["window_iv"] else None),
            "overall_p99_ms": _ms(_pctl(r["all_iv"], 99)),
            "prefill_stall_ms": _ms(r["prefill_stall_s"]),
            "tokens_per_s": round(r["tokens"] / r["wall_s"], 1),
            "wall_ms": _ms(r["wall_s"]),
        }
        if mode == "disagg":
            snap = eng.stats_snapshot()["disagg"]
            out["handoff_transfers"] = snap["handoff"]["transfers"]
            out["kv_shipped_bytes"] = snap["handoff"]["kv_shipped_bytes"]
            out["prefill_chunks"] = snap["prefill_worker"]["chunks"]
        return out, r["streams"]

    co, co_streams = leg("colocated")
    dg, dg_streams = leg("disagg")
    return {
        "environment": jax.devices()[0].platform,
        "n_short": n_short, "short_len": short_len,
        "short_new": short_new, "long_len": long_len,
        "block_size": block_size, "prefill_chunk": prefill_chunk,
        "tp": tp,
        "colocated": co,
        "disagg": dg,
        "p99_ratio": (round(co["window_p99_ms"] / dg["window_p99_ms"], 3)
                      if co["window_p99_ms"] and dg["window_p99_ms"]
                      else None),
        "tokens_s_ratio": (round(dg["tokens_per_s"] / co["tokens_per_s"],
                                 3) if co["tokens_per_s"] else None),
        "parity_ok": co_streams == dg_streams,
    }


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--n-short", type=int, default=3)
    ap.add_argument("--short-new", type=int, default=48)
    ap.add_argument("--long-len", type=int, default=192)
    ap.add_argument("--prefill-chunk", type=int, default=16)
    ap.add_argument("--tp", type=int, default=1)
    ap.add_argument("--local", action="store_true",
                    help="force the CPU backend (virtual device mesh)")
    args = ap.parse_args(argv)

    if args.local:
        os.environ["JAX_PLATFORMS"] = "cpu"
    _ensure_devices(max(8, 2 * args.tp))
    res = run(n_short=args.n_short, short_new=args.short_new,
              long_len=args.long_len, prefill_chunk=args.prefill_chunk,
              tp=args.tp)
    print(json.dumps(res))
    return 0


if __name__ == "__main__":
    sys.exit(main())
