"""A/B benchmark: KV-affinity fleet router vs round-robin on a
shared-prefix workload (ISSUE 14; inference/fleet.py).

The workload is the one the affinity signal exists for: G groups of
requests sharing a long prompt prefix (think system prompts / few-shot
templates at fleet scale). Group leaders arrive first and register
their prefix blocks on whichever replica admitted them; the followers
then either land on the SAME replica (affinity routing — their prefill
is mostly a prefix-cache hit) or get sprayed across the fleet
(round-robin — every follower on a different replica re-prefills the
whole prefix).

Both legs run greedy on identical params/replicas/requests, so every
request's token stream must match exactly across policies (parity_ok).
A final phase force-migrates one mid-decode session between replicas
and pins its stream against the unmigrated baseline (migration_ok) —
the copy-exact export/import path exercised under the bench gates.

Reported per policy:

  prefix_hit_rate   fleet-aggregate prefix-cache hit tokens / total
                    prompt tokens — the headline; affinity must beat
                    round-robin strictly.
  decode_p99_ms     p99 token interval across all streams (router-step
                    granularity; CPU numbers are A/B-relative only).
  migrations        router-counted live migrations (the forced phase).

Runs on CPU out of the box (replicas are plain paged engines on the
host device). One JSON line.

  python tools/fleet_benchmark.py --groups 4 --followers 3
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _make_cfg(max_seq_len):
    import jax.numpy as jnp

    from megatronapp_tpu.config.transformer_config import TransformerConfig
    return TransformerConfig(
        num_layers=2, hidden_size=64, num_attention_heads=4,
        num_query_groups=2, vocab_size=128,
        max_position_embeddings=max_seq_len,
        compute_dtype=jnp.float32, remat_policy="none")


def _pctl(xs, q):
    import numpy as np
    return float(np.percentile(np.asarray(xs), q)) if xs else None


def run(n_replicas: int = 2, groups: int = 4, followers: int = 3,
        prefix_len: int = 32, tail_len: int = 4, max_new: int = 8,
        block_size: int = 8, max_seq_len: int = 96,
        kv_cache_dtype: str = "bf16"):
    """Both policies on identical traffic; returns a JSON-ready dict."""
    import jax
    import numpy as np

    from megatronapp_tpu.inference.dynamic_engine import (
        DynamicInferenceEngine,
    )
    from megatronapp_tpu.inference.engine import SamplingParams
    from megatronapp_tpu.inference.fleet import FleetRouter
    from megatronapp_tpu.models.gpt import init_gpt_params

    cfg = _make_cfg(max_seq_len)
    params, _ = init_gpt_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    prompts = []          # [(group, prompt)]
    for g in range(groups):
        prefix = rng.integers(0, cfg.vocab_size, prefix_len
                              ).astype(np.int32)
        for _ in range(1 + followers):
            tail = rng.integers(0, cfg.vocab_size, tail_len
                                ).astype(np.int32)
            prompts.append((g, np.concatenate([prefix, tail])))
    gp = SamplingParams(greedy=True)

    def leg(policy):
        def factory(i, **hints):
            # Pool sized for the workload (groups' cached prefixes +
            # two active sessions) — an undersized pool turns the A/B
            # into an eviction/preemption study instead of a routing
            # one.
            return DynamicInferenceEngine(
                params, cfg, max_batch=2, max_seq_len=max_seq_len,
                prefill_buckets=(prefix_len + tail_len,),
                block_size=block_size, kv_cache_dtype=kv_cache_dtype,
                num_blocks=groups * (prefix_len // block_size + 2)
                + 4 * ((prefix_len + tail_len + max_new)
                       // block_size + 2))

        fr = FleetRouter(engine_factory=factory,
                         num_replicas=n_replicas, policy=policy)
        streams = {}
        intervals = []
        last_tok_t = {}
        # Group leaders first: submit, run until each leader's prefix is
        # registered (its request completes), then the followers — the
        # admission decision under test is the FOLLOWERS'.
        leaders = [p for i, (g, p) in enumerate(prompts)
                   if i % (1 + followers) == 0]
        followers_l = [p for i, (g, p) in enumerate(prompts)
                       if i % (1 + followers) != 0]
        lead_ids = [fr.add_request(p, max_new, gp) for p in leaders]
        res = fr.run_to_completion()
        for rid, p in zip(lead_ids, leaders):
            streams[len(streams)] = res[rid].tolist()
        f_ids = [fr.add_request(p, max_new, gp) for p in followers_l]
        t_start = time.perf_counter()
        while fr.has_work:
            ev = fr.step()
            now = time.perf_counter()
            for rid, _tok in ev["tokens"]:
                if rid in last_tok_t:
                    intervals.append(now - last_tok_t[rid])
                last_tok_t[rid] = now
        for rid, p in zip(f_ids, followers_l):
            req = fr.pop_request(rid)
            streams[len(streams)] = req.tokens.tolist()
        wall = time.perf_counter() - t_start
        snap = fr.stats_snapshot()["fleet"]
        per_replica_admits = [r.get("prefill_tokens", 0)
                              + r.get("prefix_hit_tokens", 0)
                              for r in snap["replicas"]]
        out = {
            "prefix_hit_rate": snap["prefix_hit_rate"],
            "affinity_admissions": snap["affinity_admissions"],
            "decode_p99_ms": (round(_pctl(intervals, 99) * 1e3, 2)
                              if intervals else None),
            "wall_ms": round(wall * 1e3, 1),
            "tokens_per_replica": per_replica_admits,
        }
        return out, streams, fr

    # Warmup leg (discarded): compilation is cached process-globally
    # across identical engine closures, so the FIRST leg otherwise pays
    # every trace inside its measured window — the A/B would compare
    # the compiler, not the router (same rationale as the disagg
    # benchmark's warmup drive). Measured legs run on fresh routers so
    # hit rates start from empty caches.
    leg("affinity")
    aff, aff_streams, fr_aff = leg("affinity")
    rr, rr_streams, _ = leg("round_robin")

    # Forced-migration phase on the affinity fleet: a fresh mid-decode
    # session hops replicas and must continue token-exact vs its own
    # unmigrated twin (run earlier in the round-robin leg? No — run the
    # twin on a fresh single replica for a clean baseline).
    long_prompt = np.concatenate([prompts[0][1][:prefix_len],
                                  np.asarray([1, 2, 3], np.int32)])
    base_eng = DynamicInferenceEngine(
        params, cfg, max_batch=2, max_seq_len=max_seq_len,
        prefill_buckets=(prefix_len + tail_len,),
        block_size=block_size, kv_cache_dtype=kv_cache_dtype,
        enable_prefix_caching=False)
    b_rid = base_eng.add_request(long_prompt, 12, gp)
    baseline = base_eng.run_to_completion()[b_rid].tolist()
    m_rid = fr_aff.add_request(long_prompt, 12, gp)
    src = fr_aff._owner[m_rid]
    while len(fr_aff.replicas[src].engine.requests[m_rid].generated) < 4:
        fr_aff.step()
    dst = next(r.idx for r in fr_aff.replicas if r.idx != src)
    migrated = fr_aff.migrate_request(m_rid, dst)
    res = fr_aff.run_to_completion()
    migration_ok = bool(migrated) and res[m_rid].tolist() == baseline
    for rep in fr_aff.replicas:
        rep.engine.pool.audit()

    return {
        "environment": __import__("jax").devices()[0].platform,
        "n_replicas": n_replicas, "groups": groups,
        "followers": followers, "prefix_len": prefix_len,
        "block_size": block_size, "kv_cache_dtype": kv_cache_dtype,
        "affinity": aff,
        "round_robin": rr,
        "hit_rate_win": round(
            aff["prefix_hit_rate"] - rr["prefix_hit_rate"], 4),
        "migrations": fr_aff.router_stats["migrations"],
        "migration_ok": migration_ok,
        "parity_ok": aff_streams == rr_streams,
    }


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--replicas", type=int, default=2)
    ap.add_argument("--groups", type=int, default=4)
    ap.add_argument("--followers", type=int, default=3)
    ap.add_argument("--prefix-len", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--kv-cache-dtype", default="bf16")
    ap.add_argument("--local", action="store_true",
                    help="force the CPU backend")
    args = ap.parse_args(argv)
    if args.local:
        os.environ["JAX_PLATFORMS"] = "cpu"
    res = run(n_replicas=args.replicas, groups=args.groups,
              followers=args.followers, prefix_len=args.prefix_len,
              max_new=args.max_new, kv_cache_dtype=args.kv_cache_dtype)
    print(json.dumps(res))
    return 0


if __name__ == "__main__":
    sys.exit(main())
