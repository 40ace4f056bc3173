"""KV capacity tiers A/B microbenchmark (ISSUE 20;
inference/dynamic_engine.py HostSpillTier park/unpark,
inference/fleet.py + fleet_rpc.py FleetPrefixStore).

Three measurements, all deterministic (virtual steps, exact byte
accounting — no wall-clock gates):

  capacity: sessions RESIDENT (KV held somewhere, token-exact
            resumable) at a FIXED HBM block budget, with vs without the
            host-RAM spill tier. Without spill, residency is bounded by
            pool blocks; with spill, idle sessions park to host RAM and
            the freed blocks admit more. The acceptance gate is
            ratio >= 2.0. Byte accounting is exact: the tier's
            bytes_used must equal the sum of the parked payloads'
            nbytes.
  resume:   park -> idle steps -> unpark -> drain, compared
            token-for-token against an unparked baseline run — greedy
            AND seeded-sampled streams must match exactly (the sampler
            folds (seed, rid, position), so placement can't leak into
            the stream). Runs per KV dtype (--dtypes; bf16 by default,
            tests/test_kv_spill.py covers all three).
  prefix:   a 2-replica fleet with the fleet-global prefix store vs
            without: the same long shared prefix submitted to BOTH
            replicas. With the store, the second replica gathers the
            prefix blocks instead of recomputing prefill — gates:
            store hit-rate strictly above the storeless baseline (0)
            and prefill_chunks_avoided >= 1 with exact chunk math
            (prefill_chunk=8 so a 25-token prompt spans >1 chunk).

Runs on CPU out of the box.

  python tools/kv_spill_benchmark.py --local
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# Documented gate (README "KV capacity tiers"): resident sessions at a
# fixed HBM budget with the spill tier vs without.
SESSIONS_RATIO_GATE = 2.0


def _make_cfg():
    import jax.numpy as jnp

    from megatronapp_tpu.config.transformer_config import TransformerConfig
    return TransformerConfig(
        num_layers=2, hidden_size=64, num_attention_heads=4,
        num_query_groups=2, vocab_size=128, max_position_embeddings=64,
        compute_dtype=jnp.float32, remat_policy="none")


def _build(cfg, params, kv_dtype="bf16", max_batch=2, max_seq_len=48,
           block_size=8, num_blocks=None, spill_mb=0.0, watermark=0,
           prefix_caching=False, prefill_chunk=8, tokenizer=None):
    from megatronapp_tpu.inference.dynamic_engine import (
        DynamicInferenceEngine,
    )
    return DynamicInferenceEngine(
        params, cfg, tokenizer=tokenizer, max_batch=max_batch,
        max_seq_len=max_seq_len, prefill_buckets=(16,),
        block_size=block_size, num_blocks=num_blocks,
        kv_cache_dtype=kv_dtype, enable_prefix_caching=prefix_caching,
        prefill_chunk=prefill_chunk, spill_host_mb=spill_mb,
        spill_watermark_blocks=watermark)


def _step_until_token(engine, rid, streams, max_steps=64):
    for _ in range(max_steps):
        ev = engine.step()
        for r, tok in ev["tokens"]:
            streams.setdefault(r, []).append(int(tok))
        if streams.get(rid):
            return
    raise RuntimeError(f"request {rid} emitted no token in "
                       f"{max_steps} steps")


def _drain(engine, streams, max_steps=4096):
    while engine.has_work:
        ev = engine.step()
        for r, tok in ev["tokens"]:
            streams.setdefault(r, []).append(int(tok))
        max_steps -= 1
        if max_steps <= 0:
            raise RuntimeError("engine did not drain")


def run_capacity(num_blocks: int = 8, block_size: int = 8,
                 prompt_len: int = 17, sessions: int = 6,
                 spill_mb: float = 4.0, max_new: int = 20):
    """Resident sessions at a fixed HBM block budget, exact bytes."""
    import jax
    import numpy as np

    from megatronapp_tpu.inference.engine import SamplingParams
    from megatronapp_tpu.models.gpt import init_gpt_params

    cfg = _make_cfg()
    params, _ = init_gpt_params(jax.random.PRNGKey(7), cfg)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, prompt_len)
               .astype(np.int32) for _ in range(sessions)]
    greedy = SamplingParams(greedy=True)

    # Baseline leg: no spill tier — submit everything, one admission
    # pass, count sessions whose KV is resident in the pool.
    base = _build(cfg, params, max_batch=sessions,
                  num_blocks=num_blocks, block_size=block_size)
    for p in prompts:
        base.add_request(p, max_new, greedy)
    base.step()
    resident_base = sum(1 for r in base.slots if r is not None)

    # Spill leg: same HBM budget — each session decodes its first
    # token, then the client parks it (held: a long-idle session whose
    # KV must survive). Parking frees the blocks, so the next session
    # admits; every parked payload stays token-exact resumable.
    eng = _build(cfg, params, max_batch=sessions,
                 num_blocks=num_blocks, block_size=block_size,
                 spill_mb=spill_mb)
    streams = {}
    rids = []
    for p in prompts:
        rid = eng.add_request(p, max_new, greedy)
        rids.append(rid)
        _step_until_token(eng, rid, streams)
        assert eng.park_request(rid), f"park failed for rid {rid}"
    sstats = eng.spill.stats()
    resident_spill = (sstats["parked"]
                      + sum(1 for r in eng.slots if r is not None))
    ratio = resident_spill / max(resident_base, 1)

    # Exact byte accounting: the tier's resident bytes are the sum of
    # the parked payloads' nbytes (export_slot-format, numpy-backed).
    payload_bytes = sum(eng.export_request(r)["nbytes"] for r in rids)

    # Token-exact resume: wake everything and drain; compare against
    # an unconstrained baseline of the same greedy requests.
    for rid in rids:
        eng.resume_request(rid)
    _drain(eng, streams)
    eng.pool.audit()
    ref = _build(cfg, params, max_batch=sessions, block_size=block_size)
    ref_streams = {}
    ref_rids = [ref.add_request(p, max_new, greedy) for p in prompts]
    _drain(ref, ref_streams)
    exact = all(streams[r] == ref_streams[rr]
                for r, rr in zip(rids, ref_rids))
    return {
        "num_blocks": num_blocks, "block_size": block_size,
        "prompt_len": prompt_len, "sessions_submitted": sessions,
        "resident_no_spill": resident_base,
        "resident_with_spill": resident_spill,
        "sessions_ratio": round(ratio, 4),
        "ratio_gate": SESSIONS_RATIO_GATE,
        "ratio_ok": ratio >= SESSIONS_RATIO_GATE,
        "spill_budget_bytes": sstats["budget_bytes"],
        "spill_bytes_used_at_peak": sstats["peak_bytes"],
        "payload_bytes_exact": payload_bytes == sstats["peak_bytes"],
        "parks": eng.spill.stats()["parks"],
        "unparks": eng.spill.stats()["unparks"],
        "resume_token_exact": exact,
    }


def run_resume(dtypes=("bf16",), prompt_len: int = 11,
               max_new: int = 12, idle_steps: int = 3):
    """Park/idle/unpark stream parity per KV dtype, greedy + sampled."""
    import jax
    import numpy as np

    from megatronapp_tpu.inference.engine import SamplingParams
    from megatronapp_tpu.models.gpt import init_gpt_params

    cfg = _make_cfg()
    params, _ = init_gpt_params(jax.random.PRNGKey(7), cfg)
    prompt = np.arange(1, prompt_len + 1, dtype=np.int32)
    out = {}
    for dtype in dtypes:
        entry = {}
        for name, sp in (
                ("greedy", SamplingParams(greedy=True)),
                ("sampled", SamplingParams(temperature=0.9, top_k=20,
                                           seed=13))):
            ref = _build(cfg, params, kv_dtype=dtype)
            ref_streams = {}
            ref_rid = ref.add_request(prompt, max_new, sp)
            _drain(ref, ref_streams)

            eng = _build(cfg, params, kv_dtype=dtype, spill_mb=2.0)
            streams = {}
            rid = eng.add_request(prompt, max_new, sp)
            _step_until_token(eng, rid, streams)
            assert eng.park_request(rid)
            for _ in range(idle_steps):
                eng.step()          # parked: no tokens for this rid
            mid = len(streams[rid])
            eng.resume_request(rid)
            _drain(eng, streams)
            eng.pool.audit()
            entry[name] = {
                "tokens_before_park": mid,
                "exact": streams[rid] == ref_streams[ref_rid],
            }
        out[dtype] = entry
    out["all_exact"] = all(v[n]["exact"] for k, v in out.items()
                           if isinstance(v, dict) and "greedy" in v
                           for n in ("greedy", "sampled"))
    return out


def run_fleet_prefix(prefill_chunk: int = 8, prompt_len: int = 25,
                     max_new: int = 4):
    """Fleet-global prefix store vs storeless baseline: the second
    replica's admission must hit the store and skip prefill chunks."""
    import jax
    import numpy as np

    from megatronapp_tpu.inference.engine import SamplingParams
    from megatronapp_tpu.inference.fleet import FleetRouter
    from megatronapp_tpu.models.gpt import init_gpt_params

    cfg = _make_cfg()
    params, _ = init_gpt_params(jax.random.PRNGKey(7), cfg)
    prompt = np.asarray(list(range(1, prompt_len + 1)), np.int32)
    greedy = SamplingParams(greedy=True)

    def _leg(store_mb):
        router = FleetRouter(
            engine_factory=lambda i, **kw: _build(
                cfg, params, prefix_caching=True,
                prefill_chunk=prefill_chunk),
            num_replicas=2, policy="round_robin", migrate=False,
            prefix_store_mb=store_mb)
        streams = {}
        r1 = router.add_request(prompt, max_new, greedy)
        _drain(router, streams)       # replica 0 decodes + registers
        r2 = router.add_request(prompt, max_new, greedy)
        _drain(router, streams)       # replica 1: store gather or miss
        for rep in router.replicas:
            rep.engine.pool.audit()
        fs = router.router_stats
        stats = {
            "prefill_chunks_avoided": fs["prefill_chunks_avoided"],
            "store_admission_hits": fs["prefix_store_admission_hits"],
            "seeded_blocks": fs["prefix_store_seeded_blocks"],
            "seeded_bytes": fs["prefix_store_seeded_bytes"],
        }
        if router.prefix_store is not None:
            st = router.prefix_store.stats()
            stats["store_hits"] = st["hits"]
            stats["store_hit_rate"] = round(
                st["hits"] / max(st["hits"] + st["misses"], 1), 4)
        match = streams[r1] == streams[r2]
        return stats, match

    with_store, match_w = _leg(store_mb=1.0)
    baseline, match_b = _leg(store_mb=0.0)
    return {
        "prefill_chunk": prefill_chunk, "prompt_len": prompt_len,
        "with_store": with_store, "baseline": baseline,
        "streams_match": match_w and match_b,
        "hit_rate_above_baseline": (
            with_store.get("store_hit_rate", 0.0) > 0.0
            and with_store["store_admission_hits"]
            > baseline["store_admission_hits"]),
        "chunks_avoided_ok": with_store["prefill_chunks_avoided"] >= 1,
    }


def run(**kw):
    """All three measurements; returns a JSON-ready dict."""
    import jax

    cap_kw = {k: v for k, v in kw.items()
              if k in ("num_blocks", "sessions", "spill_mb")}
    res = {
        "environment": jax.devices()[0].platform,
        "capacity": run_capacity(**cap_kw),
        "resume": run_resume(dtypes=kw.get("dtypes", ("bf16",))),
        "fleet_prefix": run_fleet_prefix(),
    }
    res["ok"] = bool(
        res["capacity"]["ratio_ok"]
        and res["capacity"]["resume_token_exact"]
        and res["capacity"]["payload_bytes_exact"]
        and res["resume"]["all_exact"]
        and res["fleet_prefix"]["hit_rate_above_baseline"]
        and res["fleet_prefix"]["chunks_avoided_ok"]
        and res["fleet_prefix"]["streams_match"])
    return res


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--num-blocks", type=int, default=8)
    ap.add_argument("--sessions", type=int, default=6)
    ap.add_argument("--spill-mb", type=float, default=4.0)
    ap.add_argument("--dtypes", default="bf16",
                    help="comma list of KV dtypes for the resume leg")
    ap.add_argument("--local", action="store_true",
                    help="force the CPU backend")
    args = ap.parse_args(argv)

    if args.local:
        os.environ["JAX_PLATFORMS"] = "cpu"
    res = run(num_blocks=args.num_blocks, sessions=args.sessions,
              spill_mb=args.spill_mb,
              dtypes=tuple(args.dtypes.split(",")))
    print(json.dumps(res))
    return 0


if __name__ == "__main__":
    sys.exit(main())
