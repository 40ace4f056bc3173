"""Deterministic trace-replay load generator for fleet serving
(ISSUE 18; the production-traffic harness for inference/fleet_rpc.py).

A seeded trace models the traffic shapes the fleet machinery exists
for, all from one RNG so two runs of the same seed replay the SAME
requests in the SAME arrival order:

- **arrival bursts**: a base arrival gap punctuated every
  ``burst_every`` steps by ``burst_size`` simultaneous arrivals (the
  queue-depth spikes admission scoring and SLO attainment are scored
  under);
- **length mixes**: per-request prompt tails and decode budgets drawn
  from seeded ranges (short chat turns next to long completions — the
  continuous-batching case);
- **shared-system-prompt tenant groups**: ``tenants`` groups, each with
  its own ``prefix_len``-token system prefix shared by every request in
  the group (the KV-affinity signal: followers should land where their
  tenant's prefix blocks live);
- **abort/timeout rates**: a seeded fraction of requests cancels after
  a seeded number of emitted tokens (client disconnects mid-stream —
  the abort path under load).

``replay()`` drives any engine-shaped router (in-process FleetRouter,
cross-process ProcessFleetRouter, or a bare engine — anything with
add_request/step/abort_request/pop_request) through the trace on a
VIRTUAL clock (one router step = one tick, arrivals keyed to ticks), so
the submitted workload is identical across legs regardless of wall
speed; wall-clock TTFT and token intervals are measured into the
PR-12 ``utils/metrics.Histogram`` primitive and the SLO gates read
p99 / attainment off those histograms — the same estimator /metrics
exports.

Standalone CLI (spawns a cross-process fleet, replays, one JSON line):

  python tools/loadgen.py --fleet-procs 2 --requests 24 --seed 0

tools/fleet_proc_benchmark.py imports make_trace/replay instead of
shelling out twice.
"""

import argparse
import json
import os
import sys
import tempfile
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def make_trace(seed: int = 0, n_requests: int = 24, tenants: int = 3,
               prefix_len: int = 24, tail_min: int = 2,
               tail_max: int = 8, max_new_min: int = 4,
               max_new_max: int = 12, arrival_gap: int = 2,
               burst_every: int = 8, burst_size: int = 3,
               abort_rate: float = 0.0, abort_after_min: int = 2,
               idle_every: int = 0, idle_after: int = 2,
               idle_steps: int = 6, vocab: int = 128):
    """Build the seeded event list. Each event:
    {id, arrive_step, tenant, prompt, max_new, abort_after,
    idle_after, idle_steps} — prompts are tenant_prefix + per-request
    tail; abort_after is None or the emitted-token count after which
    the client cancels. Long-idle phases (ISSUE 20): every
    ``idle_every``-th request goes idle after ``idle_after`` emitted
    tokens — the client parks the session (host-RAM KV spill) and
    resumes it ``idle_steps`` virtual steps later. Selection is
    modular, not an extra RNG draw, so existing seeds replay the
    exact same trace when idling is off."""
    rng = np.random.default_rng(seed)
    prefixes = [rng.integers(0, vocab, size=prefix_len).astype(np.int32)
                for _ in range(tenants)]
    events = []
    step = 0
    k = 0
    while k < n_requests:
        burst = (burst_size if burst_every and k
                 and k % burst_every == 0 else 1)
        for _ in range(min(burst, n_requests - k)):
            tenant = int(rng.integers(0, tenants))
            tail = rng.integers(
                0, vocab,
                size=int(rng.integers(tail_min, tail_max + 1)))
            max_new = int(rng.integers(max_new_min, max_new_max + 1))
            abort_after = None
            if abort_rate > 0 and rng.random() < abort_rate:
                abort_after = int(rng.integers(
                    abort_after_min, max(abort_after_min + 1, max_new)))
            idle = bool(idle_every
                        and k % idle_every == idle_every - 1
                        and abort_after is None
                        and max_new > idle_after)
            events.append({
                "id": k, "arrive_step": step, "tenant": tenant,
                "prompt": np.concatenate(
                    [prefixes[tenant], tail.astype(np.int32)]),
                "max_new": max_new, "abort_after": abort_after,
                "idle_after": idle_after if idle else None,
                "idle_steps": idle_steps,
            })
            k += 1
        step += arrival_gap
    return events


def replay(router, trace, slo_ttft_ms=None, slo_interval_ms=None,
           max_steps: int = 100_000, tenant_adapters=None):
    """Replay `trace` against `router` on the virtual step clock.
    Returns {streams, ttft_hist, interval_hist, tenant_hists, report} —
    streams maps trace id -> emitted token list (the cross-leg parity
    surface), histograms are live Histogram objects (the /metrics
    estimator), and report is the JSON-ready gate summary with a
    per-tenant percentile/attainment section.

    tenant_adapters (ISSUE 19): optional {tenant index -> adapter_id}.
    When given, every submit carries its tenant's adapter_id plus a
    ``tenant-<i>`` label — the multi-tenant LoRA workload over a
    router/engine built with an AdapterCache. When None, no lora/tenant
    kwargs are passed (bare engines without the plumbing stay
    replayable)."""
    from megatronapp_tpu.utils.metrics import Histogram

    def _hist():
        return Histogram(lo=1e-2, hi=1e6, growth=1.25)

    ttft_hist = _hist()
    interval_hist = _hist()
    # Per-tenant latency split (keyed by the TRACE's tenant index, so
    # it works even when the router is not tenant-aware).
    tenant_ttft = {}
    tenant_interval = {}
    tenant_requests = {}
    pending = sorted(trace, key=lambda e: (e["arrive_step"], e["id"]))
    rid_to_ev = {}
    submit_t = {}
    last_tok_t = {}
    streams = {}
    aborted = set()
    finished = set()
    idled = set()
    parked = {}          # rid -> virtual step to resume at
    step = 0
    while pending or any(
            rid not in finished for rid in rid_to_ev):
        if step >= max_steps:
            raise RuntimeError(
                f"loadgen replay did not drain within {max_steps} "
                f"steps ({len(finished)}/{len(rid_to_ev)} finished)")
        while pending and pending[0]["arrive_step"] <= step:
            ev = pending.pop(0)
            kw = {}
            if tenant_adapters is not None:
                kw = {"adapter_id": tenant_adapters.get(ev["tenant"]),
                      "tenant": f"tenant-{ev['tenant']}"}
            rid = router.add_request(ev["prompt"], ev["max_new"], **kw)
            rid_to_ev[rid] = ev
            submit_t[rid] = time.monotonic()
            streams[ev["id"]] = []
            tenant_requests[ev["tenant"]] = (
                tenant_requests.get(ev["tenant"], 0) + 1)
        for rid in [r for r, at in parked.items() if at <= step]:
            # Long-idle phase over: the client comes back for its next
            # token, which unparks the spilled KV (token-exact resume).
            del parked[rid]
            fn = getattr(router, "resume_request", None)
            if fn is not None:
                fn(rid)
        events = router.step()
        now = time.monotonic()
        for rid, tok in events["tokens"]:
            ev = rid_to_ev.get(rid)
            if ev is None:
                continue
            toks = streams[ev["id"]]
            t = ev["tenant"]
            if not toks:
                ttft = (now - submit_t[rid]) * 1e3
                ttft_hist.observe(ttft)
                tenant_ttft.setdefault(t, _hist()).observe(ttft)
            elif rid in last_tok_t:
                gap = (now - last_tok_t[rid]) * 1e3
                interval_hist.observe(gap)
                tenant_interval.setdefault(t, _hist()).observe(gap)
            last_tok_t[rid] = now
            toks.append(int(tok))
            if (ev["abort_after"] is not None and rid not in aborted
                    and len(toks) >= ev["abort_after"]):
                aborted.add(rid)
                router.abort_request(rid)
            if (ev.get("idle_after") is not None and rid not in idled
                    and rid not in aborted
                    and len(toks) >= ev["idle_after"]):
                # Client goes idle mid-stream: park the session so its
                # KV spills to host RAM (routers without the spill tier
                # just keep decoding — park_request returns False).
                fn = getattr(router, "park_request", None)
                if fn is not None and fn(rid):
                    idled.add(rid)
                    parked[rid] = step + int(ev.get("idle_steps", 1))
        for rid in events["finished"] + events["expired"]:
            if rid in rid_to_ev:
                finished.add(rid)
        step += 1
    for rid, ev in rid_to_ev.items():
        req = router.pop_request(rid)
        if req is not None and len(req.generated) > len(
                streams[ev["id"]]):
            streams[ev["id"]] = [int(t) for t in req.generated]
    report = {
        "requests": len(rid_to_ev),
        "steps": step,
        "aborted": len(aborted),
        "idled": len(idled),
        "tokens_out": sum(len(s) for s in streams.values()),
        "ttft_p50_ms": round(ttft_hist.percentile(50), 3),
        "ttft_p99_ms": round(ttft_hist.percentile(99), 3),
        "interval_p99_ms": round(interval_hist.percentile(99), 3),
    }
    if slo_ttft_ms is not None:
        report["ttft_attainment"] = round(
            ttft_hist.fraction_below(slo_ttft_ms), 4)
    if slo_interval_ms is not None:
        report["interval_attainment"] = round(
            interval_hist.fraction_below(slo_interval_ms), 4)
    tenants = {}
    for t in sorted(tenant_requests):
        entry = {"requests": tenant_requests[t]}
        th = tenant_ttft.get(t)
        ih = tenant_interval.get(t)
        if th is not None:
            entry["ttft_p99_ms"] = round(th.percentile(99), 3)
            if slo_ttft_ms is not None:
                entry["ttft_attainment"] = round(
                    th.fraction_below(slo_ttft_ms), 4)
        if ih is not None:
            entry["interval_p99_ms"] = round(ih.percentile(99), 3)
            if slo_interval_ms is not None:
                entry["interval_attainment"] = round(
                    ih.fraction_below(slo_interval_ms), 4)
        if tenant_adapters is not None:
            entry["adapter_id"] = tenant_adapters.get(t)
        tenants[f"tenant-{t}"] = entry
    report["tenants"] = tenants
    return {"streams": streams, "ttft_hist": ttft_hist,
            "interval_hist": interval_hist,
            "tenant_hists": {"ttft": tenant_ttft,
                             "interval": tenant_interval},
            "report": report}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="deterministic trace-replay load generator "
                    "(ISSUE 18)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--requests", type=int, default=24)
    ap.add_argument("--tenants", type=int, default=3)
    ap.add_argument("--prefix-len", type=int, default=24)
    ap.add_argument("--arrival-gap", type=int, default=2)
    ap.add_argument("--burst-every", type=int, default=8)
    ap.add_argument("--burst-size", type=int, default=3)
    ap.add_argument("--abort-rate", type=float, default=0.0)
    ap.add_argument("--idle-every", type=int, default=0,
                    help="every Nth request goes idle mid-stream and "
                         "is parked to the host-RAM spill tier "
                         "(0 = no idle phases)")
    ap.add_argument("--idle-after", type=int, default=2,
                    help="emitted tokens before an idle request parks")
    ap.add_argument("--idle-steps", type=int, default=6,
                    help="virtual steps an idle request stays parked")
    ap.add_argument("--kv-spill-host-mb", type=float, default=0.0,
                    help="per-replica host-RAM spill budget (MiB); "
                         "required for --idle-every to actually park")
    ap.add_argument("--kv-spill-watermark-blocks", type=int, default=0)
    ap.add_argument("--slo-ttft-ms", type=float, default=None)
    ap.add_argument("--slo-interval-ms", type=float, default=None)
    ap.add_argument("--lora-adapters", type=int, default=0,
                    help="generate this many random LoRA adapters into "
                         "a temp dir and map tenant i -> adapter "
                         "i%%N on every submit (0 = LoRA off)")
    ap.add_argument("--lora-rank", type=int, default=4)
    ap.add_argument("--fleet-procs", type=int, default=2,
                    help="replica worker processes to spawn (0 = "
                         "replay against one in-process engine)")
    ap.add_argument("--supervisor", choices=("off", "thread",
                                             "process"), default="off")
    ap.add_argument("--state-dir", default=None,
                    help="fleet state dir (default: a temp dir)")
    ap.add_argument("--trace-out", default=None,
                    help="write the merged multi-process Chrome trace "
                         "here (cross-process mode)")
    args = ap.parse_args(argv)

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from megatronapp_tpu.inference.fleet_rpc import (
        ProcessFleetRouter, build_engine_from_spec, default_engine_spec,
    )

    trace = make_trace(
        seed=args.seed, n_requests=args.requests,
        tenants=args.tenants, prefix_len=args.prefix_len,
        arrival_gap=args.arrival_gap, burst_every=args.burst_every,
        burst_size=args.burst_size, abort_rate=args.abort_rate,
        idle_every=args.idle_every, idle_after=args.idle_after,
        idle_steps=args.idle_steps)
    spec = default_engine_spec(max_seq_len=64, max_batch=2)
    if args.kv_spill_host_mb:
        spec.update(
            kv_spill_host_mb=args.kv_spill_host_mb,
            kv_spill_watermark_blocks=args.kv_spill_watermark_blocks)
    tenant_adapters = None
    if args.lora_adapters > 0:
        import jax.numpy as jnp

        from megatronapp_tpu.config.transformer_config import (
            TransformerConfig,
        )
        from megatronapp_tpu.inference.lora import LoraAdapter

        cfg = TransformerConfig(
            num_layers=spec["num_layers"],
            hidden_size=spec["hidden_size"],
            num_attention_heads=spec["num_attention_heads"],
            num_query_groups=spec["num_query_groups"],
            vocab_size=spec["vocab_size"],
            max_position_embeddings=spec["max_position_embeddings"],
            compute_dtype=jnp.float32, remat_policy="none")
        lora_dir = tempfile.mkdtemp(prefix="loadgen-lora-")
        for i in range(args.lora_adapters):
            LoraAdapter.random(
                f"adapter-{i}", cfg, rank=args.lora_rank,
                seed=100 + i).save(lora_dir)
        spec.update(lora_dir=lora_dir, lora_rank=args.lora_rank,
                    max_resident_adapters=max(4, args.lora_adapters))
        tenant_adapters = {t: f"adapter-{t % args.lora_adapters}"
                           for t in range(args.tenants)}
    if args.fleet_procs > 0:
        state_dir = args.state_dir or tempfile.mkdtemp(
            prefix="fleet-loadgen-")
        router = ProcessFleetRouter.launch(
            state_dir, spec, num_replicas=args.fleet_procs,
            supervise=None if args.supervisor == "off"
            else args.supervisor)
        try:
            out = replay(router, trace,
                         slo_ttft_ms=args.slo_ttft_ms,
                         slo_interval_ms=args.slo_interval_ms,
                         tenant_adapters=tenant_adapters)
            out["report"]["rpc"] = router.rpc_totals()
            out["report"]["supervisor_restarts"] = sum(
                router.supervisor_restarts().values())
            if args.trace_out:
                with open(args.trace_out, "w") as f:
                    json.dump(router.merged_trace(), f)
        finally:
            router.shutdown()
    else:
        engine = build_engine_from_spec(spec)
        out = replay(engine, trace, slo_ttft_ms=args.slo_ttft_ms,
                     slo_interval_ms=args.slo_interval_ms,
                     tenant_adapters=tenant_adapters)
    print(json.dumps(out["report"]))
    return 0


if __name__ == "__main__":
    sys.exit(main())
