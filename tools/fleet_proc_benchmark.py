"""A/B gate: cross-process fleet vs the in-process fleet on the same
seeded trace (ISSUE 18; inference/fleet_rpc.py + tools/loadgen.py).

Both legs replay ONE deterministic loadgen trace (same seed → same
prompts, arrival bursts, tenant prefixes, submission order → same rid
space) — the in-process `FleetRouter` and the RPC-backed
`ProcessFleetRouter` over real sockets. Because the sampler's fold_in
chain is (seed ∘ rid ∘ step-index), a stream's tokens are
placement-independent, so EVERY stream must match token-exact across
the process boundary (parity_ok) even where the two routers made
different admission choices.

Deterministic gates (the wall clock never decides pass/fail):

  parity_ok           every replayed stream identical across legs
  rpc_accounting_ok   exact frame accounting: for each replica, the
                      router client's sent messages/bytes equal the
                      worker server's received messages/bytes and vice
                      versa — counted off the ACTUAL serialized frames
                      on both ends of the socket, so a lost or
                      double-counted frame anywhere fails the gate
  migration_ok        a forced mid-decode cross-process migration
                      (export_slot bytes over the wire) finishes
                      token-exact vs the unmigrated in-process leg
  attainment_ok       TTFT/interval SLO attainment read off the PR-12
                      histograms lands in [0,1] with every submitted
                      request observed (counts are deterministic;
                      the percentiles themselves are reported but not
                      gated — CPU wall time is machine-relative)
  trace_ok            the merged Chrome trace (merge_process_traces)
                      carries process rows from >= 2 distinct OS
                      replica processes

Runs on CPU out of the box. One JSON line.

  python tools/fleet_proc_benchmark.py --requests 12
  python tools/fleet_proc_benchmark.py --threaded   # no subprocesses
"""

import argparse
import json
import os
import shutil
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def run(n_replicas: int = 2, requests: int = 12, tenants: int = 2,
        prefix_len: int = 16, max_new: int = 8, seed: int = 0,
        slo_ttft_ms: float = 5000.0, slo_interval_ms: float = 2000.0,
        threaded: bool = False):
    import numpy as np

    from megatronapp_tpu.inference.fleet import FleetRouter
    from megatronapp_tpu.inference.fleet_rpc import (
        ProcessFleetRouter, build_engine_from_spec, default_engine_spec,
        launch_threaded,
    )
    from megatronapp_tpu.trace.request_trace import get_request_tracer
    from tools.loadgen import make_trace, replay

    spec = default_engine_spec()
    trace = make_trace(seed=seed, n_requests=requests, tenants=tenants,
                       prefix_len=prefix_len, max_new_min=max_new // 2,
                       max_new_max=max_new, abort_rate=0.0)

    # Leg A: the in-process fleet (the PR-14 baseline).
    base = FleetRouter(
        engine_factory=lambda i, **kw: build_engine_from_spec(spec),
        num_replicas=n_replicas)
    a = replay(base, trace, slo_ttft_ms=slo_ttft_ms,
               slo_interval_ms=slo_interval_ms)

    # Leg B: the same trace over real sockets (and, unless --threaded,
    # real OS worker processes with request tracing on).
    get_request_tracer().configure(enabled=True)
    state_dir = tempfile.mkdtemp(prefix="fleet-proc-bench-")
    t0 = time.monotonic()
    servers = None
    if threaded:
        router, servers = launch_threaded(state_dir, spec,
                                          num_replicas=n_replicas)
    else:
        router = ProcessFleetRouter.launch(
            state_dir, spec, num_replicas=n_replicas,
            extra_env={"MEGATRON_REQUEST_TRACE": "1"})
    startup_s = time.monotonic() - t0
    try:
        b = replay(router, trace, slo_ttft_ms=slo_ttft_ms,
                   slo_interval_ms=slo_interval_ms)
        parity_ok = all(a["streams"][k] == b["streams"][k]
                        for k in a["streams"]) and (
            set(a["streams"]) == set(b["streams"]))

        # Exact frame accounting, per replica: snapshot the client
        # counters BEFORE the stats call, then check both directions
        # (the stats REQUEST frame is counted on both ends before the
        # worker snapshots; its REPLY is excluded from both).
        rpc_accounting_ok = True
        rpc_detail = []
        for rep in router._reps:
            c = rep.client
            pre = (c.msgs_sent, c.bytes_sent, c.msgs_recv, c.bytes_recv)
            st = c.call("stats")["rpc"]
            ok = (st["msgs_recv"] == pre[0] + 1
                  and st["bytes_recv"] == c.bytes_sent
                  and st["msgs_sent"] == pre[2]
                  and st["bytes_sent"] == pre[3])
            rpc_accounting_ok = rpc_accounting_ok and ok
            rpc_detail.append({"replica": rep.idx, "ok": ok,
                               "bytes_to_worker": st["bytes_recv"],
                               "bytes_from_worker": st["bytes_sent"]})

        # Forced cross-process migration phase: both legs admit two
        # fresh identical requests (same rids — the replay left both
        # counters equal), leg B migrates one mid-decode.
        rng = np.random.default_rng(seed + 1)
        mig_prompts = [rng.integers(0, 128, size=8).astype(np.int32)
                       for _ in range(2)]
        base_rids = [base.add_request(p, max_new) for p in mig_prompts]
        proc_rids = [router.add_request(p, max_new)
                     for p in mig_prompts]
        assert base_rids == proc_rids, (base_rids, proc_rids)
        base_res = base.run_to_completion()
        for _ in range(3):
            router.step()
        migrated = router.migrate_request(proc_rids[0])
        proc_res = router.run_to_completion()
        migration_ok = bool(migrated) and all(
            proc_res[r].tolist() == base_res[r].tolist()
            for r in proc_rids)

        rb = b["report"]
        attainment_ok = (
            0.0 <= rb["ttft_attainment"] <= 1.0
            and 0.0 <= rb["interval_attainment"] <= 1.0
            and b["ttft_hist"].count == requests)

        merged = router.merged_trace()
        proc_rows = {e["pid"] for e in merged.get("traceEvents", [])
                     if e.get("ph") == "M"
                     and e.get("name") == "process_name"}
        replica_rows = {
            e["pid"] // 100 for e in merged.get("traceEvents", [])
            if e.get("ph") == "M" and e.get("name") == "process_name"
            and e["pid"] >= 100}
        trace_ok = len(replica_rows) >= min(2, n_replicas)

        out = {
            "config": {"n_replicas": n_replicas, "requests": requests,
                       "tenants": tenants, "seed": seed,
                       "threaded": threaded,
                       "worker_startup_s": round(startup_s, 2)},
            "in_process": a["report"],
            "cross_process": rb,
            "rpc": dict(router.rpc_totals(), detail=rpc_detail),
            "migrated_kv_bytes":
                router.router_stats["migrated_kv_bytes"],
            "trace_process_rows": len(proc_rows),
            "parity_ok": parity_ok,
            "rpc_accounting_ok": rpc_accounting_ok,
            "migration_ok": migration_ok,
            "attainment_ok": attainment_ok,
            "trace_ok": trace_ok,
        }
        out["gates_ok"] = all(out[k] for k in (
            "parity_ok", "rpc_accounting_ok", "migration_ok",
            "attainment_ok", "trace_ok"))
        return out
    finally:
        router.shutdown()
        if servers:
            for s in servers:
                s.stop()
        shutil.rmtree(state_dir, ignore_errors=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="cross-process fleet A/B gate (ISSUE 18)")
    ap.add_argument("--replicas", type=int, default=2)
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--tenants", type=int, default=2)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--threaded", action="store_true",
                    help="thread-backed replica servers (same sockets "
                         "and frames, no subprocess spawn cost)")
    args = ap.parse_args(argv)
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    out = run(n_replicas=args.replicas, requests=args.requests,
              tenants=args.tenants, max_new=args.max_new,
              seed=args.seed, threaded=args.threaded)
    print(json.dumps(out))
    return 0 if out["gates_ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
