"""Cross-process fleet serving tests (ISSUE 18).

Covers the tentpole and its satellites:

- the wire protocol: length-prefixed pickle frames with EXACT byte
  accounting on both ends, numpy payload fidelity, oversized-frame
  rejection;
- `merge_process_traces`: per-process pid offsets, per-ring timestamp
  normalization, labeled process rows in ONE Chrome trace;
- `tools/loadgen.py`: seeded traces are deterministic (same seed, same
  events; different seed differs), burst/tenant/abort structure;
- the new `--fleet-procs`/`--replica-rpc-port`/`--supervisor` flags and
  their parse-time validation;
- thread-backed fleet smoke (launch_threaded: the SAME frames, verbs,
  chaos window, and accounting over real loopback sockets, no
  subprocess spawn cost): stream parity vs the in-process FleetRouter,
  cross-process token-exact migration, the `fleet-rpc` chaos drill
  (lost-acknowledgement rollback, audit clean), /metrics aggregation,
  and RPC accounting exactness;
- supervisor unification: FleetRouter.kill_replica/revive_replica and
  the poll loop route through ONE Supervisor code path with shared
  restart accounting;
- slow subprocess drills (tests/slow_manifest.txt): SIGKILL a replica
  worker mid-stream → the supervisor detects, relaunches, the router
  fails sessions over and reattaches, streams token-exact; kill the
  ROUTER and recover via ProcessFleetRouter.attach — zero sessions
  lost in either direction.
"""

import os
import signal
import socket
import time

import numpy as np
import pytest

from megatronapp_tpu.inference.fleet_rpc import (
    ACTIVE, DEAD, ProcessFleetRouter, ReplicaClient, ReplicaServer,
    build_engine_from_spec, default_engine_spec, launch_threaded,
    read_addr, recv_msg, send_msg,
)
from megatronapp_tpu.utils import chaos


@pytest.fixture(autouse=True)
def _chaos_clean():
    chaos.disarm()
    yield
    chaos.disarm()


def _prompts(n, seed=0, lo=4, hi=10, vocab=64):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab,
                         size=int(rng.integers(lo, hi))).astype(np.int32)
            for _ in range(n)]


def _baseline_streams(spec, prompts, max_new=6):
    """Single in-process engine, same spec and submission order → same
    rids, and (fold_in chain = seed ∘ rid ∘ step) the exact streams any
    fleet placement must reproduce."""
    eng = build_engine_from_spec(spec)
    rids = [eng.add_request(p, max_new) for p in prompts]
    while eng.has_work:
        eng.step()
    out = {}
    for rid in rids:
        req = eng.pop_request(rid)
        out[rid] = req.tokens.tolist()
    return out


# ---------------------------------------------------------------------------
class TestWireCodec:
    def test_roundtrip_and_exact_byte_accounting(self):
        a, b = socket.socketpair()
        try:
            payload = {"verb": "submit", "rid": 3,
                       "prompt": np.arange(17, dtype=np.int32),
                       "nested": {"keys": [b"k0", b"k1"], "f": 1.5}}
            sent = send_msg(a, payload)
            got, received = recv_msg(b)
            assert sent == received          # both ends count the frame
            assert sent > 8                  # prefix + pickle body
            assert got["rid"] == 3 and got["nested"]["f"] == 1.5
            np.testing.assert_array_equal(got["prompt"],
                                          payload["prompt"])
            assert got["prompt"].dtype == np.int32
        finally:
            a.close()
            b.close()

    def test_oversized_frame_rejected(self):
        import struct

        a, b = socket.socketpair()
        try:
            a.sendall(struct.pack("!Q", 1 << 40))
            with pytest.raises(ValueError, match="exceeds"):
                recv_msg(b)
        finally:
            a.close()
            b.close()

    def test_peer_close_midframe_is_connection_error(self):
        a, b = socket.socketpair()
        import struct

        a.sendall(struct.pack("!Q", 128) + b"short")
        a.close()
        try:
            with pytest.raises(ConnectionError, match="mid-frame"):
                recv_msg(b)
        finally:
            b.close()


# ---------------------------------------------------------------------------
class TestTraceMerge:
    def test_pid_offsets_labels_and_normalization(self):
        from megatronapp_tpu.trace.request_trace import (
            merge_process_traces,
        )

        def ring(t0):
            return [
                {"name": "decode-step", "ph": "B", "ts": t0,
                 "pid": 0, "tid": 0, "iteration": 0, "args": {}},
                {"name": "decode-step", "ph": "E", "ts": t0 + 5.0,
                 "pid": 0, "tid": 0, "iteration": 0, "args": {}},
            ]

        merged = merge_process_traces([
            ("router", ring(1000.0), {0: "decode-mesh"}),
            ("replica-0", ring(9000.0), {0: "decode-mesh"}),
            ("replica-1", ring(50.0), {0: "decode-mesh"}),
        ])
        ev = merged["traceEvents"]
        rows = {e["pid"]: e["args"]["name"] for e in ev
                if e.get("ph") == "M" and e["name"] == "process_name"}
        # Process groups at 0 / 100 / 200 — distinct rows per process.
        assert {p // 100 for p in rows} == {0, 1, 2}
        assert any("router" in n for n in rows.values())
        assert any("replica-1" in n for n in rows.values())
        # Per-ring normalization: every ring starts near ts 0, so rings
        # captured at wildly different process uptimes still align.
        spans = [e for e in ev if e.get("ph") == "X"]
        assert spans and all(e["ts"] <= 10.0 for e in spans)

    def test_empty_rings_skipped(self):
        from megatronapp_tpu.trace.request_trace import (
            merge_process_traces,
        )
        merged = merge_process_traces([("router", [], {})])
        assert merged["traceEvents"] == []


# ---------------------------------------------------------------------------
class TestLoadgen:
    def test_trace_deterministic_in_seed(self):
        from tools.loadgen import make_trace
        a = make_trace(seed=3, n_requests=16, abort_rate=0.3)
        b = make_trace(seed=3, n_requests=16, abort_rate=0.3)
        c = make_trace(seed=4, n_requests=16, abort_rate=0.3)
        assert len(a) == len(b) == 16
        for ea, eb in zip(a, b):
            assert ea["arrive_step"] == eb["arrive_step"]
            assert ea["tenant"] == eb["tenant"]
            assert ea["max_new"] == eb["max_new"]
            assert ea["abort_after"] == eb["abort_after"]
            np.testing.assert_array_equal(ea["prompt"], eb["prompt"])
        assert any(not np.array_equal(ea["prompt"], ec["prompt"])
                   for ea, ec in zip(a, c))

    def test_bursts_tenants_and_aborts(self):
        from tools.loadgen import make_trace
        tr = make_trace(seed=0, n_requests=20, tenants=3, prefix_len=8,
                        burst_every=5, burst_size=3, arrival_gap=2,
                        abort_rate=0.5)
        # Burst structure: some arrival steps carry multiple requests.
        by_step = {}
        for e in tr:
            by_step.setdefault(e["arrive_step"], []).append(e)
        assert max(len(v) for v in by_step.values()) >= 3
        # Tenant groups share their system-prefix tokens verbatim.
        by_tenant = {}
        for e in tr:
            by_tenant.setdefault(e["tenant"], []).append(e["prompt"][:8])
        for group in by_tenant.values():
            for p in group[1:]:
                np.testing.assert_array_equal(p, group[0])
        aborts = [e for e in tr if e["abort_after"] is not None]
        assert aborts and all(e["abort_after"] >= 2 for e in aborts)

    def test_replay_drains_bare_engine(self):
        from tools.loadgen import make_trace, replay
        spec = default_engine_spec()
        eng = build_engine_from_spec(spec)
        tr = make_trace(seed=1, n_requests=4, tenants=2, prefix_len=8,
                        tail_max=4, max_new_min=3, max_new_max=5)
        out = replay(eng, tr, slo_ttft_ms=60_000.0)
        rep = out["report"]
        assert rep["requests"] == 4
        assert rep["tokens_out"] >= 4 * 3
        assert out["ttft_hist"].count == 4
        assert 0.0 <= rep["ttft_attainment"] <= 1.0


# ---------------------------------------------------------------------------
class TestFleetProcArgs:
    def _parse(self, argv):
        import argparse

        from megatronapp_tpu.config.arguments import add_serving_args
        ap = argparse.ArgumentParser()
        add_serving_args(ap)
        return ap.parse_args(argv)

    def test_flags_parse_with_defaults(self):
        args = self._parse([])
        assert args.fleet_procs == 0
        assert args.replica_rpc_port == 0
        assert args.supervisor == "off"
        args = self._parse(["--engine", "dynamic",
                            "--fleet-procs", "3",
                            "--replica-rpc-port", "29000",
                            "--supervisor", "thread"])
        assert (args.fleet_procs, args.replica_rpc_port,
                args.supervisor) == (3, 29000, "thread")

    @pytest.mark.parametrize("argv,msg", [
        (["--engine", "dynamic",
          "--fleet-procs", "-1"], "must be >= 0"),
        (["--engine", "dynamic", "--serve-fleet",
          "2", "--fleet-procs", "2"], "mutually exclusive"),
        (["--fleet-procs", "2"], "--engine dynamic"),
        (["--engine", "dynamic",
          "--replica-rpc-port", "29000"], "needs --fleet-procs"),
        (["--engine", "dynamic", "--fleet-procs",
          "2", "--replica-rpc-port", "80"], "out of range"),
        (["--engine", "dynamic", "--fleet-procs",
          "4", "--replica-rpc-port", "65533"], "out of range"),
        (["--engine", "dynamic",
          "--supervisor", "thread"], "needs --fleet-procs"),
    ])
    def test_invalid_combos_rejected(self, argv, msg):
        from megatronapp_tpu.config.arguments import (
            validate_serving_args,
        )
        with pytest.raises(SystemExit, match=msg):
            validate_serving_args(self._parse(argv))

    def test_valid_combo_passes(self):
        from megatronapp_tpu.config.arguments import (
            validate_serving_args,
        )
        validate_serving_args(self._parse(
            ["--engine", "dynamic",
             "--fleet-procs", "2", "--replica-rpc-port", "29000",
             "--supervisor", "process"]))


# ---------------------------------------------------------------------------
class TestThreadBackedFleet:
    """launch_threaded: real loopback sockets and the full verb table,
    replica servers in daemon threads — the fast tier-1 lane for every
    protocol-level property (subprocess workers each pay a full jax
    import; those drills live in the slow manifest)."""

    def test_parity_accounting_and_snapshot(self, tmp_path):
        spec = default_engine_spec()
        prompts = _prompts(4, seed=11)
        base = _baseline_streams(spec, prompts)
        router, _ = launch_threaded(str(tmp_path), spec,
                                    num_replicas=2)
        try:
            rids = [router.add_request(p, 6) for p in prompts]
            assert rids == sorted(base)      # one shared rid space
            res = router.run_to_completion()
            for rid in rids:
                assert res[rid].tolist() == base[rid]

            # Exact frame accounting, both directions: the stats
            # REQUEST is counted on both ends before the worker
            # snapshots; its REPLY is excluded from both.
            for rep in router._reps:
                c = rep.client
                pre = (c.msgs_sent, c.msgs_recv, c.bytes_recv)
                st = c.call("stats")["rpc"]
                assert st["msgs_recv"] == pre[0] + 1
                assert st["bytes_recv"] == c.bytes_sent
                assert st["msgs_sent"] == pre[1]
                assert st["bytes_sent"] == pre[2]

            snap = router.stats_snapshot()
            f = snap["fleet"]
            assert snap["engine"] == "fleet" and f["process_backed"]
            assert f["num_replicas"] == f["live_replicas"] == 2
            assert f["admissions"] == 4
            assert f["rpc"]["msgs_sent"] == f["rpc"]["msgs_recv"]
            assert len(f["replicas"]) == 2
            assert all("incarnation" in r and "restarts" in r
                       for r in f["replicas"])
            router.audit()
        finally:
            router.shutdown()

    def test_migration_token_exact_across_processes(self, tmp_path):
        spec = default_engine_spec()
        prompts = _prompts(2, seed=5)
        base = _baseline_streams(spec, prompts)
        router, _ = launch_threaded(str(tmp_path), spec,
                                    num_replicas=2)
        try:
            rids = [router.add_request(p, 6) for p in prompts]
            for _ in range(3):
                router.step()
            src = router._owner[rids[0]]
            assert router.migrate_request(rids[0])
            assert router._owner[rids[0]] != src
            assert router.router_stats["migrations"] == 1
            assert router.router_stats["migrated_kv_bytes"] > 0
            res = router.run_to_completion()
            for rid in rids:
                assert res[rid].tolist() == base[rid]
            router.audit()
        finally:
            router.shutdown()

    def test_fleet_gauges_aggregation(self, tmp_path):
        spec = default_engine_spec()

        class _Reg:
            def __init__(self):
                self.gauges = {}

            def labeled(self, name, **labels):
                return name + "".join(f"{{{k}={v}}}"
                                      for k, v in sorted(labels.items()))

            def set_gauge(self, key, val):
                self.gauges[key] = val

        router, _ = launch_threaded(str(tmp_path), spec,
                                    num_replicas=2)
        try:
            reg = _Reg()
            router.export_fleet_gauges(registry=reg)
            assert reg.gauges["fleet_replica_up{replica=0}"] == 1
            assert reg.gauges["fleet_replica_up{replica=1}"] == 1
            assert reg.gauges["fleet_supervisor_restarts_total"] == 0
            assert "fleet_replica_attainment{replica=0}" in reg.gauges
        finally:
            router.shutdown()


# ---------------------------------------------------------------------------
class TestChaosRpc:
    """The `fleet-rpc` site (the chaos registry pin in
    tests/test_resilience.py routes here): a fault AFTER the reply
    frame is deserialized and BEFORE the router commits it — the
    lost-acknowledgement window. Submit rolls back with the idempotent
    evict verb and the retried stream is unchanged; mid-migration loss
    evicts the destination copy and the session keeps decoding on the
    source. Both pools audit clean after every drill."""

    def test_submit_ack_lost_rolls_back_and_stream_exact(self, tmp_path):
        spec = default_engine_spec()
        prompts = _prompts(2, seed=21)
        base = _baseline_streams(spec, prompts)
        router, _ = launch_threaded(str(tmp_path), spec,
                                    num_replicas=2)
        try:
            rids = [router.add_request(prompts[0], 6)]
            chaos.arm("fleet-rpc", times=1)
            rids.append(router.add_request(prompts[1], 6))
            assert not chaos.active()        # the drill fired
            assert router.router_stats["rpc_rollbacks"] == 1
            res = router.run_to_completion()
            for rid in rids:
                assert res[rid].tolist() == base[rid]
            router.audit()
        finally:
            router.shutdown()

    def test_migration_ack_lost_keeps_source_exact(self, tmp_path):
        spec = default_engine_spec()
        prompts = _prompts(2, seed=22)
        base = _baseline_streams(spec, prompts)
        router, _ = launch_threaded(str(tmp_path), spec,
                                    num_replicas=2)
        try:
            rids = [router.add_request(p, 6) for p in prompts]
            for _ in range(2):
                router.step()
            owner = dict(router._owner)
            # Fire on the SECOND in-flight verb (export's ack lands,
            # the loss hits the migration exchange after it).
            chaos.arm("fleet-rpc", times=1, after=1)
            assert not router.migrate_request(rids[0])
            chaos.disarm()
            assert router.router_stats["migration_failures"] == 1
            assert router._owner[rids[0]] == owner[rids[0]]
            res = router.run_to_completion()
            for rid in rids:
                assert res[rid].tolist() == base[rid]
            router.audit()
        finally:
            router.shutdown()


# ---------------------------------------------------------------------------
class TestSupervisorUnified:
    """ONE supervisor code path: FleetRouter.kill_replica /
    revive_replica, the poll loop, and the cross-process backend all
    run inference/supervisor.Supervisor with shared restart
    accounting."""

    def _fleet(self, spec, n=2):
        from megatronapp_tpu.inference.fleet import FleetRouter
        return FleetRouter(
            engine_factory=lambda i, **kw: build_engine_from_spec(spec),
            num_replicas=n)

    def test_manual_drills_route_through_supervisor(self):
        spec = default_engine_spec()
        fleet = self._fleet(spec)
        prompts = _prompts(2, seed=31)
        base = _baseline_streams(spec, prompts)
        rids = [fleet.add_request(p, 6) for p in prompts]
        for _ in range(2):
            fleet.step()
        fleet.kill_replica(0)
        assert fleet.replicas[0].state == DEAD
        assert fleet._supervisor is not None    # drill built the policy
        assert fleet.supervisor.total_restarts == 0   # kill != restart
        res = fleet.run_to_completion()
        for rid in rids:
            assert res[rid].tolist() == base[rid]   # zero lost sessions
        fleet.revive_replica(0)
        assert fleet.replicas[0].state == ACTIVE
        assert fleet.supervisor.restarts[0] == 1    # a revive IS one

    def test_poll_once_detects_and_revives(self):
        spec = default_engine_spec()
        fleet = self._fleet(spec)
        fleet._kill_impl(0)                  # death the watcher must see
        assert fleet.replicas[0].state == DEAD
        recovered = fleet.supervisor.poll_once()
        assert recovered == [0]
        assert fleet.replicas[0].state == ACTIVE
        assert fleet.supervisor.restarts[0] == 1
        assert fleet.supervisor.poll_once() == []   # healthy: no-op
        snap = fleet.stats_snapshot()
        assert snap["fleet"]["supervisor_restarts"] == 1


# ---------------------------------------------------------------------------
class TestSubprocessDrills:
    """Real OS worker processes (tests/slow_manifest.txt — each worker
    pays a full jax import before binding its port)."""

    def _wait(self, pred, timeout=60.0, msg="condition"):
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if pred():
                return
            time.sleep(0.2)
        raise TimeoutError(f"{msg} not reached within {timeout}s")

    def test_sigkill_midstream_supervisor_relaunch_token_exact(
            self, tmp_path):
        spec = default_engine_spec()
        prompts = _prompts(3, seed=41)
        base = _baseline_streams(spec, prompts, max_new=6)
        router = ProcessFleetRouter.launch(
            str(tmp_path), spec, num_replicas=2, supervise="thread",
            stale_after=3.0)
        try:
            rids = [router.add_request(p, 6) for p in prompts]
            for _ in range(2):
                router.step()
            victim = read_addr(str(tmp_path), 0)
            os.kill(victim["pid"], signal.SIGKILL)
            # The stream must finish token-exact across the death: the
            # router fails replica 0's sessions over with
            # prompt+generated intact (preemption-resume — fold_in
            # never references placement).
            res = router.run_to_completion()
            for rid in rids:
                assert res[rid].tolist() == base[rid]
            assert router.router_stats["replica_deaths"] >= 1
            # Supervisor: detect → SIGKILL → relaunch (incarnation
            # bump); the router reattaches in its step loop.
            self._wait(
                lambda: router.supervisor_restarts().get(0, 0) >= 1,
                msg="supervisor restart of replica 0")
            self._wait(
                lambda: (router.step() or True) and all(
                    r.state == ACTIVE for r in router._reps),
                msg="router reattach to the relaunched worker")
            assert router._reps[0].incarnation >= 1
            # The revived fleet serves: one more request, still exact
            # (rid continues the shared space → rid 3 in the baseline
            # engine too).
            extra = _prompts(4, seed=41)[3]
            eng = build_engine_from_spec(spec)
            for p in prompts:
                eng.add_request(p, 6)
            rid4 = eng.add_request(extra, 6)
            while eng.has_work:
                eng.step()
            want = eng.pop_request(rid4).tokens.tolist()
            got_rid = router.add_request(extra, 6)
            assert got_rid == rid4
            res2 = router.run_to_completion()
            assert res2[got_rid].tolist() == want
            snap = router.stats_snapshot()
            assert snap["fleet"]["supervisor_restarts"] >= 1
        finally:
            router.shutdown()

    def test_router_restart_recovery_zero_lost(self, tmp_path):
        spec = default_engine_spec()
        prompts = _prompts(3, seed=51)
        base = _baseline_streams(spec, prompts, max_new=6)
        router = ProcessFleetRouter.launch(str(tmp_path), spec,
                                           num_replicas=2)
        try:
            rids = [router.add_request(p, 6) for p in prompts]
            for _ in range(2):
                router.step()
            # The router "dies": drop its sockets without shutdown.
            for rep in router._reps:
                rep.client.close()
            recovered = ProcessFleetRouter.attach(str(tmp_path))
            assert sorted(recovered._sessions) == rids
            assert recovered._affinity      # rebuilt from live prompts
            res = recovered.run_to_completion()
            for rid in rids:
                assert res[rid].tolist() == base[rid]
            # The rid counter resumed past the recovered sessions.
            nxt = recovered.add_request(prompts[0], 4)
            assert nxt == max(rids) + 1
            recovered.run_to_completion()
            recovered.shutdown()     # stops the workers for real
        finally:
            for rep in router._reps:
                if rep.proc is not None and rep.proc.poll() is None:
                    rep.proc.kill()
