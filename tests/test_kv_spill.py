"""KV capacity tiers tests (ISSUE 20).

Covers the tentpole and its satellites:

- `HostSpillTier` / `FleetPrefixStore` unit behaviour: strict byte
  budgets (spill never evicts; the store LRU-evicts), exact byte
  accounting off the serialized payloads, counter semantics;
- park/unpark through the engine: token-exact resumed streams for ALL
  KV_CACHE_DTYPES, greedy AND sampled (the sampler folds
  (seed, rid, position) — placement can't leak into the stream);
- spill-vs-preempt ordering under pool pressure: parking is preferred
  (fewer preemptions than the spill-less run), preemption remains the
  fallback when the tier's byte budget refuses;
- the fleet-global prefix store: a second replica's admission gathers
  the shared prefix from the store instead of recomputing prefill —
  in-process FleetRouter AND the cross-process verbs
  (prefix_put/prefix_get over launch_threaded), with exact
  chunks-avoided/byte pins;
- migration of a PARKED session (the spill payload IS the migration
  payload);
- the non-local addr.json guard and the serving-flag validations.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from megatronapp_tpu.config.transformer_config import TransformerConfig
from megatronapp_tpu.inference.dynamic_engine import DynamicInferenceEngine
from megatronapp_tpu.inference.engine import SamplingParams
from megatronapp_tpu.inference.fleet import FleetRouter
from megatronapp_tpu.inference.paged_cache import (
    KV_CACHE_DTYPES, FleetPrefixStore, HostSpillTier, cdiv,
    prefix_block_keys,
)
from megatronapp_tpu.models.gpt import init_gpt_params

ALL_DTYPES = sorted(KV_CACHE_DTYPES)


def _gqa_cfg(max_pos=64):
    return TransformerConfig(
        num_layers=2, hidden_size=64, num_attention_heads=4,
        num_query_groups=2, vocab_size=128,
        max_position_embeddings=max_pos,
        compute_dtype=jnp.float32, remat_policy="none")


@pytest.fixture(scope="module")
def gqa_params():
    cfg = _gqa_cfg()
    params, _ = init_gpt_params(jax.random.PRNGKey(7), cfg)
    return cfg, params


def _engine(params, cfg, dt="bf16", max_batch=2, num_blocks=None,
            spill_mb=0.0, watermark=0, prefix_caching=True,
            prefill_chunk=8):
    return DynamicInferenceEngine(
        params, cfg, max_batch=max_batch, max_seq_len=48,
        prefill_buckets=(16,), paged=True, block_size=8,
        num_blocks=num_blocks, kv_cache_dtype=dt,
        enable_prefix_caching=prefix_caching,
        prefill_chunk=prefill_chunk, spill_host_mb=spill_mb,
        spill_watermark_blocks=watermark)


def _drain(engine, streams=None, max_steps=2048):
    streams = {} if streams is None else streams
    while engine.has_work:
        ev = engine.step()
        for r, tok in ev["tokens"]:
            streams.setdefault(r, []).append(int(tok))
        max_steps -= 1
        assert max_steps > 0, "engine did not drain"
    return streams


def _step_until_token(engine, rid, streams, max_steps=64):
    for _ in range(max_steps):
        ev = engine.step()
        for r, tok in ev["tokens"]:
            streams.setdefault(r, []).append(int(tok))
        if streams.get(rid):
            return
    raise AssertionError(f"rid {rid} emitted no token")


# ---------------------------------------------------------------------------
# Tier unit behaviour.
# ---------------------------------------------------------------------------
class TestHostSpillTier:
    def test_budget_is_strict_and_counters_exact(self):
        tier = HostSpillTier(100)
        assert tier.put(1, {"nbytes": 60})
        assert 1 in tier and len(tier) == 1
        # Over budget: refused, tier untouched, reject counted — the
        # tier NEVER evicts (parked sessions are live state).
        assert not tier.put(2, {"nbytes": 50})
        assert 2 not in tier and tier.bytes_used == 60
        assert tier.put(2, {"nbytes": 40})
        st = tier.stats()
        assert st["parks"] == 2 and st["rejects"] == 1
        assert st["park_bytes"] == 100 and st["bytes_used"] == 100
        assert st["peak_bytes"] == 100 and st["peak_parked"] == 2
        # FIFO unpark order = insertion order.
        assert tier.rids() == [1, 2]
        # Genuine resume counts an unpark; abort/expiry does not.
        assert tier.pop(1)["nbytes"] == 60
        assert tier.pop(2, unpark=False)["nbytes"] == 40
        st = tier.stats()
        assert st["unparks"] == 1 and st["unpark_bytes"] == 60
        assert st["bytes_used"] == 0 and len(tier) == 0
        assert tier.pop(99) is None

    def test_double_park_asserts(self):
        tier = HostSpillTier(100)
        assert tier.put(7, {"nbytes": 10})
        with pytest.raises(AssertionError):
            tier.put(7, {"nbytes": 10})


class TestFleetPrefixStore:
    def test_lru_eviction_and_counters(self):
        store = FleetPrefixStore(100)
        assert store.put(b"a", {"nbytes": 40})
        assert store.put(b"a", {"nbytes": 40})      # idempotent True
        assert store.put(b"b", {"nbytes": 40})
        assert store.stats()["puts"] == 2
        # Oversized payload refused outright.
        assert not store.put(b"huge", {"nbytes": 101})
        # A hit refreshes LRU position, so "b" (not "a") evicts next.
        assert store.get(b"a")["nbytes"] == 40
        assert store.put(b"c", {"nbytes": 40})
        st = store.stats()
        assert st["evictions"] == 1
        assert store.has(b"a") and store.has(b"c")
        assert not store.has(b"b")
        assert store.get(b"b") is None
        assert st["hits"] == 1 and st["hit_bytes"] == 40
        assert store.stats()["misses"] == 1
        assert store.stats()["bytes_used"] == 80

    def test_clear_counts_flush_only_when_nonempty(self):
        store = FleetPrefixStore(100)
        store.clear()
        assert store.stats()["flushes"] == 0
        store.put(b"a", {"nbytes": 10})
        store.clear()
        assert store.stats()["flushes"] == 1
        assert store.stats()["bytes_used"] == 0 and len(store) == 0


# ---------------------------------------------------------------------------
# Park/unpark stream exactness — every dtype, greedy and sampled.
# ---------------------------------------------------------------------------
class TestParkUnparkExact:
    @pytest.mark.parametrize("dt", ALL_DTYPES)
    @pytest.mark.parametrize("sampled", [False, True],
                             ids=["greedy", "sampled"])
    def test_resumed_stream_token_exact(self, gqa_params, dt, sampled):
        cfg, params = gqa_params
        prompt = np.arange(1, 12, dtype=np.int32)
        sp = (SamplingParams(temperature=0.9, top_k=20, seed=13)
              if sampled else SamplingParams(greedy=True))

        ref = _engine(params, cfg, dt=dt)
        ref_rid = ref.add_request(prompt, 10, sp)
        ref_streams = _drain(ref)

        eng = _engine(params, cfg, dt=dt, spill_mb=2.0)
        streams = {}
        rid = eng.add_request(prompt, 10, sp)
        _step_until_token(eng, rid, streams)
        n_before = len(streams[rid])
        assert eng.park_request(rid)
        assert rid in eng._parked and eng.requests[rid].slot == -1
        # Parked + held: idle steps emit nothing for this session.
        for _ in range(3):
            ev = eng.step()
            assert not any(r == rid for r, _ in ev["tokens"])
        assert eng.resume_request(rid)
        _drain(eng, streams)
        eng.pool.audit()
        assert streams[rid] == ref_streams[ref_rid]
        assert len(streams[rid]) > n_before
        st = eng.spill.stats()
        assert st["parks"] == st["unparks"] == 1
        assert st["park_bytes"] == st["unpark_bytes"] > 0
        assert st["bytes_used"] == 0

    def test_park_bytes_pin(self, gqa_params):
        """Exact serialized-byte pin: a parked payload is
        2 (K+V) x layers x valid rows x kv-heads x head-dim x the
        STORED itemsize — measured off the exported arrays (the pool
        keeps unquantized KV in the compute dtype)."""
        cfg, params = gqa_params
        prompt = np.arange(1, 12, dtype=np.int32)
        eng = _engine(params, cfg, spill_mb=2.0)
        rid = eng.add_request(prompt, 10, SamplingParams(greedy=True))
        _step_until_token(eng, rid, {})
        valid = int(eng.lengths[eng.requests[rid].slot])
        assert eng.park_request(rid)
        payload = eng.spill.get(rid)
        hkv = cfg.num_query_groups
        itemsize = payload["rows"][0].dtype.itemsize
        want = (2 * cfg.num_layers * valid * hkv * cfg.head_dim
                * itemsize)
        assert payload["nbytes"] == want
        assert eng.spill.bytes_used == want

    def test_watermark_without_budget_rejected(self, gqa_params):
        cfg, params = gqa_params
        with pytest.raises(ValueError, match="budget"):
            _engine(params, cfg, watermark=2)


# ---------------------------------------------------------------------------
# Spill-vs-preempt ordering under pool pressure.
# ---------------------------------------------------------------------------
class TestSpillVsPreempt:
    def _pressure_run(self, cfg, params, spill_mb):
        rng = np.random.default_rng(3)
        prompts = [rng.integers(0, cfg.vocab_size, 11).astype(np.int32)
                   for _ in range(4)]
        eng = _engine(params, cfg, max_batch=4, num_blocks=6,
                      spill_mb=spill_mb, prefix_caching=False)
        rids = [eng.add_request(p, 8, SamplingParams(greedy=True))
                for p in prompts]
        streams = _drain(eng)
        eng.pool.audit()
        return eng, rids, streams, prompts

    def test_spill_preferred_over_preemption(self, gqa_params):
        cfg, params = gqa_params
        base, b_rids, b_streams, prompts = self._pressure_run(
            cfg, params, spill_mb=0.0)
        eng, rids, streams, _ = self._pressure_run(
            cfg, params, spill_mb=4.0)
        st = eng.spill.stats()
        assert st["parks"] > 0 and st["parks"] == st["unparks"]
        # Pressure routed through the tier first: strictly fewer KV
        # throw-aways than the spill-less run.
        assert (eng.pool.stats["preemptions"]
                < base.pool.stats["preemptions"])
        # Both legs complete every stream identically (preemption
        # re-prefills, parking restores bytes — greedy is exact
        # either way).
        for r_a, r_b in zip(rids, b_rids):
            assert streams[r_a] == b_streams[r_b]
            assert len(streams[r_a]) == 8

    def test_budget_reject_falls_back_to_preemption(self, gqa_params):
        cfg, params = gqa_params
        # A 1 KiB budget can't hold a single payload: every park is
        # refused and pressure falls through to preemption, which
        # still completes the work.
        eng, rids, streams, _ = self._pressure_run(
            cfg, params, spill_mb=1 / 1024.0)
        st = eng.spill.stats()
        assert st["parks"] == 0 and st["rejects"] > 0
        assert eng.pool.stats["preemptions"] > 0
        assert all(len(streams[r]) == 8 for r in rids)

    def test_watermark_parks_idle_sessions(self, gqa_params):
        """A watermark drains blocks below the floor by parking the
        lowest-priority runner even before admission starves."""
        cfg, params = gqa_params
        eng = _engine(params, cfg, max_batch=2, num_blocks=8,
                      spill_mb=4.0, watermark=7, prefix_caching=False)
        rid = eng.add_request(np.arange(1, 12, dtype=np.int32), 6,
                              SamplingParams(greedy=True))
        streams = {}
        _step_until_token(eng, rid, streams)
        # 12 tokens -> 2 blocks in use, 6 free < the 7-block floor:
        # the policy parks the session at the next step (and the idle
        # engine unparks it to make progress — thrash is bounded to
        # one park/unpark pair per step by _no_repark).
        eng.step()
        assert eng.spill.stats()["parks"] >= 1
        _drain(eng, streams)
        eng.pool.audit()
        assert len(streams[rid]) == 6


# ---------------------------------------------------------------------------
# Fleet-global prefix store — in-process router.
# ---------------------------------------------------------------------------
class TestFleetPrefixStoreRouting:
    def _fleet(self, cfg, params, store_mb, spill_mb=0.0):
        return FleetRouter(
            engine_factory=lambda i, **kw: _engine(
                params, cfg, spill_mb=spill_mb),
            num_replicas=2, policy="round_robin", migrate=False,
            prefix_store_mb=store_mb)

    def _drain_router(self, router, streams, max_steps=512):
        while router.has_work:
            ev = router.step()
            for r, tok in ev["tokens"]:
                streams.setdefault(r, []).append(int(tok))
            max_steps -= 1
            assert max_steps > 0

    def test_second_replica_gathers_prefix_from_store(self, gqa_params):
        cfg, params = gqa_params
        prompt = np.asarray(list(range(1, 26)), np.int32)
        router = self._fleet(cfg, params, store_mb=1.0)
        streams = {}
        r1 = router.add_request(prompt, 4, SamplingParams(greedy=True))
        self._drain_router(router, streams)
        # Replica 0 registered the prefix; its blocks were exported
        # into the store (3 full blocks of the 25-token prompt).
        st = router.prefix_store.stats()
        assert st["entries"] == 3
        r2 = router.add_request(prompt, 4, SamplingParams(greedy=True))
        self._drain_router(router, streams)
        for rep in router.replicas:
            rep.engine.pool.audit()
        fs = router.router_stats
        # Exact accounting: 3 blocks seeded, bf16 block bytes =
        # 2(K+V) x L x 8 x hkv x d x 2 = 4096, and at prefill_chunk=8
        # the 25-token prompt skips 3 of its 4 chunks.
        assert fs["prefix_store_seeded_blocks"] == 3
        assert fs["prefix_store_seeded_bytes"] == 3 * 4096
        assert fs["prefix_store_admission_hits"] == 1
        assert fs["prefill_chunks_avoided"] == 3
        assert router.prefix_store.stats()["hits"] == 3
        assert streams[r1] == streams[r2]

    def test_storeless_baseline_avoids_nothing(self, gqa_params):
        cfg, params = gqa_params
        prompt = np.asarray(list(range(1, 26)), np.int32)
        router = self._fleet(cfg, params, store_mb=0.0)
        streams = {}
        router.add_request(prompt, 4, SamplingParams(greedy=True))
        self._drain_router(router, streams)
        router.add_request(prompt, 4, SamplingParams(greedy=True))
        self._drain_router(router, streams)
        assert router.prefix_store is None
        assert router.router_stats["prefill_chunks_avoided"] == 0

    def test_reload_flushes_store(self, gqa_params):
        cfg, params = gqa_params
        prompt = np.asarray(list(range(1, 26)), np.int32)
        router = self._fleet(cfg, params, store_mb=1.0)
        streams = {}
        router.add_request(prompt, 4, SamplingParams(greedy=True))
        self._drain_router(router, streams)
        assert len(router.prefix_store) == 3
        router.begin_rolling_reload(params)
        self._drain_router(router, streams)
        # Stored blocks hold KV from weights no longer guaranteed
        # fleet-wide: the reload flushed them.
        assert len(router.prefix_store) == 0
        assert router.prefix_store.stats()["flushes"] >= 1

    def test_parked_session_migrates(self, gqa_params):
        cfg, params = gqa_params
        prompt = np.arange(1, 12, dtype=np.int32)
        ref_eng = _engine(params, cfg)
        ref_rid = ref_eng.add_request(prompt, 8,
                                      SamplingParams(greedy=True))
        ref_streams = _drain(ref_eng)

        router = self._fleet(cfg, params, store_mb=0.0, spill_mb=2.0)
        streams = {}
        rid = router.add_request(prompt, 8, SamplingParams(greedy=True))
        src = router.replicas[router._owner[rid]]
        while not streams.get(rid):
            ev = router.step()
            for r, tok in ev["tokens"]:
                streams.setdefault(r, []).append(int(tok))
        assert router.park_request(rid)
        assert rid in src.engine._parked
        # The spill payload IS the migration payload: the parked
        # session moves replicas without ever re-entering the source
        # pool, and the source drops the entry without an unpark.
        assert router.migrate_request(rid)
        dst = router.replicas[router._owner[rid]]
        assert dst.idx != src.idx
        assert rid not in src.engine._parked
        assert rid in dst.engine.requests
        assert src.engine.spill.stats()["unparks"] == 0
        self._drain_router(router, streams)
        for rep in router.replicas:
            rep.engine.pool.audit()
        assert streams[rid] == ref_streams[ref_rid]


# ---------------------------------------------------------------------------
# Cross-process: prefix verbs + the non-local addr guard.
# ---------------------------------------------------------------------------
class TestCrossProcessStore:
    def _spec(self, **kw):
        from megatronapp_tpu.inference.fleet_rpc import (
            default_engine_spec,
        )
        return default_engine_spec(prefill_chunk=8, **kw)

    def test_prefix_verbs_seed_second_replica(self, tmp_path):
        from megatronapp_tpu.inference.fleet_rpc import launch_threaded
        router, _ = launch_threaded(
            str(tmp_path), self._spec(), num_replicas=2,
            policy="round_robin", prefix_store_mb=1.0)
        try:
            prompt = np.asarray(list(range(1, 26)), np.int32)
            streams = {}
            r1 = router.add_request(prompt, 4,
                                    SamplingParams(greedy=True))
            while router.has_work:
                for r, tok in router.step()["tokens"]:
                    streams.setdefault(r, []).append(int(tok))
            assert router.prefix_store.stats()["entries"] == 3
            r2 = router.add_request(prompt, 4,
                                    SamplingParams(greedy=True))
            while router.has_work:
                for r, tok in router.step()["tokens"]:
                    streams.setdefault(r, []).append(int(tok))
            fs = router.router_stats
            assert fs["prefix_store_seeded_blocks"] == 3
            assert fs["prefix_store_seeded_bytes"] == 3 * 4096
            assert fs["prefill_chunks_avoided"] == 3
            assert streams[r1] == streams[r2]
            router.audit()
        finally:
            router.shutdown()

    def test_park_resume_verbs(self, tmp_path):
        from megatronapp_tpu.inference.fleet_rpc import launch_threaded
        spec = self._spec(kv_spill_host_mb=2.0)
        router, _ = launch_threaded(str(tmp_path), spec,
                                    num_replicas=2)
        try:
            prompt = np.arange(1, 12, dtype=np.int32)
            streams = {}
            rid = router.add_request(prompt, 8,
                                     SamplingParams(greedy=True))
            while not streams.get(rid):
                for r, tok in router.step()["tokens"]:
                    streams.setdefault(r, []).append(int(tok))
            assert router.park_request(rid)
            for _ in range(3):
                ev = router.step()
                assert not any(r == rid for r, _ in ev["tokens"])
            assert router.resume_request(rid)
            while router.has_work:
                for r, tok in router.step()["tokens"]:
                    streams.setdefault(r, []).append(int(tok))
            assert len(streams[rid]) == 8
            router.audit()
        finally:
            router.shutdown()

    def test_nonlocal_addr_fails_loudly(self, tmp_path):
        from megatronapp_tpu.inference.fleet_rpc import (
            _write_json_atomic, read_addr, replica_dir,
        )
        os.makedirs(replica_dir(str(tmp_path), 0), exist_ok=True)
        _write_json_atomic(
            os.path.join(replica_dir(str(tmp_path), 0), "addr.json"),
            {"host": "10.0.0.5", "port": 9999, "pid": 1,
             "incarnation": 0})
        with pytest.raises(RuntimeError,
                           match="multi-host spawn not yet supported"):
            read_addr(str(tmp_path), 0)


# ---------------------------------------------------------------------------
# Serving-flag validations.
# ---------------------------------------------------------------------------
class TestServingFlags:
    def _args(self, extra):
        from megatronapp_tpu.config.arguments import build_parser
        return build_parser().parse_args(
            ["--num-layers", "2", "--hidden-size", "64",
             "--num-attention-heads", "4"] + extra)

    def _check(self, extra, frag=None):
        from megatronapp_tpu.config.arguments import (
            validate_serving_args,
        )
        args = self._args(extra)
        if frag is None:
            validate_serving_args(args)
        else:
            with pytest.raises(SystemExit, match=frag):
                validate_serving_args(args)

    def test_valid_combinations(self):
        self._check(["--engine", "dynamic",
                     "--kv-spill-host-mb", "64",
                     "--kv-spill-watermark-blocks", "4"])
        self._check(["--engine", "dynamic",
                     "--serve-fleet", "2",
                     "--fleet-prefix-store-mb", "8"])

    def test_rejections(self):
        self._check(["--kv-spill-host-mb", "-1"], "kv-spill-host-mb")
        self._check(["--engine", "static", "--kv-spill-host-mb", "8"],
                    "dynamic")
        self._check(["--engine", "dynamic",
                     "--serve-disagg", "--kv-spill-host-mb", "8"],
                    "disagg")
        self._check(["--engine", "dynamic",
                     "--kv-spill-watermark-blocks", "4"], "watermark")
        self._check(["--fleet-prefix-store-mb", "4"], "fleet")


# ---------------------------------------------------------------------------
# The loadgen long-idle phases + the bench gates (one cheap smoke).
# ---------------------------------------------------------------------------
class TestLoadgenAndBench:
    def test_loadgen_trace_marks_idle_requests(self):
        from tools.loadgen import make_trace
        trace = make_trace(seed=0, n_requests=12, idle_every=3,
                           idle_after=2, idle_steps=4)
        idle = [e for e in trace if e["idle_after"] is not None]
        assert idle, "idle_every=3 marked no requests"
        assert all(e["abort_after"] is None for e in idle)
        # Off switch replays the exact same trace as before the
        # feature existed (no extra RNG draws).
        base = make_trace(seed=0, n_requests=12)
        assert all(e["idle_after"] is None for e in base)
        for a, b in zip(trace, base):
            assert np.array_equal(a["prompt"], b["prompt"])
            assert a["max_new"] == b["max_new"]

    def test_loadgen_replay_parks_and_resumes(self, gqa_params):
        from tools.loadgen import make_trace, replay
        cfg, params = gqa_params
        eng = _engine(params, cfg, max_batch=4, spill_mb=4.0,
                      prefix_caching=False)
        trace = make_trace(seed=1, n_requests=6, tenants=2,
                           prefix_len=8, tail_min=2, tail_max=4,
                           max_new_min=4, max_new_max=6,
                           idle_every=2, idle_after=1, idle_steps=3)
        out = replay(eng, trace)
        assert out["report"]["idled"] >= 1
        st = eng.spill.stats()
        assert st["parks"] >= out["report"]["idled"]
        assert st["unparks"] == st["parks"]
        eng.pool.audit()
        # Every stream ran to its budget despite the idle phases.
        by_id = {e["id"]: e for e in trace}
        for i, toks in out["streams"].items():
            assert len(toks) == by_id[i]["max_new"]

    @pytest.mark.slow
    def test_kv_spill_benchmark_gates(self):
        from tools.kv_spill_benchmark import run
        res = run(num_blocks=8, sessions=6, spill_mb=4.0,
                  dtypes=("bf16",))
        assert res["ok"], res
        cap = res["capacity"]
        assert cap["sessions_ratio"] >= cap["ratio_gate"] == 2.0
        assert cap["resume_token_exact"]
        assert res["fleet_prefix"]["with_store"][
            "prefill_chunks_avoided"] >= 1
