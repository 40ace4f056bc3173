"""Fleet serving subsystem tests (ISSUE 14).

Covers the tentpole and its satellites:

- `PagedKVCache.export_slot`/`import_slot` across ALL KV_CACHE_DTYPES:
  exact byte-count pins off the addressable exported arrays, verbatim
  round-trip bytes, refcount/CoW invariants under migration, and
  exhaustion/fault rollback (audit-clean both pools);
- live session migration through the router: greedy AND sampled streams
  token-exact vs an unmigrated run for every dtype;
- KV-affinity admission: shared-prefix followers steer to the replica
  holding the prefix (round-robin spreads them), fed from the pool's
  prefix-insert events;
- drain-aware rolling reload: zero dropped requests, per-replica swap,
  router affinity flushed (negated-params discrimination);
- replica death: sessions fail over with nothing lost, streams exact;
- a 3-replica mixed-traffic soak with a mid-soak replica kill;
- the args/validation satellites and the bench smoke gate.
"""

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from megatronapp_tpu.config.transformer_config import TransformerConfig
from megatronapp_tpu.inference.dynamic_engine import DynamicInferenceEngine
from megatronapp_tpu.inference.engine import SamplingParams
from megatronapp_tpu.inference.fleet import (
    ACTIVE, DEAD, FleetRouter, MeshSplitAutoscaler,
)
from megatronapp_tpu.inference.paged_cache import (
    KV_CACHE_DTYPES, PagedKVCache, prefix_block_keys,
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


def _mla_cfg(max_pos=64):
    return TransformerConfig(
        num_layers=2, hidden_size=64, num_attention_heads=4,
        num_query_groups=2, vocab_size=128,
        max_position_embeddings=max_pos,
        compute_dtype=jnp.float32, remat_policy="none",
        multi_latent_attention=True, kv_lora_rank=32,
        qk_head_dim=16, qk_pos_emb_head_dim=8, v_head_dim=16)


@pytest.fixture(scope="module")
def mla_params():
    cfg = _mla_cfg()
    params, _ = init_gpt_params(jax.random.PRNGKey(9), cfg)
    return cfg, params


from jitted import greedy_oracle as _greedy_oracle  # noqa: E402


def _engine(params, cfg, dt="bf16", max_batch=2, num_blocks=None):
    return DynamicInferenceEngine(
        params, cfg, max_batch=max_batch, max_seq_len=48,
        prefill_buckets=(16,), paged=True, block_size=8,
        num_blocks=num_blocks, kv_cache_dtype=dt)


def _fleet(params, cfg, n=2, dt="bf16", **kw):
    return FleetRouter(
        engine_factory=lambda i, **h: _engine(params, cfg, dt=dt),
        num_replicas=n, **kw)


# ---------------------------------------------------------------------------
class TestExportImportPool:
    @pytest.mark.parametrize("dt", ALL_DTYPES)
    def test_byte_pin_and_verbatim_roundtrip(self, gqa_params, dt):
        """Exact byte-count pin off the addressable exported arrays
        (quantized pools ship 1-byte rows + fp32 scales; the baseline
        ships compute-dtype rows), and export→import→export returns
        bit-identical bytes — the copy-exact foundation."""
        cfg, _ = gqa_params
        a = PagedKVCache(cfg, 2, 64, block_size=8, kv_cache_dtype=dt)
        b = PagedKVCache(cfg, 2, 64, block_size=8, kv_cache_dtype=dt)
        toks = np.arange(19, dtype=np.int32)
        plan = a.admit(0, toks)
        a.pages = tuple(p.at[:, plan.blocks[0]].set(1) for p in a.pages)
        pay = a.export_slot(0, 19)
        L, hkv, d = cfg.num_layers, cfg.num_query_groups, cfg.head_dim
        v = 19
        spec = KV_CACHE_DTYPES[dt]
        if spec.quantized:
            want = 2 * (L * v * hkv * d * 1 + L * v * hkv * 4)
        else:
            itemsize = jnp.dtype(cfg.compute_dtype).itemsize
            want = 2 * L * v * hkv * d * itemsize
        assert pay["nbytes"] == want
        assert pay["nbytes"] == sum(
            r.nbytes for r in pay["rows"]) + sum(
            s.nbytes for s in (pay["scales"] or ()))
        assert b.import_slot(1, pay)
        a.audit(), b.audit()
        pay2 = b.export_slot(1, 19)
        for r1, r2 in zip(pay["rows"], pay2["rows"]):
            assert r1.dtype == r2.dtype
            assert np.array_equal(r1.view(np.uint8), r2.view(np.uint8))
        if pay["scales"] is not None:
            for s1, s2 in zip(pay["scales"], pay2["scales"]):
                assert np.array_equal(s1, s2)

    @pytest.mark.parametrize("dt", ALL_DTYPES)
    def test_exhaustion_rolls_back_clean(self, gqa_params, dt):
        cfg, _ = gqa_params
        a = PagedKVCache(cfg, 2, 64, block_size=8, kv_cache_dtype=dt)
        a.admit(0, np.arange(19, dtype=np.int32))
        pay = a.export_slot(0, 19)
        tiny = PagedKVCache(cfg, 1, 16, num_blocks=1, block_size=8,
                            kv_cache_dtype=dt)
        assert tiny.import_slot(0, pay) is False
        assert tiny.free_blocks() == 1 and not tiny.slot_blocks(0)
        tiny.audit()

    def test_dtype_mismatch_rejected(self, gqa_params):
        cfg, _ = gqa_params
        a = PagedKVCache(cfg, 1, 32, block_size=8, kv_cache_dtype="int8")
        a.admit(0, np.arange(9, dtype=np.int32))
        pay = a.export_slot(0, 9)
        b = PagedKVCache(cfg, 1, 32, block_size=8, kv_cache_dtype="fp8")
        with pytest.raises(ValueError, match="verbatim"):
            b.import_slot(0, pay)

    def test_refcount_and_cow_invariants_after_import(self, gqa_params):
        """The imported slot's blocks are private (rc==1); registering
        its prefix makes a FULL-hit follower take the CoW path on the
        destination exactly like a locally-prefilled prompt would —
        migration does not weaken block-sharing semantics."""
        cfg, params = gqa_params
        a = _engine(params, cfg)
        b = _engine(params, cfg)
        prompt = np.arange(16, dtype=np.int32)     # exactly 2 blocks
        ra = a.add_request(prompt, 6, SamplingParams(greedy=True))
        while len(a.requests[ra].generated) < 3:
            a.step()
        pay = a.export_request(ra)
        assert b.import_request(pay)
        a.release_exported(ra)
        slot = b.requests[ra].slot
        for blk in b.pool.slot_blocks(slot):
            assert b.pool.refcount(blk) == 1
        cow_before = b.pool.stats["cow_copies"]
        # Full-prefix-hit follower on the DESTINATION: must CoW the last
        # block, never write a shared one.
        rb = b.add_request(prompt.copy(), 2, SamplingParams(greedy=True))
        b.run_to_completion()
        assert b.pool.stats["cow_copies"] == cow_before + 1
        assert b.pool.stats["prefix_hit_tokens"] >= 15
        b.pool.audit()
        a.pool.audit()
        assert a.pool.blocks_in_use() == 0


# ---------------------------------------------------------------------------
class TestMigratedStreams:
    @pytest.mark.parametrize("dt", ALL_DTYPES)
    def test_greedy_stream_token_exact(self, gqa_params, dt):
        """The decisive pin: a session migrated mid-decode continues
        with a token-exact greedy stream vs the unmigrated baseline,
        for every KV dtype."""
        cfg, params = gqa_params
        rng = np.random.default_rng(1)
        prompt = rng.integers(0, 128, 13).astype(np.int32)
        base_eng = _engine(params, cfg, dt=dt)
        r0 = base_eng.add_request(prompt, 10, SamplingParams(greedy=True))
        base = base_eng.run_to_completion()[r0].tolist()
        fr = _fleet(params, cfg, dt=dt)
        rid = fr.add_request(prompt, 10, SamplingParams(greedy=True))
        src = fr._owner[rid]
        while len(fr.replicas[src].engine.requests[rid].generated) < 4:
            fr.step()
        dst = 1 - src
        assert fr.migrate_request(rid, dst)
        assert fr._owner[rid] == dst
        out = fr.run_to_completion()[rid].tolist()
        assert out == base
        for rep in fr.replicas:
            rep.engine.pool.audit()
        assert fr.replicas[src].engine.pool.blocks_in_use() == 0
        assert fr.router_stats["migrations"] == 1

    def test_sampled_stream_token_exact(self, gqa_params):
        """Sampled streams migrate exactly too: the fold_in key chain
        (seed ∘ rid ∘ step) never references the replica, and the rid
        space is fleet-global."""
        cfg, params = gqa_params
        rng = np.random.default_rng(2)
        prompt = rng.integers(0, 128, 11).astype(np.int32)
        sp = SamplingParams(temperature=0.8, top_k=20, seed=5)
        base_eng = _engine(params, cfg)
        r0 = base_eng.add_request(prompt, 10, sp)
        base = base_eng.run_to_completion()[r0].tolist()
        fr = _fleet(params, cfg)
        rid = fr.add_request(prompt, 10, sp)
        assert rid == r0, "fleet rid space must mirror the single engine"
        src = fr._owner[rid]
        while len(fr.replicas[src].engine.requests[rid].generated) < 5:
            fr.step()
        assert fr.migrate_request(rid, 1 - src)
        out = fr.run_to_completion()[rid].tolist()
        assert out == base

    @pytest.mark.parametrize("dt", ["bf16", "int8"])
    def test_mla_greedy_stream_token_exact(self, mla_params, dt):
        """ISSUE 17: MLA latent pools migrate token-exact too — the
        export payload ships [klat] latent + [dpe] roped-key rows (and
        per-row SCALAR scales when quantized) verbatim; nothing in the
        hop re-expands through kv_up."""
        cfg, params = mla_params
        rng = np.random.default_rng(4)
        prompt = rng.integers(0, 128, 13).astype(np.int32)
        base_eng = _engine(params, cfg, dt=dt)
        r0 = base_eng.add_request(prompt, 10, SamplingParams(greedy=True))
        base = base_eng.run_to_completion()[r0].tolist()
        fr = _fleet(params, cfg, dt=dt)
        rid = fr.add_request(prompt, 10, SamplingParams(greedy=True))
        src = fr._owner[rid]
        while len(fr.replicas[src].engine.requests[rid].generated) < 4:
            fr.step()
        dst = 1 - src
        assert fr.migrate_request(rid, dst)
        assert fr._owner[rid] == dst
        out = fr.run_to_completion()[rid].tolist()
        assert out == base
        for rep in fr.replicas:
            rep.engine.pool.audit()
        assert fr.replicas[src].engine.pool.blocks_in_use() == 0
        assert fr.router_stats["migrations"] == 1

    def test_mla_sampled_stream_token_exact(self, mla_params):
        cfg, params = mla_params
        rng = np.random.default_rng(5)
        prompt = rng.integers(0, 128, 11).astype(np.int32)
        sp = SamplingParams(temperature=0.8, top_k=20, seed=5)
        base_eng = _engine(params, cfg)
        r0 = base_eng.add_request(prompt, 10, sp)
        base = base_eng.run_to_completion()[r0].tolist()
        fr = _fleet(params, cfg)
        rid = fr.add_request(prompt, 10, sp)
        src = fr._owner[rid]
        while len(fr.replicas[src].engine.requests[rid].generated) < 5:
            fr.step()
        assert fr.migrate_request(rid, 1 - src)
        out = fr.run_to_completion()[rid].tolist()
        assert out == base

    def test_disagg_replica_migration_delegates(self, gqa_params,
                                                devices8):
        """A DisaggServingEngine replica exports/imports through its
        decode engine — a decode-slot session hops between two disagg
        replicas token-exact."""
        from megatronapp_tpu.inference.disagg import DisaggServingEngine
        cfg, params = gqa_params

        def factory(i, **hints):
            return DisaggServingEngine(
                params, cfg, max_batch=2, max_seq_len=48,
                prefill_buckets=(16,), block_size=8, prefill_chunk=8,
                prefill_slots=1, devices=devices8[2 * i:2 * i + 2])

        rng = np.random.default_rng(3)
        prompt = rng.integers(0, 128, 9).astype(np.int32)
        base = _greedy_oracle(params, cfg, prompt, 8)
        fr = FleetRouter(engine_factory=factory, num_replicas=2)
        rid = fr.add_request(prompt, 8, SamplingParams(greedy=True))
        src = fr._owner[rid]
        # Step until the session is decoding (adopted into a slot).
        for _ in range(60):
            fr.step()
            req = fr.replicas[src].engine.requests.get(rid)
            if req is not None and req.slot >= 0 and len(
                    req.generated) >= 3:
                break
        assert fr.migrate_request(rid, 1 - src)
        out = fr.run_to_completion()[rid].tolist()
        assert out == base
        for rep in fr.replicas:
            rep.engine.pool.audit()


# ---------------------------------------------------------------------------
class TestAffinityRouting:
    def test_followers_steer_to_prefix_replica(self, gqa_params):
        """Same-prefix followers land on the replica whose pool holds
        the prefix blocks (fed by prefix-insert events); round-robin
        spreads them. The affinity fleet's aggregate hit rate must beat
        round-robin's on identical traffic."""
        cfg, params = gqa_params
        rng = np.random.default_rng(4)
        shared = rng.integers(0, 128, 16).astype(np.int32)
        followers = [np.concatenate(
            [shared, rng.integers(0, 128, 3).astype(np.int32)])
            for _ in range(3)]

        def hit_rate(policy):
            # Followers run sequentially: the admission decision under
            # test is affinity-vs-idle-fleet (load differentials are
            # their own term in the score and tested by the weights'
            # semantics, not here).
            fr = _fleet(params, cfg, policy=policy)
            lead = fr.add_request(shared.copy(), 2,
                                  SamplingParams(greedy=True))
            leader_rep = fr._owner[lead]
            fr.run_to_completion()
            owners = []
            for p in followers:
                rid = fr.add_request(p, 2, SamplingParams(greedy=True))
                owners.append(fr._owner[rid])
                fr.run_to_completion()
            snap = fr.stats_snapshot()["fleet"]
            return snap["prefix_hit_rate"], owners, leader_rep, snap

        aff_rate, aff_owners, leader, snap = hit_rate("affinity")
        rr_rate, rr_owners, _, _ = hit_rate("round_robin")
        assert all(o == leader for o in aff_owners), (
            f"affinity must steer followers to replica {leader}, "
            f"got {aff_owners}")
        assert len(set(rr_owners)) > 1, "round robin must spread"
        assert aff_rate > rr_rate
        assert snap["affinity_admissions"] >= 3
        assert snap["affinity_entries"] > 0

    def test_affinity_map_bounded(self, gqa_params):
        cfg, params = gqa_params
        fr = _fleet(params, cfg, affinity_capacity=3)
        fr._note_prefixes(0, [bytes([i]) for i in range(10)])
        assert len(fr._affinity) == 3

    def test_router_and_pool_share_hashing(self, gqa_params):
        """The router walks the SAME rolling hashes the pool registers
        — pinned by feeding pool-registered keys back through
        prefix_block_keys."""
        cfg, params = gqa_params
        eng = _engine(params, cfg)
        prompt = np.arange(16, dtype=np.int32)
        rid = eng.add_request(prompt, 2, SamplingParams(greedy=True))
        seen = []
        eng.pool.prefix_listener = seen.append
        eng.run_to_completion()
        keys = prefix_block_keys(prompt, 8, len(prompt))
        assert seen and set(keys) >= set(seen[0])


# ---------------------------------------------------------------------------
class TestRollingReloadFleet:
    def test_rolling_reload_zero_drops_and_affinity_flush(
            self, gqa_params):
        """The acceptance pin: a fleet-wide reload drains replicas one
        at a time with ZERO dropped requests; after the roll every
        replica serves the new weights (negated-params discrimination)
        and the router's affinity map is empty — a reloaded replica
        cannot be steered to for stale-weight hits (satellite 1)."""
        from megatronapp_tpu.inference.server import DynamicBatchingDriver
        cfg, params = gqa_params
        params2 = jax.tree.map(lambda x: -x, params)
        rng = np.random.default_rng(5)
        prompt_cached = rng.integers(0, 128, 16).astype(np.int32)
        fr = _fleet(params, cfg, n=2, migrate=True)
        drv = DynamicBatchingDriver(fr)
        # Warm the affinity map with a cached prefix on some replica.
        r0, d0 = drv.submit(prompt_cached, 4, SamplingParams(greedy=True))
        assert d0.wait(120)
        assert len(fr._affinity) > 0
        # A long-running request must survive the roll (migrated or
        # drained, never dropped).
        p_long = rng.integers(0, 128, 6).astype(np.int32)
        first_tok = threading.Event()
        rl, dl = drv.submit(p_long, 14, SamplingParams(greedy=True),
                            token_cb=lambda r, t: first_tok.set())
        assert first_tok.wait(120)
        ev = drv.request_reload(params2)
        assert dl.wait(120), "in-flight request dropped by the roll"
        assert ev.wait(120), "rolling reload never completed"
        assert fr.router_stats["reloads"] == 1
        assert fr.router_stats["replica_reloads"] == 2
        assert all(r.params_version == fr._version for r in fr.replicas)
        assert len(fr._affinity) == 0, (
            "router affinity must flush with the pools")
        assert drv.stats()["reload_pending"] is False
        # The in-flight request completed with ALL its tokens (old or
        # migrated-exact path — never truncated).
        toks = drv.result_tokens(rl)
        assert toks is not None and len(toks) == len(p_long) + 14
        # Discrimination: the previously-cached prompt now decodes the
        # NEGATED-params oracle on whatever replica admits it.
        r2, d2 = drv.submit(prompt_cached.copy(), 4,
                            SamplingParams(greedy=True))
        assert d2.wait(120)
        assert drv.result_tokens(r2).tolist() == _greedy_oracle(
            params2, cfg, prompt_cached, 4)
        for rep in fr.replicas:
            rep.engine.pool.audit()

    def test_admission_during_drain_queues_not_errors(self, gqa_params):
        """Review fix: a drain window with no ACTIVE replica (e.g. a
        single-replica fleet mid-reload) must QUEUE new requests on a
        draining replica — the reload promise is zero drops, and the
        replaced single-engine path queued during its drain too."""
        cfg, params = gqa_params
        fr = _fleet(params, cfg, n=1)
        ev = fr.begin_rolling_reload(jax.tree.map(lambda x: -x, params))
        fr.replicas[0].state = "draining"    # mid-drain window
        prompt = np.arange(7, dtype=np.int32)
        rid = fr.add_request(prompt, 3, SamplingParams(greedy=True))
        out = fr.run_to_completion()[rid].tolist()
        assert ev.is_set()
        # Queued through the drain, decoded on the NEW weights.
        assert out == _greedy_oracle(
            jax.tree.map(lambda x: -x, params), cfg, prompt, 3)

    def test_reload_with_pending_rebuild_does_not_strand(self,
                                                         gqa_params):
        """Review fix: a rolling reload racing a pending autoscale
        rebuild must not flip the replica back to ACTIVE with its
        rebuild_hints stranded — has_work would spin forever. The swap
        leaves the replica DRAINING; the rebuild applies; the fleet
        quiesces."""
        cfg, params = gqa_params
        fr = _fleet(params, cfg, n=2)
        fr.replicas[0].rebuild_hints = {}      # pending rebuild (no-op)
        fr.replicas[0].state = "draining"
        ev = fr.begin_rolling_reload(jax.tree.map(lambda x: -x, params))
        for _ in range(8):
            if ev.is_set() and not fr.has_work:
                break
            fr.step()
        assert ev.is_set()
        assert fr.replicas[0].rebuild_hints is None
        assert fr.replicas[0].state == ACTIVE
        assert not fr.has_work, "stranded rebuild hints spin the stepper"

    def test_revive_after_reload_serves_new_params(self, gqa_params):
        """Review fix: the engine factory captures STARTUP params — a
        replica revived after a reload must be swapped onto the
        current weights, not claim the new version holding stale
        ones."""
        cfg, params = gqa_params
        params2 = jax.tree.map(lambda x: -x, params)
        fr = _fleet(params, cfg, n=2)
        ev = fr.begin_rolling_reload(params2)
        while not ev.is_set():
            fr.step()
        fr.kill_replica(0)
        fr.revive_replica(0)
        prompt = np.arange(9, dtype=np.int32)
        # Force admission onto the revived replica.
        fr.replicas[1].state = "draining"
        rid = fr.add_request(prompt, 4, SamplingParams(greedy=True))
        assert fr._owner[rid] == 0
        fr.replicas[1].state = ACTIVE
        out = fr.run_to_completion()[rid].tolist()
        assert out == _greedy_oracle(params2, cfg, prompt, 4)

    def test_evacuation_version_fence_keeps_midstream(self, gqa_params):
        """Review fix: a preempted request carrying generated tokens is
        version-fenced on evacuation — with no same-version target it
        stays queued on the draining replica instead of continuing a
        half-old-half-new stream elsewhere; fresh requests move."""
        from megatronapp_tpu.inference.dynamic_engine import Request
        cfg, params = gqa_params
        fr = _fleet(params, cfg, n=2)
        src, dst = fr.replicas
        dst.params_version = 7     # mismatched version, only target
        fresh = Request(next(fr._ids), np.arange(5, dtype=np.int32), 2,
                        SamplingParams(greedy=True))
        mid = Request(next(fr._ids), np.arange(5, dtype=np.int32), 4,
                      SamplingParams(greedy=True))
        mid.generated = [3]
        for req in (fresh, mid):
            src.engine.requests[req.request_id] = req
            src.engine.waiting.append(req)
        src.state = "draining"
        fr._evacuate_waiting(src)
        assert fresh in dst.engine.waiting     # version-free: moved
        assert mid in src.engine.waiting       # fenced: stayed
        src.engine.waiting.clear()
        src.engine.requests.clear()
        dst.engine.waiting.clear()
        dst.engine.requests.clear()

    def test_migration_version_fence(self, gqa_params):
        """A half-rolled fleet must not migrate a stream between params
        versions: destinations are fenced on params_version."""
        cfg, params = gqa_params
        fr = _fleet(params, cfg, n=2)
        rid = fr.add_request(np.arange(9, dtype=np.int32), 10,
                             SamplingParams(greedy=True))
        src = fr._owner[rid]
        while len(fr.replicas[src].engine.requests[rid].generated) < 2:
            fr.step()
        # Fake the other replica onto a newer version.
        fr.replicas[1 - src].params_version = 99
        assert fr.migrate_request(rid, 1 - src) is False
        fr.replicas[1 - src].params_version = fr.replicas[
            src].params_version
        assert fr.migrate_request(rid, 1 - src) is True
        fr.run_to_completion()


# ---------------------------------------------------------------------------
class TestReplicaDeath:
    def test_failover_stream_exact_nothing_lost(self, gqa_params):
        """A dead replica's sessions fail over and finish with streams
        exactly equal to the never-killed oracle (resume == re-prefill
        prompt+generated, the preemption path)."""
        cfg, params = gqa_params
        rng = np.random.default_rng(6)
        prompt = rng.integers(0, 128, 9).astype(np.int32)
        want = _greedy_oracle(params, cfg, prompt, 8)
        fr = _fleet(params, cfg, n=2)
        rid = fr.add_request(prompt, 8, SamplingParams(greedy=True))
        src = fr._owner[rid]
        while len(fr.replicas[src].engine.requests[rid].generated) < 3:
            fr.step()
        fr.kill_replica(src)
        assert fr.replicas[src].state == DEAD
        assert fr._owner[rid] != src
        out = fr.run_to_completion()[rid].tolist()
        assert out == want
        assert fr.router_stats["failovers"] == 1
        snap = fr.stats_snapshot()
        assert snap["fleet"]["live_replicas"] == 1

    def test_step_exception_fails_over_not_fleetwide(self, gqa_params):
        """A replica whose step() raises is failed over INSIDE the
        fleet round — the fleet keeps serving and only raises when no
        live replica remains."""
        cfg, params = gqa_params
        fr = _fleet(params, cfg, n=2)
        rid = fr.add_request(np.arange(7, dtype=np.int32), 6,
                             SamplingParams(greedy=True))
        src = fr._owner[rid]

        def boom():
            raise RuntimeError("injected replica fault")

        fr.replicas[src].engine.step = boom
        out = fr.run_to_completion()[rid]
        assert len(out) == 7 + 6
        assert fr.replicas[src].state == DEAD
        # Second failure with no survivor left surfaces to the caller.
        other = fr.replicas[1 - src]
        r2 = fr.add_request(np.arange(5, dtype=np.int32), 2,
                            SamplingParams(greedy=True))
        other.engine.step = boom
        with pytest.raises(RuntimeError, match="injected"):
            for _ in range(4):
                fr.step()

    def test_revive_replaces_dead_replica(self, gqa_params):
        cfg, params = gqa_params
        fr = _fleet(params, cfg, n=2)
        fr.kill_replica(0)
        assert fr.stats_snapshot()["fleet"]["live_replicas"] == 1
        fr.revive_replica(0)
        assert fr.replicas[0].state == ACTIVE
        rid = fr.add_request(np.arange(6, dtype=np.int32), 2,
                             SamplingParams(greedy=True))
        fr.run_to_completion()
        assert fr.stats_snapshot()["fleet"]["live_replicas"] == 2


# ---------------------------------------------------------------------------
class TestFleetSoak:
    def test_three_replica_soak_with_kill_zero_lost(self, gqa_params):
        """3-replica mixed-traffic soak: concurrent submitters, one
        replica killed mid-soak — zero lost sessions, per-step audits
        clean on every LIVE pool, all streams complete."""
        from megatronapp_tpu.inference.server import DynamicBatchingDriver
        cfg, params = gqa_params
        fr = _fleet(params, cfg, n=3, migrate=True)
        audits = {"n": 0}
        orig_step = fr.step

        def audited_step():
            ev = orig_step()
            for rep in fr.replicas:
                if rep.state != DEAD:
                    rep.engine.pool.audit()
            audits["n"] += 1
            return ev

        fr.step = audited_step
        drv = DynamicBatchingDriver(fr)
        rng = np.random.default_rng(8)
        results = {}
        lock = threading.Lock()
        killed = threading.Event()

        def client(i):
            subs = []
            for j in range(3):
                n = int(rng.integers(4, 12))
                prompt = rng.integers(0, 128, n).astype(np.int32)
                want = int(rng.integers(6, 12))
                rid, done = drv.submit(prompt, want,
                                       SamplingParams(greedy=True))
                subs.append((rid, done, n, want))
                time.sleep(0.02)
                if i == 0 and j == 1 and not killed.is_set():
                    # Kill a replica that owns at least one session.
                    with lock:
                        victim = fr._owner.get(subs[0][0], 0)
                    fr.kill_replica(victim)
                    killed.set()
            for rid, done, plen, want in subs:
                assert done.wait(180), f"request {rid} lost"
                toks = drv.result_tokens(rid)
                with lock:
                    results[rid] = (toks, plen, want)

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(3)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(240)
            assert not t.is_alive(), "client thread hung"
        assert killed.is_set()
        assert len(results) == 9, "sessions lost in the soak"
        for rid, (toks, plen, want) in results.items():
            assert toks is not None and len(toks) == plen + want
        assert audits["n"] > 0
        snap = fr.stats_snapshot()["fleet"]
        assert snap["live_replicas"] == 2
        for rep in fr.replicas:
            if rep.state != DEAD:
                assert rep.engine.pool.blocks_in_use() == 0


# ---------------------------------------------------------------------------
class TestAutoscaler:
    def test_recommendation_logic(self):
        a = MeshSplitAutoscaler(target_attainment=0.9, queue_high=1.0,
                                cooldown=2)
        # Low attainment → shrink prefill.
        for _ in range(4):
            a.observe(0, 0.5, 0)
        assert a.recommend(0, prefill_devices=2, decode_devices=2) == 1
        # Cooldown suppresses the immediate follow-up.
        assert a.recommend(0, 2, 2) is None
        # Healthy attainment + deep prefill queue → grow prefill.
        b = MeshSplitAutoscaler(target_attainment=0.9, queue_high=1.0,
                                cooldown=2)
        for _ in range(6):
            b.observe(1, 1.0, 4)
        assert b.recommend(1, prefill_devices=1, decode_devices=3) == 2
        # Floor: never shrink a side below one tp group.
        c = MeshSplitAutoscaler(target_attainment=0.9)
        for _ in range(4):
            c.observe(2, 0.1, 0)
        assert c.recommend(2, prefill_devices=1, decode_devices=1) is None

    def test_autoscale_rebuilds_disagg_split(self, gqa_params, devices8):
        """Integration: a disagg replica with poor forced attainment
        drains and rebuilds with a smaller prefill sub-mesh through the
        engine factory, dropping nothing."""
        from megatronapp_tpu.inference.disagg import DisaggServingEngine
        cfg, params = gqa_params

        def factory(i, prefill_devices=2, **hints):
            return DisaggServingEngine(
                params, cfg, max_batch=2, max_seq_len=48,
                prefill_buckets=(16,), block_size=8, prefill_chunk=8,
                prefill_slots=1, devices=devices8[:4],
                prefill_devices=prefill_devices, **hints)

        fr = FleetRouter(engine_factory=factory, num_replicas=1,
                         autoscale=True, slo_ms=1e-6, migrate=False)
        fr.autoscaler = MeshSplitAutoscaler(
            target_attainment=0.9, cooldown=2)
        assert fr.replicas[0].engine.prefill_ctx.num_devices == 2
        rid = fr.add_request(np.arange(9, dtype=np.int32), 10,
                             SamplingParams(greedy=True))
        res = fr.run_to_completion()
        assert len(res[rid]) == 19        # nothing dropped
        # The impossible SLO forced attainment ~0 → a shrink decision;
        # the rebuild applies once drained (run_to_completion keeps
        # stepping through the DRAINING state).
        assert fr.router_stats["autoscale_rebuilds"] >= 1
        assert fr.replicas[0].engine.prefill_ctx.num_devices == 1
        assert fr.replicas[0].engine.decode_ctx.num_devices == 3
        assert fr.replicas[0].state == ACTIVE

    def test_uneven_split_validation(self, devices8):
        from megatronapp_tpu.inference.disagg import split_serving_meshes
        pre, dec = split_serving_meshes(tp=1, devices=devices8[:4],
                                        prefill_devices=1)
        assert pre.num_devices == 1 and dec.num_devices == 3
        with pytest.raises(ValueError, match="multiple of tp"):
            split_serving_meshes(tp=2, devices=devices8[:4],
                                 prefill_devices=1)


# ---------------------------------------------------------------------------
class TestFleetServer:
    def test_driver_and_snapshots(self, gqa_params):
        """The server facade serves a fleet unchanged: driver submit /
        healthz / stats / labeled metrics all work against FleetRouter."""
        from megatronapp_tpu.inference.server import TextGenerationServer
        from megatronapp_tpu.utils import metrics as telemetry
        cfg, params = gqa_params

        class Tok:
            eod = None

            def tokenize(self, s):
                return [ord(c) % 128 for c in s]

            def detokenize(self, ids):
                return "".join(chr(65 + (i % 26)) for i in ids)

        fr = FleetRouter(
            engine_factory=lambda i, **h: DynamicInferenceEngine(
                params, cfg, tokenizer=Tok(), max_batch=2,
                max_seq_len=48, prefill_buckets=(16,), paged=True,
                block_size=8),
            num_replicas=2)
        srv = TextGenerationServer(fr)
        assert srv._driver is not None
        telemetry.enable()
        try:
            rid, done = srv._driver.submit(
                np.arange(6, dtype=np.int32), 3,
                SamplingParams(greedy=True))
            assert done.wait(120)
            assert len(srv._driver.result_tokens(rid)) == 9
            snap = srv.stats_snapshot()
            assert snap["engine"] == "fleet"
            assert snap["fleet"]["num_replicas"] == 2
            assert snap["pool"]["num_blocks"] > 0
            health = srv.health_snapshot()
            assert health["status"] == "ok"
            assert health["fleet"]["live_replicas"] == 2
            text = srv.metrics_text()
            assert 'fleet_replica_up{replica="0"} 1' in text
            assert 'fleet_replica_up{replica="1"} 1' in text
            # One TYPE line per labeled family.
            assert text.count("# TYPE fleet_replica_up gauge") == 1
            fr.kill_replica(0)
            health = srv.health_snapshot()
            assert health["status"] == "degraded"
        finally:
            telemetry.disable()

    def test_migration_spans_join_request_timeline(self, gqa_params):
        """ISSUE 14 satellite: migration emits a paired migrate B/E
        span plus migrate-out/migrate-in instants on the request's own
        tid row — the migrated lifetime reads as ONE timeline."""
        from megatronapp_tpu.trace.request_trace import (
            get_request_tracer,
        )
        cfg, params = gqa_params
        rt = get_request_tracer()
        rt.configure(enabled=True)
        rt.reset()
        try:
            fr = _fleet(params, cfg)
            rid = fr.add_request(np.arange(9, dtype=np.int32), 8,
                                 SamplingParams(greedy=True))
            src = fr._owner[rid]
            while len(fr.replicas[src].engine.requests[rid]
                      .generated) < 3:
                fr.step()
            assert fr.migrate_request(rid, 1 - src)
            fr.run_to_completion()
            recs = rt.dump()
            mig = [r for r in recs if r["name"] == "migrate"]
            assert [r["ph"] for r in mig] == ["B", "E"]
            assert mig[0]["args"]["rid"] == rid
            assert mig[0]["args"]["src_replica"] == src
            names = {r["name"] for r in recs
                     if r["args"].get("rid") == rid}
            assert {"migrate-out", "migrate-in", "retire"} <= names
            # The fleet labels its aggregate process rows.
            trace = rt.chrome_trace()
            labels = {e["args"]["name"]
                      for e in trace["traceEvents"]
                      if e.get("name") == "process_name"}
            assert "decode-mesh (fleet)" in labels
        finally:
            rt.configure(enabled=False)
            rt.reset()

    def test_labeled_metric_rendering(self):
        from megatronapp_tpu.utils.metrics import (
            MetricsRegistry, labeled,
        )
        reg = MetricsRegistry()
        reg.set_gauge(labeled("g", replica=0), 1.0)
        reg.set_gauge(labeled("g", replica=1), 2.0)
        reg.observe(labeled("h", replica=0), 5.0, lo=1.0, hi=100.0)
        text = reg.render_prometheus()
        assert 'g{replica="0"} 1' in text and 'g{replica="1"} 2' in text
        assert text.count("# TYPE g gauge") == 1
        assert '_bucket{replica="0",le=' in text
        assert 'h_count{replica="0"} 1' in text
        assert 'h_sum{replica="0"} 5' in text


# ---------------------------------------------------------------------------
class TestFleetArgs:
    def _parse(self, argv):
        import argparse

        from megatronapp_tpu.config.arguments import add_serving_args
        ap = argparse.ArgumentParser()
        add_serving_args(ap)
        return ap.parse_args(argv)

    def test_flags_parse(self):
        args = self._parse(["--engine", "dynamic",
                            "--serve-fleet", "3", "--fleet-migrate"])
        assert args.serve_fleet == 3 and args.fleet_migrate
        assert not args.fleet_autoscale

    @pytest.mark.parametrize("argv,msg", [
        (["--serve-fleet", "2"], "--engine dynamic"),
        (["--engine", "dynamic", "--fleet-migrate"],
         "--serve-fleet >= 2"),
        (["--engine", "dynamic", "--serve-fleet",
          "0"], ">= 1"),
        (["--engine", "dynamic",
          "--fleet-autoscale"], "--serve-disagg"),
    ])
    def test_invalid_combos_rejected(self, argv, msg):
        from megatronapp_tpu.config.arguments import (
            validate_serving_args,
        )
        args = self._parse(argv)
        with pytest.raises(SystemExit, match=msg):
            validate_serving_args(args)

    def test_valid_fleet_combo_passes(self):
        from megatronapp_tpu.config.arguments import (
            validate_serving_args,
        )
        args = self._parse(["--engine", "dynamic",
                            "--serve-fleet", "2", "--fleet-migrate"])
        validate_serving_args(args)

    def test_mismatched_replica_pools_rejected(self, gqa_params):
        cfg, params = gqa_params
        engines = [_engine(params, cfg, dt="bf16"),
                   _engine(params, cfg, dt="int8")]
        with pytest.raises(ValueError, match="share block_size and "
                                             "kv_cache_dtype"):
            FleetRouter(engines=engines)


# ---------------------------------------------------------------------------
class TestBenchmarkSmoke:
    def test_fleet_benchmark_gates(self):
        """Tier-1 smoke gate for the bench.py extra: affinity must beat
        round-robin on fleet prefix hit rate, with stream parity exact
        and the forced live migration token-exact."""
        from tools.fleet_benchmark import run
        # prefix 32 = 4 blocks: affinity (32 tokens) must dominate a
        # one-request load differential (queue_weight 16) so steering
        # is deterministic under batched submission.
        res = run(n_replicas=2, groups=2, followers=2, prefix_len=32,
                  tail_len=3, max_new=4, max_seq_len=64)
        assert res["parity_ok"]
        assert res["migration_ok"]
        assert res["affinity"]["prefix_hit_rate"] > \
            res["round_robin"]["prefix_hit_rate"], res
        assert res["migrations"] >= 1
