"""Multi-tenant batched-LoRA serving tests (ISSUE 19).

Covers the tentpole and its satellites:

- adapter registry: .npz round-trip exactness, unknown-id KeyError,
  the MLA refusal, and the rank-exact byte formula;
- AdapterCache: LRU evict/park, refcount pinning, AdapterSlotsPinned
  under full pins, slot-0 NULL discipline, audit() exact-partition and
  stats_snapshot byte pins;
- segmented kernel: lora_segment_info grouping, kernel vs jnp oracle
  <= 1e-5 across ranks / adapters-per-batch / GQA projection shapes,
  named ineligibility reasons;
- serving parity: zero-B adapters leave streams BITWISE unchanged; a
  mixed batch of >=4 distinct adapters decodes in ONE batched step
  with greedy streams token-exact vs serial single-adapter runs, on
  the bf16 base AND the resident-int8 base; cache audit() clean after
  EVERY step;
- fleet: a session carrying an adapter migrates mid-decode token-exact
  (banks re-acquired on dst, released on src);
- per-tenant SLO classes composing with (priority, rid) scheduling,
  tenant counters in stats_snapshot, and the loadgen per-tenant report;
- parse-time flag validation for --lora-dir / --lora-rank /
  --max-resident-adapters.
"""

import argparse

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from megatronapp_tpu.config.transformer_config import TransformerConfig
from megatronapp_tpu.inference.dynamic_engine import DynamicInferenceEngine
from megatronapp_tpu.inference.engine import SamplingParams
from megatronapp_tpu.inference.lora import (
    SLO_CLASSES, AdapterCache, AdapterRegistry, AdapterSlotsPinned,
    LoraAdapter, TenantSLO, adapter_nbytes, lora_target_dims,
)
from megatronapp_tpu.models.gpt import init_gpt_params

RANK = 4


def _cfg(**kw):
    d = dict(num_layers=2, hidden_size=64, num_attention_heads=4,
             num_query_groups=2, vocab_size=128,
             max_position_embeddings=64,
             compute_dtype=jnp.float32, remat_policy="none")
    d.update(kw)
    return TransformerConfig(**d)


@pytest.fixture(scope="module")
def gqa_params():
    cfg = _cfg()
    params, _ = init_gpt_params(jax.random.PRNGKey(7), cfg)
    return cfg, params


def _registry(cfg, ids, rank=RANK, zero_b=False):
    reg = AdapterRegistry()
    for i, aid in enumerate(ids):
        reg.register(LoraAdapter.random(
            aid, cfg, rank=rank, seed=10 + i, zero_b=zero_b))
    return reg


def _engine(params, cfg, cache=None, max_batch=4, **kw):
    return DynamicInferenceEngine(
        params, cfg, max_batch=max_batch, max_seq_len=48,
        prefill_buckets=(16,), paged=True, block_size=8,
        adapter_cache=cache, **kw)


def _resident(params):
    from megatronapp_tpu.inference.quantization import (
        quantize_params, residentize_params,
    )
    q, _ = quantize_params(params, resident_only=True)
    return residentize_params(q)


# ---------------------------------------------------------------------------
class TestRegistry:
    def test_npz_round_trip_exact(self, gqa_params, tmp_path):
        cfg, _ = gqa_params
        ad = LoraAdapter.random("t0", cfg, rank=RANK, seed=3)
        ad.save(str(tmp_path))
        back = LoraAdapter.load(str(tmp_path), "t0")
        assert back.rank == RANK
        for t in lora_target_dims(cfg):
            np.testing.assert_array_equal(np.asarray(ad.a[t]),
                                          np.asarray(back.a[t]))
            np.testing.assert_array_equal(np.asarray(ad.b[t]),
                                          np.asarray(back.b[t]))
        reg = AdapterRegistry(str(tmp_path))
        assert "t0" in reg
        assert reg.get("t0").adapter_id == "t0"

    def test_unknown_adapter_is_permanent_keyerror(self, gqa_params):
        cfg, _ = gqa_params
        reg = _registry(cfg, ["a"])
        with pytest.raises(KeyError):
            reg.get("nope")
        assert "nope" not in reg

    def test_mla_has_no_adaptable_kernels(self):
        cfg = _cfg(multi_latent_attention=True, kv_lora_rank=32,
                   qk_head_dim=16, qk_pos_emb_head_dim=8, v_head_dim=16)
        with pytest.raises(ValueError, match="latent"):
            lora_target_dims(cfg)

    def test_adapter_nbytes_formula_matches_arrays(self, gqa_params):
        """The rank-exact HBM byte formula IS the sum of the factor
        array sizes — the benchmark's byte gate leans on this."""
        cfg, _ = gqa_params
        ad = LoraAdapter.random("t0", cfg, rank=RANK, seed=0)
        want = sum(np.asarray(ad.a[t]).nbytes + np.asarray(ad.b[t]).nbytes
                   for t in lora_target_dims(cfg))
        assert ad.nbytes == want
        assert adapter_nbytes(cfg, RANK, cfg.num_layers, 4) == want


# ---------------------------------------------------------------------------
class TestAdapterCache:
    def _cache(self, cfg, reg, max_resident=2):
        return AdapterCache(cfg, reg, max_resident=max_resident,
                            rank=RANK)

    def test_null_slot_and_hit_miss_books(self, gqa_params):
        cfg, _ = gqa_params
        cache = self._cache(cfg, _registry(cfg, ["a", "b"]))
        assert cache.acquire(None) == 0
        s = cache.acquire("a")
        assert s != 0
        assert cache.stats["misses"] == 1
        assert cache.acquire("a") == s
        assert cache.stats["hits"] == 1
        cache.release(s)
        cache.release(s)
        cache.release(0)                        # NULL release: no-op
        cache.audit()
        snap = cache.stats_snapshot()
        assert snap["resident"] == 1 and snap["pinned"] == 0
        assert snap["resident_bytes"] == cache.adapter_nbytes
        assert snap["bank_bytes"] >= snap["resident_bytes"]

    def test_lru_evicts_least_recent_unpinned(self, gqa_params):
        cfg, _ = gqa_params
        cache = self._cache(cfg, _registry(cfg, ["a", "b", "c"]))
        sa = cache.acquire("a")
        sb = cache.acquire("b")
        cache.release(sa)
        cache.release(sb)                       # park order: a then b
        sc = cache.acquire("c")                 # evicts a (LRU)
        assert sc == sa
        assert cache.slot_of("a") is None
        assert cache.slot_of("b") == sb
        assert cache.stats["evictions"] == 1
        cache.audit()
        cache.release(sc)
        cache.audit()

    def test_all_pinned_raises_transient(self, gqa_params):
        cfg, _ = gqa_params
        cache = self._cache(cfg, _registry(cfg, ["a", "b", "c"]),
                            max_resident=2)
        sa = cache.acquire("a")
        sb = cache.acquire("b")
        with pytest.raises(AdapterSlotsPinned):
            cache.acquire("c")
        cache.audit()
        cache.release(sa)                       # one retirement frees it
        assert cache.acquire("c") == sa
        cache.audit()
        cache.release(sb)
        cache.release(sa)
        cache.audit()

    def test_rank_mismatch_rejected(self, gqa_params):
        cfg, _ = gqa_params
        reg = AdapterRegistry()
        reg.register(LoraAdapter.random("fat", cfg, rank=8, seed=1))
        cache = self._cache(cfg, reg)
        with pytest.raises(ValueError, match="rank"):
            cache.acquire("fat")
        cache.audit()


# ---------------------------------------------------------------------------
class TestSegmentedKernel:
    def test_segment_info_groups_by_first_occurrence(self):
        from megatronapp_tpu.ops.pallas.kernel_gen import (
            lora_segment_info,
        )
        row = jnp.asarray([2, 2, 0, 1, 2, 1, 0, 3], jnp.int32)
        seg_adapter, row_seg, nseg = lora_segment_info(row)
        assert int(nseg) == 4
        assert row_seg.tolist() == [0, 0, 1, 2, 0, 2, 1, 3]
        assert seg_adapter.tolist()[:4] == [2, 0, 1, 3]
        assert all(s == 0 for s in seg_adapter.tolist()[4:])

    @pytest.mark.parametrize("rank", [1, 4, 8])
    @pytest.mark.parametrize("din,dout", [(64, 64), (64, 32), (64, 256)])
    def test_kernel_matches_oracle(self, rank, din, dout):
        """Segmented Pallas kernel vs the jnp gather oracle across
        ranks, adapters-per-batch mixes, and the GQA projection shapes
        (dout=32 is the tiny model's fused-KV width)."""
        from megatronapp_tpu.ops.pallas.kernel_gen import (
            lora_delta_reference, lora_kernel_ineligible_reason,
            lora_segmented_delta,
        )
        assert lora_kernel_ineligible_reason(din, dout, rank, 8) is None
        rng = np.random.default_rng(rank * 1000 + dout)
        slots, rows = 5, 8
        x = jnp.asarray(rng.standard_normal((rows, din)), jnp.float32)
        a = jnp.asarray(rng.standard_normal((slots, din, rank)) * 0.1,
                        jnp.float32)
        b = jnp.asarray(rng.standard_normal((slots, rank, dout)) * 0.1,
                        jnp.float32)
        for row in ([0] * rows,                       # all NULL
                    [1] * rows,                       # one adapter
                    [1, 1, 2, 3, 4, 2, 0, 1],         # mixed + NULL rows
                    list(rng.integers(0, slots, rows))):
            ra = jnp.asarray(row, jnp.int32)
            got = lora_segmented_delta(x, a, b, ra)
            want = lora_delta_reference(x, a, b, ra)
            np.testing.assert_allclose(np.asarray(got),
                                       np.asarray(want), atol=1e-5)

    def test_ineligible_reasons_are_named(self):
        from megatronapp_tpu.ops.pallas.kernel_gen import (
            lora_kernel_ineligible_reason,
        )
        r = lora_kernel_ineligible_reason(16, 16, 32, 4)
        assert r is not None and "rank" in r


# ---------------------------------------------------------------------------
def _prompts(n, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 128, int(rng.integers(6, 14))).astype(
        np.int32) for _ in range(n)]


class TestServingParity:
    def test_zero_b_streams_bitwise_unchanged(self, gqa_params):
        """Zero-B adapters add an exact 0.0: streams from the LoRA
        engine are BITWISE those of an engine with no adapter cache."""
        cfg, params = gqa_params
        prompts = _prompts(3, seed=1)
        base = _engine(params, cfg)
        rids = [base.add_request(p, 6, SamplingParams(greedy=True))
                for p in prompts]
        want = base.run_to_completion()
        reg = _registry(cfg, ["z0", "z1", "z2"], zero_b=True)
        eng = _engine(params, cfg,
                      AdapterCache(cfg, reg, max_resident=4, rank=RANK))
        got_ids = [eng.add_request(p, 6, SamplingParams(greedy=True),
                                   request_id=r, adapter_id=f"z{i}")
                   for i, (p, r) in enumerate(zip(prompts, rids))]
        got = eng.run_to_completion()
        for r in rids:
            assert got[r].tolist() == want[r].tolist()
        eng.adapters.audit()
        assert eng.adapters.stats_snapshot()["pinned"] == 0
        eng.pool.audit()

    @pytest.mark.parametrize("resident", [False, True],
                             ids=["bf16-base", "resident-int8-base"])
    def test_mixed_four_adapters_one_batched_step(self, gqa_params,
                                                  resident):
        """THE acceptance pin: a mixed batch of 4 DISTINCT adapters
        decodes in one batched step (4 rids emit in a single step()),
        greedy streams token-exact vs serial single-adapter runs, on
        the bf16 base and the resident-int8 base; audits clean after
        every step."""
        cfg, params = gqa_params
        p = _resident(params) if resident else params
        prompts = _prompts(4, seed=2)
        ids = [f"tenant-{i}" for i in range(4)]
        reg = _registry(cfg, ids)
        eng = _engine(p, cfg,
                      AdapterCache(cfg, reg, max_resident=4, rank=RANK))
        rids = [eng.add_request(pr, 6, SamplingParams(greedy=True),
                                adapter_id=aid)
                for pr, aid in zip(prompts, ids)]
        streams = {r: [] for r in rids}
        one_batched = False
        open_rids = set(rids)
        while open_rids:
            ev = eng.step()
            eng.adapters.audit()
            eng.pool.audit()
            emitted = set()
            for r, t in ev["tokens"]:
                streams[r].append(int(t))
                emitted.add(r)
            if set(rids) <= emitted:
                one_batched = True
            open_rids -= set(ev["finished"]) | set(ev["expired"])
        assert one_batched, (
            "4 distinct adapters never decoded in one batched step")
        assert eng.adapters.stats_snapshot()["resident"] == 4
        assert eng.adapters.stats_snapshot()["pinned"] == 0
        # Serial legs on the SAME engine (same compiled steps, same
        # fold_in rids): each request alone in the batch must emit the
        # exact tokens it emitted in the mixed batch.
        for rid in rids:
            eng.pop_request(rid)
        for rid, pr, aid in zip(rids, prompts, ids):
            s = eng.add_request(pr, 6, SamplingParams(greedy=True),
                                request_id=rid, adapter_id=aid)
            serial = eng.run_to_completion()[s].tolist()[len(pr):]
            eng.pop_request(s)
            eng.adapters.audit()
            assert streams[rid] == serial, (
                f"{aid}: mixed {streams[rid]} != serial {serial}")

    def test_adapters_change_streams(self, gqa_params):
        """Sanity that the parity above is not vacuous: a real
        (non-zero-B) adapter steers the greedy stream away from the
        base model's."""
        cfg, params = gqa_params
        prompt = _prompts(1, seed=3)[0]
        base = _engine(params, cfg, max_batch=1)
        r0 = base.add_request(prompt, 8, SamplingParams(greedy=True))
        want = base.run_to_completion()[r0].tolist()
        reg = AdapterRegistry()
        reg.register(LoraAdapter.random("a", cfg, rank=RANK, seed=0,
                                        scale=2.0))
        eng = _engine(params, cfg,
                      AdapterCache(cfg, reg, max_resident=2,
                                   rank=RANK), max_batch=1)
        r = eng.add_request(prompt, 8, SamplingParams(greedy=True),
                            request_id=r0, adapter_id="a")
        got = eng.run_to_completion()[r].tolist()
        assert got != want, (
            "a scale-2.0 adapter did not perturb the greedy stream")

# ---------------------------------------------------------------------------
class TestFleetMigration:
    def test_migrated_adapter_stream_token_exact(self, gqa_params):
        """A session carrying an adapter migrates mid-decode with a
        token-exact greedy stream: the adapter id rides the export
        payload, dst acquires its own bank copy, src releases."""
        from megatronapp_tpu.inference.fleet import FleetRouter
        cfg, params = gqa_params
        reg = _registry(cfg, ["tenant-a"])
        prompt = _prompts(1, seed=5)[0]
        base = _engine(params, cfg,
                       AdapterCache(cfg, reg, max_resident=2,
                                    rank=RANK), max_batch=2)
        r0 = base.add_request(prompt, 10, SamplingParams(greedy=True),
                              adapter_id="tenant-a")
        want = base.run_to_completion()[r0].tolist()
        fr = FleetRouter(
            engine_factory=lambda i, **h: _engine(
                params, cfg,
                AdapterCache(cfg, reg, max_resident=2, rank=RANK),
                max_batch=2),
            num_replicas=2)
        rid = fr.add_request(prompt, 10, SamplingParams(greedy=True),
                             adapter_id="tenant-a")
        assert rid == r0
        src = fr._owner[rid]
        while len(fr.replicas[src].engine.requests[rid].generated) < 4:
            fr.step()
        dst = 1 - src
        assert fr.migrate_request(rid, dst)
        out = fr.run_to_completion()[rid].tolist()
        assert out == want
        for rep in fr.replicas:
            rep.engine.pool.audit()
            rep.engine.adapters.audit()
            assert rep.engine.adapters.stats_snapshot()["pinned"] == 0
        assert fr.replicas[dst].engine.adapters.slot_of(
            "tenant-a") is not None


# ---------------------------------------------------------------------------
class TestTenantSLO:
    def test_compose_shifts_priority_and_deadline(self):
        slo = TenantSLO()
        slo.assign("gold", "premium")
        slo.assign("bulk", "batch")
        assert slo.class_of(None) == "standard"
        assert slo.compose("gold", priority=0)[0] < slo.compose(
            "anon", priority=0)[0] < slo.compose("bulk", priority=0)[0]
        # Caller deadline always wins; caller priority ADDS.
        pr, dl = slo.compose("gold", priority=3, deadline_s=12.5)
        assert pr == 3 + SLO_CLASSES["premium"]["priority_offset"]
        assert dl == 12.5
        with pytest.raises(ValueError, match="SLO class"):
            slo.assign("x", "platinum")
        with pytest.raises(ValueError, match="SLO class"):
            TenantSLO(default_class="wat")

    def test_engine_tenant_counters(self, gqa_params):
        cfg, params = gqa_params
        prompts = _prompts(3, seed=6)
        eng = _engine(params, cfg, max_batch=3)
        for p, t in zip(prompts, ["t1", "t1", "t2"]):
            eng.add_request(p, 4, SamplingParams(greedy=True), tenant=t)
        eng.run_to_completion()
        ten = eng.stats_snapshot()["tenants"]
        assert ten["t1"]["requests"] == 2
        assert ten["t2"]["requests"] == 1
        assert ten["t1"]["tokens"] > 0
        assert ten["t2"]["slo_attainment"] == 1.0

    def test_tenant_label_cardinality_bounded(self, gqa_params):
        cfg, params = gqa_params
        eng = _engine(params, cfg, max_batch=1)
        for i in range(eng._TENANT_LABEL_CAP + 5):
            eng._tenant_inc(f"tenant-{i}", "requests")
        stats = eng._tenant_stats
        assert len(stats) <= eng._TENANT_LABEL_CAP + 1
        assert "_other" in stats
        assert stats["_other"]["requests"] == 5  # overflow folds here


# ---------------------------------------------------------------------------
class TestLoadgenTenants:
    def test_per_tenant_report_sections(self, gqa_params):
        """replay() splits TTFT/interval percentiles per trace tenant
        and maps tenants to adapter ids on submit."""
        from tools.loadgen import make_trace, replay
        cfg, params = gqa_params
        reg = _registry(cfg, ["adapter-0", "adapter-1"])
        eng = _engine(params, cfg,
                      AdapterCache(cfg, reg, max_resident=4, rank=RANK),
                      max_batch=2)
        trace = make_trace(seed=3, n_requests=6, tenants=2,
                           prefix_len=8, max_new_min=2, max_new_max=4)
        out = replay(eng, trace, slo_ttft_ms=60_000.0,
                     tenant_adapters={0: "adapter-0", 1: "adapter-1"})
        rep = out["report"]
        assert rep["requests"] == 6
        assert set(rep["tenants"]) == {"tenant-0", "tenant-1"}
        for t, entry in rep["tenants"].items():
            assert entry["requests"] >= 1
            assert entry["ttft_p99_ms"] > 0
            assert 0.0 <= entry["ttft_attainment"] <= 1.0
            assert entry["adapter_id"] in ("adapter-0", "adapter-1")
        eng.adapters.audit()
        assert eng.adapters.stats_snapshot()["pinned"] == 0


# ---------------------------------------------------------------------------
class TestServingArgs:
    def _ns(self, **kw):
        base = dict(engine="dynamic", serve_disagg=False,
                    serve_fleet=1, kv_cache_dtype="bf16",
                    quantized_weights=False,
                    lora_dir="/tmp/adapters", lora_rank=4,
                    max_resident_adapters=4)
        base.update(kw)
        return argparse.Namespace(**base)

    def test_lora_flag_combos(self):
        from megatronapp_tpu.config.arguments import validate_serving_args
        ok = validate_serving_args
        ok(self._ns(), multi_latent_attention=False)
        ok(self._ns(lora_dir=None, lora_rank=8),
           multi_latent_attention=False)
        with pytest.raises(SystemExit, match="dynamic"):
            ok(self._ns(engine="static"), multi_latent_attention=False)
        with pytest.raises(SystemExit, match="multi-latent"):
            ok(self._ns(), multi_latent_attention=True)
        with pytest.raises(SystemExit, match="serve-disagg"):
            ok(self._ns(serve_disagg=True), multi_latent_attention=False)
        with pytest.raises(SystemExit, match="lora-rank"):
            ok(self._ns(lora_rank=0), multi_latent_attention=False)
        with pytest.raises(SystemExit, match="max-resident-adapters"):
            ok(self._ns(max_resident_adapters=0),
               multi_latent_attention=False)

    def test_engine_rejects_adapter_without_cache(self, gqa_params):
        cfg, params = gqa_params
        eng = _engine(params, cfg, max_batch=1)
        with pytest.raises(ValueError, match="adapter cache"):
            eng.add_request(np.arange(1, 6), 2,
                            SamplingParams(greedy=True),
                            adapter_id="a")
        reg = _registry(cfg, ["a"])
        eng2 = _engine(params, cfg,
                       AdapterCache(cfg, reg, max_resident=2,
                                    rank=RANK), max_batch=1)
        with pytest.raises(KeyError, match="unknown adapter"):
            eng2.add_request(np.arange(1, 6), 2,
                             SamplingParams(greedy=True),
                             adapter_id="nope")
