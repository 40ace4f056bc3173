"""Fleet serving: multi-replica router with KV-affinity admission, live
session migration, and drain-aware replica lifecycle (ISSUE 14).

Everything below a single engine is built (paged quantized KV, SLO
admission, disagg handoff, telemetry percentiles); this module is the
layer ABOVE it: N engine replicas (each a `DynamicInferenceEngine` or
`DisaggServingEngine` on its own sub-mesh/device slice) behind ONE
router that presents the same stepping surface as a single engine — the
server's `DynamicBatchingDriver` and every /stats, /healthz, /metrics
endpoint serve a fleet unchanged. The reference's MegaFBD virtual-rank
coordinator (PAPER.md §MegaFBD) is the blueprint: the coordinator owns
PLACEMENT (admission, migration, drain order), the replicas own
EXECUTION (their step loops are untouched).

Admission scores every live replica and admits to the argmax of

    affinity_tokens                       (prefix-cache affinity)
  - queue_weight    * load                (queue depth + active slots)
  - pressure_weight * pool_pressure      (blocks_in_use / num_blocks)
  + slo_weight      * attainment          (histogram-backed SLO signal)

- **Affinity** comes from the pool's rolling full-block prefix hashes
  (`paged_cache.prefix_block_keys` — the SAME hashing the prefix cache
  uses, so router hits == pool hits by construction). Each replica's
  pool feeds prefix-INSERT events into a bounded hash→replica map; a new
  prompt's leading-block hash chain is walked against it and each
  matched block counts block_size affinity tokens. A replica whose pool
  flushes (rolling reload) fires its flush listener and the router drops
  its entries — a swapped replica can never be steered to for
  stale-weight "hits" (the ISSUE 14 small-fix satellite, made structural
  rather than call-site-dependent).
- **Load/pressure** read the engine facades directly (waiting + staged +
  active, pool occupancy) — the same numbers `stats_snapshot()` reports.
- **Attainment** reads each ENGINE'S own always-on decode-interval
  Histogram (utils/metrics.py, the PR-12 primitive — the disagg
  coordinator has carried one since PR 12, the plain engine grows one
  here): the fraction of back-to-back decode intervals within `slo_ms`
  (1.0 while no SLO is set). The router never times its own step loop
  for this — it steps replicas serially, so loop timing would measure
  the whole fleet round and inflate every replica's "interval" by the
  fleet size.

Rebalancing is LIVE SESSION MIGRATION — the PR-8/10 disagg handoff
generalized cross-pool: `PagedKVCache.export_slot` ships the stored
(possibly int8/fp8-quantized) KV rows + scales VERBATIM, the Request
object carries the sampler fold_in chain position, and
`import_slot` scatters the bytes into fresh blocks on the destination —
so a migrated greedy OR sampled stream continues token-exact (pinned in
tests/test_fleet.py for every KV dtype). Replica overload, replica
death, and fleet-wide rolling reloads all reduce to "export → re-admit
elsewhere":

- **Overload**: a replica with queued work and no free slots hands one
  running session to an underloaded same-params-version replica
  (bounded per step).
- **Death**: a replica whose step() raises is marked DEAD and every
  session it held fails over — running ones lose their KV (the pool
  died with the replica) and re-enter another replica's queue with
  prompt+generated intact, so they resume exactly like a preemption
  (the unified ragged prefill/decode step, arXiv 2604.15464, makes
  "resume anywhere" the same code path as admission). Zero sessions
  lost; greedy streams stay exact.
- **Rolling reload** (`begin_rolling_reload`): replicas drain ONE at a
  time — admission pauses on the draining replica, its running sessions
  migrate out (or finish), `set_params` swaps (flushing pool prefix
  cache AND router affinity), admission resumes, next replica. The
  fleet never stops admitting; migration only pairs replicas on the
  same params version so a half-rolled fleet cannot mix weights within
  one stream.

The policy layer on top is `MeshSplitAutoscaler`: per-replica EWMAs of
SLO attainment and prefill-queue depth recommend moving devices between
a disagg replica's prefill and decode sub-meshes
(`split_serving_meshes(prefill_devices=...)`); the router applies a
recommendation by draining the replica and rebuilding it through its
`engine_factory` with the new split — the same drain machinery as
reload and death-replacement.

The chaos site "fleet-migrate" fires between KV export and destination
import; because export is read-only and import is all-or-nothing, the
failed migration leaves BOTH pools audit-clean and the session decoding
on the source (drilled in tests/test_resilience.py).
"""

from __future__ import annotations

import dataclasses
import itertools
import logging
import threading
import time
from collections import OrderedDict
from typing import Callable, Dict, List, Optional

import numpy as np

from megatronapp_tpu.inference.paged_cache import (
    FleetPrefixStore, cdiv, prefix_block_keys,
)
from megatronapp_tpu.trace.request_trace import (
    DECODE_PID, PREFILL_PID, get_request_tracer,
)
from megatronapp_tpu.utils import chaos
from megatronapp_tpu.utils import metrics as telemetry
from megatronapp_tpu.utils.metrics import Ewma, Histogram

logger = logging.getLogger(__name__)

# Replica lifecycle states.
ACTIVE = "active"        # admitting + stepping
DRAINING = "draining"    # stepping, admission paused (reload/rebuild)
DEAD = "dead"            # step() raised; sessions failed over


@dataclasses.dataclass
class Replica:
    """One engine replica + the router-side state attached to it."""
    idx: int
    engine: object
    state: str = ACTIVE
    params_version: int = 0
    reloads: int = 0
    steps: int = 0
    # Pending autoscale rebuild kwargs (engine_factory hints), applied
    # once the replica drains.
    rebuild_hints: Optional[dict] = None

    def attainment(self, slo_ms: Optional[float],
                   default: float = 1.0) -> float:
        """Histogram-backed SLO attainment (the PR-12 primitive) read
        from the ENGINE'S OWN decode-interval histogram — the router
        steps every replica serially, so timing its own loop would
        measure the whole fleet round, inflating every replica's
        'interval' by the fleet size. Both engine types keep a private
        always-on interval_hist (disagg coordinator since PR 12, the
        plain engine since this PR)."""
        hist = getattr(self.engine, "interval_hist", None)
        if hist is None or slo_ms is None or not hist.count:
            return default
        return hist.fraction_below(slo_ms)

    def interval_hist(self) -> Optional[Histogram]:
        return getattr(self.engine, "interval_hist", None)


class MeshSplitAutoscaler:
    """EWMA-attainment-driven prefill/decode mesh-split policy (the
    tentpole's policy layer). Consumes the router's per-replica signals
    — decode-SLO attainment and prefill-queue depth — as EWMAs and
    recommends a new prefill-device count for a disagg replica:

    - attainment below `target` with devices to spare on the prefill
      side → shrink prefill by one tp group (decode is the bottleneck);
    - attainment healthy but the prefill queue persistently deep →
      grow prefill by one tp group (TTFT is the bottleneck).

    Recommendations are rate-limited per replica (`cooldown` recommend
    calls) so one noisy window cannot thrash the split; applying one
    costs a full replica drain + rebuild."""

    def __init__(self, target_attainment: float = 0.9,
                 queue_high: float = 1.0, alpha: float = 0.3,
                 cooldown: int = 32, min_groups: int = 1):
        self.target = target_attainment
        self.queue_high = queue_high
        self.alpha = alpha
        self.cooldown = cooldown
        self.min_groups = min_groups
        self._att: Dict[int, Ewma] = {}
        self._queue: Dict[int, Ewma] = {}
        self._cool: Dict[int, int] = {}

    def observe(self, idx: int, attainment: float, prefill_waiting: int):
        self._att.setdefault(idx, Ewma(self.alpha)).observe(attainment)
        self._queue.setdefault(idx, Ewma(self.alpha)).observe(
            float(prefill_waiting))

    def recommend(self, idx: int, prefill_devices: int,
                  decode_devices: int, tp: int = 1) -> Optional[int]:
        """New prefill-device count, or None (keep the split)."""
        cool = self._cool.get(idx, 0)
        if cool > 0:
            self._cool[idx] = cool - 1
            return None
        att = self._att.get(idx)
        if att is None or att.value is None:
            return None
        q = self._queue.get(idx)
        q_depth = 0.0 if q is None or q.value is None else q.value
        if (att.value < self.target
                and prefill_devices - tp >= self.min_groups * tp):
            self._cool[idx] = self.cooldown
            return prefill_devices - tp
        if (att.value >= self.target and q_depth > self.queue_high
                and decode_devices - tp >= self.min_groups * tp):
            self._cool[idx] = self.cooldown
            return prefill_devices + tp
        return None


class FleetRouter:
    """Multi-replica serving router (module docstring). Drop-in for a
    single engine behind `DynamicBatchingDriver`: same
    add_request/step/has_work/abort/stats surface; one rid space spans
    the fleet (every replica draws from the router's shared counter, so
    the driver's per-rid bookkeeping never collides across replicas).

    Construct with ready-made `engines` or with an `engine_factory`
    (`factory(idx, **hints) -> engine`) — the factory additionally
    enables dead-replica replacement (`revive_replica`) and autoscale
    rebuilds. All replicas must share block_size and kv_cache_dtype
    (migration ships stored KV bytes verbatim between their pools)."""

    def __init__(self, engines: Optional[List] = None,
                 engine_factory: Optional[Callable] = None,
                 num_replicas: int = 2, policy: str = "affinity",
                 migrate: bool = True, autoscale: bool = False,
                 slo_ms: Optional[float] = None,
                 affinity_capacity: int = 8192,
                 max_migrations_per_step: int = 1,
                 queue_weight: Optional[float] = None,
                 pressure_weight: Optional[float] = None,
                 slo_weight: Optional[float] = None,
                 prefix_store_mb: float = 0.0):
        assert policy in ("affinity", "round_robin"), policy
        if engines is None:
            assert engine_factory is not None, (
                "FleetRouter needs engines or an engine_factory")
            engines = [engine_factory(i) for i in range(num_replicas)]
        assert engines, "FleetRouter needs at least one replica"
        self.engine_factory = engine_factory
        # ONE rid space across the fleet: every replica's engine draws
        # request ids from this shared counter.
        self._ids = itertools.count()
        self.replicas = [Replica(i, e) for i, e in enumerate(engines)]
        for rep in self.replicas:
            self._wire(rep)
        pools = [rep.engine.pool for rep in self.replicas]
        block_sizes = {p.block_size for p in pools}
        dtypes = {p.kv_cache_dtype for p in pools}
        if len(block_sizes) != 1 or len(dtypes) != 1:
            raise ValueError(
                "fleet replicas must share block_size and kv_cache_dtype "
                f"(got block sizes {sorted(block_sizes)}, dtypes "
                f"{sorted(dtypes)}): affinity hashes and migrated KV "
                "bytes cross pools verbatim")
        self.block_size = block_sizes.pop()
        self.kv_cache_dtype = dtypes.pop()
        self.policy = policy
        self.migrate = migrate
        self.slo_ms = slo_ms
        self.max_migrations_per_step = max_migrations_per_step
        # Scoring weights in affinity-token units: one queued/active
        # request outweighs ~2 cached blocks, a full pool ~4, a fully
        # attained SLO ~2 — affinity dominates only between comparably
        # loaded replicas.
        self.queue_weight = (2.0 * self.block_size if queue_weight is None
                             else queue_weight)
        self.pressure_weight = (4.0 * self.block_size
                                if pressure_weight is None
                                else pressure_weight)
        self.slo_weight = (2.0 * self.block_size if slo_weight is None
                           else slo_weight)
        self.tokenizer = self.replicas[0].engine.tokenizer
        self.max_batch = sum(r.engine.max_batch for r in self.replicas)
        self.pause_admission = False        # driver-facade compat
        # Bounded hash→replica affinity map (LRU past capacity).
        self.affinity_capacity = affinity_capacity
        self._affinity: OrderedDict = OrderedDict()
        # Bounded tenant/adapter→replica affinity (ISSUE 19): steering a
        # tenant's requests back to the replica whose AdapterCache
        # already holds its adapter avoids an HBM bank write (and a
        # possible eviction of someone else's pinned working set) per
        # admission. Same bounded-OrderedDict machinery as the prefix
        # map; an adapter reload costs far more than a prefix-block
        # re-prefill, so its weight defaults higher.
        self.tenant_affinity_capacity = 1024
        self._tenant_affinity: OrderedDict = OrderedDict()
        self.tenant_weight = 8.0 * self.block_size
        self._owner: Dict[int, int] = {}    # rid -> replica idx
        self._lock = threading.RLock()
        self._rr = 0                        # round-robin cursor
        self._version = 0                   # fleet params version target
        self._reload = None                 # rolling-reload state
        self._params = None                 # latest reloaded params
        self.autoscaler = MeshSplitAutoscaler() if autoscale else None
        # Fleet-global prefix store (ISSUE 20): exported prefix-block
        # payloads keyed by the same rolling hashes as the affinity
        # map — a replica that misses a hot prefix locally gathers the
        # blocks from the store at admission instead of recomputing the
        # prefill (in-process flavor; fleet_rpc.py ships the same
        # payloads over the prefix_put/prefix_get verbs).
        self.prefix_store = (FleetPrefixStore(int(prefix_store_mb
                                                  * (1 << 20)))
                             if prefix_store_mb else None)
        self.router_stats = {
            "migrations": 0, "migration_failures": 0,
            "migrated_kv_bytes": 0, "failovers": 0, "replica_deaths": 0,
            "reloads": 0, "replica_reloads": 0, "autoscale_rebuilds": 0,
            "autoscale_aborts": 0, "affinity_admissions": 0,
            "tenant_affinity_admissions": 0, "admissions": 0,
            "prefix_store_admission_hits": 0,
            "prefix_store_seeded_blocks": 0,
            "prefix_store_seeded_bytes": 0,
            "prefill_chunks_avoided": 0,
        }
        self._rt = get_request_tracer()
        # Fleet process rows aggregate every replica's events (spans
        # carry replica indices in their args; migrate-out/in instants
        # mark the hop) — label the rows so trace readers know.
        self._rt.set_process_name(DECODE_PID, "decode-mesh (fleet)")
        self._rt.set_process_name(PREFILL_PID, "prefill-mesh (fleet)")
        self._supervisor = None             # lazy (see .supervisor)

    # ---- replica wiring --------------------------------------------------
    def _wire(self, rep: Replica):
        """Attach a (new) engine to the router: shared rid counter +
        pool prefix/flush listeners feeding the affinity map."""
        eng = rep.engine
        inner = getattr(eng, "engine", eng)   # disagg facade → inner
        inner._ids = self._ids
        idx = rep.idx
        eng.pool.prefix_listener = (
            lambda keys, _i=idx: self._note_prefixes(_i, keys))
        eng.pool.flush_listener = lambda _i=idx: self._flush_replica(_i)

    def _note_prefixes(self, idx: int, keys: List[bytes]):
        with self._lock:
            for key in keys:
                self._affinity[key] = idx
                self._affinity.move_to_end(key)
            while len(self._affinity) > self.affinity_capacity:
                self._affinity.popitem(last=False)
        if self.prefix_store is not None:
            # Populate the fleet store from the same prefix-insert
            # events: export each NEW block once (host gather), after
            # which every replica serves it from host RAM.
            pool = self.replicas[idx].engine.pool
            for key in keys:
                if self.prefix_store.has(key):
                    continue
                payload = pool.export_prefix_block(key)
                if payload is not None:
                    self.prefix_store.put(key, payload)

    def _flush_replica(self, idx: int):
        """Drop every affinity entry pointing at replica `idx` (its
        prefix cache flushed, or it died)."""
        with self._lock:
            stale = [k for k, v in self._affinity.items() if v == idx]
            for k in stale:
                del self._affinity[k]
        if self.prefix_store is not None:
            # One replica's flush means a params reload is in flight (or
            # it died mid-anything): stored blocks are no longer
            # guaranteed to match the weights every replica will run, so
            # the WHOLE store drops — it repopulates from the next
            # prefix inserts, same as each pool's own prefix cache.
            self.prefix_store.clear()

    def _note_tenant(self, key: Optional[str], idx: int):
        if key is None:
            return
        with self._lock:
            self._tenant_affinity[key] = idx
            self._tenant_affinity.move_to_end(key)
            while (len(self._tenant_affinity)
                   > self.tenant_affinity_capacity):
                self._tenant_affinity.popitem(last=False)

    def _drop_tenant_replica(self, idx: int):
        """Drop tenant/adapter steering entries pointing at replica
        `idx` — its AdapterCache is gone (death) or fresh (rebuild), so
        steering there for residency "hits" would be stale. Prefix
        flushes do NOT call this: the adapter banks survive a params
        reload."""
        with self._lock:
            stale = [k for k, v in self._tenant_affinity.items()
                     if v == idx]
            for k in stale:
                del self._tenant_affinity[k]

    # ---- admission -------------------------------------------------------
    def _replica_load(self, eng) -> int:
        load = len(eng.waiting)
        load += sum(1 for s in eng.slots if s is not None)
        # Disagg facade: staged prefills count as load too.
        load += len(getattr(eng, "_inflight", ()))
        load += len(getattr(eng, "_parked", ()))
        return load

    def _admit_target(self, prompt: np.ndarray,
                      affinity_key: Optional[str] = None
                      ) -> Optional[Replica]:
        live = [r for r in self.replicas if r.state == ACTIVE]
        if not live:
            # Drain window (rolling reload / rebuild with every replica
            # DRAINING): queue on a draining replica rather than
            # erroring — queued work survives a reload in place (the
            # single-engine reload semantics) and rebuilds evacuate
            # their queue. Reload-draining replicas are preferred over
            # rebuild-draining ones (the latter's engine is replaced).
            # Only an all-DEAD fleet has nowhere to queue.
            live = [r for r in self.replicas if r.state == DRAINING
                    and r.rebuild_hints is None]
            live = live or [r for r in self.replicas
                            if r.state == DRAINING]
        if not live:
            return None
        if self.policy == "round_robin":
            rep = live[self._rr % len(live)]
            self._rr += 1
            return rep
        keys = prefix_block_keys(prompt, self.block_size, len(prompt))
        owners = [self._affinity.get(k) for k in keys]
        tenant_home = (None if affinity_key is None
                       else self._tenant_affinity.get(affinity_key))
        best = best_key = None
        best_aff = 0.0
        best_tenant = False
        for rep in live:
            aff = 0.0
            for o in owners:
                if o != rep.idx:
                    break
                aff += self.block_size
            taff = self.tenant_weight if tenant_home == rep.idx else 0.0
            eng = rep.engine
            load = self._replica_load(eng)
            pool = eng.pool
            pressure = pool.blocks_in_use() / pool.num_blocks
            score = (aff + taff
                     - self.queue_weight * load
                     - self.pressure_weight * pressure
                     + self.slo_weight * rep.attainment(self.slo_ms))
            # Deterministic tie-break: least loaded, then lowest index.
            key = (score, -load, -rep.idx)
            if best_key is None or key > best_key:
                best, best_key = rep, key
                best_aff, best_tenant = aff, taff > 0
        if best_aff > 0:
            self.router_stats["affinity_admissions"] += 1
        if best_tenant:
            self.router_stats["tenant_affinity_admissions"] += 1
        return best

    def add_request(self, prompt_tokens, max_new_tokens: int,
                    sampling=None, eod_id: Optional[int] = None,
                    priority: int = 0,
                    deadline_s: Optional[float] = None,
                    adapter_id: Optional[str] = None,
                    tenant: Optional[str] = None) -> int:
        prompt = np.asarray(prompt_tokens, np.int32).reshape(-1)
        # Steering key: the ADAPTER is what's expensive to move between
        # replicas (an HBM bank write on a miss), so it keys the
        # affinity map; a tenant without an adapter still benefits from
        # sticking to one replica (its prefix blocks live there).
        affinity_key = adapter_id if adapter_id is not None else tenant
        extra = {}
        if adapter_id is not None:
            extra["adapter_id"] = adapter_id
        if tenant is not None:
            extra["tenant"] = tenant
        # The WHOLE admission holds the router lock: _fail_replica (the
        # stepper thread) also holds it for its whole failover, so a
        # request can never land in a replica's books between the
        # death snapshot and the DEAD mark — the window that would
        # silently lose a session despite the zero-lost guarantee.
        # (Engine add_request is cheap — validation + a deque append —
        # and the driver already serializes submits under its own cv.)
        with self._lock:
            rep = self._admit_target(prompt, affinity_key)
            if rep is None:
                raise RuntimeError(
                    "fleet has no live replica to admit into (every "
                    "replica is dead — drain windows queue instead)")
            if self.prefix_store is not None:
                self._seed_from_store(rep, prompt)
            rid = rep.engine.add_request(
                prompt, max_new_tokens, sampling, eod_id=eod_id,
                priority=priority, deadline_s=deadline_s, **extra)
            self._owner[rid] = rep.idx
            self._note_tenant(affinity_key, rep.idx)
        self.router_stats["admissions"] += 1
        telemetry.inc("fleet_admissions")
        return rid

    def _seed_from_store(self, rep: Replica, prompt: np.ndarray):
        """Gather this prompt's missing leading prefix blocks from the
        fleet store into the target replica's pool (import_prefix_block
        — rc==0 LRU entries, exactly like a local insert) BEFORE
        admission, so pool.admit() hits them and the chunked prefill
        skips the covered tokens. Prefill-chunks-avoided is exact: the
        chunk counts before/after seeding follow admit()'s own
        cached-token arithmetic (len(leading hits) * block_size, capped
        at p_len - 1 for the CoW case)."""
        store = self.prefix_store
        keys = prefix_block_keys(prompt, self.block_size, len(prompt))
        if not keys:
            return
        eng = rep.engine
        inner = getattr(eng, "engine", eng)   # disagg facade → inner
        pool = eng.pool
        local = 0                  # leading blocks already present
        for k in keys:
            if not pool.has_prefix(k):
                break
            local += 1
        seeded = 0
        chain = local              # leading present-or-seeded blocks
        for k in keys[local:]:
            if pool.has_prefix(k):
                chain += 1
                continue
            payload = store.get(k)         # counts the hit/miss
            if payload is None or not pool.import_prefix_block(
                    k, payload):
                break                      # only a LEADING run helps
            chain += 1
            seeded += 1
            self.router_stats["prefix_store_seeded_blocks"] += 1
            self.router_stats["prefix_store_seeded_bytes"] += (
                payload["nbytes"])
        if not seeded:
            return
        p_len = len(prompt)
        chunk = int(getattr(inner, "prefill_chunk", 32))

        def chunks_at(blocks_cached: int) -> int:
            cached = min(blocks_cached * self.block_size, p_len - 1)
            return cdiv(p_len - cached, chunk)

        avoided = chunks_at(local) - chunks_at(chain)
        self.router_stats["prefix_store_admission_hits"] += 1
        self.router_stats["prefill_chunks_avoided"] += avoided
        telemetry.inc("fleet_prefill_chunks_avoided", avoided)

    # ---- per-request forwarding ------------------------------------------
    def _owner_engine(self, rid: int):
        with self._lock:
            idx = self._owner.get(rid)
        if idx is None:
            return None
        return self.replicas[idx].engine

    def pop_request(self, request_id: int):
        eng = self._owner_engine(request_id)
        req = None if eng is None else eng.pop_request(request_id)
        with self._lock:
            self._owner.pop(request_id, None)
        return req

    def abort_request(self, request_id: int) -> Optional[str]:
        eng = self._owner_engine(request_id)
        return None if eng is None else eng.abort_request(request_id)

    def park_request(self, request_id: int) -> bool:
        """Forward a client park (long-idle session) to the owning
        replica's spill tier; False when the owner has no spill tier
        (disagg facade / spill off) or the session isn't parkable."""
        eng = self._owner_engine(request_id)
        fn = getattr(eng, "park_request", None)
        return bool(fn and fn(request_id))

    def resume_request(self, request_id: int) -> bool:
        eng = self._owner_engine(request_id)
        fn = getattr(eng, "resume_request", None)
        return bool(fn and fn(request_id))

    def expire_overdue(self, now: Optional[float] = None) -> List[int]:
        expired: List[int] = []
        for rep in self.replicas:
            if rep.state != DEAD:
                expired += rep.engine.expire_overdue(now)
        return expired

    def abort_all(self):
        with self._lock:
            self._owner.clear()
        for rep in self.replicas:
            if rep.state == DEAD:
                continue
            try:
                rep.engine.abort_all()
            except Exception:  # noqa: BLE001 — best-effort reclaim
                logger.warning("abort_all failed on replica %d", rep.idx,
                               exc_info=True)

    # ---- facade surface (driver/server) ----------------------------------
    @property
    def has_work(self) -> bool:
        if self._reload is not None:
            return True
        if any(r.rebuild_hints is not None and r.state != DEAD
               for r in self.replicas):
            return True
        return any(r.state != DEAD and r.engine.has_work
                   for r in self.replicas)

    @property
    def slots(self) -> List:
        out: List = []
        for rep in self.replicas:
            if rep.state != DEAD:
                out += list(rep.engine.slots)
        return out

    @property
    def waiting(self) -> List:
        out: List = []
        for rep in self.replicas:
            if rep.state != DEAD:
                out += list(rep.engine.waiting)
        return out

    @property
    def requests(self) -> Dict:
        out: Dict = {}
        for rep in self.replicas:
            if rep.state != DEAD:
                out.update(rep.engine.requests)
        return out

    @property
    def reload_pending(self) -> bool:
        return self._reload is not None

    def reset_compilation(self):
        for rep in self.replicas:
            if rep.state != DEAD:
                rep.engine.reset_compilation()

    def free_decode_slots(self) -> int:
        return sum(rep.engine.free_decode_slots()
                   for rep in self.replicas if rep.state == ACTIVE)

    def drained_for_reload(self) -> bool:
        """Generic-driver compat: True when EVERY live replica is
        drained (the fleet-native path is begin_rolling_reload, which
        never requires this fleet-wide state)."""
        return all(rep.engine.drained_for_reload()
                   for rep in self.replicas if rep.state != DEAD)

    def set_params(self, params):
        """Immediate fleet-wide swap (generic-driver/test path; the
        production path is begin_rolling_reload). Each pool's prefix
        flush fires its listener, so the affinity map empties too."""
        self._version += 1
        self._params = params
        for rep in self.replicas:
            if rep.state == DEAD:
                continue
            rep.engine.set_params(params)
            rep.params_version = self._version
            rep.reloads += 1

    # ---- live session migration ------------------------------------------
    def migrate_request(self, rid: int,
                        dst_idx: Optional[int] = None) -> bool:
        """Move a RUNNING session from its replica to `dst_idx` (or the
        best eligible destination): export → ["fleet-migrate" chaos
        site] → import → source release. Exception-safe by
        construction: export is read-only and import is all-or-nothing,
        so ANY failure in the window leaves the session decoding on the
        source with both pools audit-clean — the retried stream is
        bit-identical because nothing moved."""
        with self._lock:
            src_idx = self._owner.get(rid)
        if src_idx is None:
            return False
        src = self.replicas[src_idx]
        dst = self._pick_destination(src, dst_idx)
        if dst is None:
            return False
        self._rt.begin("migrate", rid, src_replica=src.idx,
                       dst_replica=dst.idx)
        try:
            payload = src.engine.export_request(rid)
            if payload is None:
                return False
            # Chaos site: the worst point — KV exported, destination not
            # yet admitted (a destination death lands exactly here).
            chaos.fire("fleet-migrate")
            if not dst.engine.import_request(payload):
                self.router_stats["migration_failures"] += 1
                return False
        except Exception as e:  # noqa: BLE001 — rollback is "do nothing"
            self.router_stats["migration_failures"] += 1
            telemetry.inc("fleet_migration_failures")
            logger.warning(
                "migration of request %d (replica %d -> %d) failed — "
                "session stays on the source, pools untouched: %s",
                rid, src.idx, dst.idx, e)
            return False
        finally:
            self._rt.end("migrate", rid)
        src.engine.release_exported(rid)
        with self._lock:
            self._owner[rid] = dst.idx
        self.router_stats["migrations"] += 1
        self.router_stats["migrated_kv_bytes"] += payload["nbytes"]
        telemetry.inc("fleet_migrations")
        return True

    def _pick_destination(self, src: Replica,
                          dst_idx: Optional[int]) -> Optional[Replica]:
        """An ACTIVE same-params-version replica with a free decode slot
        and headroom (a half-rolled fleet must never continue a stream
        on different weights)."""
        def eligible(rep: Replica) -> bool:
            if rep is src or rep.state != ACTIVE:
                return False
            if rep.params_version != src.params_version:
                return False
            eng = rep.engine
            if eng.free_decode_slots() == 0:
                return False
            pool = eng.pool
            return pool.blocks_in_use() / pool.num_blocks < 0.9
        if dst_idx is not None:
            rep = self.replicas[dst_idx]
            return rep if eligible(rep) else None
        cands = [r for r in self.replicas if eligible(r)]
        if not cands:
            return None
        return min(cands, key=lambda r: (self._replica_load(r.engine),
                                         r.idx))

    def _migratable_rids(self, rep: Replica) -> List[int]:
        """Requests currently decoding in `rep`'s slots (the only ones
        owning exportable KV), most-remaining-work first."""
        inner = getattr(rep.engine, "engine", rep.engine)
        rids = []
        for req in inner.slots:
            if req is not None and not req.finished and req.generated:
                rids.append((req.max_new_tokens - len(req.generated),
                             req.request_id))
        return [rid for _, rid in sorted(rids, reverse=True)]

    def _rebalance(self) -> None:
        """One step's migration budget: drain DRAINING replicas first,
        then relieve an overloaded ACTIVE replica (queued work, no free
        slot) toward an underloaded one."""
        budget = self.max_migrations_per_step
        for rep in self.replicas:
            if budget <= 0:
                return
            if rep.state != DRAINING:
                continue
            # Evacuate queued work first (no KV — requeue is free).
            self._evacuate_waiting(rep)
            for rid in self._migratable_rids(rep):
                if budget <= 0:
                    return
                if self.migrate and self.migrate_request(rid):
                    budget -= 1
        if not self.migrate or budget <= 0:
            return
        for rep in self.replicas:
            if rep.state != ACTIVE:
                continue
            eng = rep.engine
            if not len(eng.waiting) or eng.free_decode_slots() > 0:
                continue
            for rid in self._migratable_rids(rep):
                if budget <= 0:
                    return
                if self.migrate_request(rid):
                    budget -= 1
                    break   # one relief migration per replica per step

    def _evacuate_waiting(self, rep: Replica):
        """Requeue a draining replica's QUEUED requests onto active
        replicas (they own no KV — a queue move, not a migration).
        Fresh requests (nothing generated yet) may go to any version —
        they run wholly on the destination's weights; preempted ones
        carrying generated tokens are fenced to SAME-params-version
        destinations and otherwise stay queued here (they drain with
        the reload's swap, never mixing weights in one stream). No-op
        when no destination exists; the reload then simply swaps with
        the queue in place (single-replica fleet)."""
        eng = rep.engine
        targets = [r for r in self.replicas
                   if r is not rep and r.state == ACTIVE]
        same_ver = [r for r in targets
                    if r.params_version == rep.params_version]
        if not targets or not len(eng.waiting):
            return
        moved, kept = [], []
        while True:
            try:
                req = eng.waiting.popleft()
            except IndexError:
                break
            if req.finished:
                continue
            if req.generated and not same_ver:
                kept.append(req)       # version-fenced: stays here
                continue
            moved.append(req)
        eng.waiting.extend(kept)
        for i, req in enumerate(moved):
            eng.requests.pop(req.request_id, None)
            pool = same_ver if (same_ver and req.generated) else targets
            self._requeue_on(pool[i % len(pool)], req)

    def _requeue_on(self, rep: Replica, req):
        """Hand a request (no KV) to another replica's queue: both
        engine types re-enter through their waiting deque — the disagg
        facade's is its prefill queue."""
        eng = rep.engine
        req.slot = -1
        req.queued_t = time.monotonic()
        eng.requests[req.request_id] = req
        eng.waiting.append(req)
        with self._lock:
            self._owner[req.request_id] = rep.idx

    # ---- replica failure / replacement -----------------------------------
    def _fail_replica(self, rep: Replica, err: Exception):
        """A replica's step() raised: mark it DEAD and fail every
        session it held over to the survivors. Running sessions lose
        their KV (the pool died with the replica) and resume by
        re-prefilling prompt+generated — the preemption-resume path, so
        greedy streams stay exact and nothing is lost. Holds the router
        lock for the WHOLE failover so a concurrent add_request cannot
        land a session in the dying replica's books mid-snapshot.
        Mid-stream sessions prefer a SAME-params-version survivor
        (tokens already emitted came from this version's weights);
        when a half-rolled fleet leaves none, availability wins over
        version purity — the session continues on a different version
        with a loud log rather than dropping. Raises only when NO live
        replica remains (the driver watchdog then owns it)."""
        logger.warning(
            "fleet replica %d DIED on step (%s) — failing its sessions "
            "over", rep.idx, err)
        with self._lock:
            rep.state = DEAD
            rep.rebuild_hints = None   # a dead engine cannot drain
            self.router_stats["replica_deaths"] += 1
            telemetry.inc("fleet_replica_deaths")
            self._flush_replica(rep.idx)
            self._drop_tenant_replica(rep.idx)
            eng = rep.engine
            orphans = list(eng.requests.items())
            # Failover targets: ACTIVE first, else DRAINING survivors
            # (alive — reload-draining preferred, same tiering as
            # admission; their queue survives the swap). Only an
            # all-DEAD fleet has nowhere to fail over to.
            live = [r for r in self.replicas if r.state == ACTIVE]
            if not live:
                live = [r for r in self.replicas if r.state == DRAINING
                        and r.rebuild_hints is None]
                live = live or [r for r in self.replicas
                                if r.state == DRAINING]
            if not live:
                raise err
            same_ver = [r for r in live
                        if r.params_version == rep.params_version]
            for i, (rid, req) in enumerate(orphans):
                if req.finished:
                    # Finished-but-unpopped results stay fetchable
                    # through the new owner's books.
                    tgt = live[0]
                    tgt.engine.requests[rid] = req
                    self._owner[rid] = tgt.idx
                    continue
                pool = same_ver if (same_ver and req.generated) else live
                if req.generated and not same_ver:
                    logger.warning(
                        "failover of mid-stream request %d crosses "
                        "params versions (no same-version survivor) — "
                        "continuing on v%d", rid,
                        live[0].params_version)
                self._requeue_on(pool[i % len(pool)], req)
                self.router_stats["failovers"] += 1
                telemetry.inc("fleet_failovers")
                self._rt.instant("failover", rid, dead_replica=rep.idx)
            eng.requests.clear()

    @property
    def supervisor(self):
        """The ONE supervisor code path (inference/supervisor.py):
        manual drills (`kill_replica`/`revive_replica`) and the
        threaded poll loop (`.supervisor.start()`) both run the same
        Supervisor policy over an in-process backend, so "playing
        supervisor by hand" and the real watcher cannot drift. The
        cross-process fleet wires the SAME Supervisor over a process
        backend (inference/fleet_rpc.py)."""
        if self._supervisor is None:
            from megatronapp_tpu.inference.supervisor import Supervisor
            self._supervisor = Supervisor(_InProcessBackend(self),
                                          interval=0.5)
        return self._supervisor

    def kill_replica(self, idx: int):
        """Operator/drill entry: treat replica `idx` as dead right now
        (same path a step() exception takes) — routed through the one
        supervisor code path."""
        self.supervisor.kill(idx)

    def revive_replica(self, idx: int, **hints):
        """Replace a DEAD (or rebuild a live, drained) replica —
        routed through the one supervisor code path (the backend's
        relaunch mechanism is `_revive_impl`)."""
        self.supervisor.revive(idx, **hints)

    def _kill_impl(self, idx: int):
        rep = self.replicas[idx]
        if rep.state == DEAD:
            return
        self._fail_replica(rep, RuntimeError("killed by operator"))

    def _revive_impl(self, idx: int, **hints):
        """Rebuild replica `idx` through the engine_factory. The
        factory builds with its captured (startup) params, so when the
        fleet has since rolled to newer weights the rebuilt engine is
        swapped onto them before it serves — a revived replica may
        never claim the current version while holding factory-stale
        weights."""
        assert self.engine_factory is not None, (
            "revive_replica needs an engine_factory")
        # Router lock across the swap: add_request could otherwise
        # admit into the OLD engine's queue between the drained check
        # and the replacement — an orphaned session in a discarded
        # engine (the same mutual exclusion _fail_replica holds).
        with self._lock:
            rep = self.replicas[idx]
            old = rep.engine
            rep.engine = self.engine_factory(idx, **hints)
            self._wire(rep)
            self._drop_tenant_replica(idx)   # fresh AdapterCache
            # Finished-but-unfetched results must survive the engine
            # swap (a client whose done event fired but who has not
            # yet called result_tokens would otherwise get None back)
            # — same transplant _fail_replica does.
            try:
                for rid, req in list(old.requests.items()):
                    if req.finished:
                        rep.engine.requests[rid] = req
            except Exception:  # noqa: BLE001 — a dead engine may refuse
                pass
            if self._params is not None:
                rep.engine.set_params(self._params)
            rep.state = ACTIVE
            rep.params_version = self._version
            rep.rebuild_hints = None

    # ---- drain-aware rolling reload --------------------------------------
    def begin_rolling_reload(self, params) -> threading.Event:
        """Schedule a FLEET-WIDE rolling params swap: replicas drain and
        swap one at a time inside step(), so fleet admission never
        pauses and zero requests drop. Returns an event that fires when
        the LAST replica has swapped. A second call before the roll
        completes supersedes the params and restarts the roll; all
        waiters fire when the latest roll lands."""
        ev = threading.Event()
        with self._lock:
            self._version += 1
            self._params = params
            if self._reload is None:
                self._reload = {"params": params, "events": [ev],
                                "idx": 0}
            else:
                self._reload["params"] = params
                self._reload["idx"] = 0
                self._reload["events"].append(ev)
        return ev

    def _advance_reload(self):
        # The whole advance holds the router lock: begin_rolling_reload
        # (request threads) mutates the same state — without mutual
        # exclusion a superseding reload could append its event in the
        # window between the roll finishing and self._reload clearing,
        # firing a waiter whose params were never applied. Reentrant
        # callbacks (set_params → pool flush → _flush_replica) take the
        # same RLock on this thread.
        with self._lock:
            r = self._reload
            if r is None:
                return
            while r["idx"] < len(self.replicas):
                rep = self.replicas[r["idx"]]
                if (rep.state == DEAD
                        or rep.params_version == self._version):
                    r["idx"] += 1
                    continue
                rep.engine.pause_admission = True
                if rep.state == ACTIVE:
                    rep.state = DRAINING   # _rebalance drains it empty
                if not rep.engine.drained_for_reload():
                    return              # keep stepping; drain continues
                # Preempted requests version-fenced into this queue
                # (no same-version survivor to evacuate to) inevitably
                # resume on the NEW weights after the swap — their
                # already-emitted tokens came from the old ones. Same
                # availability-over-purity tradeoff as the death
                # failover's cross-version path; log as loudly.
                for req in list(rep.engine.waiting):
                    if getattr(req, "generated", None):
                        logger.warning(
                            "reload of replica %d carries queued "
                            "mid-stream request %d across params "
                            "versions (no same-version survivor held "
                            "it)", rep.idx, req.request_id)
                rep.engine.set_params(r["params"])  # flush → affinity
                rep.params_version = self._version
                rep.reloads += 1
                rep.engine.pause_admission = False
                if rep.rebuild_hints is None:
                    rep.state = ACTIVE
                # else: stay DRAINING — a pending autoscale rebuild
                # still owns the drain (its hints would otherwise
                # strand: _advance_rebuilds only acts on DRAINING and
                # has_work would spin on the un-clearable hints).
                r["idx"] += 1
                self.router_stats["replica_reloads"] += 1
                telemetry.inc("fleet_replica_reloads")
            self.router_stats["reloads"] += 1
            events = r["events"]
            self._reload = None
        for ev in events:
            ev.set()

    # ---- autoscaling ------------------------------------------------------
    def _maybe_autoscale(self, rep: Replica):
        if (self.autoscaler is None or self.engine_factory is None
                or rep.state != ACTIVE or rep.rebuild_hints is not None):
            return
        eng = rep.engine
        if not hasattr(eng, "prefill_ctx"):
            return          # the split knob exists on disagg replicas
        self.autoscaler.observe(rep.idx, rep.attainment(self.slo_ms),
                                len(eng.waiting))
        tp = eng.decode_ctx.tp
        target = self.autoscaler.recommend(
            rep.idx, eng.prefill_ctx.num_devices,
            eng.decode_ctx.num_devices, tp=tp)
        if target is None:
            return
        logger.warning(
            "fleet autoscale: replica %d prefill devices %d -> %d "
            "(attainment %.3f, prefill queue %d) — draining for rebuild",
            rep.idx, eng.prefill_ctx.num_devices, target,
            rep.attainment(self.slo_ms), len(eng.waiting))
        rep.rebuild_hints = {"prefill_devices": target}
        rep.state = DRAINING
        rep.engine.pause_admission = True
        telemetry.inc("fleet_autoscale_decisions")

    def _advance_rebuilds(self):
        if self.engine_factory is None:
            return
        # Under the router lock: the drained/empty-queue check and the
        # engine swap must be atomic vs concurrent add_request (which
        # can queue on DRAINING replicas during an all-draining
        # window).
        with self._lock:
            self._advance_rebuilds_locked()

    def _advance_rebuilds_locked(self):
        for rep in self.replicas:
            if rep.rebuild_hints is None or rep.state != DRAINING:
                continue
            eng = rep.engine
            self._evacuate_waiting(rep)
            if len(eng.waiting) and not any(
                    r.state == ACTIVE for r in self.replicas
                    if r is not rep):
                # Queued work with nowhere to evacuate (e.g. a
                # single-replica fleet whose drain window admitted into
                # this queue): a rebuild that waits for an empty queue
                # while admission is paused would livelock. Abort the
                # rebuild — availability beats the split change; the
                # autoscaler will re-recommend once traffic allows.
                logger.warning(
                    "fleet autoscale: aborting replica %d rebuild — "
                    "queued work and no evacuation target", rep.idx)
                rep.rebuild_hints = None
                rep.state = ACTIVE
                eng.pause_admission = False
                self.router_stats["autoscale_aborts"] += 1
                continue
            if not eng.drained_for_reload() or len(eng.waiting):
                continue
            hints = rep.rebuild_hints
            self.revive_replica(rep.idx, **hints)
            self.router_stats["autoscale_rebuilds"] += 1
            telemetry.inc("fleet_autoscale_rebuilds")

    # ---- main loop --------------------------------------------------------
    def step(self) -> Dict[str, List]:
        """One fleet round: advance the rolling reload + pending
        rebuilds, rebalance (drain/overload migrations), then step every
        live replica once and merge their event dicts. A replica whose
        step raises is failed over inside the round — the fleet round
        only raises when no live replica remains."""
        events: Dict[str, List] = {"admitted": [], "tokens": [],
                                   "finished": [], "preempted": [],
                                   "expired": []}
        self._advance_reload()
        self._advance_rebuilds()
        self._rebalance()
        for rep in self.replicas:
            if rep.state == DEAD:
                continue
            eng = rep.engine
            if not eng.has_work:
                continue
            try:
                ev = eng.step()
            except Exception as e:  # noqa: BLE001 — replica fails over
                self._fail_replica(rep, e)
                continue
            rep.steps += 1
            for key in events:
                events[key] += ev.get(key, [])
            self._maybe_autoscale(rep)
        return events

    def run_to_completion(self, token_callback=None) -> Dict[int, np.ndarray]:
        results: Dict[int, np.ndarray] = {}
        finished: Dict[int, object] = {}
        while self.has_work:
            ev = self.step()
            if token_callback is not None:
                for rid, tok in ev["tokens"]:
                    token_callback(rid, tok)
            for rid in ev["finished"]:
                eng = self._owner_engine(rid)
                if eng is not None:
                    finished[rid] = eng.requests[rid]
        for rid, req in finished.items():
            results[rid] = req.tokens
            self.pop_request(rid)
        return results

    # ---- observability ----------------------------------------------------
    def stats_snapshot(self, include_dispatch: bool = False) -> Dict:
        """Fleet snapshot: aggregated pool + per-replica sections + the
        router's own accounting (the /stats payload; /healthz slims it).
        include_dispatch forwards to replica 0 only — the launch counts
        are those of one traced decode step, identical across replicas
        of one config."""
        live = [r for r in self.replicas if r.state != DEAD]
        agg_pool = {
            "num_blocks": 0, "blocks_in_use": 0, "blocks_free": 0,
            "blocks_evictable": 0, "pool_bytes_total": 0,
            "kv_cache_dtype": self.kv_cache_dtype,
            "block_size": self.block_size,
        }
        replicas = []
        for rep in self.replicas:
            entry = {
                "idx": rep.idx, "state": rep.state,
                "params_version": rep.params_version,
                "reloads": rep.reloads, "steps": rep.steps,
                "attainment": round(rep.attainment(self.slo_ms), 4),
            }
            hist = rep.interval_hist()
            if hist is not None and hist.count:
                entry["interval_p50_ms"] = round(hist.percentile(50), 3)
                entry["interval_p99_ms"] = round(hist.percentile(99), 3)
            if rep.state != DEAD:
                eng = rep.engine
                pool = eng.pool
                entry.update({
                    "active": sum(1 for s in eng.slots if s is not None),
                    "waiting": len(eng.waiting),
                    "parked": len(getattr(eng, "_parked", ())),
                    "blocks_in_use": pool.blocks_in_use(),
                    "prefix_hit_tokens":
                        pool.stats["prefix_hit_tokens"],
                    "prefill_tokens": pool.stats["prefill_tokens"],
                })
                agg_pool["num_blocks"] += pool.num_blocks
                agg_pool["blocks_in_use"] += pool.blocks_in_use()
                agg_pool["blocks_free"] += pool.free_blocks()
                agg_pool["blocks_evictable"] += pool.evictable_blocks()
                agg_pool["pool_bytes_total"] += pool.bytes_total
                if hasattr(eng, "prefill_ctx"):
                    entry["prefill_devices"] = eng.prefill_ctx.num_devices
                    entry["decode_devices"] = eng.decode_ctx.num_devices
            replicas.append(entry)
        hit = sum(r.get("prefix_hit_tokens", 0) for r in replicas)
        seen = hit + sum(r.get("prefill_tokens", 0) for r in replicas)
        out = {
            "engine": "fleet",
            "paged": True,
            "max_batch": self.max_batch,
            "active": sum(r.get("active", 0) for r in replicas),
            "waiting": sum(r.get("waiting", 0) for r in replicas),
            "pool": agg_pool,
            "fleet": {
                "replicas": replicas,
                "num_replicas": len(self.replicas),
                "live_replicas": len(live),
                "policy": self.policy,
                "migrate": self.migrate,
                "autoscale": self.autoscaler is not None,
                "slo_ms": self.slo_ms,
                "params_version": self._version,
                "reload_pending": self._reload is not None,
                "affinity_entries": len(self._affinity),
                "tenant_affinity_entries": len(self._tenant_affinity),
                "supervisor_restarts": (
                    self._supervisor.total_restarts
                    if self._supervisor is not None else 0),
                "prefix_hit_rate": (round(hit / seen, 4) if seen
                                    else 0.0),
                **self.router_stats,
            },
        }
        if self.prefix_store is not None:
            out["fleet"]["prefix_store"] = self.prefix_store.stats()
        if include_dispatch and live:
            try:
                out["decode_dispatch"] = (
                    live[0].engine.stats_snapshot(
                        include_dispatch=True).get("decode_dispatch"))
            except Exception:  # noqa: BLE001 — observability best-effort
                pass
        return out

    def generate_text(self, prompts, max_new_tokens: int, sampling=None,
                      token_callback=None):
        """String-level API (mirrors DynamicInferenceEngine)."""
        assert self.tokenizer is not None, "tokenizer required"
        eod = getattr(self.tokenizer, "eod", None)
        rids = []
        for prompt in prompts:
            ids = np.asarray(self.tokenizer.tokenize(prompt), np.int32)
            rids.append(self.add_request(ids, max_new_tokens, sampling,
                                         eod_id=eod))
        cb = None
        if token_callback is not None:
            def cb(rid, tok):
                token_callback(rid, np.asarray([tok]), None)
        results = self.run_to_completion(token_callback=cb)
        texts = []
        for prompt, rid in zip(prompts, rids):
            n_prompt = len(self.tokenizer.tokenize(prompt))
            new_ids = results[rid][n_prompt:].tolist()
            if eod is not None and eod in new_ids:
                new_ids = new_ids[: new_ids.index(eod)]
            texts.append(self.tokenizer.detokenize(new_ids))
        return texts


class _InProcessBackend:
    """Supervisor backend over an in-process FleetRouter: alive = the
    replica is not DEAD, kill = the step-exception failover path
    (`_fail_replica` — zero lost sessions), relaunch = the
    engine_factory rebuild. The cross-process twin lives in
    inference/fleet_rpc.py; both feed the SAME Supervisor policy
    (inference/supervisor.py), so thread mode and process mode cannot
    drift."""

    def __init__(self, router: "FleetRouter"):
        self.router = router

    def indices(self) -> List[int]:
        return [rep.idx for rep in self.router.replicas]

    def alive(self, idx: int) -> bool:
        return self.router.replicas[idx].state != DEAD

    def kill(self, idx: int):
        self.router._kill_impl(idx)

    def relaunch(self, idx: int, **hints):
        self.router._revive_impl(idx, **hints)
