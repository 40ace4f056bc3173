"""Disaggregated serving: prefill/decode sub-meshes with KV handoff.

The serving-side analogue of the reference's MegaFBD forward/backward
disaggregation (MegatronApp §4, virtual ranks on device halves —
`parallel/fbd.py` models the half-mesh construction this module reuses):
the device set splits into a PREFILL sub-mesh and a DECODE sub-mesh, so
a long prompt's prefill never occupies the decode devices and decode
token intervals stop being hostage to whoever else just connected.

Architecture (one process, one stepper thread — the
`DynamicBatchingDriver` drives `DisaggServingEngine.step()` exactly like
a plain engine):

- **Shared refcounted block pool.** One `PagedKVCache` owns all KV
  bookkeeping; its page DATA lives on the decode sub-mesh (tp > 1
  shards it over KV heads — the per-shard pools of the tp-sharded paged
  kernels). The pool carries `prefill_slots` extra page-table rows as
  prefill STAGING slots.
- **Prefill worker** (prefill sub-mesh): admits a request into a staging
  slot, runs CHUNKED prefill — fixed-size chunks through one
  `_forward_with_cache` trace against a bucket-sized dense temp cache on
  the prefill mesh — and after each chunk ships ONLY that chunk's new KV
  rows to the decode mesh, scattering them page-table-aware into the
  shared pool (`write_prompt_pages`). Prefix-cache hits are gathered
  from the pool once instead of recomputed. Chunking is the prefill-side
  scheduler: between chunks the coordinator can preempt in favor of the
  decode SLO.
- **KV handoff = page-table transfer.** When the prompt completes (first
  token sampled prefill-side on the engine's exact fold_in chain), the
  request parks until the decode engine has a free slot, then
  `PagedKVCache.transfer_slot` moves block OWNERSHIP to the decode slot:
  refcounts and page data untouched — KV is written once by prefill and
  adopted by decode with no dense copy (pinned by tests/test_disagg.py).
- **SLO-aware two-queue scheduler.** The prefill queue and the parked
  (handoff) queue are both served in (priority, request_id) order;
  over-deadline work is rejected at admission and swept while queued,
  in-flight, or parked (their staged blocks are reclaimed — the handoff
  state is a first-class lifecycle stage for `expire_overdue` /
  `abort_all`). A decode-latency budget gates prefill chunks: when the
  next chunk's EWMA-predicted cost would push the decode token interval
  past `decode_slo_ms`, the chunk is deferred (a counted
  `chunk_preemption`) and decode steps first. `/stats` and `/healthz`
  expose per-queue depth and SLO attainment.

MTP speculative decoding degrades to plain decode for adopted requests
(the proposer's pre-head hidden state is not shipped across the meshes);
ngram/draft proposers are unaffected.
"""

from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from megatronapp_tpu.config.parallel_config import ParallelConfig
from megatronapp_tpu.config.transformer_config import TransformerConfig
from megatronapp_tpu.inference.dynamic_engine import (
    DeadlineExceeded, DynamicInferenceEngine, Request, _sample_batched,
    validate_admission,
)
from megatronapp_tpu.inference.engine import (
    SamplingParams, _forward_with_cache, init_kv_cache, mask_padded_vocab,
)
from megatronapp_tpu.inference.paged_cache import PagedKVCache, cdiv
from megatronapp_tpu.parallel.fbd import build_half_meshes
from megatronapp_tpu.parallel.mesh import MeshContext
from megatronapp_tpu.trace.request_trace import (
    PREFILL_PID, get_request_tracer,
)
from megatronapp_tpu.utils import metrics as telemetry
from megatronapp_tpu.utils.metrics import Histogram


def split_serving_meshes(tp: int = 1, devices=None,
                         prefill_devices: Optional[int] = None
                         ) -> Tuple[MeshContext, MeshContext]:
    """(prefill_ctx, decode_ctx) on disjoint device subsets, each a tp
    mesh — the serving analogue of `split_fbd_meshes` (same half-mesh
    construction, no DP bookkeeping: serving replicates params).

    prefill_devices=None keeps the historical even split on the first
    2*tp devices. An explicit count gives the prefill sub-mesh that many
    devices and the decode sub-mesh the REST of `devices` — the knob the
    fleet autoscaler turns (inference/fleet.py MeshSplitAutoscaler):
    EWMA decode-SLO attainment shrinks the prefill side, prefill-queue
    pressure grows it. Both sides must hold at least one whole tp
    group."""
    if devices is None:
        devices = jax.devices()
    devices = list(devices)
    par = ParallelConfig(tensor_parallel=tp)
    if prefill_devices is None:
        need = 2 * tp
        if len(devices) < need:
            raise ValueError(
                f"prefill/decode disaggregation at tp={tp} needs {need} "
                f"devices, have {len(devices)}")
        return build_half_meshes(par, par, devices[:need])
    n_pre = int(prefill_devices)
    n_dec = len(devices) - n_pre
    if (n_pre < tp or n_dec < tp or n_pre % tp or n_dec % tp):
        raise ValueError(
            f"uneven prefill/decode split {n_pre}/{n_dec} over "
            f"{len(devices)} devices is invalid at tp={tp}: both "
            "sub-meshes need a positive multiple of tp devices")
    from megatronapp_tpu.parallel.mesh import build_mesh
    return (build_mesh(par, devices=devices[:n_pre]),
            build_mesh(par, devices=devices[n_pre:]))


def _split2(four):
    """((k, v, k_scales, v_scales)) → ((k, v), (k_scales, v_scales))."""
    return tuple(four[:2]), tuple(four[2:])


@dataclasses.dataclass
class PrefillState:
    """One in-flight (or parked) prefill on the prefill sub-mesh."""
    req: Request
    pslot: int                    # pool STAGING slot owning the blocks
    tokens: np.ndarray            # prompt + pre-preemption generated
    p_len: int
    pos: int                      # next uncomputed position
    tmp: tuple                    # dense temp cache on the prefill mesh
    bucket: int
    done: bool = False            # all chunks computed, first token out


class PrefillWorker:
    """Chunked prefill on the prefill sub-mesh, writing KV blocks into
    the shared pool on the decode sub-mesh (see module docstring)."""

    def __init__(self, params, cfg: TransformerConfig, pool: PagedKVCache,
                 ctx: MeshContext, decode_ctx: MeshContext,
                 prefill_chunk: int, prefill_buckets, max_seq_len: int):
        import functools
        from jax.sharding import NamedSharding, PartitionSpec as P
        from megatronapp_tpu.ops.pallas.paged_attention import (
            gather_prefix_pages, quantize_kv_rows, write_prompt_pages,
        )
        self.cfg = cfg
        self.pool = pool
        self.ctx = ctx
        self.chunk = prefill_chunk
        # Buckets rounded UP to chunk multiples: every chunk — including
        # the last — then slices a full chunk-shaped KV run out of the
        # temp cache, so the ship/scatter path has ONE trace per bucket.
        # manual-ok: mesh-level placement outside any manual region.
        self._params_sharding = NamedSharding(ctx.mesh, P())
        self._decode_rep = NamedSharding(decode_ctx.mesh, P())  # manual-ok: see above
        self.params = jax.device_put(params, self._params_sharding)  # manual-ok: see above
        self.buckets = tuple(sorted({
            cdiv(max(b, prefill_chunk), prefill_chunk) * prefill_chunk
            for b in (*prefill_buckets, max_seq_len)}))
        self._prefill = jax.jit(
            functools.partial(_forward_with_cache, cfg=cfg))
        self._sample = jax.jit(_sample_batched)
        # ONE fused scatter for both pool tensors per chunk (halves the
        # per-chunk dispatch overhead), with the OUTPUT sharding pinned
        # to the pool's committed placement (tp-sharded over Hkv or
        # replicated on the decode mesh): the engine's decode jit and
        # this write alternate on the same buffers, and a sharding flip
        # between them would force a retrace every handoff.
        # manual-ok: mesh-level placement outside any manual region.
        if pool.quantized:
            # int8 pool: rows quantize ON THE PREFILL MESH (one jit) so
            # the cross-mesh handoff ships int8 rows + fp32 scales —
            # (D + 4) / (2 D) of the bf16 row bytes — and the fused
            # scatter commits all four pool tensors.
            self._quantize = jax.jit(functools.partial(
                quantize_kv_rows, dtype=pool.pages[0].dtype))

            def _write_quant(pk, pv, sk, sv, rk, rv, rsk, rsv,
                             table_row, start, count):
                w = write_prompt_pages
                return (w(pk, rk, table_row, start, count),
                        w(pv, rv, table_row, start, count),
                        w(sk, rsk, table_row, start, count),
                        w(sv, rsv, table_row, start, count))

            self._write = jax.jit(
                _write_quant, donate_argnums=(0, 1, 2, 3),
                out_shardings=(pool.pages[0].sharding,
                               pool.pages[1].sharding,
                               pool.scales[0].sharding,
                               pool.scales[1].sharding))
        else:
            def _write_both(pk, pv, rk, rv, table_row, start, count):
                return (write_prompt_pages(pk, rk, table_row, start,
                                           count),
                        write_prompt_pages(pv, rv, table_row, start,
                                           count))

            self._write = jax.jit(
                _write_both, donate_argnums=(0, 1),
                out_shardings=(pool.pages[0].sharding,
                               pool.pages[1].sharding))
        self._gather = jax.jit(gather_prefix_pages, static_argnums=(2,))
        self.stats = {"prefills_started": 0, "prefills_finished": 0,
                      "chunks": 0, "kv_shipped_bytes": 0,
                      "prefix_hit_tokens": 0}
        # Prefill-mesh events land on their own pid row of the merged
        # request trace (ISSUE 12).
        self._rt = get_request_tracer()

    def set_params(self, params):
        """Rolling reload: mirror the new weights onto the prefill mesh
        (shapes unchanged, traces stay valid)."""
        # manual-ok: host-side reload path, no manual region
        self.params = jax.device_put(params, self._params_sharding)

    # ------------------------------------------------------------------
    def start(self, req: Request, pslot: int) -> Optional[PrefillState]:
        """Admit `req` into staging slot `pslot` and set up its chunked
        prefill. Returns None (nothing mutated) when the pool cannot
        host the prompt right now."""
        tokens = req.tokens
        p_len = len(tokens)
        plan = self.pool.admit(pslot, tokens)
        if plan is None:
            return None
        # Temp cache = bucket + one spare chunk: a prefix-cache hit can
        # start chunking at pos = cached (any block multiple), so the
        # fixed-width chunk window [pos, pos + chunk) may extend past
        # p_len — without the spare row range, _forward_with_cache's
        # dynamic_update_slice/dynamic_slice would CLAMP the start index
        # (silently overwriting the gathered prefix and mis-rotating
        # rope) instead of erroring. The spare rows only ever hold
        # padding-token garbage that nothing attends causally.
        bucket = next(b for b in self.buckets if b >= p_len) + self.chunk
        tmp_np = [np.zeros(c.shape, np.float32)
                  for c in init_kv_cache(self.cfg, 1, bucket)]
        cached = plan.cached_tokens
        if cached:
            # Prefix hit: gather the cached blocks' KV out of the shared
            # pool once (decode mesh) and seed the temp cache with it —
            # the cached prefix is neither recomputed nor re-shipped.
            # int8 pools dequantize the gathered rows here (the dense
            # temp cache on the prefill mesh is compute-dtype).
            nblocks = cdiv(cached, self.pool.block_size)
            table_row = jnp.asarray(self.pool.page_table[pslot])
            if self.pool.quantized:
                for t, p, sc in zip(tmp_np, self.pool.pages,
                                    self.pool.scales):
                    rows = np.asarray(jax.device_get(
                        self._gather(p, table_row, nblocks)))[:, :cached]
                    rsc = np.asarray(jax.device_get(
                        self._gather(sc, table_row,
                                     nblocks)))[:, :cached]
                    t[:, 0, :cached] = (rows.astype(np.float32)
                                        * rsc[..., None])
            else:
                for t, p in zip(tmp_np, self.pool.pages):
                    rows = np.asarray(jax.device_get(
                        self._gather(p, table_row, nblocks)))[:, :cached]
                    t[:, 0, :cached] = rows
            self.stats["prefix_hit_tokens"] += cached
        tmp = tuple(
            # manual-ok: temp-cache placement onto the prefill mesh,
            # host-side admission path, no manual region
            jax.device_put(jnp.asarray(t, self.cfg.compute_dtype),
                           self._params_sharding)
            for t in tmp_np)
        self.stats["prefills_started"] += 1
        return PrefillState(req=req, pslot=pslot, tokens=tokens,
                            p_len=p_len, pos=cached, tmp=tmp,
                            bucket=bucket)

    def advance(self, state: PrefillState, sync: bool = True) -> bool:
        """Run ONE chunk of `state`'s prefill and ship its KV rows into
        the shared pool. Returns True when the whole prompt is computed
        (state.req then carries its first generated token). With `sync`
        the call blocks until the chunk is done — the coordinator needs
        the real chunk latency for its decode-SLO budget EWMA; without
        an SLO the chunks pipeline asynchronously against the decode
        mesh."""
        self._rt.begin("prefill-chunk", state.req.request_id,
                       pid=PREFILL_PID, pos=state.pos)
        c = min(self.chunk, state.p_len - state.pos)
        padded = np.zeros((1, self.chunk), np.int32)
        padded[0, :c] = state.tokens[state.pos:state.pos + c]
        logits, state.tmp = self._prefill(
            self.params, jnp.asarray(padded), state.tmp, state.pos)
        # Ship ONLY this chunk's rows (fixed chunk shape, count-masked
        # padding) to the decode mesh and scatter them page-table-aware
        # in one fused write. int8 pools quantize ON THE PREFILL MESH
        # first, so the handoff ships int8 rows + fp32 scales instead of
        # bf16 rows (the shipped-bytes accounting below reads the actual
        # transferred arrays either way).
        from megatronapp_tpu.utils import chaos
        table_row = jnp.asarray(self.pool.page_table[state.pslot])
        rows = []
        for t in state.tmp:
            r = t[:, 0, state.pos:state.pos + self.chunk]
            if self.pool.quantized:
                r_q, r_s = self._quantize(r)
                # manual-ok: cross-mesh handoff transfer (prefill →
                # decode), outside any manual region — the one data
                # movement of the handoff (quantized chunk rows +
                # scales, never the pool).
                rows.append((jax.device_put(r_q, self._decode_rep),
                             # manual-ok: cross-mesh handoff, see above
                             jax.device_put(r_s, self._decode_rep)))
                self.stats["kv_shipped_bytes"] += sum(
                    int(x.size) * x.dtype.itemsize for x in rows[-1])
            else:
                # manual-ok: cross-mesh handoff transfer (prefill →
                # decode), outside any manual region — the one data
                # movement of the handoff (block-granular chunk rows,
                # never the pool).
                rows.append(jax.device_put(r, self._decode_rep))
                self.stats["kv_shipped_bytes"] += int(
                    r.size) * r.dtype.itemsize
        if self.pool.quantized:
            # Chaos site "kv-quant-write": fires between quantize and
            # the page-table commit of the shipped rows — the pool is
            # untouched, state.pos unchanged, so the retry (or the
            # release path on abort) leaves the allocator audit-clean.
            chaos.fire("kv-quant-write")
            (self.pool.pages,
             self.pool.scales) = _split2(self._write(
                 self.pool.pages[0], self.pool.pages[1],
                 self.pool.scales[0], self.pool.scales[1],
                 rows[0][0], rows[1][0], rows[0][1], rows[1][1],
                 table_row, state.pos, c))
        else:
            self.pool.pages = self._write(
                self.pool.pages[0], self.pool.pages[1], rows[0], rows[1],
                table_row, state.pos, c)
        state.pos += c
        self.stats["chunks"] += 1
        telemetry.inc("disagg_prefill_chunks")
        if state.pos < state.p_len:
            if sync:
                jax.block_until_ready(logits)
            self._rt.end("prefill-chunk", state.req.request_id,
                         pid=PREFILL_PID)
            return False
        # Prompt complete: register its blocks for followers and sample
        # the first generated token with the engine's exact key chain
        # (PRNGKey(seed) ∘ request_id ∘ step) — streams are independent
        # of WHERE the prefill ran.
        self.pool.register_prefix(state.pslot, state.tokens, state.p_len)
        req = state.req
        s = req.sampling
        last = mask_padded_vocab(logits[0, c - 1], self.cfg)
        tok = int(jax.device_get(self._sample(
            last[None], jnp.asarray([s.seed], jnp.int32),
            jnp.asarray([req.request_id], jnp.int32),
            jnp.asarray([len(req.generated)], jnp.int32),
            jnp.asarray([s.temperature], jnp.float32),
            jnp.asarray([s.top_k], jnp.int32),
            jnp.asarray([s.top_p], jnp.float32),
            jnp.asarray([s.greedy], bool)))[0])
        req.generated.append(tok)
        if (tok == req.eod_id
                or len(req.generated) >= req.max_new_tokens):
            req.finished = True
        state.done = True
        self.stats["prefills_finished"] += 1
        self._rt.end("prefill-chunk", req.request_id, pid=PREFILL_PID)
        return True

    def release(self, state: PrefillState):
        """Return a staged prefill's blocks to the pool (abort/expiry
        while in flight or parked) — the handoff lifecycle stage leaks
        nothing."""
        self.pool.release(state.pslot, state.tokens,
                          min(state.pos, state.p_len))


class DisaggServingEngine:
    """Prefill/decode-disaggregated serving engine (module docstring).

    Drop-in for `DynamicInferenceEngine` behind the server's
    `DynamicBatchingDriver`: same add_request/step/has_work/abort/stats
    surface, but prompts prefill on their own sub-mesh and enter the
    decode batch by block handoff."""

    def __init__(self, params, cfg: TransformerConfig, tokenizer=None,
                 max_batch: int = 4, max_seq_len: Optional[int] = None,
                 prefill_buckets: Tuple[int, ...] = (32, 128, 512),
                 block_size: int = 16, num_blocks: Optional[int] = None,
                 enable_prefix_caching: bool = True,
                 prefill_chunk: Optional[int] = 32, prefill_slots: int = 2,
                 decode_slo_ms: Optional[float] = None, tp: int = 1,
                 devices=None, spec_method: Optional[str] = None,
                 spec_k: int = 4, draft_params=None, draft_cfg=None,
                 idle_chunks_per_step: int = 4,
                 kv_cache_dtype: str = "bf16",
                 prefill_devices: Optional[int] = None):
        self.prefill_ctx, self.decode_ctx = split_serving_meshes(
            tp=tp, devices=devices, prefill_devices=prefill_devices)
        max_seq_len = max_seq_len or cfg.max_position_embeddings
        # One quantum for the worker's dense prefill and the engine's
        # paged calls (preemption's recomputation): the server passes None
        # where --prefill-chunk is not given.
        prefill_chunk = prefill_chunk or 32
        pool = PagedKVCache(
            cfg, max_batch, max_seq_len, num_blocks=num_blocks,
            block_size=block_size,
            enable_prefix_caching=enable_prefix_caching,
            extra_slots=prefill_slots, kv_cache_dtype=kv_cache_dtype)
        self.engine = DynamicInferenceEngine(
            params, cfg, tokenizer=tokenizer, max_batch=max_batch,
            max_seq_len=max_seq_len, prefill_buckets=prefill_buckets,
            prefill_chunk=prefill_chunk,
            spec_method=spec_method, spec_k=spec_k,
            draft_params=draft_params, draft_cfg=draft_cfg,
            ctx=self.decode_ctx, pool=pool)
        self.worker = PrefillWorker(
            params, cfg, pool, self.prefill_ctx, self.decode_ctx,
            prefill_chunk, prefill_buckets, max_seq_len)
        self.cfg = cfg
        self.tokenizer = tokenizer
        self.max_batch = max_batch
        self.max_seq_len = max_seq_len
        self.prefill_slots = prefill_slots
        self.decode_slo_s = (None if decode_slo_ms is None
                             else decode_slo_ms / 1e3)
        self.idle_chunks_per_step = idle_chunks_per_step
        self.pause_admission = False

        self.waiting: deque = deque()        # prefill queue (priority)
        self._inflight: List[PrefillState] = []
        self._parked: List[PrefillState] = []  # done, awaiting handoff
        self._aborted: List[Request] = []
        self.requests: Dict[int, Request] = self.engine.requests
        self._last_decode_t: Optional[float] = None
        self._chunk_ewma_s: Optional[float] = None
        self.slo_stats = {"decode_intervals": 0, "attained": 0,
                          "worst_interval_ms": 0.0,
                          "chunk_preemptions": 0,
                          "rejected_at_admission": 0}
        # Histogram-backed SLO accounting (ISSUE 12): token-interval and
        # TTFT percentiles replace the single worst-interval scalar as
        # the attainment signal. Private Histogram instances — live even
        # when the global metrics registry is off (the fleet router will
        # score replicas off these).
        self.interval_hist = Histogram(lo=1e-2, hi=1e6, growth=1.25)
        self.ttft_hist = Histogram(lo=1e-2, hi=1e7, growth=1.25)
        self._rt = get_request_tracer()

    # ---- engine-facade surface ------------------------------------------
    @property
    def pool(self) -> PagedKVCache:
        return self.engine.pool

    @property
    def slots(self):
        return self.engine.slots

    @property
    def has_work(self) -> bool:
        return bool(self.waiting or self._inflight or self._parked
                    or self.engine.has_work)

    def add_request(self, prompt_tokens, max_new_tokens: int,
                    sampling: Optional[SamplingParams] = None,
                    eod_id: Optional[int] = None, priority: int = 0,
                    deadline_s: Optional[float] = None,
                    request_id: Optional[int] = None) -> int:
        """Same contract/validation as the engine's add_request (the
        shared `validate_admission`); requests enter the PREFILL queue
        (served in (priority, request_id) order — SLO-aware admission)
        instead of the decode waiting queue. `request_id` lets the
        cross-process fleet router (inference/fleet_rpc.py) mint the id."""
        try:
            prompt = validate_admission(prompt_tokens, max_new_tokens,
                                        self.max_seq_len, pool=self.pool,
                                        deadline_s=deadline_s)
        except DeadlineExceeded:
            self.slo_stats["rejected_at_admission"] += 1
            raise
        now = time.monotonic()
        if request_id is None:
            request_id = next(self.engine._ids)
        elif request_id in self.requests:
            raise ValueError(f"request id {request_id} already admitted")
        req = Request(request_id, prompt, max_new_tokens,
                      sampling or SamplingParams(), eod_id=eod_id,
                      priority=priority, deadline_s=deadline_s,
                      admit_t=now, queued_t=now)
        self.waiting.append(req)
        self.requests[req.request_id] = req
        telemetry.inc("serving_requests_admitted")
        rt = self._rt
        if rt.enabled:
            rt.instant("admit", req.request_id,
                       prompt_tokens=len(prompt), priority=priority)
            rt.begin("request", req.request_id)
            rt.begin("queue-wait", req.request_id)
        return req.request_id

    def pop_request(self, request_id: int) -> Optional[Request]:
        return self.engine.pop_request(request_id)

    def abort_request(self, request_id: int) -> Optional[str]:
        req = self.requests.get(request_id)
        if req is None:
            return None
        if req in self.waiting:
            try:
                self.waiting.remove(req)
            except ValueError:
                pass        # raced with prefill start: running below
            else:
                req.finished = True
                self._rt.finish(request_id, "abort")
                return "waiting"
        if not req.finished:
            # In-flight prefill, parked, or decoding: the next step's
            # sweep releases its blocks (staging or decode slot alike).
            req.finished = True
            self._rt.instant("abort", request_id)
            return "running"
        return None

    def expire_overdue(self, now: Optional[float] = None) -> List[int]:
        """Deadline sweep across ALL lifecycle stages — queued,
        in-flight prefill, PARKED IN HANDOFF, and decoding. Marking here;
        block reclaim happens in the same step's sweep pass, so no leak
        path opens between the sub-meshes."""
        if now is None:
            now = time.monotonic()
        expired: List[int] = []

        def overdue(r: Request) -> bool:
            return (r.deadline_s is not None and not r.finished
                    and now >= r.deadline_s)

        for _ in range(4):
            try:
                overdue_waiting = [r for r in self.waiting if overdue(r)]
                break
            except RuntimeError:
                continue
        else:
            overdue_waiting = []
        for req in overdue_waiting:
            try:
                self.waiting.remove(req)
            except ValueError:
                continue
            req.finished = True
            self._aborted.append(req)
            expired.append(req.request_id)
            self._rt.finish(req.request_id, "expire")
        for state in self._inflight + self._parked:
            if overdue(state.req):
                state.req.finished = True     # reclaimed by _sweep_staged
                expired.append(state.req.request_id)
                self._rt.instant("expire", state.req.request_id)
        if expired:
            telemetry.inc("serving_deadline_expired", len(expired))
        expired += self.engine.expire_overdue(now)
        return expired

    def abort_all(self):
        """Drop everything (server error recovery): queued, staged
        (in-flight + parked — their pool blocks are released), and the
        decode engine's own state."""
        for req in list(self.waiting):
            self.requests.pop(req.request_id, None)
            self._rt.finish(req.request_id, "abort")
        self.waiting.clear()
        for state in self._inflight + self._parked:
            try:
                self.worker.release(state)
            except Exception:  # noqa: BLE001 — best-effort reclaim
                pass
            self.requests.pop(state.req.request_id, None)
            self._rt.finish(state.req.request_id, "abort")
        self._inflight = []
        self._parked = []
        self.engine.abort_all()

    def set_params(self, params):
        """Rolling reload: swap weights on BOTH sub-meshes (the raw
        host-side pytree is placed onto each mesh independently)."""
        self.engine.set_params(params)
        self.worker.set_params(params)

    # ---- live session migration (ISSUE 14, inference/fleet.py) ----------
    # Only sessions already in a DECODE slot are exportable (their KV is
    # complete in the shared pool); in-flight/parked prefills and queued
    # requests return None from export and migrate by requeue instead —
    # the engine-level export checks slot occupancy, so the delegation
    # is safe for every lifecycle stage.
    def export_request(self, rid: int) -> Optional[dict]:
        return self.engine.export_request(rid)

    def import_request(self, payload: dict) -> bool:
        return self.engine.import_request(payload)

    def release_exported(self, rid: int):
        return self.engine.release_exported(rid)

    def free_decode_slots(self) -> int:
        return self.engine.free_decode_slots()

    def drained_for_reload(self) -> bool:
        """True when a params swap is safe: no decode slot occupied, no
        prefill mid-flight, and nothing PARKED in handoff — a parked
        request's prompt KV was computed with the old weights, so it
        must adopt and finish on them (adoption keeps running while
        admission is paused) before the swap lands. Queued work holds
        its position and prefills on the new weights."""
        return (not self._inflight and not self._parked
                and all(r is None for r in self.engine.slots))

    # ---- scheduling internals -------------------------------------------
    def _pop_priority(self) -> Optional[Request]:
        """Pop the highest-priority (lowest (priority, request_id))
        waiting request; tolerant of concurrent submit/abort mutation
        like the engine's expiry sweep."""
        for _ in range(4):
            try:
                snapshot = sorted(self.waiting,
                                  key=lambda r: (r.priority,
                                                 r.request_id))
                break
            except RuntimeError:
                continue
        else:
            return None
        for req in snapshot:
            try:
                self.waiting.remove(req)
            except ValueError:
                continue
            return req
        return None

    def _free_pslot(self) -> Optional[int]:
        used = {s.pslot for s in self._inflight + self._parked}
        for i in range(self.max_batch,
                       self.max_batch + self.prefill_slots):
            if i not in used:
                return i
        return None

    def _sweep_staged(self, events):
        """Release staged (in-flight/parked) requests aborted or expired
        since the last step — the handoff lifecycle stage reclaims its
        blocks exactly like a decode slot does."""
        for lst in (self._inflight, self._parked):
            for state in list(lst):
                if state.req.finished:
                    self.worker.release(state)
                    lst.remove(state)
                    events["finished"].append(state.req.request_id)
                    # abort/expire instants already fired at the mark
                    # site; this closes every span still open (prefill
                    # on the prefill pid, handoff-parked, request).
                    self._rt.finish(state.req.request_id)

    def _adopt_parked(self, events):
        """Hand finished prefills to the decode side in (priority, rid)
        order while it has free slots: pure page-table/refcount
        transfer, no KV movement."""
        for state in sorted(self._parked,
                            key=lambda s: (s.req.priority,
                                           s.req.request_id)):
            if self.engine.free_decode_slots() == 0:
                break
            self._parked.remove(state)
            self._rt.end("handoff-parked", state.req.request_id)
            self.engine.adopt_request(state.req, state.pslot,
                                      state.p_len)
            events["admitted"].append(state.req.request_id)

    def _start_prefills(self, events):
        while not self.pause_admission:
            pslot = self._free_pslot()
            if pslot is None:
                return
            req = self._pop_priority()
            if req is None:
                return
            if req.finished:               # aborted while queued
                self._aborted.append(req)
                continue
            state = self.worker.start(req, pslot)
            if state is None:
                # Pool pressure: strict priority — the head of the queue
                # waits for blocks rather than letting lower-priority
                # work overtake it.
                self.waiting.appendleft(req)
                return
            self._inflight.append(state)
            rt = self._rt
            rt.end("queue-wait", req.request_id)
            telemetry.observe("serving_queue_wait_ms",
                              (time.monotonic() - req.queued_t) * 1e3)
            rt.begin("prefill", req.request_id, pid=PREFILL_PID,
                     prompt_tokens=state.p_len,
                     cached_tokens=state.pos)

    def _prefill_budget_chunks(self, t_decode_done: float,
                               decode_active: bool) -> None:
        """Run prefill chunks under the decode-latency budget: chunks
        keep running while the EWMA-predicted next-chunk cost fits
        inside the decode SLO window; the first deferred chunk counts as
        a preemption. With no active decode the budget is a simple
        per-step chunk cap (keep TTFT moving, return control to the
        stepper regularly)."""
        ran = 0
        cap = 1 if decode_active else self.idle_chunks_per_step
        while self._inflight:
            state = min(self._inflight,
                        key=lambda s: (s.req.priority, s.req.request_id))
            if decode_active and self.decode_slo_s is not None:
                est = self._chunk_ewma_s or 0.0
                elapsed = time.monotonic() - t_decode_done
                if elapsed + est > 0.8 * self.decode_slo_s:
                    if state.pos < state.p_len:
                        self.slo_stats["chunk_preemptions"] += 1
                    return
            elif ran >= cap:
                return
            t0 = time.monotonic()
            done = self.worker.advance(
                state, sync=self.decode_slo_s is not None)
            dt = time.monotonic() - t0
            self._chunk_ewma_s = (dt if self._chunk_ewma_s is None
                                  else 0.5 * self._chunk_ewma_s
                                  + 0.5 * dt)
            ran += 1
            if done:
                self._inflight.remove(state)
                self._finish_prefill(state)

    def _finish_prefill(self, state: PrefillState):
        """Prompt fully computed: emit the first token (next step's
        events) and park for handoff — or finish outright when the
        request is already done (max_new_tokens == 1 / immediate eod /
        aborted mid-prompt)."""
        rid = state.req.request_id
        self._rt.end("prefill", rid, pid=PREFILL_PID)
        if len(state.req.generated) == 1:
            # First completion only: a preempted request resumes through
            # the prefill queue with generated tokens already recorded —
            # its Nth token is not a TTFT sample (duplicate, oversized
            # observations would inflate the replica-scoring
            # percentiles).
            ttft_ms = (time.monotonic() - state.req.admit_t) * 1e3
            self.ttft_hist.observe(ttft_ms)
            telemetry.observe("serving_ttft_ms", ttft_ms)
        self._first_tokens.append((state.req.request_id,
                                   state.req.generated[-1]))
        if state.req.finished:
            self.worker.release(state)
            self._finished_staged.append(state.req.request_id)
            telemetry.inc("serving_requests_retired")
            self._rt.finish(rid, "retire",
                            generated=len(state.req.generated))
        else:
            self._parked.append(state)
            self._rt.begin("handoff-parked", rid)

    # ---- main loop -------------------------------------------------------
    def step(self) -> Dict[str, List]:
        """One coordinator round: sweep deadlines → reclaim staged
        aborts → adopt parked prefills → decode step (decode sub-mesh) →
        budgeted prefill chunks (prefill sub-mesh). Event dict matches
        the plain engine's contract."""
        self._first_tokens: List = []
        self._finished_staged: List[int] = []
        expired = self.expire_overdue()
        events = {"admitted": [], "tokens": [], "finished": [],
                  "preempted": [], "expired": expired}
        self._sweep_staged(events)
        self._adopt_parked(events)
        self._start_prefills(events)

        decode_active = any(
            r is not None and not r.finished for r in self.engine.slots)
        if not decode_active:
            # Idle gap: a stale timestamp would charge the whole gap to
            # the first post-idle decode interval and poison worst/
            # attainment — intervals only measure back-to-back decodes.
            self._last_decode_t = None
        if decode_active or self.engine.waiting:
            t0 = time.monotonic()
            if decode_active and self._last_decode_t is not None:
                interval = t0 - self._last_decode_t
                self.slo_stats["decode_intervals"] += 1
                self.slo_stats["worst_interval_ms"] = max(
                    self.slo_stats["worst_interval_ms"], interval * 1e3)
                self.interval_hist.observe(interval * 1e3)
                if (self.decode_slo_s is None
                        or interval <= self.decode_slo_s):
                    self.slo_stats["attained"] += 1
            ev = self.engine.step()
            if decode_active:
                self._last_decode_t = time.monotonic()
            for key in ("tokens", "finished", "preempted", "expired"):
                events[key] += ev[key]
            # Decode-side preemptions re-enter through the PREFILL queue
            # (they re-prefill prompt+generated on the prefill mesh,
            # usually re-hitting their own cached blocks) — the decode
            # mesh never runs a prefill.
            for rid in ev["preempted"]:
                req = self.requests.get(rid)
                if req is not None and req in self.engine.waiting:
                    try:
                        self.engine.waiting.remove(req)
                    except ValueError:
                        continue
                    # (queued_t was already stamped by the engine's
                    # _preempt; the move between queues is instant.)
                    self.waiting.append(req)
        t_decode_done = time.monotonic()

        self._prefill_budget_chunks(t_decode_done, decode_active)

        events["tokens"] += self._first_tokens
        events["finished"] += self._finished_staged
        events["finished"] += [r.request_id for r in self._aborted]
        self._aborted = []
        return events

    def run_to_completion(self, token_callback=None
                          ) -> Dict[int, np.ndarray]:
        results: Dict[int, np.ndarray] = {}
        finished: Dict[int, Request] = {}
        while self.has_work:
            ev = self.step()
            if token_callback is not None:
                for rid, tok in ev["tokens"]:
                    token_callback(rid, tok)
            for rid in ev["finished"]:
                finished[rid] = self.requests[rid]
        for rid, req in finished.items():
            results[rid] = req.tokens
            self.requests.pop(rid, None)
        return results

    # ---- observability ---------------------------------------------------
    def reset_compilation(self):
        self.engine.reset_compilation()

    def stats_snapshot(self, include_dispatch: bool = False) -> Dict:
        """Engine snapshot + the disagg section: per-queue depths, SLO
        attainment (histogram-backed percentiles, ISSUE 12), handoff
        accounting (the /stats payload). include_dispatch forwards to
        the decode engine's launch counts (dispatch_stats) —
        the facade accepts the same kwarg as the plain engine, so the
        server no longer TypeError-falls-back to a dispatch-less
        snapshot."""
        out = self.engine.stats_snapshot(include_dispatch=include_dispatch)
        out["engine"] = "disagg"
        s = dict(self.slo_stats)
        n = s["decode_intervals"]
        ih, th = self.interval_hist, self.ttft_hist
        if n:
            # Percentiles estimated FROM the log-bucket histogram — the
            # fleet-scale signal the single worst-interval scalar could
            # not provide (worst_interval_ms stays for compatibility).
            s["interval_p50_ms"] = round(ih.percentile(50), 3)
            s["interval_p90_ms"] = round(ih.percentile(90), 3)
            s["interval_p99_ms"] = round(ih.percentile(99), 3)
        if th.count:
            s["ttft_p50_ms"] = round(th.percentile(50), 3)
            s["ttft_p99_ms"] = round(th.percentile(99), 3)
        out["disagg"] = {
            "prefill_devices": self.prefill_ctx.num_devices,
            "decode_devices": self.decode_ctx.num_devices,
            "tp": self.decode_ctx.tp,
            "queues": {
                "prefill_waiting": len(self.waiting),
                "prefill_inflight": len(self._inflight),
                "handoff_parked": len(self._parked),
                "decode_active": sum(
                    1 for r in self.engine.slots if r is not None),
            },
            "slo": {
                "decode_slo_ms": (None if self.decode_slo_s is None
                                  else self.decode_slo_s * 1e3),
                "attainment": (round(s["attained"] / n, 4) if n
                               else 1.0),
                **s,
            },
            "handoff": {
                "transfers": self.pool.stats["handoff_transfers"],
                # Actual transferred bytes (int8 rows + fp32 scales on a
                # quantized pool — ~(D+4)/2D of the bf16 rows), read off
                # the shipped arrays, never assumed from the param
                # dtype.
                "kv_shipped_bytes":
                    self.worker.stats["kv_shipped_bytes"],
                "kv_cache_dtype": self.pool.kv_cache_dtype,
                "dense_copies": 0,     # by construction: transfer_slot
            },
            "prefill_worker": dict(self.worker.stats),
        }
        return out

    def generate_text(self, prompts, max_new_tokens: int,
                      sampling: Optional[SamplingParams] = None,
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
