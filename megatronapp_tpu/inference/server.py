"""Text-generation server: REST /api + WebSocket per-token streaming.

Parity with /root/reference/megatron/inference/text_generation_server.py
(MegatronServer Flask PUT /api :487, InferenceWSServer/InferenceGenerate
:29-298 — the MegaScope inference-mode streaming contract) and
tools/run_text_generation_server.py. aiohttp replaces Flask+ws (both in one
event loop; generation runs in a worker thread so the loop stays live).

With a DynamicInferenceEngine (--engine dynamic), the server runs TRUE
continuous batching: every connection submits into one shared engine and
a single stepper thread (DynamicBatchingDriver) drives engine.step(), so
concurrent requests decode in the same batch instead of serializing
whole generations behind _gen_lock. Static/mamba engines keep the
serialized path (their caches are per-generation).

REST:  PUT /api  {"prompts": [...], "tokens_to_generate": N,
                  "temperature": f, "top_k": i, "top_p": f, "greedy": b}
       → {"text": [...], "segments": [...]}
       GET /stats → serving observability without log scraping: engine
       type, active batch size / waiting queue, paged-pool occupancy
       (blocks in use / free / evictable, prefix-cache hit rate,
       preemptions), and speculative-decoding acceptance rate +
       tokens/step (DynamicInferenceEngine.stats_snapshot).
WS:    /ws — client sends the same JSON; server streams
       {"type": "token", "step": i, "token": id, "text": str} per token
       then {"type": "done", "text": full}.

MegaScope inference mode (reference InferenceWSServer/InferenceGenerate,
text_generation_server.py:211-239): a WS request may add
"visualization" (FlagType→layers map), "compressor" {pixels, method} and
"disturbance" configs — the server then also streams per-token capture
payloads {update_type, site, layer_id, result} (same wire contract as
training mode) and attaches the top-20 candidate list (tik_result) to
each token message. Toggling captures re-traces the engine's jits —
the documented cost of dynamic reconfiguration under jit.
"""

from __future__ import annotations

import asyncio
import json
import threading
import time
from typing import Optional
from megatronapp_tpu.inference.dynamic_engine import DeadlineExceeded
from megatronapp_tpu.inference.engine import (
    SamplingParams, StaticInferenceEngine,
)
from megatronapp_tpu.trace.request_trace import (
    PhaseStats, get_request_tracer,
)
from megatronapp_tpu.utils import chaos
from megatronapp_tpu.utils import metrics as telemetry


class _ClientGone(Exception):
    """Raised inside the generation worker when the WS client vanished
    mid-stream (cooperative cancellation via the token callback)."""


class DynamicBatchingDriver:
    """One stepper thread drives a shared DynamicInferenceEngine for ALL
    server connections (continuous batching across clients).

    submit() is thread-safe and returns (request_id, done_event); the
    optional token_cb(rid, token) fires from the stepper thread for every
    generated token. cancel() aborts a request (waiting requests complete
    immediately; running ones retire on the next step, releasing their
    cache). The stepper is a daemon thread started on first submit and
    parks on a condition variable whenever the engine has no work.

    Self-healing (ISSUE 6): per-request deadlines (submit timeout_s —
    expired work is rejected at admission, overdue in-flight work is
    aborted by the engine's expiry sweep and surfaces DeadlineExceeded);
    a stepper watchdog (a failing engine.step broadcasts clean error
    frames, reclaims the pool via abort_all, counts a restart, and backs
    off exponentially on consecutive failures so a persistent fault
    can't spin the thread hot); GET /healthz reports liveness, restart
    count, and pool pressure.

    Rolling engine reload (ISSUE 9): `request_reload(params)` swaps the
    model weights WITHOUT dropping the in-flight batch — admission
    pauses, running requests drain to completion, the swap lands on an
    empty batch (both sub-meshes for a disaggregated engine), and the
    still-waiting queue is then admitted against the new weights. The
    returned event fires when the swap is done; /healthz counts
    `reloads`."""

    def __init__(self, engine, crash_backoff_base: float = 0.25,
                 crash_backoff_cap: float = 5.0):
        self.engine = engine
        self._cv = threading.Condition()
        self._subs = {}     # rid -> {"cb": fn|None, "done": Event}
        self._errors = {}   # rid -> Exception from a failed step
        self._thread = None
        self.max_active = 0   # high-water concurrently-active slots
        # Watchdog / restart accounting.
        self.restarts = 0             # step failures survived
        self.thread_restarts = 0      # stepper threads found dead
        self.consecutive_failures = 0
        self.deadline_expired = 0     # requests aborted past deadline
        self.crash_backoff_base = crash_backoff_base
        self.crash_backoff_cap = crash_backoff_cap
        # Rolling reload state: (params, done_event) or None.
        self._reload = None
        self.reloads = 0
        # Always on, like the engine's step_stats: what the token
        # callbacks and done events cost the stepper after each step.
        self.deliver_stats = PhaseStats(("deliver",))

    def _ensure_thread(self):
        if self._thread is None or not self._thread.is_alive():
            if self._thread is not None:
                # A dead stepper thread (BaseException escape) is a
                # restart-worthy event — account for it in /healthz.
                self.thread_restarts += 1
            self._thread = threading.Thread(
                target=self._loop, name="dynamic-engine-stepper",
                daemon=True)
            self._thread.start()

    def submit(self, prompt_ids, max_new_tokens, sampling, eod_id=None,
               token_cb=None, priority: int = 0,
               timeout_s: Optional[float] = None,
               adapter_id: Optional[str] = None,
               tenant: Optional[str] = None):
        """timeout_s: per-request deadline in seconds from now. Already-
        expired work (timeout_s <= 0) is rejected at admission with
        DeadlineExceeded — a clean error frame instead of queueing work
        the client has given up on.

        adapter_id/tenant: multi-tenant LoRA serving (ISSUE 19) —
        adapter_id picks the tenant's adapter from the engine's cache
        (unknown ids are rejected at submit), tenant labels per-tenant
        telemetry and composes the tenant's SLO class (TenantSLO on the
        engine, when configured) into (priority, deadline)."""
        deadline = None
        if timeout_s is not None:
            if timeout_s <= 0:
                self.deadline_expired += 1
                telemetry.inc("serving_deadline_expired")
                raise DeadlineExceeded(
                    "request deadline expired at admission "
                    f"(timeout_s={timeout_s})")
            deadline = time.monotonic() + timeout_s
        slo = getattr(self.engine, "tenant_slo", None)
        if slo is not None:
            priority, deadline = slo.compose(tenant, priority=priority,
                                             deadline_s=deadline)
        # Tenancy kwargs only when set: engines without the plumbing
        # (the disagg facade) keep their add_request signature.
        extra = {}
        if adapter_id is not None:
            extra["adapter_id"] = adapter_id
        if tenant is not None:
            extra["tenant"] = tenant
        with self._cv:
            rid = self.engine.add_request(prompt_ids, max_new_tokens,
                                          sampling, eod_id=eod_id,
                                          priority=priority,
                                          deadline_s=deadline, **extra)
            done = threading.Event()
            self._subs[rid] = {"cb": token_cb, "done": done}
            self._ensure_thread()
            self._cv.notify_all()
        return rid, done

    def request_reload(self, params) -> threading.Event:
        """Schedule a rolling params swap (checkpoint reload): pauses
        admission, lets running requests drain, swaps on the empty
        batch, then resumes admission for the waiting queue. Returns an
        event that fires once the new weights are live. Thread-safe; a
        second reload request before the first lands supersedes its
        params, and BOTH events fire when the (latest) swap lands — a
        superseded waiter must not block forever.

        Fleet engines (inference/fleet.FleetRouter) own a BETTER reload
        than the generic drain-the-whole-engine machinery: replicas
        drain and swap ONE AT A TIME inside their step loop, so fleet
        admission never pauses and zero requests drop — the driver
        delegates to `begin_rolling_reload` and only keeps the stepper
        awake (reload accounting lives in the fleet's own snapshot)."""
        if hasattr(self.engine, "begin_rolling_reload"):
            done = self.engine.begin_rolling_reload(params)
            with self._cv:
                self._ensure_thread()
                self._cv.notify_all()
            return done
        done = threading.Event()
        with self._cv:
            waiters = ([done] if self._reload is None
                       else self._reload[1] + [done])
            self._reload = (params, waiters)
            self._ensure_thread()
            self._cv.notify_all()
        return done

    def _maybe_reload_locked(self):
        """Advance the rolling reload state machine (caller holds _cv):
        pause admission while a reload is pending; perform the swap the
        moment the engine is drained of RUNNING work (waiting requests
        keep their queue position and decode on the new weights)."""
        if self._reload is None:
            return
        self.engine.pause_admission = True
        drained = (self.engine.drained_for_reload()
                   if hasattr(self.engine, "drained_for_reload")
                   else all(r is None for r in self.engine.slots))
        if not drained:
            return
        params, waiters = self._reload
        try:
            self.engine.set_params(params)
        finally:
            self.engine.pause_admission = False
            self._reload = None
        self.reloads += 1
        for done in waiters:
            done.set()

    def cancel(self, rid):
        with self._cv:
            state = self.engine.abort_request(rid)
            if state == "waiting":
                # Never ran: no finish event will fire — complete here.
                self.engine.pop_request(rid)
                sub = self._subs.pop(rid, None)
                if sub:
                    sub["done"].set()

    def result_tokens(self, rid):
        """Full token array of a finished request (pops it). Raises the
        stepper-side error if the request's step failed."""
        err = self._errors.pop(rid, None)
        if err is not None:
            # The request is dead either way: drop its engine-side
            # record too. The step-failure path already popped it via
            # abort_all (pop is a no-op then), but deadline-expired
            # requests are only RETIRED by the step — without this pop
            # every expiry would leak one Request in engine.requests.
            self.engine.pop_request(rid)
            raise err
        req = self.engine.pop_request(rid)
        return None if req is None else req.tokens

    def _loop(self):
        while True:
            with self._cv:
                while not (self.engine.has_work or
                           self._reload is not None):
                    self._cv.wait()
                self._maybe_reload_locked()
                if not self.engine.has_work:
                    continue
            try:
                chaos.fire("stepper-step")
                ev = self.engine.step()
                self.consecutive_failures = 0
            except Exception as e:  # noqa: BLE001 — broadcast & reset
                self.restarts += 1
                self.consecutive_failures += 1
                telemetry.inc("serving_step_failures")
                with self._cv:
                    for rid, sub in self._subs.items():
                        self._errors[rid] = e
                        sub["done"].set()
                    self._subs.clear()
                    # Drop ALL queued/running work: the engine state is
                    # suspect, and leaving occupied slots would spin this
                    # loop on the same exception forever. abort_all
                    # releases paged pool blocks too — clearing slots by
                    # hand would leak them and poison every later admit.
                    self.engine.abort_all()
                # Crash-loop backoff: repeated step failures (a wedged
                # compile cache, a persistent device fault) sleep
                # exponentially instead of spinning hot; one success
                # resets the clock.
                time.sleep(min(self.crash_backoff_cap,
                               self.crash_backoff_base *
                               2 ** (self.consecutive_failures - 1)))
                continue
            self.max_active = max(self.max_active, sum(
                1 for r in self.engine.slots if r is not None))
            with get_request_tracer().span(
                    "driver.deliver", stats=self.deliver_stats), self._cv:
                # Deadline-expired requests get a clean error frame
                # BEFORE the generic finished handling pops their sub
                # (their pool blocks were reclaimed by the step's retire
                # pass).
                # (the engine's expiry sweep already counted these into
                # the telemetry registry — only driver bookkeeping here)
                for rid in ev.get("expired", ()):
                    if rid in self._subs:
                        self.deadline_expired += 1
                        self._errors[rid] = DeadlineExceeded(
                            f"request {rid} aborted: deadline exceeded")
                for rid, tok in ev["tokens"]:
                    sub = self._subs.get(rid)
                    if sub and sub["cb"] is not None:
                        try:
                            sub["cb"](rid, int(tok))
                        except Exception:  # noqa: BLE001 — dead sink
                            sub["cb"] = None
                for rid in ev["finished"]:
                    sub = self._subs.pop(rid, None)
                    if sub:
                        sub["done"].set()

    def stats(self) -> dict:
        """Stepper health for GET /healthz."""
        return {
            "started": self._thread is not None,
            "alive": self._thread is not None and self._thread.is_alive(),
            "restarts": self.restarts,
            "thread_restarts": self.thread_restarts,
            "consecutive_failures": self.consecutive_failures,
            "deadline_expired": self.deadline_expired,
            "subscribers": len(self._subs),
            "max_active": self.max_active,
            "reloads": self.reloads,
            "deliver": self.deliver_stats.snapshot()["deliver"],
            "reload_pending": (self._reload is not None
                               or getattr(self.engine, "reload_pending",
                                          False)),
        }



def _sampling_from_request(req: dict) -> SamplingParams:
    return SamplingParams(
        temperature=float(req.get("temperature", 1.0)),
        top_k=int(req.get("top_k", 0)),
        top_p=float(req.get("top_p", 0.0)),
        greedy=bool(req.get("greedy", False)),
        seed=int(req.get("random_seed", 0)),
    )


class TextGenerationServer:
    def __init__(self, engine: StaticInferenceEngine, host="0.0.0.0",
                 port=5000):
        self.engine = engine
        self.host = host
        self.port = port
        # One generation at a time (static/mamba engines): the engine,
        # capture hooks, and disturbance are process-global, and viz
        # requests re-trace the engine's jits — concurrent generations
        # would cross-contaminate (the reference server serializes with a
        # lock too, text_generation_server.py MegatronServer).
        self._gen_lock = threading.Lock()
        # Continuous batching for DynamicInferenceEngine (and the
        # disaggregated coordinator, which exposes the same stepping
        # surface): connections share one engine through a single
        # stepper thread.
        from megatronapp_tpu.inference.disagg import DisaggServingEngine
        from megatronapp_tpu.inference.dynamic_engine import (
            DynamicInferenceEngine,
        )
        from megatronapp_tpu.inference.fleet import FleetRouter
        from megatronapp_tpu.inference.fleet_rpc import ProcessFleetRouter
        self._driver = (DynamicBatchingDriver(engine)
                        if isinstance(engine, (DynamicInferenceEngine,
                                               DisaggServingEngine,
                                               FleetRouter,
                                               ProcessFleetRouter))
                        else None)

    # ------------------------------------------------------------------
    def _submit_and_wait(self, prompts, n, sampling,
                         cancel: Optional[threading.Event] = None,
                         token_cb=None, timeout_s: Optional[float] = None,
                         adapter_id: Optional[str] = None,
                         tenant: Optional[str] = None):
        """Driver path (dynamic engine): submit every prompt into the
        shared batch, wait for completion, detokenize. token_cb(rid, tok)
        streams tokens of the FIRST prompt (WS contract). timeout_s:
        per-request deadline (expired work is rejected/aborted with a
        clean error surfaced through the normal error paths).
        adapter_id/tenant: multi-tenant LoRA fields forwarded to
        submit() (ISSUE 19)."""
        import numpy as np
        tok = self.engine.tokenizer
        assert tok is not None, "tokenizer required"
        eod = getattr(tok, "eod", None)
        subs = []
        for i, prompt in enumerate(prompts):
            ids = np.asarray(tok.tokenize(prompt), np.int32)
            rid, done = self._driver.submit(
                ids, n, sampling, eod_id=eod,
                token_cb=token_cb if i == 0 else None,
                timeout_s=timeout_s, adapter_id=adapter_id,
                tenant=tenant)
            subs.append((ids, rid, done))
        texts = []
        first_err = None
        for ids, rid, done in subs:
            while not done.wait(timeout=0.1):
                if cancel is not None and cancel.is_set():
                    self._driver.cancel(rid)
                    done.wait(timeout=60)   # retires on the next step
                    break
            try:
                toks = self._driver.result_tokens(rid)
            except Exception as e:  # noqa: BLE001 — re-raised after drain
                # Drain EVERY rid before surfacing the error: bailing on
                # the first failed prompt would leave the later prompts'
                # results/errors in the driver and engine forever (each
                # timed-out multi-prompt call would leak them all).
                if first_err is None:
                    first_err = e
                continue
            if cancel is not None and cancel.is_set():
                raise _ClientGone()
            new_ids = [] if toks is None else toks[len(ids):].tolist()
            if eod is not None and eod in new_ids:
                new_ids = new_ids[: new_ids.index(eod)]
            texts.append(tok.detokenize(new_ids))
        if first_err is not None:
            raise first_err
        return texts

    # ------------------------------------------------------------------
    async def handle_api(self, request):
        from aiohttp import web
        try:
            req = await request.json()
            prompts = req["prompts"]
            n = int(req.get("tokens_to_generate", 64))
            sampling = _sampling_from_request(req)
            timeout_s = req.get("timeout_s")
            timeout_s = None if timeout_s is None else float(timeout_s)
            adapter_id = req.get("adapter_id")
            tenant = req.get("tenant")
            loop = asyncio.get_running_loop()

            def run_api():
                if self._driver is not None:
                    # Continuous batching: concurrent /api calls share
                    # the decode batch instead of queueing on the lock.
                    return self._submit_and_wait(prompts, n, sampling,
                                                 timeout_s=timeout_s,
                                                 adapter_id=adapter_id,
                                                 tenant=tenant)
                with self._gen_lock:
                    return self.engine.generate_text(prompts, n, sampling)

            texts = await loop.run_in_executor(None, run_api)
            return web.json_response({
                "text": [p + t for p, t in zip(prompts, texts)],
                "segments": texts,
            })
        except Exception as e:  # parity: reference returns 400 with message
            return web.json_response({"message": str(e)}, status=400)

    async def handle_ws(self, request):
        from aiohttp import web
        ws = web.WebSocketResponse()
        await ws.prepare(request)
        loop = asyncio.get_running_loop()
        # One persistent receive task doubles as the mid-generation
        # disconnect watcher: cancelling a ws.receive() mid-flight can
        # drop frames, so the SAME pending task is awaited between
        # requests and select()-ed against the payload queue during one.
        # TEXT frames that arrive mid-generation are buffered in
        # `pending` and served in order once the current one finishes
        # (sequential pipelining, matching the old async-for semantics).
        # Bounded: each buffered request later holds _gen_lock serially,
        # so an unbounded queue lets one client grow memory and head-of-
        # line latency without limit. Past the cap the socket is closed
        # with a policy-violation code (client should await replies).
        MAX_PENDING = 32
        import collections
        pending: collections.deque = collections.deque()
        recv_task = asyncio.ensure_future(ws.receive())
        while True:
            if len(pending) > MAX_PENDING:
                await ws.close(
                    code=1008,
                    message=b"too many pipelined requests; await replies")
                break
            if pending:
                msg = pending.popleft()
            else:
                msg = await recv_task
                if msg.type == 1:
                    recv_task = asyncio.ensure_future(ws.receive())
            if msg.type != 1:  # not TEXT → close/closing/error: done
                break
            req = json.loads(msg.data)
            prompts = req.get("prompts") or [req.get("prompt", "")]
            n = int(req.get("tokens_to_generate", 64))
            sampling = _sampling_from_request(req)
            viz = req.get("visualization")
            if viz and self._driver is not None:
                await ws.send_json({
                    "type": "error",
                    "message": "visualization requires --engine static "
                               "(the continuous-batching backend shares "
                               "one step loop across connections)"})
                continue
            queue: asyncio.Queue = asyncio.Queue()
            # Client-gone cancellation: a disconnect mid-stream must not
            # leave the generation running to completion while holding
            # _gen_lock (round-2 advisor finding) — the per-token
            # callback aborts the executor job at the next token.
            cancel = threading.Event()

            def cb(step, tokens, logits):
                if cancel.is_set():
                    raise _ClientGone()
                payload = {
                    "type": "token", "step": int(step),
                    "token": int(tokens[0]),
                    "text": (self.engine.tokenizer.detokenize(
                        [int(tokens[0])]) if self.engine.tokenizer
                        else ""),
                }
                if viz and logits is not None:
                    # Reference tik_result: sampled token + top-20
                    # candidates with decoded text.
                    from megatronapp_tpu.scope.tensor_tracer import (
                        get_tensor_tracer,
                    )
                    payload["candidates"] = get_tensor_tracer(
                    ).report_result(logits[0], int(tokens[0]),
                                    self.engine.tokenizer)["candidates"]
                loop.call_soon_threadsafe(queue.put_nowait, payload)

            def run_generation():
                if self._driver is not None:
                    # Dynamic engine: stream through the shared stepper
                    # (no lock — other connections keep decoding in the
                    # same batch). The driver callback must never raise
                    # in the stepper thread; disconnects abort via
                    # driver.cancel inside _submit_and_wait.
                    state = {"step": 0}

                    def driver_cb(rid, token):
                        if cancel.is_set():
                            return
                        payload = {
                            "type": "token", "step": state["step"],
                            "token": int(token),
                            "text": (self.engine.tokenizer.detokenize(
                                [int(token)]) if self.engine.tokenizer
                                else ""),
                        }
                        state["step"] += 1
                        loop.call_soon_threadsafe(queue.put_nowait,
                                                  payload)

                    return self._submit_and_wait(
                        prompts[:1], n, sampling, cancel=cancel,
                        token_cb=driver_cb,
                        timeout_s=(float(req["timeout_s"])
                                   if req.get("timeout_s") is not None
                                   else None),
                        adapter_id=req.get("adapter_id"),
                        tenant=req.get("tenant"))
                # Capture hooks are thread-local and baked in at trace
                # time: activate in THIS worker thread and re-trace the
                # engine around the toggle. The lock serializes against
                # every other generation (shared engine/global hooks).
                with self._gen_lock:
                    if not viz:
                        return self.engine.generate_text(
                            prompts[:1], n, sampling, token_callback=cb)
                    import jax

                    from megatronapp_tpu.scope.disturbance import (
                        get_disturbance,
                    )
                    from megatronapp_tpu.scope.hooks import (
                        capture_payload,
                    )
                    from megatronapp_tpu.scope.tensor_tracer import (
                        get_tensor_tracer,
                    )
                    comp = req.get("compressor") or {}
                    tt = get_tensor_tracer()

                    def report(site, layer_id, arr):
                        loop.call_soon_threadsafe(
                            queue.put_nowait,
                            capture_payload(site, layer_id, arr))

                    # Config application sits INSIDE the try: a malformed
                    # client config must not leave hooks globally active.
                    try:
                        tt.set_flags_from_config(viz)
                        tt.activate(report,
                                    pixels=int(comp.get("pixels", 16)),
                                    method=comp.get("method", "mean"))
                        if req.get("disturbance") is not None:
                            get_disturbance().configure(
                                req["disturbance"],
                                seed=int(req.get("random_seed", 0)))
                        self.engine.reset_compilation()
                        return self.engine.generate_text(
                            prompts[:1], n, sampling, token_callback=cb)
                    finally:
                        jax.effects_barrier()
                        tt.deactivate()
                        tt.clear_records()
                        get_disturbance().clear()
                        self.engine.reset_compilation()

            fut = loop.run_in_executor(None, run_generation)
            # Sentinel-terminated drain: per-token callbacks enqueue via
            # call_soon_threadsafe BEFORE the executor job finishes, and
            # the done-callback fires on the loop after those are
            # scheduled, so FIFO order guarantees every payload precedes
            # the sentinel (no racy cancel of an in-flight queue.get).
            _DONE = object()
            fut.add_done_callback(lambda _: queue.put_nowait(_DONE))
            # Drain payloads while WATCHING the socket: a close frame (or
            # any mid-stream client traffic) must abort the in-flight
            # generation — the token callback raises _ClientGone at the
            # next token, releasing _gen_lock instead of running to
            # completion (round-2 advisor finding). A bare queue.get()
            # would never see the disconnect. recv_task is the
            # persistent watcher; on a mid-stream fire it stays
            # completed and the top of the outer loop consumes it.
            completed = False
            get_task = asyncio.ensure_future(queue.get())
            try:
                while True:
                    done, _ = await asyncio.wait(
                        {get_task, recv_task},
                        return_when=asyncio.FIRST_COMPLETED)
                    if recv_task in done:
                        m = recv_task.result()
                        if m.type == 1 and len(pending) < MAX_PENDING:
                            # Pipelined request: buffer it, keep
                            # streaming the current generation.
                            pending.append(m)
                            recv_task = asyncio.ensure_future(
                                ws.receive())
                            continue
                        if m.type == 1:
                            pending.append(m)  # outer loop closes 1008
                        break           # disconnect/flood → abort
                    payload = get_task.result()
                    if payload is _DONE:
                        completed = True
                        break
                    await ws.send_json(payload)
                    get_task = asyncio.ensure_future(queue.get())
            except (ConnectionResetError, RuntimeError):
                pass                    # TCP reset mid-send → abort
            finally:
                if not completed:
                    cancel.set()
                if not get_task.done():
                    get_task.cancel()   # queue.get cancel is loss-free
            if not completed:
                try:
                    await fut      # worker aborts at the next token
                except _ClientGone:
                    pass
                except Exception:  # noqa: BLE001 — client already gone
                    pass
                continue           # outer loop handles the fired recv
            try:
                texts = fut.result()
            except _ClientGone:
                continue
            except Exception as e:
                # Client-input-driven failures (bad flag names, malformed
                # disturbance configs) surface as an error frame, matching
                # the REST handler's 400-with-message behavior.
                await ws.send_json({"type": "error", "message": str(e)})
                continue
            await ws.send_json({"type": "done", "text": texts[0]})
        if not recv_task.done():
            recv_task.cancel()     # connection is closing anyway
        return ws

    # ------------------------------------------------------------------
    def stats_snapshot(self) -> dict:
        """Serving stats for GET /stats. Dynamic engines report their
        full snapshot (pool / speculation / batch occupancy — plus the
        traced decode step's launch counts: /stats opts into
        include_dispatch, whose first call traces the step once and is
        cached after, and nothing is compiled; /healthz keeps the
        snapshot without it); static and mamba engines report what
        exists for them."""
        eng = self.engine
        if hasattr(eng, "stats_snapshot"):
            # Both the plain engine and the disagg facade accept
            # include_dispatch (ISSUE 12 satellite: the facade used to
            # TypeError here, silently dropping dispatch stats).
            out = eng.stats_snapshot(include_dispatch=True)
        else:
            out = {"engine": type(eng).__name__.replace(
                "InferenceEngine", "").lower()}
        if self._driver is not None:
            out["driver_max_active"] = self._driver.max_active
            out["driver_deliver"] = \
                self._driver.deliver_stats.snapshot()["deliver"]
        return out

    async def handle_stats(self, request):
        from aiohttp import web
        return web.json_response(self.stats_snapshot())

    # ------------------------------------------------------------------
    def health_snapshot(self) -> dict:
        """GET /healthz payload: stepper liveness + restart accounting
        (DynamicBatchingDriver watchdog) and pool pressure, so an
        external orchestrator can probe the server without scraping
        logs. status: 'ok' (healthy / static engine), 'degraded'
        (stepper currently failing steps but self-healing), 'unhealthy'
        (stepper thread dead — probe should restart the server)."""
        out = {"status": "ok",
               "engine": type(self.engine).__name__.replace(
                   "InferenceEngine", "").lower()}
        if self._driver is not None:
            st = self._driver.stats()
            out["stepper"] = st
            out["restarts"] = st["restarts"] + st["thread_restarts"]
            eng = self.engine
            out["active"] = sum(1 for r in eng.slots if r is not None)
            out["waiting"] = len(eng.waiting)
            snap = (eng.stats_snapshot()
                    if hasattr(eng, "stats_snapshot") else {})
            if "disagg" in snap:
                # Per-queue depth + SLO attainment ride into /healthz so
                # an orchestrator can rotate on SLO pressure without
                # scraping /stats.
                out["disagg"] = {
                    "queues": snap["disagg"]["queues"],
                    "slo": snap["disagg"]["slo"],
                }
            if "fleet" in snap:
                # Aggregated fleet health: replica states + attainment
                # so an orchestrator sees a degraded fleet (dead
                # replica, reduced capacity) without scraping /stats.
                f = snap["fleet"]
                out["fleet"] = {
                    "num_replicas": f["num_replicas"],
                    "live_replicas": f["live_replicas"],
                    "reload_pending": f["reload_pending"],
                    "migrations": f["migrations"],
                    "failovers": f["failovers"],
                    # Cross-process fleets (inference/fleet_rpc.py)
                    # report supervisor restart accounting; in-process
                    # fleets report 0 until their supervisor runs.
                    "supervisor_restarts": f.get(
                        "supervisor_restarts", 0),
                    "replicas": [
                        {k: r.get(k) for k in
                         ("idx", "state", "active", "waiting",
                          "attainment", "params_version")}
                        for r in f["replicas"]],
                }
                if f["live_replicas"] < f["num_replicas"]:
                    out["status"] = "degraded"
            pool_stats = snap.get("pool")
            if pool_stats is not None:
                # One source of truth for the pool fields (the engine's
                # /stats payload); only the pressure ratio is derived
                # here.
                pool_stats["pressure"] = round(
                    pool_stats["blocks_in_use"] / pool_stats["num_blocks"],
                    4)
                out["pool"] = pool_stats
            if st["started"] and not st["alive"]:
                out["status"] = "unhealthy"
            elif st["consecutive_failures"] > 0 and self.engine.has_work:
                # Degraded = actively struggling. After a crash drains
                # the queue (abort_all) the stepper is parked with
                # nothing to fail on — an idle server must not stay
                # 'degraded' forever and get pulled from rotation; the
                # restart counters still record that it happened.
                out["status"] = "degraded"
        return out

    async def handle_healthz(self, request):
        from aiohttp import web
        payload = self.health_snapshot()
        return web.json_response(
            payload, status=503 if payload["status"] == "unhealthy"
            else 200)

    # ------------------------------------------------------------------
    def _export_live_gauges(self):
        """Point-in-time gauges refreshed at scrape time (counters and
        histograms accumulate at the instrumented sites; queue depths
        and pool occupancy are state, not events)."""
        eng = self.engine
        if hasattr(eng, "slots"):
            telemetry.set_gauge("serving_active_slots", sum(
                1 for r in eng.slots if r is not None))
        if hasattr(eng, "waiting"):
            telemetry.set_gauge("serving_waiting", len(eng.waiting))
        pool = getattr(eng, "pool", None)
        if pool is not None:
            telemetry.set_gauge("paged_blocks_in_use",
                                pool.blocks_in_use())
            telemetry.set_gauge("paged_blocks_free", pool.free_blocks())
            telemetry.set_gauge("paged_blocks_evictable",
                                pool.evictable_blocks())
        adapters = getattr(eng, "adapters", None)
        if adapters is not None:
            # LoRA adapter cache occupancy: resident/pinned counts and
            # rank-exact resident bytes. Hit/miss/eviction COUNTERS
            # accumulate at the cache's instrumented sites.
            lstats = adapters.stats_snapshot()
            telemetry.set_gauge("lora_adapters_resident",
                                lstats["resident"])
            telemetry.set_gauge("lora_adapters_pinned", lstats["pinned"])
            telemetry.set_gauge("lora_resident_bytes",
                                adapters.resident_bytes())
        spill = getattr(eng, "spill", None)
        if spill is not None:
            # Host-RAM KV spill tier (ISSUE 20): occupancy is state
            # (parked sessions, exact resident bytes vs budget); the
            # park/unpark COUNTERS accumulate at the tier's
            # instrumented sites.
            sstats = spill.stats()
            telemetry.set_gauge("kv_spill_parked", sstats["parked"])
            telemetry.set_gauge("kv_spill_bytes_used",
                                sstats["bytes_used"])
            telemetry.set_gauge("kv_spill_budget_bytes",
                                sstats["budget_bytes"])
        store = getattr(eng, "prefix_store", None)
        if store is not None:
            # Fleet-global prefix store (ISSUE 20): entry count and
            # exact resident bytes; hit/miss/eviction counters
            # accumulate inside the store.
            pstats = store.stats()
            telemetry.set_gauge("fleet_prefix_store_entries",
                                pstats["entries"])
            telemetry.set_gauge("fleet_prefix_store_bytes",
                                pstats["bytes_used"])
            telemetry.set_gauge("fleet_prefix_store_hit_total",
                                pstats["hits"])
        tstats = getattr(eng, "_tenant_stats", None)
        if tstats:
            # Per-tenant SLO attainment gauges (bounded cardinality —
            # the engine folds tenants past its label cap into
            # "_other"); per-tenant request/token COUNTERS accumulate
            # at the engine's _tenant_inc sites.
            lab = telemetry.labeled
            for t, st in list(tstats.items()):
                closed = st["finished"] + st["expired"]
                telemetry.set_gauge(
                    lab("serving_tenant_slo_attainment", tenant=t),
                    round(st["finished"] / closed, 4) if closed else 1.0)
        if hasattr(eng, "export_fleet_gauges"):
            # Cross-process fleet (inference/fleet_rpc.py): the router
            # exports its own per-replica labeled gauges + supervisor
            # restart counts — the replica engines live in OTHER
            # processes, so their state is only reachable through the
            # router's last step replies. One scrape covers the fleet.
            eng.export_fleet_gauges(telemetry)
        reps = getattr(eng, "replicas", None)
        if reps is not None:
            # Per-replica labeled series (one metric family, N labeled
            # series — the fleet dashboard shape).
            lab = telemetry.labeled
            for rep in reps:
                r = str(rep.idx)
                telemetry.set_gauge(
                    lab("fleet_replica_up", replica=r),
                    int(rep.state != "dead"))
                telemetry.set_gauge(
                    lab("fleet_replica_attainment", replica=r),
                    round(rep.attainment(getattr(eng, "slo_ms", None)),
                          4))
                if rep.state == "dead":
                    # Zero the capacity series: frozen last-alive
                    # values would over-count live capacity on
                    # dashboards forever.
                    for g in ("fleet_replica_active_slots",
                              "fleet_replica_waiting",
                              "fleet_replica_blocks_in_use"):
                        telemetry.set_gauge(lab(g, replica=r), 0)
                    continue
                reng = rep.engine
                telemetry.set_gauge(
                    lab("fleet_replica_active_slots", replica=r),
                    sum(1 for s in reng.slots if s is not None))
                telemetry.set_gauge(
                    lab("fleet_replica_waiting", replica=r),
                    len(reng.waiting))
                telemetry.set_gauge(
                    lab("fleet_replica_blocks_in_use", replica=r),
                    reng.pool.blocks_in_use())
            sup = getattr(eng, "_supervisor", None)
            if sup is not None:
                # Same restart-accounting series the cross-process
                # router exports — kill/revive drills route through the
                # one Supervisor, so the counters exist in-process too.
                for idx, n in sup.restarts.items():
                    telemetry.set_gauge(
                        lab("fleet_supervisor_restarts",
                            replica=str(idx)), n)
                telemetry.set_gauge("fleet_supervisor_restarts_total",
                                    sup.total_restarts)
        if self._driver is not None:
            st = self._driver.stats()
            telemetry.set_gauge("serving_stepper_alive",
                                int(st["alive"]))
            telemetry.set_gauge("serving_stepper_restarts",
                                st["restarts"] + st["thread_restarts"])

    def metrics_text(self) -> str:
        """Prometheus text for GET /metrics (also the driver-side dump
        hook — callers can scrape without an HTTP round-trip)."""
        if telemetry.enabled():
            self._export_live_gauges()
        return telemetry.render_prometheus()

    async def handle_metrics(self, request):
        """GET /metrics: Prometheus text exposition of the telemetry
        registry (enable with --serving-metrics / MEGATRON_METRICS=1;
        a disabled registry serves a one-line comment, not a 404, so
        scrapers keep a stable target)."""
        from aiohttp import web
        return web.Response(text=self.metrics_text(),
                            content_type="text/plain")

    # ------------------------------------------------------------------
    def dump_request_trace(self, path: Optional[str] = None) -> dict:
        """Driver hook: render the request-trace ring as one merged
        Chrome trace (prefill + decode mesh rows); optionally write it
        to `path` for chrome://tracing / Perfetto. A process-backed
        fleet merges every replica worker's ring over RPC into the
        same trace (one pid row per process)."""
        if hasattr(self.engine, "merged_trace"):
            trace = self.engine.merged_trace()
        else:
            trace = get_request_tracer().chrome_trace()
        if path is not None:
            with open(path, "w") as f:
                json.dump(trace, f)
        return trace

    async def handle_trace(self, request):
        """GET /trace: the per-request lifecycle ring as a Chrome trace
        JSON (enable with --request-trace / MEGATRON_REQUEST_TRACE=1).
        Server-side file dumps go through the dump_request_trace driver
        hook — a client-supplied path here would be an arbitrary-file-
        write primitive on an unauthenticated endpoint."""
        from aiohttp import web
        rt = get_request_tracer()
        if not rt.enabled:
            return web.json_response(
                {"message": "request tracing disabled — enable with "
                            "--request-trace or MEGATRON_REQUEST_TRACE=1"},
                status=404)
        return web.json_response(self.dump_request_trace())

    # ------------------------------------------------------------------
    def build_app(self):
        from aiohttp import web
        app = web.Application()
        app.router.add_put("/api", self.handle_api)
        app.router.add_post("/api", self.handle_api)
        app.router.add_get("/stats", self.handle_stats)
        app.router.add_get("/healthz", self.handle_healthz)
        app.router.add_get("/metrics", self.handle_metrics)
        app.router.add_get("/trace", self.handle_trace)
        app.router.add_get("/ws", self.handle_ws)
        return app

    def run(self):
        from aiohttp import web
        web.run_app(self.build_app(), host=self.host, port=self.port)
