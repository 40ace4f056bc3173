"""MegaScan-TPU tracer: operator/phase-granularity event collection.

Parity with /root/reference/megatron/training/trace.py:242-617 (Tracer:
scoped B/E/i records, iteration windows, bandwidth attrs, rank gather) —
re-designed for TPU/XLA:

- CUDA events don't exist on TPU; instead we combine
  (a) host wall-clock scopes around dispatched work (schedule phases:
      forward/backward per microbatch, optimizer, data),
  (b) in-graph markers via ``io_callback(ordered=True)`` that timestamp the
      moment the running XLA program reaches a point — the TPU analogue of a
      CUDA event record, and
  (c) a per-iteration ``block_until_ready`` calibration fence, mirroring the
      reference's torch.cuda.synchronize at iteration_end
      (trace.py:385-411).
- Interval windows: trace only iterations where
  (iter - 1) % interval < continuous_iterations (trace.py:594-614).
- Records are Chrome-trace-style dicts {name, ph, ts(ns), pid, tid, args};
  per-process JSON files are merged by trace/aggregate.py exactly like the
  reference's per-rank files (scripts/aggregate.py).
"""

from __future__ import annotations

import contextlib
import json
import os
import threading
import time
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

# Granularity sets (reference trace.py:75-132): 'full' records everything,
# 'schedule' only phase events, 'collective' adds comm ops.
GRANULARITY_EVENTS = {
    "schedule": {
        "train-step", "forward", "backward", "optimizer", "loss",
        "allreduce", "grad-sync", "data", "recv-warmup", "send-forward",
        "recv-forward", "send-backward", "recv-backward", "exchange-next",
        "exchange-prev", "checkpoint",
    },
    "collective": {
        "all-reduce", "all-gather", "reduce-scatter", "collective-permute",
        "all-to-all", "tp-overlap-compute", "tp-overlap-permute",
        "cp-overlap-compute", "cp-overlap-permute",
        "moe-a2a-compute", "moe-a2a-permute", "pp-overlap-permute",
    },
}


def _now_ns() -> int:
    return time.perf_counter_ns()


_CALLBACKS_SUPPORTED: Optional[bool] = None


def callbacks_supported() -> bool:
    """Whether the backend supports host callbacks (io_callback).

    The standard PJRT TPU and CPU backends do. On a backend that does not
    (UNIMPLEMENTED: host send/recv callbacks) the tracer degrades to
    host-side scopes (train-step/iteration spans) — the schedule-phase
    spans need callbacks.
    """
    global _CALLBACKS_SUPPORTED
    if _CALLBACKS_SUPPORTED is None:
        from jax.experimental import io_callback

        def probe(x):
            tok = io_callback(lambda _: np.zeros((), np.int32),
                              jax.ShapeDtypeStruct((), np.int32), x)
            return x + tok
        try:
            jax.device_get(jax.jit(probe)(np.int32(0)))
            _CALLBACKS_SUPPORTED = True
        except jax.errors.JaxRuntimeError as e:
            # The one error this probe is for; anything else is a bug and
            # is not taken for a backend's answer.
            if "UNIMPLEMENTED" not in str(e):
                raise
            _CALLBACKS_SUPPORTED = False
    return _CALLBACKS_SUPPORTED


class Tracer:
    """Singleton tracer (reference get_tracer via global_vars.py)."""

    def __init__(self):
        self.enabled = False
        self.interval = 5
        self.continuous_iterations = 2
        self.trace_dir = "trace"
        self.granularity = "full"
        self.process_index = 0
        self.mesh_ctx = None
        self._records: List[Dict[str, Any]] = []
        self._iteration = -1
        self._iter_t0 = 0
        self.active = False
        self._lock = threading.Lock()
        self._scope_stack: List[str] = []
        self._save_lock = threading.Lock()
        self._saver_threads: List[threading.Thread] = []
        self._overhead_ns = 0

    # -- configuration ----------------------------------------------------
    def configure(self, enabled: bool = True, trace_dir: str = "trace",
                  interval: int = 5, continuous_iterations: int = 2,
                  granularity: str = "full", mesh_ctx=None):
        self.enabled = enabled
        self.trace_dir = trace_dir
        self.interval = max(interval, 1)
        self.continuous_iterations = max(continuous_iterations, 1)
        self.granularity = granularity
        self.mesh_ctx = mesh_ctx
        self.process_index = jax.process_index()
        if enabled:
            os.makedirs(trace_dir, exist_ok=True)

    def _window_active(self, iteration: int) -> bool:
        # Reference interval predicate (trace.py:594-614), 0-indexed iters.
        return iteration % self.interval < self.continuous_iterations

    # -- iteration lifecycle ----------------------------------------------
    def iteration_begin(self, iteration: int):
        if not self.enabled:
            return
        self.active = self._window_active(iteration)
        if not self.active:
            return
        self._iteration = iteration
        self._iter_t0 = _now_ns()
        self._emit("iteration", "B", 0, {"iteration": iteration})

    def iteration_end(self, iteration: int, fence: Any = None):
        if not self.enabled or not self.active:
            return
        # Calibration fence — analogous to torch.cuda.synchronize before
        # resolving events (reference trace.py iteration_end).
        if fence is not None:
            jax.block_until_ready(fence)
        self._emit("iteration", "E", _now_ns() - self._iter_t0, {})
        self.active = False

    # -- scopes ------------------------------------------------------------
    def _allowed(self, name: str) -> bool:
        if self.granularity == "full":
            return True
        allowed = GRANULARITY_EVENTS.get(self.granularity, set())
        return name in allowed or name in GRANULARITY_EVENTS["schedule"]

    @contextlib.contextmanager
    def scope(self, name: str, **attrs):
        if not (self.enabled and self.active and self._allowed(name)):
            yield self
            return
        t0 = _now_ns()
        self._emit(name, "B", t0 - self._iter_t0, attrs)
        self._scope_stack.append(name)
        try:
            yield self
        finally:
            self._scope_stack.pop()
            self._emit(name, "E", _now_ns() - self._iter_t0, attrs)

    def instant(self, name: str, **attrs):
        if self.enabled and self.active and self._allowed(name):
            self._emit(name, "i", _now_ns() - self._iter_t0, attrs)

    def set_attr(self, **attrs):
        """Attach attrs to the innermost open scope's B record (reference
        tracers.set / set_group, trace.py:499-526)."""
        if not (self.enabled and self.active and self._scope_stack):
            return
        target = self._scope_stack[-1]
        with self._lock:
            for rec in reversed(self._records):
                if rec["name"] == target and rec["ph"] == "B":
                    rec["args"].update(attrs)
                    break

    # -- in-graph phase spans ----------------------------------------------
    def phase_event(self, name: str, ph: str, tid: int = 0, **attrs):
        """Host-side record emission used by in-graph callbacks.

        tid: per-process timeline; 0 is the host-scope timeline, the
        tp-overlap ring spans use tid = tp_rank + 1 (parallel/overlap.py)
        so per-rank B/E pairs nest cleanly in the merged trace."""
        if self.enabled and self.active:
            self._emit(name, ph, _now_ns() - self._iter_t0, attrs, tid=tid)

    # -- in-graph markers ---------------------------------------------------
    def marker(self, name: str, x, **attrs):
        """In-graph event marker: identity on x, records host time when the
        XLA program reaches this point (ordered io_callback) — the TPU
        analogue of torch.cuda.Event. Safe under jit; no-op python-side when
        tracing disabled at trace time."""
        if not self.enabled:
            return x
        from jax.experimental import io_callback

        def _cb(_):
            if self.active:
                self._emit(name, "i", _now_ns() - self._iter_t0,
                           dict(attrs, marker=True))
            return np.zeros((), np.int32)

        token = io_callback(_cb, jax.ShapeDtypeStruct((), np.int32),
                            np.zeros((), np.int32), ordered=True)
        # Tie the callback into the data flow so XLA cannot reorder it away.
        first = jax.tree.leaves(x)[0]
        anchored = first + token.astype(first.dtype) * 0
        leaves = jax.tree.leaves(x)
        leaves[0] = anchored
        return jax.tree.unflatten(jax.tree.structure(x), leaves)

    # -- record handling -----------------------------------------------------
    def _emit(self, name: str, ph: str, ts_ns: int, args: Dict[str, Any],
              tid: int = 0):
        rec = {
            "name": name, "ph": ph, "ts": ts_ns / 1e3,  # Chrome trace: µs
            "pid": self.process_index,
            "tid": tid,
            "iteration": self._iteration,
            "args": dict(args),
        }
        if "data" in args:
            rec["args"]["bytes"] = int(args["data"])
        with self._lock:
            self._records.append(rec)

    def now_in_iteration_us(self) -> float:
        """Current offset inside the open iteration window (µs)."""
        return (_now_ns() - self._iter_t0) / 1e3

    def add_collective_records(self, events: List[Dict[str, Any]],
                               offset_us: Optional[float] = None):
        """Merge profiler-derived collective events
        (trace/profiler_collectives.py; per-device pids already disjoint
        from process pids) into this iteration's records.

        offset_us anchors the capture inside the iteration window — pass
        the value of now_in_iteration_us() taken BEFORE the profiled
        execution, so events land where the collectives ran rather than
        after the (per-process, variable) profile parsing delay that
        would skew cross-process stage-2 comparisons."""
        if not (self.enabled and self.active and events):
            return
        base = min(e["ts"] for e in events)
        if offset_us is None:
            offset_us = self.now_in_iteration_us()
        recs = []
        for e in events:
            recs.append({
                "name": e["name"], "ph": "X",
                "ts": e["ts"] - base + offset_us,
                "dur": e.get("dur", 0.0),
                "pid": e["pid"],
                "tid": e.get("tid", 0),
                "iteration": self._iteration,
                "args": dict(e.get("args", {}),
                             iteration=self._iteration),
            })
        with self._lock:
            self._records.extend(recs)

    def drain(self) -> List[Dict[str, Any]]:
        with self._lock:
            recs, self._records = self._records, []
        return recs

    def peek(self) -> List[Dict[str, Any]]:
        """Non-destructive snapshot of the buffered records: the
        trace-driven pipeline planner (parallel/schedule.Planner) reads
        the per-stage ring-hop spans of a traced iteration BEFORE save()
        drains them to disk."""
        with self._lock:
            return list(self._records)

    def save(self, path: Optional[str] = None):
        """Append records to the per-process trace file (reference background
        saver thread, trace.py:136-193; file naming parity with
        benchmark-data-*.json)."""
        recs = self.drain()
        if not recs:
            return
        ctx = self.mesh_ctx
        if ctx is not None:
            fname = (f"benchmark-data-{ctx.dp}-pipeline-{ctx.pp}"
                     f"-tensor-{ctx.tp}-process-{self.process_index}.json")
        else:
            fname = f"benchmark-data-process-{self.process_index}.json"
        path = path or os.path.join(self.trace_dir, fname)

        def _write():
            # _save_lock serializes concurrent save() calls so the
            # read-modify-write below cannot drop or corrupt records.
            with self._save_lock:
                existing = []
                if os.path.exists(path):
                    with open(path) as f:
                        try:
                            existing = json.load(f)
                        except json.JSONDecodeError:
                            existing = []
                existing.extend(recs)
                tmp = path + ".tmp"
                with open(tmp, "w") as f:
                    json.dump(existing, f)
                os.replace(tmp, path)

        t = threading.Thread(target=_write, daemon=True)
        t.start()
        self._saver_threads.append(t)

    def finalize(self):
        self.save()
        for t in self._saver_threads:
            t.join()
        self._saver_threads.clear()


_TRACER = Tracer()


def get_tracer() -> Tracer:
    return _TRACER


# ---------------------------------------------------------------------------
# In-graph schedule-phase spans (SURVEY §2.4: the schedule-phase events —
# forward/backward/loss/optimizer — whose emit sites the reference lost in
# its rebase and the detector depends on). A span is a custom-VJP identity:
# its forward emits the forward-phase record, and because cotangents traverse
# the graph in reverse, the SAME pair of spans around a forward region
# automatically emits a correctly-oriented 'backward' span during the
# backward pass — the TPU-native analogue of wrapping both fwd and bwd
# schedule phases with CUDA events.
# ---------------------------------------------------------------------------

def _phase_cb(name: str, ph: str):
    def cb(_):
        _TRACER.phase_event(name, ph)
        return np.zeros((), np.int32)
    return cb


def _emit_in_graph(x_anchor, name: str, ph: str):
    from jax.experimental import io_callback
    from jax.sharding import SingleDeviceSharding
    # Under SPMD partitioning a side-effecting callback may not be
    # replicated — pin it to one device (this process records one timeline,
    # like the reference's one-tracer-per-rank). ordered=True is not
    # SPMD-compatible (its ordering token stays replicated → partitioner
    # RET_CHECK); execution order is enforced by the data dependency on
    # x_anchor instead.
    token = io_callback(_phase_cb(name, ph),
                        jax.ShapeDtypeStruct((), np.int32),
                        x_anchor, ordered=False,
                        sharding=SingleDeviceSharding(jax.local_devices()[0]))
    return token


def _anchor_scalar(tree):
    leaf = jax.tree.leaves(tree)[0]
    return (jax.lax.stop_gradient(leaf).ravel()[0] * 0).astype(np.float32)


def _tie(tree, token):
    leaves = jax.tree.leaves(tree)
    first = leaves[0]
    leaves[0] = first + token.astype(first.dtype) * 0
    return jax.tree.unflatten(jax.tree.structure(tree), leaves)


def _make_span(fwd_ph: str, bwd_ph: str):
    def span(tree, fwd_name: str, bwd_name: Optional[str] = None):
        def _primal(t):
            # The primal body must ALSO emit: JAX uses the primal (not the
            # fwd rule) when the span is not on a differentiation path
            # (e.g. spans around the optimizer update).
            tok = _emit_in_graph(_anchor_scalar(t), fwd_name, fwd_ph)
            return _tie(t, tok)

        @jax.custom_vjp
        def f(t):
            return _primal(t)

        def fwd(t):
            return _primal(t), None

        def bwd(_, g):
            if bwd_name is not None:
                # Cotangent leaves can be float0 (int inputs); anchor on a
                # constant — ordering comes from surrounding data deps.
                tok = _emit_in_graph(jnp.zeros((), jnp.float32),
                                     bwd_name, bwd_ph)
                floats = [l for l in jax.tree.leaves(g)
                          if hasattr(l, "dtype") and
                          jnp.issubdtype(l.dtype, jnp.floating)]
                if floats:
                    g = _tie_first_float(g, tok)
            return (g,)

        f.defvjp(fwd, bwd)
        return f(tree)

    return span


def _tie_first_float(tree, token):
    leaves = jax.tree.leaves(tree)
    for i, l in enumerate(leaves):
        if hasattr(l, "dtype") and jnp.issubdtype(l.dtype, jnp.floating):
            leaves[i] = l + token.astype(l.dtype) * 0
            break
    return jax.tree.unflatten(jax.tree.structure(tree), leaves)


# Open fwd_name in the forward pass; close bwd_name in the backward pass.
phase_span_begin = _make_span("B", "E")
# Close fwd_name in the forward pass; open bwd_name in the backward pass.
phase_span_end = _make_span("E", "B")
