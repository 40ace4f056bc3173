"""Per-request lifecycle tracing: an always-on bounded ring of B/E spans.

The MegaScan tracer (trace/tracer.py) is iteration-window-gated — right
for training, useless for serving, where the interesting timeline is a
REQUEST's: admit → queue wait → prefill chunks → parked/handoff → adopt
→ decode steps → spec rounds → retire/expire/abort/preempt. This module
is the serving-side counterpart (ISSUE 12): a singleton ring-buffer
tracer that the engines emit Chrome-trace-style B/E/i records into,
bounded by ``capacity`` (old records fall off — tracing can stay ON in
production), with the SAME record schema as tracer.py so the existing
aggregation machinery (trace/aggregate.py: B/E→X pairing, Chrome trace
metadata) renders it.

Timeline layout:

- ``pid`` is the LOGICAL mesh/component: ``DECODE_PID`` (0) for the
  engine/decode side, ``PREFILL_PID`` (1) for the disaggregated prefill
  worker — a disagg request's prefill chunks and its decode lifetime
  merge into ONE Chrome trace with one process row per mesh.
- ``tid`` is the request id + 1 for per-request spans (each request gets
  its own timeline row; B/E pairing in aggregate.py keys on
  (pid, tid, name), so concurrent requests never mis-pair), and 0 for
  step-granularity spans (decode-step, spec-round).

Pairing is guaranteed by construction: ``end()`` is a no-op unless that
span is open (no orphan E), and ``finish()`` closes every span a
request still has open (retire/expire/abort paths all funnel through
it — no orphan B). tests/test_metrics.py pins every-B-has-a-matching-E
across the full lifecycle including expire and preempt.

The disabled path is one attribute truthiness check per call site.

``span()`` is the ONE emission point of a serving phase (ISSUE 26): it
always opens a ``jax.profiler.TraceAnnotation("mta." + name)``, so the
phase lands on the ``/host:CPU`` plane of the same ``.xplane.pb`` as the
device's ``XLA Ops``, on one clock, whenever a profiler session runs (and
costs about a microsecond otherwise); it adds the phase's wall time to the
``PhaseStats`` its caller owns (the engine's ``step_stats``, the driver's
``deliver``), always; and it emits the ring's B/E pair under the ring's
own name when the ring is on. The span names are an interface that
perfbench's readers match: ``mta.engine.{step, admit, prefill,
prefill_call, prefill.sample, capacity, decode_round, decode.stage,
decode.stage.sample, decode.stage.put, decode.stage.dispatch, decode.wait,
decode.record, retire}`` and ``mta.driver.deliver``; the training loop's
``mta.train.step`` (``iteration``, ``micro_batches``, ``tokens``: a step's
dispatch) and ``mta.train.sync`` (the ``device_get`` of a log interval's
metrics; ``steps``, its last step's ``loss`` and ``grad_norm`` unrounded
and, on a model that counts its held experts' load, the
interval's ``assignments``, ``assignments_here``, ``assignments_absent``,
``here_max_rows``, ``row_buffer_rows`` (the rows of the buffers the held
experts walked: moe._row_buffer_rungs), ``experts_here``,
``moe_layer_passes``, ``router_loss``, summed over its steps, micro-batches
and layers: training/train.py).

``mta.engine.step`` carries no attribute: what a step admitted is the
``mta.engine.prefill`` spans inside it (one a request), what it read the
``mta.engine.decode_round`` inside it, and ``perfbench/admission_spans.py``
splits the first chip's idle by the first (``admit_gap_ms_step``,
``round_gap_ms_round``). ``stats_snapshot()["steps"]`` counts
``admit_steps`` (steps that admitted), ``admitted`` (ISSUE 50) and
``first_samples_ahead`` (ISSUE 53, below), for ``/stats``.

An admission is ``mta.engine.admit`` > ``mta.engine.prefill`` (``rid``,
``prompt_tokens``, ``cached_tokens``: the ring's ``prefill`` record) >
``mta.engine.prefill_call`` (``tokens``, ``width``, a tenant's
``summaries`` / ``window_blocks``; read by ``prefill_call_host_ms``,
``prefill_call_device_ms``, ``prefill_fill_share``). The request's first
token is sampled behind the prompt's last call, inside ``prefill``, and
stays on the device; ``mta.engine.prefill.sample`` (``rid``, ``ahead``) is
the ``device_get`` of it, where the host stands until the device has run
the round in flight and every call of the prompt
(``first_sample_wait_ms``). Since ISSUE 53 that span lies in the same step
but no longer inside ``prefill``: it lies inside the step's
``decode_round``, behind the ``decode.stage`` of the round ahead and
before ``decode.wait``, one span after the other for the requests the step
admitted, and ``ahead`` = 1 says that a round was dispatched between the
sample and its fetch with that token as its row's operand, so that the chip
runs that round while the host stands (``first_samples_ahead`` counts
them). ``ahead`` = 0: the step dispatched no round (nothing goes on, or the
pool does not cover the next rows: the span lies where it would, or
straight inside ``step`` where no round is read either, and the chip
stands under it), or the engine speculates, whose proposer reads the host's
tokens (the span then lies inside ``prefill``, where it always was).

``mta.engine.decode_round`` is one span a round, opened when the round's
tokens are read, with the attributes of its dispatch (``batch``,
``kv_tokens``, ``kv_blocks``, a tenant's ``kv_rows`` / ``window_blocks``
/ ...: the byte functions' numerators) and, on a plain round, ``ahead``:
1 where the round was dispatched before the tokens of the round before it
were read (the engine's decode loop runs one round ahead, ISSUE 47;
``rounds_ahead_share``). Inside it: ``decode.stage`` (the NEXT round's),
which is its three children one after the other: ``.stage.sample`` (the
unread round's sampler, its operands and its dispatch; absent where no
round runs ahead; on a speculative round the verifier's operands),
``.stage.put`` (the step's host arrays to the device: tokens, page
tables, lengths, the active mask, adapters) and ``.stage.dispatch`` (the
decode or verify call and the commit of the pools it returns)
(``stage_{sample,put,dispatch}_ms_round``); ``decode.wait`` (the
``device_get`` of this round's tokens); ``decode.record``. Beside the
phases, ``stats_snapshot()["steps"]`` counts ``rounds_ahead`` (rounds with
``ahead`` = 1) and ``overrun_rows`` (rows of a round in flight whose
request ended or left its slot before they were read; their tokens are
dropped): ``/stats``, ``perfbench/tools/slowest_rounds.py`` and
``tests/test_engine_run_ahead.py`` read them. Idle of the chip under
``step``, ``admit``, ``prefill``, ``decode_round`` or ``decode.stage``
themselves, or under no span, has no phase: ``idle_unnamed_share.serve``.
"""

from __future__ import annotations

import json
import os
import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional

from jax.profiler import TraceAnnotation

DECODE_PID = 0      # engine / decode sub-mesh timeline
PREFILL_PID = 1     # disaggregated prefill sub-mesh timeline

_PROCESS_NAMES = {DECODE_PID: "decode-mesh", PREFILL_PID: "prefill-mesh"}


class PhaseStats:
    """Always-on ``count`` / ``total_s`` / ``max_s`` per phase, owned by
    whoever runs the phases (an engine, a driver) and fed by
    ``RequestTracer.span``. One writer thread; readers take a snapshot."""

    def __init__(self, phases=()):
        self.phases: Dict[str, List[float]] = {p: [0, 0.0, 0.0]
                                               for p in phases}

    def add(self, phase: str, seconds: float):
        row = self.phases.get(phase)
        if row is None:
            row = self.phases[phase] = [0, 0.0, 0.0]
        row[0] += 1
        row[1] += seconds
        if seconds > row[2]:
            row[2] = seconds

    def snapshot(self) -> Dict[str, Dict[str, float]]:
        return {p: {"count": int(c), "total_s": t, "max_s": m}
                for p, (c, t, m) in list(self.phases.items())}


class _Span:
    """What ``RequestTracer.span`` returns (a class, not a generator: ten
    of these open in every engine step)."""

    __slots__ = ("rt", "name", "rid", "stats", "ring", "attrs", "ann", "t0",
                 "seconds", "late")

    def __init__(self, rt, name, rid, stats, ring, attrs):
        self.rt, self.name, self.rid = rt, name, rid
        self.stats, self.ring, self.attrs = stats, ring, attrs
        self.late = None

    def __enter__(self):
        if self.ring is not None and self.rt.enabled:
            self.rt.begin(self.ring, self.rid, **self.attrs)
        if self.rid is None:
            self.ann = TraceAnnotation("mta." + self.name, **self.attrs)
        else:
            self.ann = TraceAnnotation("mta." + self.name, rid=self.rid,
                                       **self.attrs)
        self.ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def set(self, **attrs):
        """Attributes known only inside the span (what its device_get
        returned): they join the annotation's before it closes, and the
        ring's E record carries them."""
        self.late = attrs
        self.ann.set_metadata(**attrs)

    def __exit__(self, exc_type, exc, tb):
        self.seconds = time.perf_counter() - self.t0
        self.ann.__exit__(exc_type, exc, tb)
        if self.stats is not None:
            # "engine.decode.wait" is the phase "decode.wait" of the
            # engine's own stats.
            self.stats.add(self.name.partition(".")[2], self.seconds)
        if self.ring is not None and self.rt.enabled:
            if exc_type is None:
                self.rt.end(self.ring, self.rid, **(self.late or {}))
            else:
                self.rt.end(self.ring, self.rid, error=True)
        return False


class RequestTracer:
    """Bounded always-on request-lifecycle tracer (singleton via
    get_request_tracer)."""

    def __init__(self, capacity: int = 16384):
        self.enabled = False
        self.capacity = capacity
        self._ring: deque = deque(maxlen=capacity)
        self._lock = threading.Lock()
        # rid -> [(pid, name), ...] open spans, innermost last.
        self._open: Dict[int, List[tuple]] = {}
        self._t0 = time.perf_counter_ns()
        # pid -> Chrome-trace process-row label. Extensible at runtime:
        # the fleet router labels replica rows ("replica-N decode") so a
        # migrated request's spans read across replicas in one trace
        # (ISSUE 14 — migration spans join the per-request timeline).
        self._pid_names: Dict[int, str] = dict(_PROCESS_NAMES)

    # -- configuration -----------------------------------------------------
    def configure(self, enabled: bool = True,
                  capacity: Optional[int] = None):
        with self._lock:
            self.enabled = enabled
            if capacity is not None and capacity != self.capacity:
                self.capacity = capacity
                self._ring = deque(self._ring, maxlen=capacity)

    def set_process_name(self, pid: int, name: str):
        """Label a process row (fleet replicas; custom meshes).
        reset() restores the default labels — custom names are part of
        the trace epoch, not global state."""
        with self._lock:
            self._pid_names[pid] = name

    def reset(self):
        """Drop all records, open-span state, and custom process
        labels (tests; fresh epochs)."""
        with self._lock:
            self._ring.clear()
            self._open.clear()
            self._pid_names = dict(_PROCESS_NAMES)
            self._t0 = time.perf_counter_ns()

    def _ts_us(self) -> float:
        return (time.perf_counter_ns() - self._t0) / 1e3

    # -- emission ----------------------------------------------------------
    def _emit(self, name: str, ph: str, rid: Optional[int], pid: int,
              attrs: Dict[str, Any]):
        rec = {
            "name": name, "ph": ph, "ts": self._ts_us(),
            "pid": pid,
            "tid": 0 if rid is None else rid + 1,
            "iteration": 0,
            "args": dict(attrs, rid=rid) if rid is not None else dict(attrs),
        }
        with self._lock:
            self._ring.append(rec)

    def begin(self, name: str, rid: Optional[int],
              pid: int = DECODE_PID, **attrs):
        if not self.enabled:
            return
        with self._lock:
            self._open.setdefault(rid, []).append((pid, name))
        self._emit(name, "B", rid, pid, attrs)

    def end(self, name: str, rid: Optional[int],
            pid: int = DECODE_PID, **attrs):
        """Close an open span. Tolerant: a no-op when `name` is not open
        for `rid` — the lifecycle paths overlap (abort during prefill,
        expire while parked) and an orphan E would corrupt B/E pairing
        downstream."""
        if not self.enabled:
            return
        with self._lock:
            spans = self._open.get(rid)
            if not spans or (pid, name) not in spans:
                return
            # Remove the innermost matching occurrence.
            for i in range(len(spans) - 1, -1, -1):
                if spans[i] == (pid, name):
                    del spans[i]
                    break
            if not spans:
                self._open.pop(rid, None)
        self._emit(name, "E", rid, pid, attrs)

    def span(self, name: str, rid: Optional[int] = None, *,
             stats: Optional[PhaseStats] = None,
             ring: Optional[str] = None, **attrs) -> _Span:
        """Context manager around one phase of serving work: a
        ``TraceAnnotation("mta." + name, **attrs)`` always, `stats` (the
        caller's own PhaseStats; the singleton keeps no counters, fleet
        replicas share it) always, and the ring's B/E pair under `ring`
        when the ring is on. Closes on an exception too (the E then
        carries ``error=True``), so a failing step leaves no open span
        and counts once."""
        return _Span(self, name, rid, stats, ring, attrs)

    def instant(self, name: str, rid: Optional[int] = None,
                pid: int = DECODE_PID, **attrs):
        if not self.enabled:
            return
        self._emit(name, "i", rid, pid, attrs)

    def finish(self, rid: int, reason: Optional[str] = None, **attrs):
        """Terminal event for a request: optional instant `reason`
        (retire/expire/abort) then close EVERY span it still has open,
        innermost first — the one funnel that guarantees no orphan B on
        any exit path."""
        if not self.enabled:
            return
        if reason is not None:
            self._emit(reason, "i", rid, DECODE_PID, attrs)
        with self._lock:
            spans = self._open.pop(rid, [])
        for pid, name in reversed(spans):
            self._emit(name, "E", rid, pid, {})

    # -- export ------------------------------------------------------------
    def dump(self) -> List[dict]:
        """Ring contents, oldest first (records stay in the ring)."""
        with self._lock:
            return list(self._ring)

    def _windowed_records(self) -> List[dict]:
        """Records wrapped in a synthetic single-iteration window per
        pid, so trace/aggregate.py's iteration-stitching machinery
        (which keys offsets on 'iteration' B/E spans) accepts a serving
        trace as one window."""
        recs = self.dump()
        if not recs:
            return []
        t_end = max(r["ts"] for r in recs) + 1.0
        out = []
        for pid in sorted({r["pid"] for r in recs}):
            out.append({"name": "iteration", "ph": "B", "ts": 0.0,
                        "pid": pid, "tid": 0, "iteration": 0, "args": {}})
        out.extend(recs)
        for pid in sorted({r["pid"] for r in recs}):
            out.append({"name": "iteration", "ph": "E", "ts": t_end,
                        "pid": pid, "tid": 0, "iteration": 0, "args": {}})
        return out

    def chrome_trace(self, process_names: Optional[Dict[int, str]] = None
                     ) -> dict:
        """Render the ring as one merged Chrome trace through the
        existing aggregation machinery (B/E→X pairing + process
        metadata) — prefill-mesh and decode-mesh events land as separate
        process rows of the SAME trace."""
        from megatronapp_tpu.trace.aggregate import (
            chrome_trace as _chrome, transform_to_complete_events,
        )
        recs = sorted(self._windowed_records(),
                      key=lambda r: (r["ts"], r["pid"]))
        events = transform_to_complete_events(recs)
        return _chrome(events, process_names or dict(self._pid_names))

    def save(self, path: Optional[str] = None, trace_dir: str = "trace"
             ) -> str:
        """Write the ring as a benchmark-data-*.json file compatible
        with `python -m megatronapp_tpu.trace.aggregate -b DIR`, so
        serving request traces stitch offline next to training traces."""
        if path is None:
            os.makedirs(trace_dir, exist_ok=True)
            path = os.path.join(trace_dir, "benchmark-data-requests.json")
        with open(path, "w") as f:
            json.dump(self._windowed_records(), f)
        return path


def merge_process_traces(procs: List[tuple]) -> dict:
    """Merge per-PROCESS request-trace rings into ONE Chrome trace —
    the MegaScan per-rank-merge story applied to serving (ISSUE 18):
    each replica worker dumps its ring over RPC and the router renders
    one timeline with a process row per (worker, logical mesh).

    `procs` is ``[(label, records, pid_names), ...]`` where `records`
    is a ring dump (RequestTracer.dump()) and `pid_names` that
    process's pid→row-label map. Each process's ring has its OWN
    perf_counter epoch, so timestamps are normalized per ring (min →
    0); pids are offset by 100·i so rows never collide, and labels
    compose as "label name" ("replica-1 decode-mesh"). Empty rings are
    skipped. B/E pairing is per-(pid, tid, name), and the pid offset
    keeps every process's spans in their own rows, so pairing never
    crosses a process boundary."""
    from megatronapp_tpu.trace.aggregate import (
        chrome_trace as _chrome, transform_to_complete_events,
    )
    merged: List[dict] = []
    names: Dict[int, str] = {}
    for i, (label, records, pid_names) in enumerate(procs):
        if not records:
            continue
        base = 100 * i
        t_min = min(r["ts"] for r in records)
        t_end = max(r["ts"] for r in records) - t_min + 1.0
        pids = sorted({r["pid"] for r in records})
        for pid in pids:
            row = (pid_names or {}).get(pid, f"pid-{pid}")
            names[base + pid] = f"{label} {row}"
            merged.append({"name": "iteration", "ph": "B", "ts": 0.0,
                           "pid": base + pid, "tid": 0, "iteration": 0,
                           "args": {}})
        for r in records:
            merged.append(dict(r, ts=r["ts"] - t_min,
                               pid=base + r["pid"]))
        for pid in pids:
            merged.append({"name": "iteration", "ph": "E", "ts": t_end,
                           "pid": base + pid, "tid": 0, "iteration": 0,
                           "args": {}})
    merged.sort(key=lambda r: (r["ts"], r["pid"]))
    return _chrome(transform_to_complete_events(merged), names)


_TRACER = RequestTracer()


def get_request_tracer() -> RequestTracer:
    return _TRACER


if os.environ.get("MEGATRON_REQUEST_TRACE"):
    _TRACER.configure(enabled=True)
