"""What the readers of the program's own spans and kernel names share.

The serving engine names its phases (``mta.engine.step``, ``.decode_round``,
``.decode.wait``, ``.prefill``, ...; ``megatronapp_tpu/trace/request_trace.py``
lists them) with ``TraceAnnotation`` spans on the profiler's clock, and every
Pallas kernel carries a family name (``flash_fwd*``, ``flash_bwd_dq*``,
``flash_bwd_dkv*``, ``paged_decode*``, ``paged_mq*``, ``fused_*``, ``lora_*``)
that its HLO instruction, and so its device events, keep. Under autodiff JAX
wraps that name (``jvp_flash_fwd_t_``, ``transpose_jvp_flash_bwd_dq_t__``), so
a family is matched anywhere in an event's name, not at its start.

Everything here takes the ``run`` dict of ``run.py`` and reads
``run["trace"]`` inside ``run["device_summary"]["window"]``. A program without
a span or a kernel (the parent commit of the PR that added them, or a later
one whose refactor dropped a phase) reads 0, never an error.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

from perfbench import trace_reduce

Interval = Tuple[int, int]
PREFIX = "mta."
ROUND = "mta.engine.decode_round"
PREFILL = "mta.engine.prefill"


def program_spans(run) -> List[list]:
    return trace_reduce.host_spans(run["trace"], prefix=PREFIX)


def clipped(events, window: Interval) -> List[Interval]:
    """The events' intervals cut to the window; one outside drops out."""
    lo, hi = window
    cut = ((max(s, lo), min(s + d, hi)) for _, s, d, _ in events)
    return [(a, b) for a, b in cut if b > a]


def clipped_s(spans, name: str, window: Interval) -> float:
    """Seconds of the spans called `name` that lie inside the window."""
    return sum(b - a for a, b in clipped(
        (e for e in spans if e[0] == name), window)) / 1e9


def rounds_in(spans, window: Interval) -> float:
    """Decode rounds in the window; one that straddles an edge counts by
    its share inside (a traced window holds only 20 to 50)."""
    lo, hi = window
    return sum(max(0, min(s + d, hi) - max(s, lo)) / d
               for n, s, d, _ in spans if n == ROUND and d > 0)


def idle_intervals(events, window: Interval) -> List[Interval]:
    """The window less the union of a device's operations."""
    lo, hi = window
    gaps, cursor = [], lo
    for a, b in trace_reduce.union(clipped(events, window)):
        if a > cursor:
            gaps.append((cursor, a))
        cursor = b
    if hi > cursor:
        gaps.append((cursor, hi))
    return gaps


def first_chip_idle(run) -> Optional[List[Interval]]:
    per_device = trace_reduce.device_op_events(run["trace"])
    if not per_device:
        return None
    return idle_intervals(next(iter(per_device.values())),
                          run["device_summary"]["window"])


def overlap_ns(gaps: List[Interval], spans, name: str) -> int:
    """Nanoseconds of `gaps` covered by spans called `name` (which do not
    overlap one another: one thread opens them one after the other)."""
    covers = [(s, s + d) for n, s, d, _ in spans if n == name]
    return sum(max(0, min(b, hi) - max(a, lo))
               for a, b in gaps for lo, hi in covers)


def innermost_timeline(spans) -> List[Tuple[int, int, str]]:
    """The spans flattened to consecutive (start, end, name) pieces, each
    named by the shortest span that covers it."""
    bounds = sorted({t for _, s, d, _ in spans for t in (s, s + d)})
    pieces = []
    for lo, hi in zip(bounds, bounds[1:]):
        covering = [(d, n) for n, s, d, _ in spans
                    if s <= lo and s + d >= hi]
        if covering:
            pieces.append((lo, hi, min(covering)[1]))
    return pieces


def idle_by_innermost(gaps: List[Interval], spans) -> Dict[str, int]:
    """Nanoseconds of the (sorted) idle intervals by the innermost span
    that covers each part of them; ``outside`` where none does."""
    pieces = innermost_timeline(spans)
    totals: Dict[str, int] = {}
    i = 0
    for a, b in gaps:
        while i < len(pieces) and pieces[i][1] <= a:
            i += 1
        covered, j = 0, i
        while j < len(pieces) and pieces[j][0] < b:
            lo, hi, name = pieces[j]
            part = min(hi, b) - max(lo, a)
            totals[name] = totals.get(name, 0) + part
            covered += part
            j += 1
        if b - a > covered:
            totals["outside"] = totals.get("outside", 0) + (b - a - covered)
    return totals


def kernel_s(run, *families: str) -> Optional[float]:
    """Device seconds in the window of the Pallas kernels whose name holds
    one of `families`, averaged over the chips; 0.0 when none ran."""
    return trace_reduce.summed_s(
        run["trace"], run["device_summary"]["window"],
        lambda ev: trace_reduce.is_pallas(ev)
        and any(f in ev[0] for f in families))


def step_counters(run) -> Optional[dict]:
    """``stats_snapshot()["steps"]`` of a serving run: {} from a program
    that has no such counters, None from a run that is not serving."""
    stats = run.get("engine_stats")
    return None if stats is None else stats.get("steps", {})
