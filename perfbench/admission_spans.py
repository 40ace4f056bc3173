"""What the readers of the admission path's spans share (ISSUE 50).

Since ISSUE 47 a pure decode round leaves the chip almost nothing, and what
is left of its idle sits in the steps that admit. The program's spans say
which those are: the engine opens one ``mta.engine.prefill`` a request it
admits and one ``mta.engine.decode_round`` a round it reads, inside the
``mta.engine.step`` that does so; the first sample of an admitted request is
``mta.engine.prefill.sample``, a prefill call says its ``tokens`` and
``width``, a round whether it ran ``ahead``, and ``mta.engine.decode.stage``
is split into ``.stage.sample``, ``.stage.put`` and ``.stage.dispatch``
(``megatronapp_tpu/trace/request_trace.py`` lists them). ``of(run)`` parses
the spans once a run, inside ``run["device_summary"]["window"]``, keeps the
result in ``run`` as ``scope_time.table`` does, and every reader of
``metrics/`` named below takes its number from it:

- ``admit_gap_ms_step``, ``round_gap_ms_round``: the first chip's idle inside
  the steps that begin in the window and admitted (a ``prefill`` span begins
  inside them), over their count; and the rest of its idle (inside the other
  steps, and outside every step), over the steps that admitted nothing and
  read a round (a ``decode_round`` begins inside them). The two split the
  window's idle exactly: ``admit_idle_ns`` + ``round_idle_ns`` = ``idle_ns``.
- ``idle_unnamed_share.serve``: of that idle, the share whose innermost
  ``mta.*`` span is a container (``CONTAINERS``) or none.
- ``first_sample_wait_ms``, ``prefill_call_host_ms``: medians of the spans
  that begin in the window; ``prefill_calls_in``: the calls of the window, one
  across an edge by its share inside (``prefill_call_device_ms`` divides the
  prefill step's device seconds by it).
- ``prefill_fill_share``, ``rounds_ahead_share``: from the attributes of the
  window's calls and rounds. A span's attributes are stats of its event,
  which ``trace_reduce.load_xplane`` drops; a runner keeps them as
  ``run["xplane_stats"]`` (``xplane_stats.load``). ``serve_closed.py``, the
  dense cell's runner, keeps none, so ``BENCHMARK.json`` lists these two in
  the other six serving cells alone.
- ``stage_{sample,put,dispatch}_ms_round``: the children's seconds in the
  window over its decode rounds (``program_spans.rounds_in``).

A program that lacks a span or an attribute (the parent of the PR that added
it) reads 0.0: never ``None``, never an error.
"""

from __future__ import annotations

import bisect
from typing import Dict, List, Tuple

from perfbench import program_spans as ps
from perfbench.stats import median

Interval = Tuple[int, int]
STEP = "mta.engine.step"
CALL = "mta.engine.prefill_call"
SAMPLE = "mta.engine.prefill.sample"
STAGE = "mta.engine.decode.stage"
# Spans that hold other spans: idle whose innermost span is one of these has
# no phase of the program's.
CONTAINERS = (STEP, ps.ROUND, ps.PREFILL, "mta.engine.admit", STAGE)
OUTSIDE = "outside"
# The readers, in BENCHMARK.json's order: every serving cell lists them.
METRICS = ("admit_gap_ms_step", "round_gap_ms_round",
           "idle_unnamed_share.serve", "first_sample_wait_ms",
           "prefill_call_device_ms", "prefill_call_host_ms",
           "prefill_fill_share", "rounds_ahead_share",
           "stage_sample_ms_round", "stage_put_ms_round",
           "stage_dispatch_ms_round")


def innermost_pieces(spans) -> List[Tuple[int, int, str]]:
    """``program_spans.innermost_timeline`` in one sweep: consecutive
    (start, end, name) pieces, each named by the shortest span open over
    it. At most a handful are open at once (they nest), so a window's few
    thousand spans cost their sort."""
    edges = []
    for i, (_, start, dur, _) in enumerate(spans):
        if dur > 0:
            edges += [(start, 1, i), (start + dur, 0, i)]
    edges.sort()
    pieces, open_, at = [], {}, None
    for t, opens, i in edges:
        if open_ and t > at:
            pieces.append((at, t, min(open_.values())[1]))
        if opens:
            open_[i] = (spans[i][2], spans[i][0])
        else:
            del open_[i]
        at = t
    return pieces


def idle_by_name(gaps: List[Interval], pieces) -> Dict[str, int]:
    """Nanoseconds of the sorted idle intervals by the piece that covers
    each part of them; ``outside`` where none does."""
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
            totals[OUTSIDE] = totals.get(OUTSIDE, 0) + (b - a - covered)
    return totals


def _step_facts(steps, spans) -> List[Tuple[int, int]]:
    """(requests admitted, rounds read) of each step: the
    ``mta.engine.prefill`` and ``mta.engine.decode_round`` spans that begin
    inside it."""
    prefills = sorted(s for n, s, _, _ in spans if n == ps.PREFILL)
    rounds = sorted(s for n, s, _, _ in spans if n == ps.ROUND)

    def begin_inside(starts, lo, hi):
        return bisect.bisect_left(starts, hi) - bisect.bisect_left(starts, lo)

    return [(begin_inside(prefills, s, s + d), begin_inside(rounds, s, s + d))
            for _, s, d, _ in steps]


def _median_ms(events) -> float:
    return median([dur / 1e6 for _, _, dur, _ in events]) if events else 0.0


def _sum(events, attr: str) -> float:
    return sum(float(attrs.get(attr, 0)) for _, _, _, attrs in events)


def _parse(run) -> dict:
    out = dict.fromkeys((
        "idle_ns", "admit_idle_ns", "round_idle_ns", "admit_steps",
        "pure_steps", "admit_gap_ms_step", "round_gap_ms_round",
        "idle_unnamed_share", "first_sample_wait_ms", "prefill_calls_in",
        "prefill_call_host_ms", "prefill_fill_share", "rounds_ahead_share",
        "stage_sample_ms_round", "stage_put_ms_round",
        "stage_dispatch_ms_round"), 0.0)
    summary = run.get("device_summary")
    if not summary:
        return out
    window = lo, hi = summary["window"]
    kept = run.get("xplane_stats")
    spans = ps.program_spans(run) if kept is None else kept["spans"]

    def begun(name):
        return [e for e in spans if e[0] == name and lo <= e[1] < hi]

    # ---- the first chip's idle, by step and by phase ----------------------
    gaps = ps.first_chip_idle(run) or []
    idle = sum(b - a for a, b in gaps)
    steps = begun(STEP)
    facts = _step_facts(steps, spans)
    admitting = [(STEP, s, d, None) for (_, s, d, _), (admitted, _)
                 in zip(steps, facts) if admitted >= 1]
    pure = sum(1 for admitted, rounds in facts if admitted == 0 and rounds)
    admit_idle = idle_by_name(gaps, innermost_pieces(admitting)).get(STEP, 0)
    out.update(idle_ns=idle, admit_idle_ns=admit_idle,
               round_idle_ns=idle - admit_idle, admit_steps=len(admitting),
               pure_steps=pure)
    if admitting:
        out["admit_gap_ms_step"] = admit_idle / 1e6 / len(admitting)
    if pure:
        out["round_gap_ms_round"] = (idle - admit_idle) / 1e6 / pure
    if idle:
        by_name = idle_by_name(gaps, innermost_pieces(spans))
        unnamed = sum(by_name.get(n, 0) for n in CONTAINERS + (OUTSIDE,))
        out["idle_unnamed_share"] = 100.0 * unnamed / idle

    # ---- an admission's own spans -----------------------------------------
    out["first_sample_wait_ms"] = _median_ms(begun(SAMPLE))
    calls = begun(CALL)
    out["prefill_call_host_ms"] = _median_ms(calls)
    out["prefill_calls_in"] = sum(
        max(0, min(s + d, hi) - max(s, lo)) / d
        for n, s, d, _ in spans if n == CALL and d > 0)
    width = _sum(calls, "width")
    if width:
        out["prefill_fill_share"] = 100.0 * _sum(calls, "tokens") / width

    # ---- the decode rounds ------------------------------------------------
    rounds = begun(ps.ROUND)
    if rounds:
        out["rounds_ahead_share"] = 100.0 * _sum(rounds, "ahead") / len(rounds)
    in_window = ps.rounds_in(spans, window)
    if in_window:
        for child in ("sample", "put", "dispatch"):
            out[f"stage_{child}_ms_round"] = ps.clipped_s(
                spans, f"{STAGE}.{child}", window) * 1e3 / in_window
    return out


def of(run) -> dict:
    """Every number above, parsed once a run."""
    if "admission_spans" not in run:
        run["admission_spans"] = _parse(run)
    return run["admission_spans"]
