"""What ``trace_reduce.load_xplane`` drops of a profiler trace and a reader
needs: the attributes of the program's ``mta.*`` spans.

``load_xplane`` keeps an event's name (parsed) and its times; a span's
attributes (``TraceAnnotation("mta.engine.decode_round", batch=...,
kv_tokens=...)``) are stats of the event. ``load`` reads them from the raw
``.xplane.pb`` in ``trace_reduce``'s event form:

    {"spans": [[name, start_ns, duration_ns, {attribute: value}], ...]}

A runner calls it before ``run.py`` removes the trace directory and keeps
the result as ``run["xplane_stats"]``. (A TPU's device events carry three
stats, ``device_offset_ps``, ``device_duration_ps`` and ``Time Scale
Multiplier``, and a name without the instruction's metadata: a
``jax.named_scope`` does not reach them. PERF.md, PR 28.)
"""

from __future__ import annotations

import glob
import os

from perfbench import program_spans as ps


def load(trace_dir: str) -> dict:
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    spans = []
    for plane in (ProfileData.from_file(paths[-1]).planes if paths else ()):
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [[ev.name, int(ev.start_ns), int(ev.duration_ns),
                           dict(ev.stats)]
                          for ev in line.events
                          if ev.name.startswith(ps.PREFIX)]
    return {"spans": sorted(spans, key=lambda e: e[1])}


def round_attrs(run, attr: str) -> float:
    """Sum of the attribute `attr` over the ``mta.engine.decode_round``
    spans of the traced window, a span that straddles an edge counted by
    its share inside; 0.0 when no span carries it."""
    lo, hi = run["device_summary"]["window"]
    total = 0.0
    for name, start, dur, attrs in (run.get("xplane_stats") or {}).get(
            "spans", []):
        if name == ps.ROUND and dur > 0 and attr in attrs:
            inside = max(0, min(start + dur, hi) - max(start, lo)) / dur
            total += inside * float(attrs[attr])
    return total
