"""What the readers of the training loop's own spans share.

``megatronapp_tpu/training/train.py`` wraps a step's dispatch in
``mta.train.step`` and the ``device_get`` of a log interval's metrics in
``mta.train.sync``; on a model whose layers count their held experts' load
the second carries the interval's routing counters as attributes, summed
over its steps, micro-batches and layers (``assignments``,
``assignments_here``, ``assignments_absent``, ``here_max_rows``,
``experts_here``, ``moe_layer_passes``, ``router_loss``). A runner keeps the
trace's span attributes as ``run["xplane_stats"]`` (``xplane_stats.load``).
A program without the spans (the commit before the one that added them)
reads nothing: a reader then returns None and the line leaves its metric
out.
"""

from __future__ import annotations

from typing import Optional

SYNC = "mta.train.sync"


def sync_sum(run, attr: str) -> Optional[float]:
    """Sum of `attr` over the ``mta.train.sync`` spans that end inside the
    traced window (a sync closes the interval it reports), or None where no
    such span carries it."""
    summary = run.get("device_summary")
    spans = (run.get("xplane_stats") or {}).get("spans", [])
    if not summary:
        return None
    lo, hi = summary["window"]
    got = [float(attrs[attr]) for name, start, dur, attrs in spans
           if name == SYNC and attr in attrs and lo <= start + dur <= hi]
    return sum(got) if got else None
