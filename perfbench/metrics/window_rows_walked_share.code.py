"""Model: the rows the sliding-window layers' walks read in the engine's
plain decode rounds as a share of what a full-length walk would have, in
percent (``stats_snapshot()["window"]``: ``rows_walked``, whole blocks from
the one that holds a slot's oldest visible key, of ``rows_full_walk``, T + 1
a slot; over the engine's life). Lower is better: ~6% at ~8.4k tokens a
slot and a window of 512; a window term that walked the whole table reads
100. 0 when the program counts no such thing."""


def read(run):
    stats = run.get("engine_stats")
    if stats is None:
        return None
    window = stats.get("window") or {}
    full = window.get("rows_full_walk", 0)
    return 100.0 * window.get("rows_walked", 0) / full if full else 0.0
