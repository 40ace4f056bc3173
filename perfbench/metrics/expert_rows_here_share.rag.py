"""Model: the share of the tokens' picks that fell on an expert held here
(``assignments_here`` / ``assignments`` of ``stats_snapshot()["moe"]``, over
the engine's plain decode rounds): 36 / 72 = 0.5 by chance at seeded
weights, and what a trained router's skew would move. It scales the experts'
work beside the mixers'. 0 when the program counts no such thing."""


def read(run):
    stats = run.get("engine_stats")
    if stats is None:
        return None
    moe = stats.get("moe", {})
    total = moe.get("assignments", 0)
    return moe.get("assignments_here", 0) / total if total else 0.0
