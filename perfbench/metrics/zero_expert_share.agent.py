"""Model: of the router's picks in the engine's plain decode rounds, the
share that fell on zero-compute (identity) experts, in percent
(``stats_snapshot()["moe"]``: ``assignments_zero`` over ``assignments``,
over the engine's life): the compute a token did not cost. 256 of 768
outputs are such experts, so seeded weights read about 33. 0 when the
program counts no such thing."""


def read(run):
    stats = run.get("engine_stats")
    if stats is None:
        return None
    moe = stats.get("moe", {})
    picks = moe.get("assignments", 0)
    return 100.0 * moe.get("assignments_zero", 0) / picks if picks else 0.0
