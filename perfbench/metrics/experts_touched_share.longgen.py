"""Model: of the (layer, expert) pairs the engine's plain decode rounds
could have touched, the share that their running requests did touch, in
percent (``stats_snapshot()["moe"]``, over the engine's life): how much of
the expert weights a round has to stream. 0 when the program counts no
such thing."""


def read(run):
    stats = run.get("engine_stats")
    if stats is None:
        return None
    moe = stats.get("moe", {})
    possible = moe.get("expert_pairs_possible", 0)
    return 100.0 * moe.get("expert_pairs_touched", 0) / possible \
        if possible else 0.0
