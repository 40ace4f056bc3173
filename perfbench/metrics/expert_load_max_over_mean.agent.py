"""Model: the straggler inside the held experts' grouped GEMM, as a ratio:
the most rows one held expert got in a layer of a round, over the mean rows
a held expert got (``stats_snapshot()["moe"]``: ``here_max_rows``, summed
over rounds and layers, over ``assignments_here`` / ``experts_here``, the
sum of those layers' means; over the engine's life). 1 is an even load. 0
when the program counts no such thing."""


def read(run):
    stats = run.get("engine_stats")
    if stats is None:
        return None
    moe = stats.get("moe", {})
    here, held = moe.get("assignments_here", 0), moe.get("experts_here", 0)
    if not here or not held or "here_max_rows" not in moe:
        return 0.0
    return moe["here_max_rows"] * held / here
