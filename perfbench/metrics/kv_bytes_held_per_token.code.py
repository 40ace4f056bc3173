"""Serving engine: pool bytes the running slots' blocks hold over their
tokens in flight, in bytes a token (``stats_snapshot()["window"]``:
``bytes_held``, both kinds of plane, of ``tokens_in_flight``, each summed
over the engine's plain decode rounds). 8,192 for the two full planes plus
the window planes' share (34 blocks x 3 planes x 65,536 B over ~8.4k tokens:
~800) where the allocator gives blocks behind the window back; 20,480 where
it does not. 0 when the program counts no such thing."""


def read(run):
    stats = run.get("engine_stats")
    if stats is None:
        return None
    window = stats.get("window") or {}
    tokens = window.get("tokens_in_flight", 0)
    return window.get("bytes_held", 0) / tokens if tokens else 0.0
