"""Model: the rows the paged kernel walked in the window's plain decode
rounds as a share of what full attention would have walked, in percent,
from the ``mta.engine.decode_round`` spans' attributes: ``kv_rows`` (R(T) a
running slot: 128 summary rows a closed window and the open window's rows)
over ``kv_tokens`` + ``batch`` (T + 1 a slot: the context and the row the
round appends). The byte mix gives about 36; a cache that stops shrinking
reads 100. 0 when the program's spans carry no ``kv_rows``; the engine's
life-long counters of the same (``stats_snapshot()["eva"]``:
``rows_walked`` of ``rows_full_attention``) are in the run's notes."""
from perfbench import xplane_stats


def read(run):
    if not run.get("device_summary"):
        return None
    walked = xplane_stats.round_attrs(run, "kv_rows")
    if not walked:
        return 0.0
    full = (xplane_stats.round_attrs(run, "kv_tokens")
            + xplane_stats.round_attrs(run, "batch"))
    return 100.0 * walked / full
