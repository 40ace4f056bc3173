"""Serving engine: median duration of the program's own
``mta.engine.decode_round`` spans that begin inside the traced window: one
token for every running request, without the admission steps that
``engine_step_ms.batch`` mixes in. 0 when the program names no such span."""
from perfbench import program_spans as ps
from perfbench.stats import median


def read(run):
    summary = run.get("device_summary")
    if not summary:
        return None
    lo, hi = summary["window"]
    rounds = [d / 1e6 for n, s, d, _ in ps.program_spans(run)
              if n == ps.ROUND and lo <= s < hi]
    return median(rounds) if rounds else 0.0
