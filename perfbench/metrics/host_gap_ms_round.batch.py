"""Serving engine, from the device's side: milliseconds per decode round in
which the first chip runs nothing, outside every ``mta.engine.prefill``
span: what the decode loop (inside a round, between rounds, in the driver's
callbacks) leaves the chip waiting. 0 when no round is in the window."""
from perfbench import program_spans as ps


def read(run):
    summary = run.get("device_summary")
    if not summary:
        return None
    gaps = ps.first_chip_idle(run)
    if gaps is None:
        return None
    spans = ps.program_spans(run)
    rounds = ps.rounds_in(spans, summary["window"])
    if not rounds:
        return 0.0
    idle = sum(b - a for a, b in gaps) - ps.overlap_ns(gaps, spans,
                                                       ps.PREFILL)
    return idle / 1e6 / rounds
