"""Serving engine: milliseconds per decode round that the stepper spends in
``mta.engine.decode.wait`` (the sampler's call up to and including the
``device_get`` of the tokens): the host blocked on the device. Close to the
round itself while the device is the bottleneck; what is left of the round
is host work. 0 when the program names no such span."""
from perfbench import program_spans as ps


def read(run):
    summary = run.get("device_summary")
    if not summary:
        return None
    spans = ps.program_spans(run)
    rounds = ps.rounds_in(spans, summary["window"])
    if not rounds:
        return 0.0
    return ps.clipped_s(spans, "mta.engine.decode.wait",
                        summary["window"]) * 1e3 / rounds
