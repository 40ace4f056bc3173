"""Serving engine: milliseconds a decode round of the window that the stepper
spends in ``mta.engine.decode.stage.put``: the step's host arrays to the
device (tokens where no round runs ahead, page tables, lengths, the active
mask, adapters). With ``stage_sample_ms_round`` and ``stage_dispatch_ms_round``
it splits ``mta.engine.decode.stage`` (``perfbench/admission_spans.py``). 0.0
on a program without the span."""
from perfbench import admission_spans


def read(run):
    return float(admission_spans.of(run)["stage_put_ms_round"])
