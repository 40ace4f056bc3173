"""Serving engine: milliseconds a decode round of the window that the stepper
spends in ``mta.engine.decode.stage.dispatch``: the decode step's call and
the commit of the pools it returns. With ``stage_sample_ms_round`` and
``stage_put_ms_round`` it splits ``mta.engine.decode.stage``
(``perfbench/admission_spans.py``). 0.0 on a program without the span."""
from perfbench import admission_spans


def read(run):
    return float(admission_spans.of(run)["stage_dispatch_ms_round"])
