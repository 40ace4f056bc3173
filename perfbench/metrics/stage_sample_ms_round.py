"""Serving engine: milliseconds a decode round of the window that the stepper
spends in ``mta.engine.decode.stage.sample``: the unread round's sampler, its
operands and its dispatch (absent where no round runs ahead: the sampler is
then dispatched in ``decode.wait``). With ``stage_put_ms_round`` and
``stage_dispatch_ms_round`` it splits ``mta.engine.decode.stage``
(``perfbench/admission_spans.py``). 0.0 on a program without the span."""
from perfbench import admission_spans


def read(run):
    return float(admission_spans.of(run)["stage_sample_ms_round"])
