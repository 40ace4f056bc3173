"""Serving engine: median milliseconds of the ``mta.engine.prefill.sample``
spans that begin in the window: how long the host stands still a request
admitted, from the first sample's dispatch to the ``device_get`` of its token,
while the device runs the round in flight and every call of the prompt
(``perfbench/admission_spans.py``). 0.0 on a program without the span."""
from perfbench import admission_spans


def read(run):
    return float(admission_spans.of(run)["first_sample_wait_ms"])
