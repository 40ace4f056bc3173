"""Serving engine: median milliseconds of the ``mta.engine.prefill_call``
spans that begin in the window: the host's time a call, which is its
dispatch while the device's queue has room and the call's own time once it
is full (``perfbench/admission_spans.py``). 0.0 where no call is in the
window."""
from perfbench import admission_spans


def read(run):
    return float(admission_spans.of(run)["prefill_call_host_ms"])
