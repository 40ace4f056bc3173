"""Serving engine: per cent of the rows of the window's prefill calls that
were prompt and not padding: the sum of ``tokens`` over the sum of ``width``
of the ``mta.engine.prefill_call`` spans that begin in the window (the
counter of the same name in ``/stats`` covers the engine's life). 0.0 where
no call says its width, as in a run whose runner kept no span attributes
(``serve_closed.py``: the dense cell is not in this metric's ``workloads``)."""
from perfbench import admission_spans


def read(run):
    return float(admission_spans.of(run)["prefill_fill_share"])
