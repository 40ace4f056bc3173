"""Serving engine: per cent of the ``mta.engine.decode_round`` spans that
begin in the window with ``ahead`` = 1: the rounds that were dispatched
before the tokens of the round before them were read, so that the chip did
not wait for the host (``perfbench/admission_spans.py``). 0.0 on a program
whose loop does not run ahead, and in a run whose runner kept no span
attributes (``serve_closed.py``: the dense cell is not in this metric's
``workloads``)."""
from perfbench import admission_spans


def read(run):
    return float(admission_spans.of(run)["rounds_ahead_share"])
