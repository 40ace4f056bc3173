"""Serving engine, from the device's side: the rest of the first chip's idle
in the window (inside steps that admitted nothing, and outside every step:
the driver's callbacks, the stepper between steps), in milliseconds over the
steps that begin in the window, admitted nothing and read a round (no
``mta.engine.prefill`` and an ``mta.engine.decode_round`` begin inside them):
what a pure decode round leaves the chip. ``admit_gap_ms_step`` x its steps +
this x its steps = the first chip's idle (``perfbench/admission_spans.py``).
0.0 where no such step is in the window."""
from perfbench import admission_spans


def read(run):
    return float(admission_spans.of(run)["round_gap_ms_round"])
