"""Serving engine, from the device's side: milliseconds the first chip runs
nothing inside an ``mta.engine.step`` span that begins in the window and
admitted (an ``mta.engine.prefill`` span begins inside it: the engine opens
one a request), over the count of such steps: what an admission leaves the
chip (the first sample's fetch, and nothing queued behind the prompt's calls
until the next round is staged). With ``round_gap_ms_round`` it splits the
window's idle exactly (``perfbench/admission_spans.py``). 0.0 where no step
of the window admitted."""
from perfbench import admission_spans


def read(run):
    return float(admission_spans.of(run)["admit_gap_ms_step"])
