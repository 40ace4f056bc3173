"""Serving engine: device milliseconds a decode round of the decode step's
``embedding`` and ``head`` and of the sampler's module, whichever call of it
(a prefill's first sample is one row) (``perfbench/scope_time.py``). 0.0 on a
program that registers neither."""
from perfbench import scope_time


def read(run):
    return (scope_time.ms_per_round(run, "decode", ("embedding", "head"))
            + scope_time.ms_per_round(run, "sampler"))
