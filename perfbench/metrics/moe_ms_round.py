"""Model: device milliseconds a decode round of the decode step's operations
in part ``moe``: router, grouped GEMMs, shared experts, combine
(``perfbench/scope_time.py``). The metric PR 28 lost. 0.0 on a program that
registers no decode step."""
from perfbench import scope_time


def read(run):
    return scope_time.ms_per_round(run, "decode", ("moe",))
