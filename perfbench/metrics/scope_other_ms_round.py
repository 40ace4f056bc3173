"""Model: device milliseconds a decode round of decode-step operations that
lie in no named part: the layer loop's weight slices and relayout copies
land here until something names them (``perfbench/scope_time.py``;
``tools/device_by_scope.py`` lists the largest). 0.0 on a program that
registers no decode step."""
from perfbench import scope_time


def read(run):
    return scope_time.ms_per_round(run, "decode", ("other",))
