"""Model: device milliseconds a training step in train-step operations that
lie in no named part (``perfbench/scope_time.py``;
``tools/device_by_scope.py`` lists the largest with their ``op_name``). 0.0
on a program that registers no train step."""
from perfbench import scope_time


def read(run):
    return scope_time.ms_per_step(run, "train", ("other",))
