"""Model: device milliseconds a training step in part ``attention`` of the
train step's module, forward, backward and recomputation (the flash kernels
or XLA's dense attention, the qkv and output projections), averaged over the
chips; by the join of ``perfbench/scope_time.py``. 0.0 on a program that
registers no train step."""
from perfbench import scope_time


def read(run):
    return scope_time.ms_per_step(run, "train", ("attention",))
