"""Model: device milliseconds a training step in part ``mlp`` of the train
step's module, both passes and recomputation, averaged over the chips
(``perfbench/scope_time.py``). 0.0 on a program that registers no train
step."""
from perfbench import scope_time


def read(run):
    return scope_time.ms_per_step(run, "train", ("mlp",))
