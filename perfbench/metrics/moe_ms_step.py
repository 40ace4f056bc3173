"""Model: device milliseconds a training step in part ``moe`` of the train
step's module, both passes and recomputation: the router, the sort and
gather of the picks, the experts' grouped products, the weighted scatter
back (``perfbench/scope_time.py``). 0.0 on a program that registers no
train step."""
from perfbench import scope_time


def read(run):
    return scope_time.ms_per_step(run, "train", ("moe",))
