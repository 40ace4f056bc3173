"""Training loop: device milliseconds a training step in parts ``optimizer``
(the norm the clip reads, the update, ZeRO-1's gather) and ``grad_accum``
(the float32 accumulation across micro-batches) of the train step's module
(``perfbench/scope_time.py``). A fusion counts whole where its root was
written. 0.0 on a program that registers no train step."""
from perfbench import scope_time


def read(run):
    return scope_time.ms_per_step(run, "train", ("optimizer", "grad_accum"))
