"""Model: device milliseconds a training step in parts ``embedding`` and
``head`` (in training the head has the loss) of the train step's module,
both passes (``perfbench/scope_time.py``). 0.0 on a program that registers no
train step."""
from perfbench import scope_time


def read(run):
    return scope_time.ms_per_step(run, "train", ("embedding", "head"))
