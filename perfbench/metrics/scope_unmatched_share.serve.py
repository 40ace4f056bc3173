"""Device: per cent of the traced window's summed device time in operations
that no scope map explains (an unregistered module, an instruction the map
lacks or describes otherwise): the yardstick's own health
(``perfbench/scope_time.py``). 0.0 on a program that makes no map."""
from perfbench import scope_time


def read(run):
    return scope_time.unmatched_share(run)
