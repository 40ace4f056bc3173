"""Model: the straggler inside the held experts' grouped products, as a
ratio: the most rows one held expert got in a layer of a micro-batch, over
the mean rows a held expert got (``here_max_rows``, summed over the traced
interval's micro-batches and layers, x ``experts_here`` a layer pass /
``assignments_here``: ``mta.train.sync``). 1 is an even load. None on a
program without the counters."""
from perfbench import train_spans


def read(run):
    here = train_spans.sync_sum(run, "assignments_here")
    most = train_spans.sync_sum(run, "here_max_rows")
    held = train_spans.sync_sum(run, "experts_here")
    passes = train_spans.sync_sum(run, "moe_layer_passes")
    if not here or not most or not held or not passes:
        return None
    return most * (held / passes) / here
