"""Model: the share of the tokens' picks that fell on an expert held here
(``assignments_here`` / ``assignments`` of the traced interval's
``mta.train.sync``): 16 / 64 = 0.25 by chance at seeded weights, and what
a trained router's skew would move. It scales the experts' work beside the
attention's. None on a program without the counters."""
from perfbench import train_spans


def read(run):
    here = train_spans.sync_sum(run, "assignments_here")
    total = train_spans.sync_sum(run, "assignments")
    return here / total if here and total else None
