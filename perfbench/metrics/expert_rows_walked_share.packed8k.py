"""Model: the rows of the buffers the held experts gathered, multiplied,
weighed and scattered, over the tokens' picks (``row_buffer_rows`` /
``assignments`` of the traced interval's ``mta.train.sync``): 1.0 where
every layer call walks all T x k pick rows, and down to
``expert_rows_here_share`` where a call's buffer holds the picks that
landed here and no other (``moe._row_buffer_rungs``: each call takes the
smallest of a few static sizes that holds them). A program that counts
``assignments`` and no ``row_buffer_rows`` is one from before the buffer
had sizes: it walks every pick row of every call, which is 1.0 (a traced
line that lacks one of its cell's metrics is refused, ``lastline.faults``,
so the commit before the counter has to read a number). None on a program
without the counters."""
from perfbench import train_spans


def read(run):
    total = train_spans.sync_sum(run, "assignments")
    if not total:
        return None
    walked = train_spans.sync_sum(run, "row_buffer_rows")
    return 1.0 if walked is None else walked / total
