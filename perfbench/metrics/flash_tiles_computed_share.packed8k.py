"""Kernels: the tile pairs the flash kernels computed over those of their
grids' causal triangle (the full layers) and band (the window layers)
(``flash_tiles_computed`` / ``flash_tiles`` of the traced interval's
``mta.train.sync``, one pass a layer and micro-batch): 1.0 where every tile
of the triangle and the band is computed whatever documents its queries and
keys lie in, and down to the share of them in which a query's document
meets a key's, which the packed rows decide (0.535 of the full layer's and
0.943 of the window layers' over 48 rows of the cell's generator). A program
that counts ``assignments`` and no ``flash_tiles`` is one from before the
kernels were handed the documents' table: it computes every such tile, which
is 1.0 (a traced line that lacks one of its cell's metrics is refused,
``lastline.faults``, so the commit before the counter has to read a number).
None on a program without the counters."""
from perfbench import train_spans


def read(run):
    if not train_spans.sync_sum(run, "assignments"):
        return None
    tiles = train_spans.sync_sum(run, "flash_tiles")
    computed = train_spans.sync_sum(run, "flash_tiles_computed")
    return computed / tiles if tiles else 1.0
