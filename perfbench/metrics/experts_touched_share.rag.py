"""Model: ``experts_touched_share.longgen``'s reading for the rag cell, where
the pairs are those of the experts HELD here (``expert_pairs_touched`` of
``expert_pairs_possible`` = layers x held experts x rounds): how much of the
held experts' weights a round streams. Nearly all of it at 9 rows an expert
a round. 0 when the program counts no such thing."""
from perfbench import manifest

read = manifest.load_module("metrics", "experts_touched_share.longgen").read
