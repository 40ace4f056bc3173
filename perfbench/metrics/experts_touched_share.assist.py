"""Model: ``experts_touched_share.longgen``'s reading for the assist cell
(``expert_pairs_touched`` of ``expert_pairs_possible`` = 8 MoE layers x 64
experts x rounds): how much of the experts' 9.66 GB a round streams. Nearly
all of it at 12 rows an expert. 0 when the program counts no such thing."""
from perfbench import manifest

read = manifest.load_module("metrics", "experts_touched_share.longgen").read
