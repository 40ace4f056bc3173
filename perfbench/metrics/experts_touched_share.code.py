"""Model: ``experts_touched_share.longgen``'s reading for the code cell
(``expert_pairs_touched`` of ``expert_pairs_possible`` = 4 sparse layers x
256 experts x rounds): how much of the experts' 6.44 GB a round streams.
About 64% at 32 slots x 8 picks (256 x (1 - (31/32)^32) = 163 of 256). 0
when the program counts no such thing."""
from perfbench import manifest

read = manifest.load_module("metrics", "experts_touched_share.longgen").read
