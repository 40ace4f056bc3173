"""Model: ``expert_load_max_over_mean.agent``'s reading for the assist cell,
where every expert is held (``here_max_rows`` x 64 / ``assignments``): the
straggler inside the grouped GEMM at 12 rows an expert a round. 1 is an even
load. 0 when the program counts no such thing."""
from perfbench import manifest

read = manifest.load_module("metrics", "expert_load_max_over_mean.agent").read
