"""Model: ``expert_load_max_over_mean.agent``'s reading for the code cell,
where every expert is held (``here_max_rows`` x 256 / ``assignments``): the
straggler inside the grouped GEMM at 1 row an expert a round. 1 is an even
load; at a mean of 1 row the most-loaded expert has a few. 0 when the
program counts no such thing."""
from perfbench import manifest

read = manifest.load_module("metrics", "expert_load_max_over_mean.agent").read
