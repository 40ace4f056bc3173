"""Model: ``expert_load_max_over_mean.agent``'s reading for the rag cell
(``here_max_rows`` x ``experts_here`` / ``assignments_here``): the straggler
inside the held experts' grouped GEMM at 9 rows an expert a round. 1 is an
even load. 0 when the program counts no such thing."""
from perfbench import manifest

read = manifest.load_module("metrics", "expert_load_max_over_mean.agent").read
