"""Serving engine: ``host_gap_ms_round.batch``'s reading for the assist cell (192 slots
of a hybrid MoE model; the reader's own docstring says what it reads and
that it gives 0 on a program without the name)."""
from perfbench import manifest

read = manifest.load_module("metrics", "host_gap_ms_round.batch").read
