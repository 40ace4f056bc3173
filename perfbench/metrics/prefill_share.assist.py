"""Serving engine: ``prefill_share.batch``'s reading for the assist cell (192 slots
of a hybrid MoE model; the reader's own docstring says what it reads and
that it gives 0 on a program without the name)."""
from perfbench import manifest

read = manifest.load_module("metrics", "prefill_share.batch").read
