"""Serving engine: ``batch_occupancy.batch``'s reading for the code cell (32 slots of a sliding-window MoE stack at ~8k tokens a slot; the reader's own docstring says what it reads and that it gives 0 on a program without the name)."""
from perfbench import manifest

read = manifest.load_module("metrics", "batch_occupancy.batch").read
