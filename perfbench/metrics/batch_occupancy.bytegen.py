"""Serving engine: ``batch_occupancy.batch``'s reading (mean over the
window's steps of occupied decode slots / max_batch, in percent, read after
each step) for the byte cell's 32 slots."""
from perfbench import manifest

read = manifest.load_module("metrics", "batch_occupancy.batch").read
