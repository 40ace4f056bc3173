"""Serving engine: ``batch_occupancy.batch``'s reading (mean over the window's steps of occupied decode slots / max_batch, in percent) for the rag cell: 64 slots of a
stack with nine Mamba-2 layers and one attention layer of 8 key/value
heads."""
from perfbench import manifest

read = manifest.load_module("metrics", "batch_occupancy.batch").read
