"""Kernels: ``paged_decode_ms_round.batch``'s reading (device milliseconds per decode round in ``paged_decode*``) for the rag cell: 64 slots of a
stack with nine Mamba-2 layers and one attention layer of 8 key/value
heads."""
from perfbench import manifest

read = manifest.load_module("metrics", "paged_decode_ms_round.batch").read
