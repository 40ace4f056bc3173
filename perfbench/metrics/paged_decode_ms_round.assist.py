"""Kernels: ``paged_decode_ms_round.batch``'s reading (device milliseconds
per decode round in ``paged_decode*``) for the assist cell: two attention
layers of 8 key/value heads of 64 under 4 query heads each, 192 slots."""
from perfbench import manifest

read = manifest.load_module("metrics", "paged_decode_ms_round.batch").read
