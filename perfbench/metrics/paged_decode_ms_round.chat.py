"""Kernels: ``paged_decode_ms_round.batch``'s reading (device milliseconds
per decode round in ``paged_decode*``) for the chat cell: two attention
layers of one key/value head under 20 query heads."""
from perfbench import manifest

read = manifest.load_module("metrics", "paged_decode_ms_round.batch").read
