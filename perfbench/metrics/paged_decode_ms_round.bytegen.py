"""Kernels: ``paged_decode_ms_round.batch``'s reading (device milliseconds
per decode round in ``paged_decode*``) for the byte cell: 8 layers of 32
key/value heads of 128 over tables of summary rows and an open window."""
from perfbench import manifest

read = manifest.load_module("metrics", "paged_decode_ms_round.batch").read
