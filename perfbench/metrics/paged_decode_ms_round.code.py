"""Kernels: ``paged_decode_ms_round.batch``'s reading (device milliseconds per decode round in ``paged_decode*``) for the code cell: the two FULL layers' walks of 48 query heads over 8 key/value heads of 128, ~8.4k rows a slot; the window layers' kernel is ``paged_window_decode*``, which this family does not match."""
from perfbench import manifest

read = manifest.load_module("metrics", "paged_decode_ms_round.batch").read
