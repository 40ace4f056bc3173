"""Serving engine: ``decode_round_ms.batch``'s reading (median duration of
the program's ``mta.engine.decode_round`` spans that begin in the traced
window) for the byte cell: 32 slots in different windows, a quarter of the
model's depth a round. 0 when the program names no such span."""
from perfbench import manifest

read = manifest.load_module("metrics", "decode_round_ms.batch").read
