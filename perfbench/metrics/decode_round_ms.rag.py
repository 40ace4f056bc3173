"""Serving engine: ``decode_round_ms.batch``'s reading (median duration of the program's ``mta.engine.decode_round`` spans that begin in the traced window) for the rag cell: 64 slots of a
stack with nine Mamba-2 layers and one attention layer of 8 key/value
heads."""
from perfbench import manifest

read = manifest.load_module("metrics", "decode_round_ms.batch").read
