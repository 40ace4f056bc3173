"""Serving engine: ``host_gap_ms_round.batch``'s reading (milliseconds per decode round in which the chip runs nothing, outside every ``mta.engine.prefill`` span) for the rag cell: 64 slots of a
stack with nine Mamba-2 layers and one attention layer of 8 key/value
heads."""
from perfbench import manifest

read = manifest.load_module("metrics", "host_gap_ms_round.batch").read
