"""Serving engine: ``host_gap_ms_round.batch``'s reading (milliseconds per
decode round in which the first chip runs nothing, outside every
``mta.engine.prefill`` span) for the chat cell: what the decode loop over
128 slots leaves the chip waiting."""
from perfbench import manifest

read = manifest.load_module("metrics", "host_gap_ms_round.batch").read
