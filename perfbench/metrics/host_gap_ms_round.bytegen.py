"""Serving engine: ``host_gap_ms_round.batch``'s reading (milliseconds per
decode round in which the first chip runs nothing, outside every
``mta.engine.prefill`` span) for the byte cell: what the decode loop over 32
slots, window closings among its work, leaves the chip waiting. A round
here runs a quarter of the model's depth, so this is about four times a
deployment's share."""
from perfbench import manifest

read = manifest.load_module("metrics", "host_gap_ms_round.batch").read
