"""Serving engine: ``decode_wait_ms_round.batch``'s reading (milliseconds per decode round that the stepper spends in ``mta.engine.decode.wait``) for the rag cell: 64 slots of a
stack with nine Mamba-2 layers and one attention layer of 8 key/value
heads."""
from perfbench import manifest

read = manifest.load_module("metrics", "decode_wait_ms_round.batch").read
