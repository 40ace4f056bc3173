"""Serving engine: ``decode_wait_ms_round.batch``'s reading (milliseconds
per decode round that the stepper spends in ``mta.engine.decode.wait``, the
sampler's call up to the ``device_get`` of the tokens) for the byte cell."""
from perfbench import manifest

read = manifest.load_module("metrics", "decode_wait_ms_round.batch").read
