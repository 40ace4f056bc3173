"""Serving engine: ``prefill_share.batch``'s reading (``steps.prefill`` over
``steps.step`` of the engine's always-on counters, in percent, over the
engine's life) for the byte cell, whose prompts run to 12,288 bytes at 32 a
call. 0 when the program has no such counters."""
from perfbench import manifest

read = manifest.load_module("metrics", "prefill_share.batch").read
