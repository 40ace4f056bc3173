"""Serving engine: ``ttft_p50_ms.batch``'s reading (median time from sending
a request to its first byte, over the requests that completed inside the
window) for the byte cell: the wait for a free slot (a closed loop of two
callers a slot) and the request's own prefill, ~95 calls of 32 bytes for a
mean prompt, inside ``engine.step()``."""
from perfbench import manifest

read = manifest.load_module("metrics", "ttft_p50_ms.batch").read
