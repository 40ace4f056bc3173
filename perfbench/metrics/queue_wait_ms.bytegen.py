"""Serving engine: ``queue_wait_ms.batch``'s reading (mean time a request
waits in the engine's queue before a slot takes it, from the engine's
always-on ``steps.queue_wait``, over the engine's LIFE) for the byte cell:
the second caller of a slot waits for the first's whole answer, ~2,300
bytes of ~18 ms rounds and the other slots' prefills between them.
``ttft_p50_ms.bytegen`` less this is the request's own prefill."""
from perfbench import manifest

read = manifest.load_module("metrics", "queue_wait_ms.batch").read
