"""Serving engine: ``itl_p50_ms.batch``'s reading (median gap between
consecutive bytes of one request, over the requests that completed inside
the window, pooled) for the byte cell: a plain decode round, since most
gaps hold no prefill. What another request's prefill does to a caller is
``itl_p999_ms.bytegen``."""
from perfbench import manifest

read = manifest.load_module("metrics", "itl_p50_ms.batch").read
