"""Serving engine: the gap that one in a thousand of the gaps between
consecutive bytes of one request exceeds, over the requests that completed
inside the window, pooled. A prefill blocks ``engine.step()``: while one
request's prompt runs (a 12,288-byte prompt is 384 calls, ~2 s) none of the
other 31 slots decodes, so about one gap in a hundred of this mix holds a
whole prefill (0.9 to 1.4% by the window: the 99th percentile lies on that
edge and reads a decode round or a quarter of a second). This is where a
caller feels the longer ones; the median (``itl_p50_ms.bytegen``) is a
plain decode round. Prefill chunked into the decode step (ROADMAP S2) would
bring it down to a few rounds."""
import statistics


def read(run):
    gaps = run.get("itl_ms")
    if not gaps:
        return None
    if len(gaps) < 2:
        return float(gaps[0])
    # inclusive: a short list's quantile never passes its longest gap
    return statistics.quantiles(gaps, n=1000, method="inclusive")[998]
