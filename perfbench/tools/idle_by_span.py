"""Where the first chip idles, by the program's own spans.

    python3 perfbench/run.py --workload W --seed N --seconds 50 --trace 1 \
        --keep-trace DIR
    python3 perfbench/tools/idle_by_span.py DIR

From the trace kept in DIR: the idle seconds of the first chip inside
``bench.window``, by the innermost ``mta.*`` span that covers each part of
them (``outside``: no span of the program was open, the stepper was between
steps or parked), and the count, median and longest of every ``mta.*`` span
that begins in the window. One JSON object on standard output.
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from perfbench import program_spans as ps          # noqa: E402
from perfbench import trace_reduce                 # noqa: E402
from perfbench.stats import median                 # noqa: E402


def report(trace: dict) -> dict:
    window = trace_reduce.window_of(trace)
    per_device = trace_reduce.device_op_events(trace)
    if window is None or not per_device:
        raise SystemExit("idle_by_span: the trace holds no bench.window "
                         "span or no device plane")
    spans = trace_reduce.host_spans(trace, prefix=ps.PREFIX)
    gaps = ps.idle_intervals(next(iter(per_device.values())), window)
    idle = ps.idle_by_innermost(gaps, spans)
    by_name = {}
    for name, start, dur, _ in spans:
        if window[0] <= start < window[1]:
            by_name.setdefault(name, []).append(dur / 1e6)
    return {
        "window_s": (window[1] - window[0]) / 1e9,
        "idle_s": sum(b - a for a, b in gaps) / 1e9,
        "idle_s_by_span": {k: v / 1e9 for k, v in
                           sorted(idle.items(), key=lambda kv: -kv[1])},
        "decode_rounds": ps.rounds_in(spans, window),
        "spans_ms": {name: {"count": len(ds), "median": median(ds),
                            "max": max(ds)}
                     for name, ds in sorted(by_name.items())},
    }


if __name__ == "__main__":
    if len(sys.argv) != 2:
        raise SystemExit(__doc__)
    print(json.dumps(report(trace_reduce.load_xplane(sys.argv[1])),
                     indent=1))
