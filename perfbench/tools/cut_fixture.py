"""Cut a few milliseconds out of a recorded profiler trace and keep them as
the fixture of ``tests/test_trace_reduce.py``.

    python3 perfbench/tools/cut_fixture.py <trace_dir> <out.json.gz> <from_ms> <for_ms>

Times are from the start of the ``bench.window`` span. Kept: every event of
every line that overlaps the slice (an enclosing ``while`` included), and a
``bench.window`` span shortened to the slice.
"""

import gzip
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from perfbench import trace_reduce  # noqa: E402


def main():
    trace_dir, out, from_ms, for_ms = sys.argv[1:5]
    trace = trace_reduce.load_xplane(trace_dir)
    w0, _ = trace_reduce.window_of(trace)
    lo = w0 + int(float(from_ms) * 1e6)
    hi = lo + int(float(for_ms) * 1e6)
    planes = []
    for plane in trace["planes"]:
        lines = []
        for line in plane["lines"]:
            events = []
            for name, start, dur, info in line["events"]:
                if name == trace_reduce.WINDOW_SPAN:
                    events.append([name, lo, hi - lo, info])
                elif start < hi and start + dur > lo:
                    events.append([name, start, dur, info])
            if events:
                lines.append({"name": line["name"], "events": events})
        if lines:
            planes.append({"name": plane["name"], "lines": lines})
    with gzip.open(out, "wt") as f:
        json.dump({"planes": planes}, f, separators=(",", ":"))
    n = sum(len(ln["events"]) for p in planes for ln in p["lines"])
    print(f"{out}: {n} events, {os.path.getsize(out)} bytes")


if __name__ == "__main__":
    main()
