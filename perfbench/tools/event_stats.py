"""A by-hand look at what ``trace_reduce.outline`` does not show: the raw
name and every stat of one event of each kind on each line of a kept trace
(where a span's attributes live, and what a device event does and does not
carry).

    python3 perfbench/tools/event_stats.py DIR   # DIR from --keep-trace
"""

import collections
import glob
import os
import sys


def main():
    from jax.profiler import ProfileData
    path = sorted(glob.glob(os.path.join(sys.argv[1], "**", "*.xplane.pb"),
                            recursive=True), key=os.path.getmtime)[-1]
    for plane in ProfileData.from_file(path).planes:
        print(f"plane {plane.name!r}")
        for line in plane.lines:
            shown = collections.Counter()
            for ev in line.events:
                kind = ev.name.split(" = ")[0].rstrip("0123456789.")[:24]
                if not shown[kind] and len(shown) < 40:
                    stats = [(k, str(v)[:160]) for k, v in ev.stats]
                    print(f"  {line.name!r}: {ev.name[:400]!r}\n      {stats}")
                shown[kind] += 1


if __name__ == "__main__":
    main()
