"""Regenerate tests/slow_manifest.txt from a pytest --durations=0 log.

  python -m pytest tests/ -q --durations=0 > /tmp/suite.txt
  python tools/update_slow_manifest.py /tmp/suite.txt [threshold_s] [--merge]

--merge unions the log's slow set with the CURRENT manifest instead of
replacing it. Use it when the log comes from a run where some slow tests
failed early (environment drift): a failing test reports an artificially
short duration and would otherwise lose its mark and leak into the
tier-1 fast lane.
"""

import re
import sys

# The fast lane's real length, as tests/conftest.py and pyproject.toml say it.
LANE = "2,024 cases, about 14 min on six workers, PR 62"

args = [a for a in sys.argv[1:] if a != "--merge"]
merge = "--merge" in sys.argv[1:]
log = args[0]
threshold = float(args[1]) if len(args) > 1 else 10.0
slow = {m.group(2) for ln in open(log)
        for m in [re.match(r"(\d+\.\d+)s call\s+(\S+)", ln)]
        if m and float(m.group(1)) > threshold}
out = "tests/slow_manifest.txt"
if merge:
    try:
        with open(out) as f:
            slow |= {ln.strip() for ln in f
                     if ln.strip() and not ln.startswith("#")}
    except OSError:
        pass
slow = sorted(slow)
with open(out, "w") as f:
    f.write("# Tests marked @slow (measured >%gs on the 8-virtual-device\n"
            "# CPU mesh; tools/update_slow_manifest.py regenerates from a\n"
            "# pytest --durations=0 log). Fast lane: pytest -m 'not slow'\n"
            "# (tier 1: %s).\n" % (threshold, LANE))
    f.writelines(t + "\n" for t in slow)
print(f"{len(slow)} slow tests → {out}")
