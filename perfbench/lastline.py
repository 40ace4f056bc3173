"""The last line of standard output, checked before it is printed.

PR 24's benchmark was refused because a traced run's last line did not meet
the contract. So the harness holds its own line to the contract first and
prints nothing when it fails.
"""

from __future__ import annotations

import json
import math
from typing import Dict, List


def _number(x) -> bool:
    return (isinstance(x, (int, float)) and not isinstance(x, bool)
            and math.isfinite(x))


def faults(line: dict, wanted: Dict[str, str], chips: int,
           traced: bool) -> List[str]:
    """Every way in which `line` misses the contract. `wanted` maps each
    metric this run must report to its unit."""
    bad: List[str] = []
    for key in ("correct", "attempted", "failed", "metrics", "device"):
        if key not in line:
            bad.append(f"missing key {key!r}")
    if bad:
        return bad
    if not isinstance(line["correct"], bool):
        bad.append("correct is not true/false")
    for key in ("attempted", "failed"):
        if not isinstance(line[key], int) or isinstance(line[key], bool) \
                or line[key] < 0:
            bad.append(f"{key} is not a count")
    metrics = line["metrics"]
    if not isinstance(metrics, dict):
        return bad + ["metrics is not an object"]
    for name, unit in wanted.items():
        got = metrics.get(name)
        if not isinstance(got, dict):
            bad.append(f"metric {name!r} is missing")
        elif not _number(got.get("value")):
            bad.append(f"metric {name!r} has no finite value: "
                       f"{got.get('value')!r}")
        elif got.get("unit") != unit:
            bad.append(f"metric {name!r} has unit {got.get('unit')!r}, "
                       f"BENCHMARK.json says {unit!r}")
    for name in metrics:
        if name not in wanted:
            bad.append(f"metric {name!r} is not one of this cell's")
    dev = line["device"]
    if not isinstance(dev, dict):
        return bad + ["device is not an object"]
    for key in ("platform", "kind"):
        if not isinstance(dev.get(key), str) or not dev.get(key):
            bad.append(f"device.{key} is missing")
    if dev.get("count") != chips:
        bad.append(f"device.count is {dev.get('count')!r}, the cell asks "
                   f"for {chips}")
    if not _number(dev.get("memory_peak_bytes")) \
            or dev["memory_peak_bytes"] <= 0:
        bad.append("device.memory_peak_bytes is not a positive number")
    if traced:
        busy, window = dev.get("busy_s"), dev.get("window_s")
        if not _number(busy) or not _number(window):
            bad.append(f"device.busy_s/window_s are not numbers: "
                       f"{busy!r}, {window!r}")
        elif not 0 < busy <= window:
            bad.append(f"device.busy_s {busy} is not in (0, window_s "
                       f"{window}]")
        br = line.get("breakdown")
        if br is not None:
            for key in ("device_ops", "idle_gaps"):
                rows = br.get(key) if isinstance(br, dict) else None
                if not isinstance(rows, list) or len(rows) > 10 or any(
                        not (isinstance(r, list) and len(r) == 2
                             and isinstance(r[0], str) and _number(r[1]))
                        for r in rows):
                    bad.append(f"breakdown.{key} is not at most ten "
                               "[name, seconds] pairs")
    try:
        text = json.dumps(line, allow_nan=False)
    except ValueError as e:
        bad.append(f"not JSON: {e}")
    else:
        if "\n" in text:
            bad.append("more than one line")
    return bad
