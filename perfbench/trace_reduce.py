"""From a profiler trace to device numbers: the one reduction every PR uses.

``load_xplane`` turns the ``.xplane.pb`` the JAX profiler wrote into plain
lists (so that a cut-down trace can be kept as a JSON fixture); everything
else works on those lists:

    {"planes": [{"name": str, "lines": [{"name": str,
        "events": [[name, start_ns, duration_ns, {stat: value}], ...]}]}]}

What a TPU v5e trace looks like (read by hand in PR 25, see PERF.md): one
plane ``/device:TPU:<n>`` per chip; its line ``XLA Ops`` holds one event per
executed HLO operation, named by the whole HLO instruction (``parse_hlo``
keeps the short name, the opcode and a custom call's target). A ``while``
encloses the events of its body on the same line, so durations may only be
*summed* over leaves, and busy time is the *union* of intervals. The line
``Async XLA Ops`` (copy-start to copy-done and the like) is not read. Host
threads are lines of the plane ``/host:CPU``, on the same clock, where the
harness's own ``TraceAnnotation`` spans appear by name (``bench.window``,
``bench.engine_step``, ``bench.prefill``, ``bench.decode_round``).
"""

from __future__ import annotations

import glob
import gzip
import json
import os
import re
from typing import Callable, Dict, Iterable, List, Optional, Tuple

Event = list        # [name, start_ns, duration_ns, stats]
Interval = Tuple[int, int]

WINDOW_SPAN = "bench.window"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
# In a CPU rehearsal there is no device plane; the XLA CPU client's
# executor threads stand in for it so that the same code runs.
REHEARSAL_PLANE = "/host:CPU"
REHEARSAL_LINES = re.compile(r"^tf_XLA")
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)")
_OPCODE = re.compile(r"\s*([a-z][\w\-]*)\(")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')


def parse_hlo(text: str) -> Tuple[str, dict]:
    """A TPU trace names an operation by its whole HLO instruction,
    ``%fusion.463 = (f32[4,16,1024]{...}, ...) fusion(...), kind=...``.
    Returns the short name (``fusion.463``) and what the reduction needs of
    the rest: the opcode, the head of the result shape and, for a custom
    call, its target (``tpu_custom_call`` is a Pallas/Mosaic kernel)."""
    if " = " not in text:
        return text.lstrip("%"), {}
    short, rest = text.split(" = ", 1)
    if rest.startswith("("):
        depth = 0
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                break
        shape, tail = rest[:i + 1], rest[i + 1:]
    else:
        shape, _, tail = rest.partition(" ")
        tail = " " + tail
    m = _OPCODE.match(tail)
    info = {"op": m.group(1) if m else "", "shape": shape[:48]}
    t = _TARGET.search(tail)
    if t:
        info["target"] = t.group(1)
    return short.lstrip("%"), info


def load_xplane(trace_dir: str) -> dict:
    """The newest ``*.xplane.pb`` under `trace_dir` as plain lists, every
    operation's name parsed by ``parse_hlo``."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    parsed: Dict[str, Tuple[str, dict]] = {}
    planes = []
    for plane in ProfileData.from_file(paths[-1]).planes:
        lines = []
        for line in plane.lines:
            events = []
            for ev in line.events:
                name = ev.name
                if name not in parsed:
                    parsed[name] = parse_hlo(name)
                short, info = parsed[name]
                events.append([short, int(ev.start_ns), int(ev.duration_ns),
                               info])
            lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def load_fixture(path: str) -> dict:
    with gzip.open(path, "rt") as f:
        return json.load(f)


def device_op_events(trace: dict) -> Dict[str, List[Event]]:
    """Per device, the events of its operations line. Keys are plane names.
    Empty when the trace holds no device plane with such a line: a number
    that no device measured is not reported. Only a trace marked
    ``trace["rehearsal"]`` (run.py sets it under PERFBENCH_REHEARSAL=1, on
    the CPU) falls back to the host's XLA threads as a stand-in."""
    out = {}
    for plane in trace["planes"]:
        if DEVICE_PLANE.match(plane["name"]):
            for line in plane["lines"]:
                if line["name"] == OPS_LINE:
                    out[plane["name"]] = sorted(line["events"],
                                                key=lambda e: (e[1], -e[2]))
    if out or not trace.get("rehearsal"):
        return out
    for plane in trace["planes"]:
        if plane["name"] == REHEARSAL_PLANE:
            for line in plane["lines"]:
                if REHEARSAL_LINES.match(line["name"]) and line["events"]:
                    out["rehearsal:" + line["name"]] = sorted(
                        line["events"], key=lambda e: (e[1], -e[2]))
    return out


def host_spans(trace: dict, prefix: str = "bench.") -> List[Event]:
    """The harness's own TraceAnnotation spans, from every host thread."""
    spans = []
    for plane in trace["planes"]:
        if plane["name"].startswith("/host:"):
            for line in plane["lines"]:
                spans += [e for e in line["events"] if e[0].startswith(prefix)]
    return sorted(spans, key=lambda e: e[1])


def window_of(trace: dict) -> Optional[Interval]:
    """[start, end) of the ``bench.window`` span, in the trace's clock."""
    for name, start, dur, _ in host_spans(trace, WINDOW_SPAN):
        if name == WINDOW_SPAN and dur > 0:
            return start, start + dur
    return None


def _clipped(events: Iterable[Event], window: Interval) -> List[Interval]:
    lo, hi = window
    out = []
    for _, start, dur, _ in events:
        a, b = max(start, lo), min(start + dur, hi)
        if b > a:
            out.append((a, b))
    return out


def union(intervals: List[Interval]) -> List[Interval]:
    merged: List[Interval] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            if b > merged[-1][1]:
                merged[-1] = (merged[-1][0], b)
        else:
            merged.append((a, b))
    return merged


def busy_ns(events: Iterable[Event], window: Interval) -> int:
    """Nanoseconds of `window` in which at least one event ran."""
    return sum(b - a for a, b in union(_clipped(events, window)))


def leaves(events: List[Event]) -> List[Event]:
    """Events that enclose no other event of the same line (an enclosing
    ``while``/``call``/``conditional`` is dropped, its body kept). `events`
    sorted by (start, -duration)."""
    out = []
    for i, ev in enumerate(events):
        end = ev[1] + ev[2]
        nxt = events[i + 1] if i + 1 < len(events) else None
        if nxt is not None and nxt[1] < end and nxt[1] + nxt[2] <= end \
                and nxt[2] < ev[2]:
            continue
        out.append(ev)
    return out


def is_collective(ev: Event) -> bool:
    """A collective that occupies the core: the synchronous operation, or
    the ``-done`` half of an asynchronous one (where the core waits for what
    the ``-start`` half set going; the ``-start`` itself lasts nanoseconds)."""
    op = ev[3].get("op") or ev[0]
    return bool(COLLECTIVE.match(op)) and not op.endswith("-start")


def is_pallas(ev: Event) -> bool:
    """A Mosaic (Pallas) kernel: an HLO custom call to ``tpu_custom_call``."""
    return ev[3].get("target") == "tpu_custom_call"


def summed_s(trace: dict, window: Interval,
             pick: Callable[[Event], bool]) -> Optional[float]:
    """Seconds of the picked leaf events inside `window`, averaged over the
    devices; None when there is no device line."""
    per_device = device_op_events(trace)
    if not per_device:
        return None
    total = 0
    for events in per_device.values():
        total += sum(b - a for a, b in _clipped(
            (e for e in leaves(events) if pick(e)), window))
    return total / len(per_device) / 1e9


def device_summary(trace: dict) -> Optional[dict]:
    """window_s, busy_s (mean over devices) and the breakdown, or None when
    the trace holds no ``bench.window`` span or no device events (run.py
    then has no ``busy_s`` to print, and lastline.py refuses the line)."""
    window = window_of(trace)
    per_device = device_op_events(trace)
    if not per_device or window is None:
        return None
    busy = [busy_ns(evs, window) for evs in per_device.values()]
    by_name: Dict[str, int] = {}
    for evs in per_device.values():
        for ev in leaves(evs):
            for a, b in _clipped([ev], window):
                key = ev[0]
                if ev[3].get("target"):
                    key += f" [{ev[3]['target']}]"
                if ev[3].get("shape"):
                    key += f" -> {ev[3]['shape']}"
                by_name[key] = by_name.get(key, 0) + (b - a)
    n = len(per_device)
    device_ops = sorted(((k, v / n / 1e9) for k, v in by_name.items()),
                        key=lambda kv: -kv[1])[:10]
    first = next(iter(per_device.values()))
    return {
        "window": window,
        "window_s": (window[1] - window[0]) / 1e9,
        "busy_s": sum(busy) / n / 1e9,
        "busy_s_per_device": [b / 1e9 for b in busy],
        "devices": n,
        "device_ops": [[k, v] for k, v in device_ops],
        "idle_gaps": idle_gaps(first, window, host_spans(trace)),
    }


def idle_gaps(events: List[Event], window: Interval,
              spans: List[Event]) -> List[list]:
    """Idle seconds of the first device inside the window, summed by the
    innermost harness span that covers each gap's middle (or ``outside any
    harness span``), largest first, at most ten."""
    lo, hi = window
    gaps = []
    cursor = lo
    for a, b in union(_clipped(events, window)):
        if a > cursor:
            gaps.append((cursor, a))
        cursor = b
    if hi > cursor:
        gaps.append((cursor, hi))
    inner = [s for s in spans if s[0] != WINDOW_SPAN]
    totals: Dict[str, int] = {}
    for a, b in gaps:
        mid = (a + b) // 2
        covering = [s for s in inner if s[1] <= mid < s[1] + s[2]]
        name = (min(covering, key=lambda s: s[2])[0] if covering
                else "outside any harness span")
        totals[name] = totals.get(name, 0) + (b - a)
    return [[k, v / 1e9] for k, v in
            sorted(totals.items(), key=lambda kv: -kv[1])[:10]]


def outline(trace: dict, top: int = 25) -> str:
    """A by-hand look at a trace: planes, lines, and each line's commonest
    event names with one example of their stats."""
    rows = []
    for plane in trace["planes"]:
        rows.append(f"plane {plane['name']!r}: {len(plane['lines'])} lines")
        for line in plane["lines"]:
            evs = line["events"]
            rows.append(f"  line {line['name']!r}: {len(evs)} events")
            agg: Dict[str, list] = {}
            for name, _, dur, stats in evs:
                key = re.sub(r"[.\d]+$", "", name) + " " + stats.get("op", "")
                slot = agg.setdefault(key, [0, 0, stats, name])
                slot[0] += 1
                slot[1] += dur
            for key, (cnt, dur, stats, name) in sorted(
                    agg.items(), key=lambda kv: -kv[1][1])[:top]:
                rows.append(f"    {cnt:7d} x {dur / 1e6:10.3f} ms  {key!r} "
                            f"e.g. {name!r} {json.dumps(stats)[:300]}")
    return "\n".join(rows)
