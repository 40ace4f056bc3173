"""Device time by part of the model: a device trace joined, once a run, to
the scope maps the program makes of its own compiled steps.

A TPU trace names an operation by its HLO instruction (``fusion.158``) and
carries no ``jax.named_scope``; the compiled text of the step does
(``metadata={op_name=".../attention/dot_general"}``), and
``megatronapp_tpu.trace.scope_map`` keeps, for every hot-path step the
program registered, ``{instruction -> part, pass}`` made from that text on
demand. ``table(run)`` joins the two inside ``device_summary["window"]``:

- an ``XLA Ops`` leaf belongs to the ``XLA Modules`` event it started in
  (the same line of the same plane says ``jit__decode_traced(<id>)`` ran
  then), so two modules whose instruction names collide stay apart;
- a module event is read by the map of the same module name that explains
  most of its time; an instruction counts as explained only where its name,
  its opcode and its result's first ``dtype[dims]`` all agree with the
  compiled text, so a map of another program does not pass for this one's;
- seconds are summed over leaves and averaged over the chips, like
  ``trace_reduce.summed_s``, by (module kind: ``train``, ``decode``,
  ``prefill``, ``sampler``; part; pass). What no map explains is
  ``unmatched``: the parts, ``other`` and ``unmatched`` add up to the
  window's summed leaf seconds by construction.

It runs in the run's own process, after the window, and so reaches the
registry directly; making the maps lowers and compiles each registered step
once more (``compile_s`` says for how long). On a program without the
registry (an older commit), with a step that would not lower, or with no
trace, every reader gives 0.0: never ``None``, never an error.
"""

from __future__ import annotations

import bisect
import re
from typing import Dict, List, Optional, Tuple

from perfbench import program_spans as ps
from perfbench import trace_reduce

MODULES_LINE = "XLA Modules"
OTHER, UNMATCHED = "other", "unmatched"
_ARRAY = re.compile(r"\w+\[[\d,]*\]")


def _program_maps() -> list:
    try:
        from megatronapp_tpu.trace.scope_map import scope_maps
    except ImportError:                 # a program from before the registry
        return []
    return scope_maps()


def _module_events(trace: dict) -> Dict[str, List[list]]:
    """Per device plane, its ``XLA Modules`` events sorted by start."""
    out = {}
    for plane in trace["planes"]:
        for line in plane["lines"]:
            if line["name"] == MODULES_LINE:
                out[plane["name"]] = sorted(line["events"],
                                            key=lambda e: e[1])
    return out


def _explains(scoped, ev) -> bool:
    op = ev[3].get("op")
    head = _ARRAY.search(ev[3].get("shape", ""))
    return (not op or scoped.opcode == op) and (
        head is None or not scoped.shape or scoped.shape == head.group(0))


def join(trace: dict, window: Tuple[int, int], maps: list) -> dict:
    """The whole table; see the module's docstring."""
    per_device = trace_reduce.device_op_events(trace)
    modules = _module_events(trace)
    by_name: Dict[str, list] = {}
    for m in maps:
        by_name.setdefault(m.module, []).append(m)
    compile_s: Dict[str, float] = {}
    for m in maps:
        key = f"{m.kind} {m.module}"
        compile_s[key] = compile_s.get(key, 0.0) + m.compile_s
    n = max(1, len(per_device))
    seconds: Dict[Tuple[str, str, str], float] = {}
    collectives: Dict[Tuple[str, str], float] = {}
    other: Dict[Tuple[str, str, str], float] = {}
    unmatched: Dict[Tuple[str, str], float] = {}
    by_module: Dict[str, dict] = {}
    leaf_s = 0.0
    for plane, events in per_device.items():
        mods = modules.get(plane, [])
        starts = [e[1] for e in mods]
        groups: Dict[Optional[str], list] = {}
        for ev in trace_reduce.leaves(events):
            a, b = max(ev[1], window[0]), min(ev[1] + ev[2], window[1])
            if b <= a:
                continue
            i = bisect.bisect_right(starts, ev[1]) - 1
            inside = i >= 0 and ev[1] < mods[i][1] + mods[i][2]
            groups.setdefault(mods[i][0] if inside else None, []).append(
                (ev, (b - a) / 1e9 / n))
        for module, evs in groups.items():
            # No modules line (a CPU rehearsal): any map may explain it.
            base = None if module is None else module.split("(")[0]
            candidates = maps if base is None else by_name.get(base, [])
            best, best_s = None, 0.0
            for m in candidates:
                s = sum(sec for ev, sec in evs
                        if ev[0] in m.instructions
                        and _explains(m.instructions[ev[0]], ev))
                if s > best_s:
                    best, best_s = m, s
            row = by_module.setdefault(
                base or "(no module)",
                {"kind": best.kind if best else None, "s": 0.0,
                 "unmatched_s": 0.0})
            for ev, sec in evs:
                leaf_s += sec
                row["s"] += sec
                scoped = best.instructions.get(ev[0]) if best else None
                if scoped is None or not _explains(scoped, ev):
                    row["unmatched_s"] += sec
                    key = (base or "", ev[0] + " " + ev[3].get("op", "")
                           + " " + ev[3].get("shape", ""))
                    unmatched[key] = unmatched.get(key, 0.0) + sec
                    continue
                key = (best.kind, scoped.part, scoped.pass_)
                seconds[key] = seconds.get(key, 0.0) + sec
                if scoped.part == OTHER:
                    okey = (best.kind, ev[0] + " " + scoped.opcode + " "
                            + scoped.shape, scoped.op_name)
                    other[okey] = other.get(okey, 0.0) + sec
                if trace_reduce.is_collective(ev):
                    ckey = (best.kind, scoped.part)
                    collectives[ckey] = collectives.get(ckey, 0.0) + sec
    return {
        "seconds": seconds, "leaf_s": leaf_s,
        "unmatched_s": sum(unmatched.values()),
        "by_module": by_module, "collectives": collectives,
        "other": other, "unmatched": unmatched,
        "compile_s": compile_s, "maps": len(maps),
    }


def table(run) -> Optional[dict]:
    """``join`` of this run, made once; None where there is no trace."""
    if "scope_time" not in run:
        summary = run.get("device_summary")
        run["scope_time"] = None if not summary else join(
            run["trace"], summary["window"],
            run["scope_maps"] if "scope_maps" in run else _program_maps())
    return run["scope_time"]


def part_s(run, kind: str, parts=None) -> float:
    """Device seconds of the window in `kind` modules' operations whose part
    is one of `parts` (all of them when None), both passes."""
    t = table(run)
    if not t:
        return 0.0
    return sum(s for (k, part, _), s in t["seconds"].items()
               if k == kind and (parts is None or part in parts))


def ms_per_step(run, kind: str, parts=None) -> float:
    steps = run.get("traced_steps")
    return part_s(run, kind, parts) * 1e3 / steps if steps else 0.0


def ms_per_round(run, kind: str, parts=None) -> float:
    summary = run.get("device_summary")
    if not summary:
        return 0.0
    rounds = ps.rounds_in(ps.program_spans(run), summary["window"])
    return part_s(run, kind, parts) * 1e3 / rounds if rounds else 0.0


def unmatched_share(run) -> float:
    """Per cent of the window's summed device time in operations that no
    map explains; 0.0 where the program made no map at all (there is no
    yardstick to be unhealthy)."""
    t = table(run)
    if not t or not t["maps"] or not t["leaf_s"]:
        return 0.0
    return 100.0 * t["unmatched_s"] / t["leaf_s"]
