"""Per-collective trace events synthesized from the XLA profiler.

Parity with the reference's hand-instrumented TP collectives
(/root/reference/megatron/core/tensor_parallel/mappings.py:27-60 records
group + bytes per op; /root/reference/megatron/training/trace.py:371-380
derives per-op Gbps) — but TPU-first: XLA inserts the collectives during
SPMD partitioning, so host code never sees them. Instead we

1. statically read the compiled HLO for every collective instruction
   (kind, output bytes, replica groups → mesh axes), and
2. capture one profiled execution (``jax.profiler.trace`` emits a Chrome
   trace with per-device X events carrying ``args.hlo_op``), then

join the two on the HLO op name into tracer-contract event dicts
({pid, name, ts, dur, args:{id, group, bytes, bandwidth_gbps,
iteration}}) that flow through trace/dependency.py ``build_dependencies``
and trace/detect.py stage 2 unchanged. The same join gives every other
device operation its part of the model (``device_op_events``: the compiled
text keeps each instruction's ``jax.named_scope``, trace/scope_map.py reads
it). This also restores collective
visibility on backends without host callbacks (trace/tracer.py
``callbacks_supported``): the profiler path needs no in-graph
instrumentation at all.
"""

from __future__ import annotations

import glob
import gzip
import json
import os
import re
import tempfile
from typing import Any, Callable, Dict, List, Optional, Sequence

import numpy as np

COLLECTIVE_KINDS = ("all-reduce", "all-gather", "reduce-scatter",
                    "collective-permute", "all-to-all")

_DTYPE_BYTES = {"f64": 8, "f32": 4, "f16": 2, "bf16": 2, "s32": 4,
                "u32": 4, "s64": 8, "u64": 8, "s16": 2, "u16": 2,
                "s8": 1, "u8": 1, "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_COLLECTIVE_OPCODES = frozenset((
    "all-reduce-start", "all-gather-start", "reduce-scatter", "all-reduce",
    "all-gather", "collective-permute-start", "collective-permute",
    "all-to-all"))
_GROUPS_RE = re.compile(r"replica_groups=(\{\{[\d,{}\s]*\}\}|\[[^\]]*\]"
                        r"<=\[[^\]]*\](?:T\([\d,]*\))?)")
_SRC_TGT_RE = re.compile(r"source_target_pairs=\{([\d,{}\s]*)\}")


def _shape_bytes(shape_text: str, result_only: bool = False) -> int:
    """'f32[32,64]{1,0}' or '(f32[8], f32[8])' → payload bytes.

    result_only: async '-start' ops have tuple shapes holding (operands,
    results); count only the result half so bytes are not double-counted
    (e.g. all-reduce-start's (in, out) pair)."""
    shapes = _SHAPE_RE.findall(shape_text)
    if result_only and len(shapes) > 1:
        shapes = shapes[len(shapes) // 2:]
    total = 0
    for dtype, dims in shapes:
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def _parse_groups(text: str) -> List[List[int]]:
    """Decode replica_groups: explicit '{{0,1},{2,3}}' or iota
    '[2,2]<=[4]' / '[2,2]<=[2,2]T(1,0)'."""
    if text.startswith("{{"):
        return [[int(x) for x in g.split(",") if x.strip()]
                for g in re.findall(r"\{([\d,\s]*)\}", text[1:-1])]
    m = re.match(r"\[([\d,]*)\]<=\[([\d,]*)\](?:T\(([\d,]*)\))?", text)
    if not m:
        return []
    gshape = [int(x) for x in m.group(1).split(",")]
    dims = [int(x) for x in m.group(2).split(",")]
    ids = np.arange(int(np.prod(dims))).reshape(dims)
    if m.group(3):
        ids = ids.transpose([int(x) for x in m.group(3).split(",")])
    return ids.reshape(gshape).tolist()


def _axes_of_groups(groups: List[List[int]], mesh) -> str:
    """Mesh axes a collective spans: axes whose coordinate varies within
    a participant group (e.g. tp for the TP all-reduce)."""
    if mesh is None or not groups or len(groups[0]) < 2:
        return ""
    # A partitioned module's groups name PARTITIONS: positions in the jit's
    # device assignment, which is the mesh's devices in row-major order,
    # not device ids (a v5e 2x2's mesh holds ids 0, 1, 3, 2).
    shape = np.shape(mesh.devices)
    n = int(np.prod(shape))
    g = [np.unravel_index(d, shape) if 0 <= d < n else None
         for d in groups[0]]
    if any(c is None for c in g):
        return ""
    varying = [mesh.axis_names[i] for i in range(len(mesh.axis_names))
               if len({c[i] for c in g}) > 1]
    return "x".join(varying)


def collectives_of(parsed, mesh=None) -> Dict[str, dict]:
    """HLO op name → {kind, bytes, groups, axes, in_loop} for every
    collective of a parsed module (``scope_map.parse_hlo_text``, the one
    reader of HLO text): the static half of the join. ``in_loop``: the
    instruction lives in a ``while`` body (or in what one calls), so it runs
    once an iteration and not once a step."""
    out: Dict[str, dict] = {}
    loops = parsed.loop_computations()
    for ins in parsed.instructions.values():
        if ins.opcode not in _COLLECTIVE_OPCODES:
            continue
        is_async = ins.opcode.endswith("-start")
        kind = ins.opcode.replace("-start", "")
        info = {"kind": kind,
                "bytes": _shape_bytes(ins.shape, result_only=is_async)}
        gm = _GROUPS_RE.search(ins.line)
        groups = _parse_groups(gm.group(1)) if gm else []
        if not groups and kind == "collective-permute":
            pm = _SRC_TGT_RE.search(ins.line)
            if pm:
                pairs = re.findall(r"\{(\d+),(\d+)\}", "{" + pm.group(1) + "}")
                members = sorted({int(a) for p in pairs for a in p})
                groups = [members]
        info["groups"] = groups
        info["axes"] = _axes_of_groups(groups, mesh)
        info["in_loop"] = ins.computation in loops
        out[ins.name] = info
    return out


def extract_hlo_collectives(hlo_text: str, mesh=None) -> Dict[str, dict]:
    """``collectives_of`` a compiled module's text."""
    from megatronapp_tpu.trace.scope_map import parse_hlo_text
    return collectives_of(parse_hlo_text(hlo_text), mesh)


def _attach_thread_ordinals(payload_events: List[dict],
                            events: List[dict]) -> None:
    """Synthesize ``args.device_ordinal`` on profiler builds that report
    all devices under ONE host plane.

    Newer jax profilers emit one Chrome-trace pid per device plane and a
    ``device_ordinal`` arg; the 0.4.x CPU profiler instead reports a
    single '/host:CPU' pid whose per-device EXECUTION THREADS carry the
    HLO X events (thread_name 'tf_XLATfrtCpuClient/...'). Map each thread
    that executed HLO ops to a device ordinal by thread_sort_index order
    (the profiler assigns them in device order) so the per-device pid
    attribution downstream keeps working."""
    missing = [e for e in events
               if "device_ordinal" not in e.get("args", {})]
    if not missing:
        return
    sort_index: Dict[tuple, int] = {}
    for e in payload_events:
        if e.get("ph") == "M" and e.get("name") == "thread_sort_index":
            sort_index[(e.get("pid"), e.get("tid"))] = int(
                e["args"]["sort_index"])
    # Only UNannotated threads get synthesized ordinals, numbered after
    # any real annotated ordinals so a mixed trace (device planes
    # annotated, host-plane HLO events not) never aliases a host thread
    # onto an existing device.
    annotated = {int(e["args"]["device_ordinal"]) for e in events
                 if "device_ordinal" in e.get("args", {})}
    base = max(annotated) + 1 if annotated else 0
    exec_threads = sorted(
        {(e.get("pid"), e.get("tid")) for e in missing},
        key=lambda k: (sort_index.get(k, 1 << 30), k))
    ordinal_of = {k: base + i for i, k in enumerate(exec_threads)}
    for e in missing:
        e.setdefault("args", {})["device_ordinal"] = \
            ordinal_of[(e.get("pid"), e.get("tid"))]


_DEVICE_PROCESS = re.compile(r"^/device:\w+:(\d+)")
_OPS_THREAD = "XLA Ops"


def _name_device_line_events(payload_events: List[dict]) -> None:
    """Give a TPU's device operations the ``args.hlo_op`` and
    ``args.device_ordinal`` that a CPU's carry.

    A TPU's Chrome trace has one process a chip (``/device:TPU:<n>``) whose
    thread ``XLA Ops`` holds one X event an executed instruction, NAMED by
    the instruction (``fusion.12``) and with ``long_name``, ``hlo_category``
    and byte counts for arguments: no ``hlo_op`` (my chip run, PR 36, where
    the filter below found 0 events of a traced step's 3 windows)."""
    ordinal, ops_threads = {}, set()
    for e in payload_events:
        if e.get("ph") != "M":
            continue
        if e.get("name") == "process_name":
            m = _DEVICE_PROCESS.match(str(e["args"].get("name", "")))
            if m:
                ordinal[e.get("pid")] = int(m.group(1))
        elif e.get("name") == "thread_name" \
                and e["args"].get("name") == _OPS_THREAD:
            ops_threads.add((e.get("pid"), e.get("tid")))
    for e in payload_events:
        if e.get("ph") == "X" and e.get("pid") in ordinal \
                and (e.get("pid"), e.get("tid")) in ops_threads:
            args = e.setdefault("args", {})
            args.setdefault("hlo_op", e["name"])
            args.setdefault("device_ordinal", ordinal[e["pid"]])


def parse_profile_dir(trace_dir: str, cleanup: bool = False) -> List[dict]:
    """Read a jax.profiler output directory → the raw per-device
    Chrome-trace X events that carry an hlo_op."""
    paths = sorted(glob.glob(
        os.path.join(trace_dir, "**", "*.trace.json.gz"), recursive=True))
    events: List[dict] = []
    if paths:
        with gzip.open(paths[-1]) as f:
            payload = json.load(f)
        all_events = payload.get("traceEvents", [])
        _name_device_line_events(all_events)
        events = [e for e in all_events
                  if e.get("ph") == "X" and "hlo_op" in e.get("args", {})]
        _attach_thread_ordinals(all_events, events)
    if cleanup:
        import shutil
        shutil.rmtree(trace_dir, ignore_errors=True)
    return events


def profile_run(run: Callable[[], Any],
                trace_dir: Optional[str] = None) -> List[dict]:
    """Execute ``run`` under jax.profiler and return the raw per-device
    Chrome-trace X events that carry an hlo_op.

    The fence is a device_get of the smallest output leaf: the profiler
    must not stop before the step ran."""
    import jax

    own = trace_dir is None
    trace_dir = trace_dir or tempfile.mkdtemp(prefix="jax_prof_")
    with jax.profiler.trace(trace_dir):
        out = run()
        leaves = [l for l in jax.tree.leaves(out) if hasattr(l, "size")]
        if leaves:
            jax.device_get(min(leaves, key=lambda l: l.size))
        jax.block_until_ready(out)
    return parse_profile_dir(trace_dir, cleanup=own)


def collective_events(raw_events: Sequence[dict],
                      hlo_info: Dict[str, dict],
                      iteration: int = 0,
                      id_base: int = 0,
                      process_index: Optional[int] = None,
                      local_device_count: Optional[int] = None
                      ) -> List[dict]:
    """Join profiler events with HLO metadata into tracer-contract
    records (trace/dependency.py: args carries id/group/bytes;
    trace/detect.py stage 2 keys on the collective name prefixes).

    Each local device gets its own timeline (the reference's per-GPU
    process granularity): pid = 1000*(process+1) + local ordinal — a
    range disjoint from process pids so device rows never collide with
    the host-side schedule records. The profiler reports LOCAL ordinals;
    replica groups contain GLOBAL device ids, so membership is checked
    against process*local_count + ordinal. args carries 'process' (owner,
    for detector stage-2 attribution) and 'device' (global id)."""
    import jax

    if process_index is None:
        process_index = jax.process_index()
    if local_device_count is None:
        local_device_count = jax.local_device_count()
    out: List[dict] = []
    next_id = id_base
    for e in sorted(raw_events, key=lambda x: (x.get("ts", 0.0))):
        op = e["args"]["hlo_op"]
        base = op.split(".")[0]
        info = hlo_info.get(op) or hlo_info.get(base)
        if info is None or info["kind"] not in COLLECTIVE_KINDS:
            continue
        ordinal = int(e["args"].get("device_ordinal", e.get("pid", 0)))
        dev = process_index * local_device_count + ordinal
        group = next((g for g in info["groups"] if dev in g),
                     info["groups"][0] if info["groups"] else [])
        dur_us = float(e.get("dur", 0.0))
        gbps = (info["bytes"] * 8e-3 / dur_us) if dur_us > 0 else 0.0
        out.append({
            "ph": "X", "pid": 1000 * (process_index + 1) + ordinal,
            "tid": e.get("tid", 0),
            "name": info["kind"], "ts": float(e["ts"]), "dur": dur_us,
            "args": {"id": next_id, "hlo_op": op, "group": group,
                     "bytes": info["bytes"], "axes": info["axes"],
                     "bandwidth_gbps": round(gbps, 3),
                     "process": process_index, "device": dev,
                     "iteration": iteration},
        })
        next_id += 1
    return out


_CONTROL_FLOW = frozenset(("while", "conditional", "call"))


def device_op_events(raw_events: Sequence[dict], smap,
                     iteration: int = 0,
                     process_index: Optional[int] = None) -> List[dict]:
    """Every other device operation of the profiled execution as a record
    named by its HLO instruction, with ``args.part`` / ``args.pass`` from
    the step's scope map `smap` (trace/scope_map.py), so that the merged Chrome
    trace shows operators by part of the model. Collectives are left to
    ``collective_events``; an operation that encloses others (``while``,
    ``conditional``, ``call``) is left out, so durations add up."""
    import jax

    if process_index is None:
        process_index = jax.process_index()
    out: List[dict] = []
    for e in raw_events:
        op = e["args"]["hlo_op"]
        scoped = smap.instructions.get(op)
        if scoped is None or op in smap.collectives \
                or scoped.opcode in _CONTROL_FLOW:
            continue
        ordinal = int(e["args"].get("device_ordinal", e.get("pid", 0)))
        out.append({
            "ph": "X", "pid": 1000 * (process_index + 1) + ordinal,
            "tid": e.get("tid", 0), "name": op, "ts": float(e["ts"]),
            "dur": float(e.get("dur", 0.0)),
            "args": {"hlo_op": op, "part": scoped.part,
                     "pass": scoped.pass_, "process": process_index,
                     "iteration": iteration},
        })
    return out


def profile_step_collectives(compiled, run: Callable[[], Any], mesh=None,
                             iteration: int = 0) -> List[dict]:
    """One-call convenience: HLO metadata from ``compiled`` (a
    jax.stages.Compiled) + one profiled execution of ``run`` → joined
    collective event records."""
    info = extract_hlo_collectives(compiled.as_text(), mesh)
    raw = profile_run(run)
    return collective_events(raw, info, iteration=iteration)
