"""Per-decode-step dispatch accounting (ISSUE 11 observability).

At decode batch sizes the per-token step is dispatch-dominated, not
FLOP-dominated (PERF.md round-2: 35.7% MFU for the full step vs 63.6%
for one layer body) — so the megakernel work's figure of merit is "how
many kernels does one decode step launch", measured deterministically
(no wall clock, no chip needed).

Two probes, both off the traced/compiled module:

``jaxpr_launch_stats`` — the GATE metric. Walks the closed jaxpr of the
decode step and estimates kernel launches per executed step: each
``pallas_call`` is exactly ONE launch (a TPU custom call — on CPU the
interpret-mode expansion is a simulation detail, which is why the CPU
HLO text is NOT the gate: it inlines the kernels and inverts the
comparison), a ``scan`` contributes length × its body's launches plus
ceil(length / unroll) loop steps (the while-iteration overhead the
scan-unroll lever removes), and ordinary equations count one launch
apiece minus a small free-op set (reshape & friends never dispatch).
Pre-fusion op counts overestimate both A/B legs the same way, so the
REDUCTION is sound; tests and tools/megakernel_benchmark.py gate on it.

``module_dispatch_stats`` / ``compiled_stats`` — the RECORD metrics:
optimized-HLO fusion/custom-call/while counts plus the XLA cost-model
totals (flops, bytes accessed) of the actually-compiled module, reported
alongside for the round tables; not yet validated on the chip.
"""

from __future__ import annotations

import math
import re
from typing import Dict, Optional

# Equations that never become their own kernel launch (pure
# layout/metadata in XLA).
_FREE_PRIMS = frozenset({
    "reshape", "squeeze", "expand_dims", "broadcast_in_dim",
    "stop_gradient", "copy",
})

# Call-like primitives whose sub-jaxpr executes inline exactly once.
_CALL_PARAM_KEYS = ("jaxpr", "call_jaxpr", "fun_jaxpr")


def _sub_jaxpr(v):
    return v.jaxpr if hasattr(v, "jaxpr") else v


def jaxpr_launch_stats(jaxpr) -> Dict[str, float]:
    """Estimated kernel launches for one execution of `jaxpr`
    (jax.make_jaxpr output or an inner jaxpr). Returns
    {launches, kernels (pallas calls), loop_steps, eqns}."""
    launches = kernels = loop_steps = eqns = 0
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        eqns += 1
        if name == "pallas_call":
            kernels += 1
            launches += 1
            continue
        if name == "scan":
            length = int(eqn.params.get("length", 1))
            unroll = int(eqn.params.get("unroll", 1) or 1)
            inner = jaxpr_launch_stats(_sub_jaxpr(eqn.params["jaxpr"]))
            launches += length * inner["launches"]
            kernels += length * inner["kernels"]
            loop_steps += (math.ceil(length / max(1, unroll))
                           + length * inner["loop_steps"])
            continue
        if name == "while":
            # Trip count is data-dependent: count the body once and one
            # loop step (decode steps built here carry no bare whiles;
            # scans are the loop of record).
            inner = jaxpr_launch_stats(_sub_jaxpr(eqn.params["body_jaxpr"]))
            launches += inner["launches"]
            kernels += inner["kernels"]
            loop_steps += 1 + inner["loop_steps"]
            continue
        if name == "cond":
            branches = [jaxpr_launch_stats(_sub_jaxpr(b))
                        for b in eqn.params["branches"]]
            worst = max(branches, key=lambda s: s["launches"])
            launches += worst["launches"]
            kernels += worst["kernels"]
            loop_steps += worst["loop_steps"]
            continue
        handled = False
        for key in _CALL_PARAM_KEYS:
            if key in eqn.params:
                inner = jaxpr_launch_stats(_sub_jaxpr(eqn.params[key]))
                launches += inner["launches"]
                kernels += inner["kernels"]
                loop_steps += inner["loop_steps"]
                handled = True
                break
        if handled:
            continue
        if name not in _FREE_PRIMS:
            launches += 1
    return {"launches": launches, "kernels": kernels,
            "loop_steps": loop_steps, "eqns": eqns}


def launch_stats(fn, *args, **kwargs) -> Dict[str, float]:
    """jaxpr_launch_stats of `fn` traced at the given (abstract or
    concrete) arguments. `fn` may be jitted (the pjit wrapper is
    recursed through) — nothing is compiled or executed."""
    import jax
    closed = jax.make_jaxpr(fn)(*args, **kwargs)
    stats = jaxpr_launch_stats(closed.jaxpr)
    stats["dispatches_per_step"] = stats["launches"] + stats["loop_steps"]
    return stats


# ---------------------------------------------------------------------------
# Compiled-module record metrics (optimized HLO text + XLA cost model)
# ---------------------------------------------------------------------------

_HDR = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s*(?:\(.*)?\{\s*$")
_WHILE_BODY = re.compile(r"\bbody=%?([\w.\-]+)")


def _split_computations(hlo_text: str) -> Dict[str, str]:
    """{computation name: body text} from HLO long text. Line-based:
    computation headers end with '{' and bodies close with a bare '}'
    (inline one-line metadata braces never span lines)."""
    comps: Dict[str, str] = {}
    name = None
    buf: list = []
    for line in hlo_text.splitlines():
        if name is None:
            m = _HDR.match(line.strip())
            if m and "=" not in line.split("{")[0]:
                name = m.group(2)
                buf = []
        else:
            if line.strip() == "}":
                comps[name] = "\n".join(buf)
                name = None
            else:
                buf.append(line)
    return comps


def module_dispatch_stats(hlo_text: str) -> Dict:
    """Fusion / custom-call / while counts of one optimized HLO module,
    split into while-loop bodies vs the rest. NOTE: on CPU the
    interpret-mode Pallas kernels are inlined into ordinary HLO here —
    these counts are the record of what THIS backend compiled, not the
    TPU launch count (jaxpr_launch_stats is the gate)."""
    comps = _split_computations(hlo_text)
    body_names = set(_WHILE_BODY.findall(hlo_text))
    in_loop = {"fusions": 0, "custom_calls": 0}
    out_loop = {"fusions": 0, "custom_calls": 0}
    for name, body in comps.items():
        # Fusion computations' insides execute as ONE kernel — count
        # only the call sites.
        if name.startswith("fused_computation"):
            continue
        tgt = in_loop if name in body_names else out_loop
        tgt["fusions"] += len(re.findall(r"=\s*\S+\s+fusion\(", body))
        tgt["custom_calls"] += len(
            re.findall(r"=\s*\S+\s+custom-call\(", body))
    return {"computations": len(comps),
            "while_loops": len(body_names),
            "in_loop": in_loop, "out_of_loop": out_loop}


def compiled_stats(jitted, *args, **kwargs) -> Dict:
    """Lower + compile `jitted` at the given (abstract or concrete)
    arguments: module_dispatch_stats of the optimized HLO plus the XLA
    cost-model totals (flops / bytes accessed) when the backend exposes
    them. This is an AOT compile — one extra compilation at these
    shapes; callers cache."""
    compiled = jitted.lower(*args, **kwargs).compile()
    stats = module_dispatch_stats(compiled.as_text())
    try:
        cost = compiled.cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0] if cost else {}
        stats["cost"] = {k: float(cost[k])
                         for k in ("flops", "bytes accessed")
                         if k in cost}
    except Exception:  # noqa: BLE001 — cost model is backend-optional
        pass
    return stats
