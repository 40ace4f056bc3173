"""Launch counts of a traced step (``GET /stats``' ``decode_dispatch``).

``jaxpr_launch_stats`` walks a closed jaxpr and estimates the kernel
launches of one execution: each ``pallas_call`` is ONE launch (a TPU
custom call — on the CPU the interpret-mode expansion is a simulation
detail, which is why the count is taken from the jaxpr and not from
compiled HLO), a ``scan`` contributes length × its body's launches plus
ceil(length / unroll) loop steps, and ordinary equations count one
launch apiece minus a small free-op set (reshape & friends never
dispatch). It counts equations before XLA fuses them, so `launches`
overestimates; `kernels`, the pallas_calls a step, is exact, and is what
chip_smoke.py checks of a served decode step. ``stack_slices`` counts the
equations that cut one layer's array out of a stack: for a custom call
(a grouped GEMM, a Pallas kernel) such a slice is a copy of the layer.
``scatters`` counts the scatter equations (a TPU writes their rows one
after another). Nothing is compiled or executed.
"""

from __future__ import annotations

import math
from typing import Dict, Iterable

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


def _inner_jaxprs(eqn):
    """Every jaxpr among an equation's parameters: a loop's body, a cond's
    branches, a call's callee."""
    for v in eqn.params.values():
        for j in v if isinstance(v, (tuple, list)) else (v,):
            j = getattr(j, "jaxpr", j)
            if hasattr(j, "eqns"):
                yield j


def stack_slices(jaxpr, shapes: Iterable[tuple]) -> int:
    """Equations of `jaxpr` (its inner jaxprs included) that cut an array
    of one of `shapes` out of a stack of them: a ``slice`` or
    ``dynamic_slice`` with such a result, leading 1s aside, and a ``scan``
    for each of its xs that reaches the body with such a shape. Equations
    are counted, not executions: 2 for a layer loop that slices two
    stacks."""
    shapes = {tuple(s) for s in shapes}
    n = 0
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name in ("slice", "dynamic_slice"):
            shape = tuple(eqn.outvars[0].aval.shape)
            while shape[:1] == (1,):
                shape = shape[1:]
            n += shape in shapes
        elif name == "scan":
            body = _sub_jaxpr(eqn.params["jaxpr"])
            xs = body.invars[eqn.params["num_consts"]
                             + eqn.params["num_carry"]:]
            n += sum(tuple(v.aval.shape) in shapes for v in xs)
        n += sum(stack_slices(j, shapes) for j in _inner_jaxprs(eqn))
    return n


def scatters(jaxpr) -> int:
    """Equations of `jaxpr` (its inner jaxprs included) that scatter:
    ``scatter``, ``scatter-add`` and kin, what ``x.at[i].set`` / ``.add``
    and the transpose of a gather trace to. Equations are counted, not
    executions."""
    return sum(eqn.primitive.name.startswith("scatter")
               + sum(scatters(j) for j in _inner_jaxprs(eqn))
               for eqn in jaxpr.eqns)


def page_copies(jaxpr) -> Dict[str, Dict]:
    """What a step of each paged walk in `jaxpr` (its inner jaxprs
    included) copies, by kernel name, as `kernel_gen._walk_call` wrote it
    into its pallas_call's metadata: `page_copies_step` (pages a step x
    pools), `page_copy_bytes` (one copy's bytes, a pool) and
    `page_copies_kernel` (how many of a step's copies the kernel starts
    itself; the pipeline brings the others as blocked operands)."""
    found: Dict[str, Dict] = {}
    for eqn in jaxpr.eqns:
        meta = eqn.params.get("metadata") or {}
        if eqn.primitive.name == "pallas_call" and "page_copies_step" in meta:
            found[eqn.params["name"]] = {
                "page_copies_step": int(meta["page_copies_step"]),
                "page_copy_bytes": [
                    int(n) for n in meta["page_copy_bytes"].split("+")],
                "page_copies_kernel": int(meta["page_copies_kernel"])}
        for inner in _inner_jaxprs(eqn):
            found.update(page_copies(inner))
    return found


def launch_stats(fn, *args, slice_shapes: Iterable[tuple] = ()
                 ) -> Dict[str, float]:
    """jaxpr_launch_stats of `fn` traced at the given (abstract or
    concrete) arguments; under `expert_stack_slices` its stack_slices
    of `slice_shapes` (one layer's expert kernels); under `scatters` its
    scatter equations; and `page_copies`'
    three counts, each a dict by paged kernel name. `fn` may be jitted
    (the pjit wrapper is recursed through) — nothing is compiled or
    executed."""
    import jax
    closed = jax.make_jaxpr(fn)(*args)
    stats = jaxpr_launch_stats(closed.jaxpr)
    stats["dispatches_per_step"] = stats["launches"] + stats["loop_steps"]
    stats["expert_stack_slices"] = stack_slices(closed.jaxpr, slice_shapes)
    stats["scatters"] = scatters(closed.jaxpr)
    walks = page_copies(closed.jaxpr)
    for key in ("page_copies_step", "page_copy_bytes", "page_copies_kernel"):
        stats[key] = {name: walk[key] for name, walk in walks.items()}
    return stats
