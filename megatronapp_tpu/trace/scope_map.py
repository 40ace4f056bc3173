"""Which part of the model a compiled instruction belongs to.

A device trace names an operation by its HLO instruction (``fusion.158``,
``copy.48``) and carries no ``jax.named_scope``. The scope is not lost in
the program, though: ``jitted.lower(...).compile().as_text()`` keeps
``metadata={op_name="jit(step)/.../attention/dot_general"}`` on every
instruction, fusions included, and a backward operation keeps its scope
behind ``transpose(jvp(...))``. This module is the one place that reads that
text:

- ``parse_hlo_text`` is the one pass over a compiled module's text that
  knows HLO's instruction syntax (``profiler_collectives
  .extract_hlo_collectives`` is a view of it);
- ``scope_map`` turns a parse into ``{instruction short name -> part,
  pass}``: `part` is the innermost segment of the instruction's ``op_name``
  that is one of ``PARTS``, looked for behind every wrapper
  (``transpose(...)``, ``jvp(...)``, ``vmap(...)``, ``jit(...)``,
  ``checkpoint``/``rematted_computation``, ``shard_map``, ``while/body``,
  ``cond/branch``); `pass` is ``bwd`` under a ``transpose(`` (a
  rematerialised forward too: it runs in the backward pass), else ``fwd``;
  an instruction in no part is ``other`` and keeps its raw ``op_name``.
  **A fusion is attributed whole to its root's part** (XLA gives a fusion
  its root's metadata; where it gave none, the root of the fused
  computation is asked): a matmul fused under an accumulating ``add``
  counts where the ``add`` was written. Nothing tries to split a fusion;
- a process-wide registry of the hot-path steps. Where a step is built it
  is registered (``register``; ``noted`` for a plain ``jax.jit``), and at
  its first call with a shape the call's ABSTRACT arguments are kept
  (``ShapeDtypeStruct`` with sharding, never an array: pools are donated,
  weights are large). Nothing is lowered then. ``scope_maps()`` lowers and
  compiles what is registered, on demand, once a (step, shapes), and
  never raises: a step that cannot be lowered yields no map and a log line.

The registry holds the newest ``MAX_STEPS`` steps strongly (their maps are
read after the engine or the training loop that built them has gone), so a
registered function must not close over arrays.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import logging
import re
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

logger = logging.getLogger(__name__)

# The parts the program names with jax.named_scope, where the work is
# written: a layer's halves (transformer/block.py), the embedders and heads
# (models/), the train step's accumulation and update (training/
# train_step.py). perfbench/scope_time.py and its metrics read them.
PARTS = ("attention", "ssm", "conv", "mlp", "moe", "embedding", "head",
         "grad_accum", "optimizer")
# What tells two kinds of layer apart INSIDE a part: a sliding-window stack's
# window layers run their attention under `attention/window`
# (transformer/block.py). A sub-part is no part: the window layers' time
# stays in `attention`, and Scoped.sub says which of it is theirs. A Mamba-2
# mixer's chunked scan and its gated norm are written under `ssm/ssd_chunk`
# and `ssm/gated_norm` (transformer/ssm.py); a Kimi-delta-attention mixer, a
# recurrent mixer too, runs under part `ssm` with its chunked pass, its decode
# kernel and its gated head norm under `ssm/kda_chunk`, `ssm/kda_update` and
# `ssm/kda_gate_norm` (transformer/kda.py).
SUBPARTS = {"attention": ("window",),
            "ssm": ("ssd_chunk", "gated_norm", "kda_chunk", "kda_update",
                    "kda_gate_norm")}
OTHER = "other"
MAX_STEPS = 16
# An option at its default value: the compiled program is the same, the
# in-memory executable cache's key is not (RegisteredStep.map_for).
_NOT_FROM_MEMORY = {"xla_embed_ir_in_executable": False}

_MODULE = re.compile(r"^HloModule\s+([^\s,]+)")
_COMPUTATION = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s+\(.*\{\s*$")
_NAME = re.compile(r"^\s*(ROOT\s+)?%?([\w.\-]+)\s+=\s+")
_OPCODE = re.compile(r"\s*([a-z][\w\-]*)\(")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_CALLS = re.compile(r"calls=%?([\w.\-]+)")
# Every computation an instruction runs: a fusion's or a call's `calls`, a
# reduction's `to_apply`, a while's `condition` and `body`, a conditional's
# branches.
_CALLED = re.compile(r"(?:calls|to_apply|condition|body|true_computation|"
                     r"false_computation)=%?([\w.\-]+)")
_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")
_WHILE_BODY = re.compile(r"\bbody=%?([\w.\-]+)")
_OPERAND = re.compile(r"%([\w.\-]+)")
_ARRAY = re.compile(r"\w+\[[\d,]*\]")
_SEGMENT = re.compile(r"[/()]+")


@dataclasses.dataclass
class HloInstruction:
    name: str
    opcode: str
    shape: str               # the whole result shape, layouts and all
    op_name: str
    calls: Optional[str]     # a fusion's computation
    operands: Tuple[str, ...]
    line: str
    computation: str = ""    # the computation the instruction is written in


@dataclasses.dataclass
class HloText:
    module: str
    instructions: Dict[str, HloInstruction]
    roots: Dict[str, str]    # computation -> its ROOT instruction's name

    def loop_computations(self) -> frozenset:
        """The computations that run inside a loop: every `while` body and
        whatever one calls, however deep (a nested loop's body and its
        condition, a fusion, a conditional's branch). An outermost loop's
        condition is not among them."""
        called: Dict[str, set] = {}
        bodies = set()
        for ins in self.instructions.values():
            names = _CALLED.findall(ins.line)
            for group in _BRANCHES.findall(ins.line):
                names += _OPERAND.findall(group)
            called.setdefault(ins.computation, set()).update(names)
            if ins.opcode == "while":
                bodies.update(_WHILE_BODY.findall(ins.line))
        inside, frontier = set(), list(bodies)
        while frontier:
            c = frontier.pop()
            if c not in inside:
                inside.add(c)
                frontier.extend(called.get(c, ()))
        return frozenset(inside)


def _split_shape(rest: str) -> Tuple[str, str]:
    """`rest` is an instruction's text after ``name = ``: the result shape
    (a tuple's parentheses may hold a tiled layout's) and what follows."""
    if rest.startswith("("):
        depth = 0
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                return rest[:i + 1], rest[i + 1:]
        return rest, ""
    shape, _, tail = rest.partition(" ")
    return shape, " " + tail


def parse_hlo_text(text: str) -> HloText:
    """One pass over ``compiled.as_text()``: every instruction of every
    computation, by its short name (unique in a module)."""
    module, computation = "", ""
    instructions: Dict[str, HloInstruction] = {}
    roots: Dict[str, str] = {}
    for line in text.splitlines():
        m = _NAME.match(line)
        if m is None:
            c = _COMPUTATION.match(line)
            if c:
                computation = c.group(2)
            elif not module:
                h = _MODULE.match(line)
                if h:
                    module = h.group(1)
            continue
        shape, tail = _split_shape(line[m.end():])
        op = _OPCODE.match(tail)
        meta = _OP_NAME.search(tail)
        calls = _CALLS.search(tail)
        ins = HloInstruction(
            name=m.group(2), opcode=op.group(1) if op else "", shape=shape,
            op_name=meta.group(1) if meta else "",
            calls=calls.group(1) if calls else None,
            operands=tuple(_OPERAND.findall(_split_shape(
                tail[op.end() - 1:])[0])) if op else (), line=line,
            computation=computation)
        instructions[ins.name] = ins
        if m.group(1):                  # ROOT
            roots[computation] = ins.name
    return HloText(module, instructions, roots)


def shape_head(shape: str) -> str:
    """``dtype[dims]`` of a result's first array, without its layout: what
    a trace event's name and the compiled text must agree on."""
    m = _ARRAY.search(shape)
    return m.group(0) if m else ""


def part_of(op_name: str) -> Tuple[str, str]:
    """(part, pass) of one ``op_name``."""
    part = next((s for s in reversed(_SEGMENT.split(op_name))
                 if s in PARTS), OTHER)
    # The wrapper, not the primitive of the same name (".../transpose").
    return part, "bwd" if "transpose(" in op_name else "fwd"


def sub_of(op_name: str) -> str:
    """The sub-part of one ``op_name``: the segment of SUBPARTS that stands
    right inside its part's, "" where there is none."""
    segments = _SEGMENT.split(op_name)
    for i in range(len(segments) - 1, -1, -1):
        if segments[i] in PARTS:
            nxt = segments[i + 1] if i + 1 < len(segments) else ""
            return nxt if nxt in SUBPARTS.get(segments[i], ()) else ""
    return ""


@dataclasses.dataclass
class Scoped:
    part: str
    pass_: str
    opcode: str
    shape: str               # shape_head
    op_name: str             # kept for `other` (what it is made of)
    sub: str = ""            # sub_of: "window" in a window layer's attention


@dataclasses.dataclass
class ScopeMap:
    """One compiled step's instructions by part. `kind` is what the step is
    to its owner (``train``, ``decode``, ``prefill``, ``sampler``)."""
    module: str
    kind: str
    instructions: Dict[str, Scoped]
    collectives: Dict[str, dict]
    compile_s: float = 0.0


# What a part is looked for THROUGH, around a custom call that names none.
_TRANSPARENT = frozenset(("get-tuple-element", "bitcast", "tuple", "copy"))


def _around(start: str, parsed: HloText, users: Dict[str, List[str]],
            parts: Dict[str, Tuple[str, str]]) -> Tuple[str, str]:
    """(part, pass) of the nearest instruction around `start` that names a
    part: operands before users, through plumbing (``_TRANSPARENT``) and
    through other custom calls that name none, a few steps at most."""
    seen, frontier = {start}, [start]
    for _ in range(4):
        reached = []
        for name in frontier:
            ins = parsed.instructions[name]
            for n in ins.operands + tuple(users.get(name, ())):
                if n in seen or n not in parsed.instructions:
                    continue
                seen.add(n)
                if parts[n][0] != OTHER:
                    return parts[n]
                opcode = parsed.instructions[n].opcode
                if opcode in _TRANSPARENT or opcode == "custom-call":
                    reached.append(n)
        frontier = reached
    return OTHER, "fwd"


def scope_map(parsed: HloText, kind: str = "",
              default_part: Optional[str] = None, mesh=None) -> ScopeMap:
    """`default_part` is the part of an instruction that names none (a
    module that is one part as a whole: the sampler).

    A custom call that names no part takes the part of what is around it
    (``_around``): XLA:TPU's own library calls come with their metadata
    rewritten (``lax.ragged_dot`` becomes ``ragged-dot-none`` with that for
    an ``op_name``, and the ``moe`` it was written under is gone from it),
    while what feeds and reads them keeps it. A Pallas kernel keeps its
    name stack and is not touched by this."""
    op_names: Dict[str, str] = {}
    parts: Dict[str, Tuple[str, str]] = {}
    for ins in parsed.instructions.values():
        op_name = ins.op_name
        if not op_name and ins.calls in parsed.roots:
            op_name = parsed.instructions[parsed.roots[ins.calls]].op_name
        op_names[ins.name], parts[ins.name] = op_name, part_of(op_name)
    lost = [ins.name for ins in parsed.instructions.values()
            if ins.opcode == "custom-call" and parts[ins.name][0] == OTHER]
    if lost and not default_part:
        users: Dict[str, List[str]] = {}
        for ins in parsed.instructions.values():
            for operand in ins.operands:
                users.setdefault(operand, []).append(ins.name)
        # all found before any is written: one lost call does not name another
        parts.update({name: _around(name, parsed, users, parts)
                      for name in lost})
    out: Dict[str, Scoped] = {}
    for ins in parsed.instructions.values():
        part, pass_ = parts[ins.name]
        if part == OTHER and default_part:
            part = default_part
        out[ins.name] = Scoped(part, pass_, ins.opcode,
                               shape_head(ins.shape),
                               op_names[ins.name] if part == OTHER else "",
                               sub_of(op_names[ins.name]))
    from megatronapp_tpu.trace.profiler_collectives import (
        collectives_of,
    )
    return ScopeMap(parsed.module, kind, out, collectives_of(parsed, mesh))


# ---------------------------------------------------------------------------
# The registry
# ---------------------------------------------------------------------------

def _abstract(x):
    """What lowering needs of one argument and no more."""
    import jax
    if isinstance(x, jax.Array):
        # An uncommitted array says nothing of where it must be: keep the
        # lowering the call's own, so that JAX's caches know it.
        return jax.ShapeDtypeStruct(
            x.shape, x.dtype, weak_type=x.weak_type,
            sharding=x.sharding if x.committed else None)
    if hasattr(x, "shape") and hasattr(x, "dtype") \
            and not isinstance(x, jax.ShapeDtypeStruct):
        return jax.ShapeDtypeStruct(x.shape, x.dtype)
    return x


class RegisteredStep:
    """One hot-path step: how to lower it, the abstract arguments of its
    calls, and the maps made from them so far."""

    def __init__(self, lower: Callable, kind: str,
                 default_part: Optional[str],
                 guard: Optional[Callable[[], Any]], mesh):
        self.lower, self.kind = lower, kind
        self.default_part, self.guard, self.mesh = default_part, guard, mesh
        self.calls: Dict[Any, Tuple[tuple, dict]] = {}
        self.maps: Dict[Any, Optional[ScopeMap]] = {}

    def note(self, key, args: tuple, kwargs: Optional[dict] = None):
        """Keep the abstract form of one call's arguments under `key`
        (whatever tells this step's compiled shapes apart)."""
        import jax
        if key not in self.calls:
            self.calls[key] = jax.tree.map(_abstract, (args, kwargs or {}))

    def map_for(self, key, about_to_call: Optional[tuple] = None
                ) -> Optional[ScopeMap]:
        """The map of the call noted under `key`, made now where it was not
        made before; None (and a log line) where the step would not lower.

        It is compiled afresh, whatever JAX's caches hold: their keys
        leave metadata out, so a cached executable carries the ``op_name``s
        of whichever commit compiled it first (my chip runs, PR 36: a train
        step out of the parent's persistent cache named no ``head`` and no
        ``optimizer``, and a second ``lower().compile()`` in the process
        got the same executable back from memory). So the persistent cache
        is off meanwhile (``utils.platform.fresh_compiles``, which the steps
        that pin a layout need anyway) and a compiler option that changes
        nothing is set, which the in-memory cache's key does hold; a
        compiler that refuses the option yields no map rather than a stale
        one. `about_to_call`: the concrete
        arguments of a call the caller is about to make itself (the
        training loop's traced window); they are lowered as they are, in
        the caller's own context (no `guard`: a mesh entered twice is
        another compile) and not afresh, so that the call shares this
        compile (and its metadata, stale or not)."""
        if key not in self.maps:
            try:
                self.maps[key] = self._compile(
                    about_to_call or self.calls[key][0],
                    {} if about_to_call else self.calls[key][1],
                    # a module that is one part whatever its op_names say
                    # may come out of a cache
                    fresh=about_to_call is None and not self.default_part)
            except Exception as e:  # noqa: BLE001 — a reader's view, never
                # the program's problem
                logger.warning("scope map: %s (%s) would not lower: %s: %s",
                               self.kind, key, type(e).__name__, e)
                self.maps[key] = None
        return self.maps[key]

    def _compile(self, args: tuple, kwargs: dict, fresh: bool) -> ScopeMap:
        with contextlib.ExitStack() as stack:
            if fresh:
                from megatronapp_tpu.utils.platform import fresh_compiles
                if self.guard is not None:
                    stack.enter_context(self.guard())
                stack.enter_context(fresh_compiles())
            t0 = time.perf_counter()
            text = self.lower(*args, **kwargs).compile(
                compiler_options=_NOT_FROM_MEMORY if fresh else None
            ).as_text()
            seconds = time.perf_counter() - t0
        made = scope_map(parse_hlo_text(text), self.kind,
                         self.default_part, self.mesh)
        made.compile_s = seconds
        return made


_LOCK = threading.Lock()
_STEPS: "collections.deque[RegisteredStep]" = collections.deque(
    maxlen=MAX_STEPS)


def register(lower: Callable, *, kind: str,
             default_part: Optional[str] = None,
             guard: Optional[Callable[[], Any]] = None,
             mesh=None) -> RegisteredStep:
    """Register a step by the callable that lowers it (a jit's ``lower``).
    `guard`: a context manager factory entered around the lowering (a mesh;
    a trace counter put back). `mesh` names a collective's axes. Costs one
    list append."""
    step = RegisteredStep(lower, kind, default_part, guard, mesh)
    with _LOCK:
        _STEPS.append(step)
    return step


class noted:
    """A ``jax.jit`` whose first call with a shape tells the registry its
    abstract arguments. `key(*args, **kwargs)` is what tells its compiled
    shapes apart (by default the positional arguments' shapes); it runs in
    every call, so keep it to a few attribute reads."""

    def __init__(self, jitted, *, kind: str, key: Optional[Callable] = None,
                 **register_kw):
        self._jitted = jitted
        self.key_of = key or _arg_shapes
        self.scope_step = register(jitted.lower, kind=kind, **register_kw)

    def __call__(self, *args, **kwargs):
        key = self.key_of(*args, **kwargs)
        if key not in self.scope_step.calls:
            self.scope_step.note(key, args, kwargs)
        return self._jitted(*args, **kwargs)

    def __getattr__(self, name):        # lower, trace, eval_shape, ...
        return getattr(self._jitted, name)


def _arg_shapes(*args, **kwargs):
    return tuple(getattr(a, "shape", None) for a in args)


def registered_steps() -> List[RegisteredStep]:
    with _LOCK:
        return list(_STEPS)


def clear() -> None:
    with _LOCK:
        _STEPS.clear()


def scope_maps() -> List[ScopeMap]:
    """The map of every registered step's every noted call
    (``RegisteredStep.map_for``). Never raises."""
    made = (step.map_for(key) for step in registered_steps()
            for key in list(step.calls))
    return [m for m in made if m is not None]
