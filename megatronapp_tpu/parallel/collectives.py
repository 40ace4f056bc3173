"""Helpers for manual-collective (shard_map) code.

The reference wraps torch.distributed in
virtual_tensor_parallel_communication.py; here the collectives themselves are
jax.lax primitives — this module only holds small shared utilities for code
running inside shard_map manual regions.

This module and ``parallel/overlap.py`` are the designated homes for raw
manual collectives (tools/check_vma.py); every full-manual subsystem
(tp overlap, cp ring attention, ep all-to-all dispatch, the pp pipeline)
builds on the compat wrappers here.
"""

from __future__ import annotations

import contextlib
import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax


def shard_map_compat(body, mesh, in_specs, out_specs):
    """FULL-MANUAL shard_map: ``jax.shard_map(..., check_vma=False)`` (the
    bodies are plain ring code; vma annotation adds nothing under full
    manual).

    Full manual (every mesh axis) is load-bearing: partial-auto manual
    regions lower ppermute/axis_index through an SPMD path XLA:CPU aborts
    on (spmd_partitioner IsManualSubgroup check / unsupported PartitionId)
    — see parallel/overlap.py design notes. Axes a body does not
    communicate over are simply threaded through the specs (split batch
    dims) or replicated (unmentioned spec dims).

    Autodiff note: grads of inputs whose spec leaves axes unmentioned come
    out correct — the transpose feeds output cotangents to a single shard
    along unmentioned out-spec axes and sums input cotangents across
    unmentioned in-spec axes — so replicated params (split batch) and
    redundantly-computed axes both transpose right without explicit
    psums. Explicit psums are still required for reductions the MATH
    needs inside custom_vjp bodies (e.g. wgrads across manual batch
    shards in overlap.py)."""
    return jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def axis_size(axis_name) -> int:
    """Static size of a bound mesh axis."""
    return lax.axis_size(axis_name)


def psum(x, axis_name):
    """All-reduce sum over a bound manual mesh axis — the designated
    entry point for shard-partial reductions in full-manual bodies
    (tools/check_vma.py gate 1), e.g. the latent-column score/value
    partials of kernel_gen._tp_place_latent. Keep operands fp32 at the
    call sites: bf16 manual all-reduces crash XLA:CPU (README known
    constraints)."""
    return lax.psum(x, axis_name)


def psum_scatter(x, axis_name, scatter_dimension: int):
    """Reduce-scatter over a bound manual mesh axis: the sum's
    ``scatter_dimension`` comes back split over ``axis_name``, a rank its
    own block (tiled). The train step's one sum of the weight gradients
    over dp lands in ZeRO-1's layout through here (fp32)."""
    return lax.psum_scatter(x, axis_name,
                            scatter_dimension=scatter_dimension, tiled=True)


def pvary(x, axes: Tuple[str, ...]):
    """Mark a replicated-over-``axes`` input of a full-manual shard_map
    body as contributing a partial cotangent per shard (pipeline stage
    params over cp and the (dp, ep) microbatch shards; microbatch inputs
    over pp), so that its cotangent is summed over ``axes`` exactly once.

    Under ``check_vma=False`` (shard_map_compat) that sum is already what
    shard_map's transpose does for every axis an in_spec leaves
    unmentioned, so this is the identity: an explicit ``lax.pcast`` would
    count twice, and its transpose (a psum of a value the untyped body
    does not know to be varying) is refused outright. The call sites stay
    as the record of which inputs rely on that sum. Keep those inputs
    fp32 — bf16 manual all-reduces crash XLA:CPU (README known
    constraints)."""
    del axes
    return x


def current_manual_axes() -> Tuple[str, ...]:
    """Mesh axes that are Manual in the ambient context (nested shard_maps
    accumulate them), read off the abstract mesh's axis types."""
    m = jax.sharding.get_abstract_mesh()
    if m is None or not m.shape:
        return ()
    Manual = jax.sharding.AxisType.Manual
    return tuple(name for name, t in zip(m.axis_names, m.axis_types)
                 if t == Manual)


def ambient_manual(*axes: str) -> bool:
    """True iff every named mesh axis is Manual in the ambient context —
    the shared detection gate for code that must switch between GSPMD
    wrappers (outside any manual region) and ambient ring bodies (inside
    the full-manual pipeline/cp regions, where a nested shard_map or a
    GSPMD collective would abort XLA:CPU)."""
    manual = current_manual_axes()
    return all(a in manual for a in axes)


def all_gather_seq(x: jnp.ndarray, axis_name: str, axis: int = 1):
    """Tiled all-gather of a manually-sharded axis inside an ambient
    manual region ([..., S/n, ...] → [..., S, ...], rank-major order —
    matching the contiguous seq-chunk layout the tp/cp rings use).

    The audited home for the bulk (non-overlapped) gathers of the
    tp-sharded pipeline stage body: small side tensors (MLA's shared
    rope key) and the ``tp_comm_overlap=False`` bulk fallback both route
    through here rather than sprinkling raw lax.all_gather calls."""
    return lax.all_gather(x, axis_name, axis=axis, tiled=True)


# Python-level attrs merged into every ring_span record while active —
# lets an enclosing region (the pp pipeline's tp-sharded stage body) tag
# the spans its inner rings emit without threading arguments through
# every ring body. Trace-time state: the tag captures at trace time like
# the enabled check itself.
_SPAN_TAGS: dict = {}


@contextlib.contextmanager
def span_tags(**tags):
    """Tag all ring_span records emitted while tracing under this context
    (e.g. ``span_tags(region="pp-stage")`` around the pipeline stage body
    marks the in-pipeline tp rings apart from top-level tp overlap).

    Scope caveat: custom_vjp BACKWARD ring bodies are traced during
    transposition — outside any forward-side ``with`` — so only
    forward-pass spans carry the tag (same boundary as the "pp hop spans
    appear on forward/eval only" scan-linearization note)."""
    global _SPAN_TAGS
    prev = _SPAN_TAGS
    _SPAN_TAGS = {**prev, **tags}
    try:
        yield
    finally:
        _SPAN_TAGS = prev


def ring_span(name: str, ph: str, dep, axis_name: str, *, step=None,
              **attrs):
    """Per-hop MegaScan record from inside a jitted manual ring body.

    Shared emission helper behind the tp/cp/ep overlap spans
    (tp-overlap-*, cp-overlap-*, moe-a2a-*, pp-overlap-*). Inserted only
    when tracing is enabled at trace time (zero overhead otherwise). Uses
    ``jax.debug.callback`` — the only callback flavor supported inside
    shard_map manual regions (ordered io_callback is
    rejected there); the data dependency on ``dep`` anchors the record
    near the op it brackets. One timeline per rank along ``axis_name``
    (tid = rank + 1; tid 0 stays the host-scope timeline).

    The timeline id is the shard's linearized rank over EVERY ambient
    manual axis (not just ``axis_name``): two shards that share a ring
    rank but differ on another axis (e.g. the dp shards of one cp rank)
    must not interleave B/E pairs onto one Chrome-trace tid, whose pairing
    is a per-tid stack. On single-ring meshes this degenerates to
    ring-rank + 1 exactly as before.

    step may be a Python int (unrolled rings) or a traced scalar (the pp
    schedule's scanned step) — it rides into the callback as an operand."""
    from megatronapp_tpu.trace.tracer import callbacks_supported, get_tracer

    tracer = get_tracer()
    if not (tracer.enabled and callbacks_supported()):
        return
    if _SPAN_TAGS:
        attrs = {**_SPAN_TAGS, **attrs}

    rank = lax.axis_index(axis_name)
    tid = jnp.zeros((), jnp.int32)
    for n in sorted(current_manual_axes()):
        tid = tid * axis_size(n) + lax.axis_index(n)

    def _cb(rank_, tid_, step_, _):
        a = dict(attrs, rank=int(rank_))
        if int(step_) >= 0:
            a["step"] = int(step_)
        tracer.phase_event(name, ph, tid=int(tid_) + 1, **a)

    anchor = lax.stop_gradient(dep).ravel()[0]
    jax.debug.callback(_cb, rank, tid,
                       jnp.asarray(-1 if step is None else step, jnp.int32),
                       anchor)


def _anchor(like: jnp.ndarray) -> jnp.ndarray:
    """Scalar zero inheriting `like`'s varying-manual-axes type, with no
    backward edge (stop_gradient) and no axis_index — safe inside nested
    shard_maps where parent-bound axis names cannot be referenced.

    Why not lax.pcast for making carries varying: pcast's transpose is a
    psum, and the current XLA build crashes on bf16 manual all-reduces
    ("Invalid binary instruction opcode copy" — reducer regions containing
    converts). This anchor adds no collective in either direction."""
    flat = jax.lax.stop_gradient(like).ravel()
    return (flat[0] * 0).astype(jnp.float32)


def zeros_like_vma(shape, dtype, like: jnp.ndarray):
    """Zeros of (shape, dtype) whose varying-manual-axes match `like`."""
    return jnp.zeros(shape, dtype) + _anchor(like).astype(dtype)


def full_like_vma(shape, fill, dtype, like: jnp.ndarray):
    return jnp.full(shape, fill, dtype) + _anchor(like).astype(dtype)
