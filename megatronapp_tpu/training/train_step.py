"""The jitted train step: microbatch grad accumulation + optimizer update.

Parity with /root/reference/megatron/training/training.py:1367 (train_step:
forward_backward_func over microbatches → finalize grads → clip → optimizer
step → skipped-iter bookkeeping). TPU-first: one jit containing a lax.scan
over microbatches, and the NaN-skip is a lax.cond instead of the fp16 scaler
path (optimizer.py:322).

Weight gradients cross the data-parallel axis ONCE a step (the reference's
``no_sync`` for all but the last micro-batch, param_and_grad_buffer.py:93).
Left to GSPMD, an accumulator laid out like the parameters (replicated over
dp) forces the sum over dp where each weight-gradient product is made: a
synchronous bf16 all-reduce of a layer's whole gradient in every layer of
every micro-batch, which nothing hides (PERF.md, PR 42: 296 ms of a 1,620 ms
step on four chips). So where dp > 1 the step hands the loss ONE COPY A RANK
of the kernels it multiplies through ``ops/per_rank.dense`` (``[L, dp, K,
N]``, the new axis split over dp: each chip holds the copy it always held).
Their gradients come back a rank's own, unreduced, accumulate in fp32 in that
shape, and are summed over the rank axis once behind the scan; ``grads``,
the norm, the clip, the NaN skip and the ZeRO-1 update see what they always
saw. Every other leaf (biases, norms, embeddings) and every loss that names
no such kernels (``loss_fn.rank_kernels``) keeps the per-micro-batch
reduction; the step says which it took in one line at its first trace
(``gradients: ...``).
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from megatronapp_tpu.config.parallel_config import DP_AXIS
from megatronapp_tpu.config.training_config import OptimizerConfig
from megatronapp_tpu.parallel.collectives import (
    psum, psum_scatter, shard_map_compat,
)
from megatronapp_tpu.parallel.mesh import MeshContext
from megatronapp_tpu.training.optimizer import global_grad_norm, lr_schedule


def batch_shardings(ctx: MeshContext) -> Any:
    """Sharding for batch dicts of [num_micro, global_batch, ...] arrays.

    Returned as a pytree PREFIX (one sharding for the whole dict) so batches
    with model-specific extra fields (BERT's tokentype_ids/is_random, T5's
    enc/dec pairs) shard uniformly over the batch axis. With cp > 1 the
    sequence axis must also shard, which requires rank-3 leaves — the GPT
    field set.
    """
    spec = ctx.batch_spec()
    if ctx.cp > 1:
        sh = NamedSharding(ctx.mesh, P(None, *spec))
        return {"tokens": sh, "labels": sh, "loss_mask": sh,
                "position_ids": sh}
    return NamedSharding(ctx.mesh, P(None, *spec))


def globalize_batch(batch: Any, ctx: MeshContext, shardings=None) -> Any:
    """Host numpy batches → global jax.Arrays for multi-process runs.

    Single-process jit accepts numpy directly; across hosts each process
    holds the SAME deterministic global batch (the mock/data streams are
    seed-identical per rank — reference per-rank loaders yield aligned
    samples), so every device slices its shard out of the local copy
    (jax.make_array_from_callback). No-op when one process."""
    if jax.process_count() == 1:
        return batch
    shardings = shardings if shardings is not None else batch_shardings(ctx)
    is_prefix = not isinstance(shardings, dict)

    def conv(x, sh):
        x = np.asarray(x)   # one host conversion; shards slice from it
        return jax.make_array_from_callback(
            x.shape, sh, lambda idx: x[idx])

    if is_prefix:
        return jax.tree.map(lambda x: conv(x, shardings), batch)
    unmatched = set(batch) - set(shardings)
    if unmatched:
        # Host numpy mixed with global arrays fails far from the cause;
        # refuse loudly (extend batch_shardings' cp>1 field set instead).
        raise ValueError(
            f"globalize_batch: no sharding for batch fields "
            f"{sorted(unmatched)} under cp>1")
    return {k: conv(v, shardings[k]) for k, v in batch.items()}


def per_micro_batch_reason(loss_fn, ctx: MeshContext, state_shardings,
                           pipeline: bool = False,
                           fp8: bool = False) -> Optional[str]:
    """Why this step leaves every gradient's sum over dp to GSPMD, inside
    every micro-batch, or None where the loss's per-rank kernels
    (``loss_fn.rank_kernels``) are summed once behind the scan. Decided from
    the mesh, the step's mode, the state's layout and what the loss says of
    itself."""
    if pipeline or ctx.pp > 1:
        return ("the pipeline schedules its micro-batches inside one "
                "full-manual region")
    if getattr(ctx, "abstract_collectives", False):
        return "an abstract mesh (forward/backward disaggregation)"
    if ctx.ep > 1:
        return "ep > 1: the batch is split over (dp, ep) jointly"
    if ctx.cp > 1:
        return "cp > 1: ring attention is a full-manual region of its own"
    if fp8:
        return "fp8 multiplies inside the tp rings' full-manual regions"
    if not getattr(loss_fn, "rank_kernels", None):
        return getattr(loss_fn, "no_rank_kernels", None) or (
            "this loss names no per-rank kernels (loss_fn.rank_kernels)")
    if any(DP_AXIS in str(sh.spec)
           for sh in jax.tree.leaves(state_shardings["params"])):
        return "the parameters are split over dp themselves (fsdp)"
    return None


def _spec(sharding, ndim: int) -> list:
    """A sharding's spec, an entry a dim."""
    return list(sharding.spec) + [None] * (ndim - len(sharding.spec))


class _PerRankKernels:
    """The kernels a loss takes one copy a data-parallel rank of
    (``loss_fn.rank_kernels``: {path: (the copies' axis, their type)}),
    their copies and the one sum of their gradients."""

    def __init__(self, kernels, ctx: MeshContext, param_shardings, landing):
        self.kernels, self.ctx = dict(kernels), ctx
        self.param_shardings, self.landing = param_shardings, landing

    def of(self, path):
        return self.kernels.get(tuple(getattr(k, "key", None) for k in path))

    def copies(self, params):
        """`params` with one more axis on each such kernel, of dp copies and
        split over dp: no byte moves, a chip's copy is the one it held."""
        def spread(path, p, sh):
            if self.of(path) is None:
                return p
            axis, dtype = self.of(path)
            spec = _spec(sh, p.ndim)
            spec.insert(axis, DP_AXIS)
            # In the type the layer multiplies in: the gradient a layer
            # hands back is then that type's too (bf16, as the parent's was
            # when it crossed dp), and fp32 from the accumulator on.
            p = jnp.expand_dims(p.astype(dtype or p.dtype), axis)
            return jax.lax.with_sharding_constraint(
                jnp.broadcast_to(
                    p, p.shape[:axis] + (self.ctx.dp,) + p.shape[axis + 1:]),
                NamedSharding(self.ctx.mesh, P(*spec)))
        return jax.tree_util.tree_map_with_path(
            spread, params, self.param_shardings)

    def summed(self, g_sum):
        """The one sum over dp, fp32, a leaf at a time: each rank's own
        copy's gradient through one psum, or one psum_scatter where the
        landing layout splits a whole dim over dp."""
        dp = self.ctx.dp

        def one(path, g, p_sh, land_sh):
            if self.of(path) is None:
                return g
            axis, nd = self.of(path)[0], g.ndim - 1
            own, land = _spec(p_sh, nd), _spec(land_sh, nd)
            shape = g.shape[:axis] + g.shape[axis + 1:]
            split = next((d for d in range(nd)
                          if land[d] == DP_AXIS and own[d] is None
                          and shape[d] % dp == 0), None)

            def body(x):
                x = jnp.squeeze(x, axis)
                if split is None:
                    return psum(x, DP_AXIS)
                return psum_scatter(x, DP_AXIS, split)

            out = [DP_AXIS if d == split else e for d, e in enumerate(own)]
            own.insert(axis, DP_AXIS)
            return shard_map_compat(
                body, self.ctx.shard_map_mesh, in_specs=(P(*own),),
                out_specs=P(*out))(g)
        return jax.tree_util.tree_map_with_path(
            one, g_sum, self.param_shardings, self.landing)

    def line(self, params, num_micro: int) -> str:
        once = rest = 0
        for (path, p), sh in zip(
                jax.tree_util.tree_leaves_with_path(params),
                jax.tree.leaves(self.param_shardings)):
            held = int(np.prod(sh.shard_shape(p.shape))) * 4
            if self.of(path) is None:
                rest += held
            else:
                once += held
        return (f"weights summed over dp once a step, fp32, "
                f"{once / 1e9:.2f} GB a chip (was: inside each of "
                f"{num_micro} micro-batches, as the other leaves' "
                f"{rest / 1e9:.2f} GB still are)")


_announced = set()


def _announce(line: str) -> None:
    """Print, once per distinct line in this process, where the gradients
    are summed over dp (beside `device:` and `attention:`)."""
    if line not in _announced:
        _announced.add(line)
        print("gradients: " + line, flush=True)


def make_train_step(
    loss_fn: Callable[[Any, Dict[str, jnp.ndarray]], Tuple[jnp.ndarray, Dict]],
    optimizer,
    opt_cfg: OptimizerConfig,
    ctx: MeshContext,
    state_shardings,
    train_iters: int,
    check_nan: bool = True,
    pipeline: bool = False,
    trace_phases: bool = False,
    donate: bool = True,
    fp8: bool = False,
):
    """loss_fn(params, microbatch_dict) -> (loss, metrics_dict).

    The step's metrics are the micro-batches' means, but for what the loss
    puts under metrics["sums"] (counters), which are their totals.

    Returns jitted step(state, batch) -> (state, metrics); batch arrays are
    [num_micro, global_batch, seq]. In pipeline mode, loss_fn consumes the
    whole microbatched batch at once (the pipeline schedules microbatches
    internally — parallel/pipeline.py); otherwise a lax.scan accumulates
    grads microbatch by microbatch (reference
    forward_backward_no_pipelining, schedules.py:618).

    fp8 (ISSUE 13): loss_fn additionally accepts fp8= (the delayed-
    scaling amax state, state["fp8"]) and the step differentiates the
    (params, fp8) PAIR — the fp8 half's "gradient" is the updated
    history (parallel/overlap.py fp8 custom_vjps), which accumulates
    with elementwise max / saturation-count sum across microbatches
    (training/fp8.fp8_accumulate), bypasses grad scaling, the grad
    norm, and the optimizer entirely, and lands in state["fp8"]
    directly. A NaN-skipped step keeps the old history (nothing
    observed)."""
    sched = lr_schedule(opt_cfg, train_iters)
    # Where the gradients are summed over dp (module docstring).
    many_ranks = ctx.dp * ctx.ep > 1
    per_micro = per_micro_batch_reason(
        loss_fn, ctx, state_shardings, pipeline=pipeline,
        fp8=fp8) if many_ranks else None
    # ZeRO-1 manual update path (--dist-opt-comm ring|bulk): the weight
    # update runs inside one full-manual shard_map with the updated
    # params returned through the overlap.py ring all-gather (ring) or a
    # tiled bulk gather. Default 'gspmd' leaves the collectives to XLA's
    # sharding propagation over the dp-sharded state layout.
    zero1_manual = (getattr(optimizer, "zero1", False)
                    and getattr(optimizer, "shard_state", True)
                    and getattr(opt_cfg, "dist_opt_comm", "gspmd")
                    in ("ring", "bulk")
                    and ctx.dp * ctx.ep > 1
                    and not getattr(ctx, "abstract_collectives", False))
    zero1_plan = None
    if zero1_manual:
        from megatronapp_tpu.training.distributed_optimizer import (
            shard_plan,
        )
        zero1_plan = shard_plan(state_shardings["params"],
                                state_shardings["opt_state"])
    # Under ZeRO-1 the sum lands in the moments' layout (a leaf split over
    # dp once more), which is all the update reads of it: a reduce-scatter,
    # half an all-reduce's bytes, and no second whole accumulator. The
    # manual update slices `grads` itself and wants them whole.
    opt_sh = state_shardings.get("opt_state")
    landing = (opt_sh["mu"] if getattr(optimizer, "zero1", False)
               and not zero1_manual
               and isinstance(opt_sh, dict) and "mu" in opt_sh
               else state_shardings["params"])
    ranks = (_PerRankKernels(loss_fn.rank_kernels, ctx,
                             state_shardings["params"], landing)
             if many_ranks and per_micro is None else None)
    # What the loss is differentiated by in the micro-batch loop where it
    # gets no copy a rank and the parameters' type is not the compute type:
    # its compute-type copies of the kernels it multiplies
    # (train.compute_dtype_kernels), the same gradients in fewer bytes.
    compute_copies = getattr(loss_fn, "compute_copies", None)

    def announce(params, num_micro):
        if ranks is not None:
            _announce(ranks.line(params, num_micro))
        elif many_ranks:
            _announce("summed over dp inside every micro-batch (GSPMD): "
                      + per_micro)

    if trace_phases:
        # MegaScan schedule-phase spans (trace/tracer.py): 'forward' spans
        # the loss computation; its custom-VJP mirrors emit the 'backward'
        # span during the gradient pass; 'loss' marks the loss value.
        from megatronapp_tpu.trace.tracer import (
            phase_span_begin, phase_span_end,
        )
        inner_loss = loss_fn

        def loss_fn(params, micro, **kw):  # noqa: F811 — traced wrapper
            # Spans must sit on the params→loss differentiation path so the
            # custom-VJP backward mirrors fire: B 'forward' on params entry
            # (its bwd emits E 'backward' when the last param cotangent
            # leaves), E 'forward' + B 'backward' mirror on the loss.
            params = phase_span_begin(params, "forward", "backward")
            loss, metrics = inner_loss(params, micro, **kw)
            loss = phase_span_end(loss, "forward", "backward")
            loss = phase_span_begin(loss, "loss")
            loss = phase_span_end(loss, "loss")
            return loss, metrics

    if fp8 and pipeline:
        raise ValueError("fp8 does not support the pipeline loss path "
                         "(fp8_ineligible_reason gates this off)")
    if fp8:
        def _fp8_target(pair, micro):
            params, fstate = pair
            return loss_fn(params, micro, fp8=fstate)
        grad_fn = jax.value_and_grad(_fp8_target, has_aux=True)
    else:
        grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    def step(state, batch):
        params = state["params"]
        num_micro = jax.tree.leaves(batch)[0].shape[0]
        fp8_new = None
        announce(params, num_micro)

        if pipeline:
            (loss, aux), grads = grad_fn(params, batch)
        else:
            from megatronapp_tpu.training.fp8 import (
                fp8_accumulate, fp8_zeros_like,
            )

            def accum(carry, micro):
                g_acc, loss_acc, aux_acc = carry
                if fp8:
                    (loss, metrics), (g, g8) = grad_fn(
                        (params, state["fp8"]), micro)
                    gp_acc, f8_acc = g_acc
                    with jax.named_scope("grad_accum"):
                        g_acc = (jax.tree.map(
                            lambda a, b: a + b.astype(a.dtype), gp_acc, g),
                            fp8_accumulate(f8_acc, g8))
                else:
                    (loss, metrics), g = grad_fn(loss_params, micro)
                    with jax.named_scope("grad_accum"):
                        g_acc = jax.tree.map(
                            lambda a, b: a + b.astype(a.dtype), g_acc, g)
                return (g_acc, loss_acc + loss,
                        jax.tree.map(lambda a, b: a + b, aux_acc,
                                     metrics)), None

            loss_params = params if ranks is None else ranks.copies(params)
            if ranks is None and compute_copies is not None:
                loss_params = compute_copies(params)
            zeros = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), loss_params)
            if fp8:
                zeros = (zeros, fp8_zeros_like(state["fp8"]))
                metrics_struct = jax.eval_shape(
                    lambda: loss_fn(
                        params, jax.tree.map(lambda x: x[0], batch),
                        fp8=state["fp8"])[1])
            else:
                metrics_struct = jax.eval_shape(
                    lambda: loss_fn(params,
                                    jax.tree.map(lambda x: x[0],
                                                 batch))[1])
            aux_zeros = jax.tree.map(
                lambda s: jnp.zeros(s.shape, s.dtype), metrics_struct)
            (g_sum, loss_sum, aux_sum), _ = jax.lax.scan(
                accum, (zeros, jnp.zeros((), jnp.float32), aux_zeros), batch)

            if fp8:
                g_sum, fp8_new = g_sum
                # Saturation totals are CUMULATIVE in the state (the
                # observations are per-step counts); histories take the
                # step's rolled value.
                from megatronapp_tpu.training.fp8 import fp8_carry_sat
                fp8_new = fp8_carry_sat(state["fp8"], fp8_new)
            inv = 1.0 / num_micro
            with jax.named_scope("grad_accum"):
                if ranks is not None:
                    g_sum = ranks.summed(g_sum)
                grads = jax.tree.map(lambda g: g * inv, g_sum)
            loss = loss_sum * inv
            # "sums" (counters) stay the step's totals; the rest are means
            sums = aux_sum.pop("sums", None)
            aux = jax.tree.map(lambda a: a * inv, aux_sum)
            if sums is not None:
                aux["sums"] = sums

        if trace_phases:
            from megatronapp_tpu.trace.tracer import (
                phase_span_begin, phase_span_end,
            )
            grads = phase_span_begin(grads, "allreduce")
        # "optimizer": the norm the clip reads, the update, ZeRO-1's gather.
        with jax.named_scope("optimizer"):
            grad_norm = global_grad_norm(grads)
            if trace_phases:
                grad_norm = phase_span_end(grad_norm, "allreduce")
                grads = phase_span_begin(grads, "optimizer")
            finite = jnp.isfinite(loss) & jnp.isfinite(grad_norm)

            def do_update(_):
                if zero1_manual:
                    from megatronapp_tpu.training.distributed_optimizer \
                        import manual_apply
                    new_params, new_opt = manual_apply(
                        optimizer, grads, state["opt_state"], params,
                        state_shardings, ctx.mesh, zero1_plan,
                        overlap=(opt_cfg.dist_opt_comm == "ring"))
                else:
                    updates, new_opt = optimizer.update(
                        grads, state["opt_state"], params)
                    if hasattr(optimizer, "apply_updates"):
                        # Master-weight aware (ZeRO-1 mixed precision):
                        # params become the rounded image of the fp32
                        # master shard.
                        new_params = optimizer.apply_updates(params, updates,
                                                             new_opt)
                    else:
                        new_params = jax.tree.map(
                            lambda p, u: (p + u.astype(p.dtype)), params,
                            updates)
                if fp8:
                    # The accumulated fp8 "gradient" IS the next history
                    # (rolled, amaxes in slot 0) — installed directly,
                    # never via the optimizer.
                    return new_params, new_opt, fp8_new
                return new_params, new_opt

            def skip(_):
                if fp8:
                    return params, state["opt_state"], state["fp8"]
                return params, state["opt_state"]

            if check_nan:
                updated = jax.lax.cond(finite, do_update, skip, operand=None)
                skipped = jnp.where(finite, 0, 1).astype(jnp.int32)
            else:
                updated = do_update(None)
                skipped = jnp.zeros((), jnp.int32)
        if fp8:
            new_params, new_opt, new_fp8 = updated
        else:
            new_params, new_opt = updated

        if trace_phases:
            new_params = phase_span_end(new_params, "optimizer")
        new_state = {
            "step": state["step"] + 1,
            "params": new_params,
            "opt_state": new_opt,
        }
        if fp8:
            new_state["fp8"] = new_fp8
        metrics = {
            "loss": loss,
            "grad_norm": grad_norm,
            "lr": sched(state["step"]),
            "skipped": skipped,
            **aux,
        }
        return new_state, metrics

    b_sh = batch_shardings(ctx)
    return jax.jit(
        step,
        in_shardings=(state_shardings, b_sh),
        out_shardings=(state_shardings, None),
        donate_argnums=(0,) if donate else (),
    )


def make_eval_step(loss_fn, ctx: MeshContext, state_shardings,
                   pipeline: bool = False, fp8: bool = False):
    """Forward-only loss (reference evaluate(), training.py eval loop).

    pipeline=True: loss_fn consumes the whole microbatched batch (the SPMD
    pipeline schedules internally), matching make_train_step.

    fp8: evaluate through the same fp8 forward as training (the amax
    state is read, never updated — no backward runs here)."""
    b_sh = batch_shardings(ctx)

    def step(state, batch):
        kw = {"fp8": state["fp8"]} if fp8 else {}
        if pipeline:
            loss, _ = loss_fn(state["params"], batch)
            return loss

        def body(acc, micro):
            loss, _ = loss_fn(state["params"], micro, **kw)
            return acc + loss, None
        total, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), batch)
        return total / jax.tree.leaves(batch)[0].shape[0]

    return jax.jit(step, in_shardings=(state_shardings, b_sh))
