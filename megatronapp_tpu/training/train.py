"""Training driver — the ``pretrain()`` analogue.

Parity with /root/reference/megatron/training/training.py:894 (pretrain) /
:668 (pretrain_body) / :1967 (train loop) / :1488 (training_log): mesh+state
setup, microbatched train loop, throughput/loss logging, checkpoint
save/resume, MegaScan tracing hooks, NaN-skip accounting.
"""

from __future__ import annotations

import functools

import contextlib
import dataclasses
import threading
import time
from typing import Any, Callable, Dict, Iterator, Optional

import jax
import jax.numpy as jnp
import numpy as np

from megatronapp_tpu.config.parallel_config import ParallelConfig
from megatronapp_tpu.config.training_config import (
    OptimizerConfig, TrainingConfig,
)
from megatronapp_tpu.config.transformer_config import TransformerConfig
from megatronapp_tpu.data.mock import mock_batches
from megatronapp_tpu.models.gpt import (
    gpt_loss, gpt_pipeline_loss, init_gpt_params,
)
from megatronapp_tpu.parallel.mesh import MeshContext, build_mesh
from megatronapp_tpu.training.checkpointing import (
    CheckpointManager, LocalCheckpointManager, read_side_state,
    write_side_state,
)
from megatronapp_tpu.training.optimizer import get_optimizer
from megatronapp_tpu.training.train_state import setup_train_state
from megatronapp_tpu.training.train_step import (
    globalize_batch, make_train_step,
)
from megatronapp_tpu.trace.tracer import get_tracer
from megatronapp_tpu.utils import metrics as telemetry
from megatronapp_tpu.utils.flops import flops_per_token
from megatronapp_tpu.utils.platform import device_line


@dataclasses.dataclass
class TrainResult:
    state: Any
    losses: list
    tokens_per_sec: float
    step_time_ms: float
    # Graceful signal exit fired (SIGTERM drained via emergency save).
    interrupted: bool = False
    # Data-stream position at exit (samples consumed incl. any resume).
    consumed_samples: int = 0


@contextlib.contextmanager
def _signal_exit_context(train_cfg: TrainingConfig, log_fn):
    """Install the graceful-exit signal handler for the duration of the
    train loop (--exit-signal-handler). Python restricts signal.signal
    to the main thread — off-main callers (e.g. a driver thread in
    tests) run without it rather than crashing."""
    if not train_cfg.exit_signal_handler:
        yield None
        return
    if threading.current_thread() is not threading.main_thread():
        log_fn("signals: --exit-signal-handler requires the main "
               "thread; running without a signal handler")
        yield None
        return
    from megatronapp_tpu.training.signals import DistSignalHandler
    with DistSignalHandler.for_config(
            sigint=train_cfg.exit_signal_handler_sigint) as handler:
        yield handler


def _emergency_side_state(step: int, consumed: int, rerun
                          ) -> Dict[str, Any]:
    """Resumable host-side bookkeeping persisted with every checkpoint:
    `consumed` is the exact data-stream position (the _RowBuffer's
    carry-over rows were fetched but NOT consumed, so recreating the
    stream at `consumed` via batch_iter_factory replays them — no
    samples dropped or double-consumed); `rerun` pins the fault-
    classification statistics (EMA, step/injection counters)."""
    return {"step": int(step), "consumed": int(consumed),
            "rerun": rerun.state_dict()}


class _CheckpointScribe:
    """ONE home for the train loop's four checkpoint moments (ROADMAP
    cleanup item): interval durable, interval local, emergency (signal
    exit), and final. Every path shares the same plumbing — the heartbeat
    'checkpointing' section bracketing, device_get + layout on the
    durable save, the side-state payload (exact stream position incl.
    _RowBuffer carry-over + rerun statistics), and best-effort semantics
    for the local copy — so threading new state layouts (the dp-sharded
    ZeRO-1 optimizer state) through checkpointing touches one place."""

    def __init__(self, ckpt, local_ckpt, train_cfg: TrainingConfig,
                 layout, ft, rerun, log_fn):
        self.ckpt = ckpt
        self.local_ckpt = local_ckpt
        self.cfg = train_cfg
        self.layout = layout
        self.ft = ft
        self.rerun = rerun
        self.log_fn = log_fn

    @contextlib.contextmanager
    def section(self):
        """Bracket a save in the heartbeat 'checkpointing' section so the
        watchdog applies the checkpoint timeout, then return to 'step'."""
        if self.ft is not None:
            self.ft.start_section("checkpointing")
        try:
            yield
        finally:
            if self.ft is not None:
                self.ft.start_section("step")

    def _side(self, step: int, consumed: int) -> Dict[str, Any]:
        return _emergency_side_state(step, consumed, self.rerun)

    def save_durable(self, step: int, state, consumed: int,
                     force: bool = False,
                     skip_if_current: bool = False) -> None:
        """Durable Orbax save + side-state sidecar. skip_if_current: a
        step already on disk is left alone — orbax rewrites same-step
        saves by delete-then-write, which inside a preemption grace
        window would drop the just-written good checkpoint. The side
        state is (re)written either way: it is an atomic sidecar."""
        if self.ckpt is None:
            return
        if not (skip_if_current and self.ckpt.latest_step == step):
            self.ckpt.save(step, jax.device_get(state), force=force,
                           layout=self.layout)
        write_side_state(self.cfg.save_dir, step,
                         self._side(step, consumed))

    def save_local(self, step: int, state, consumed: int,
                   what: str = "local checkpoint") -> None:
        """Best-effort local .npz with the side state riding as extra —
        warn-and-continue on failure (local checkpoints are an
        optimization, never worth killing the run)."""
        if self.local_ckpt is None:
            return
        try:
            self.local_ckpt.save(step, jax.device_get(state),
                                 extra=self._side(step, consumed))
        except Exception as e:  # noqa: BLE001 — best-effort path
            self.log_fn(f"{what} save failed at step {step} "
                        f"({type(e).__name__}: {e}); continuing — "
                        "local checkpoints are best-effort")


def reshape_global_batch(batch: Dict[str, np.ndarray], num_micro: int
                         ) -> Dict[str, np.ndarray]:
    """[global_batch, seq] → [num_micro, global_batch/num_micro, seq]."""
    def r(x):
        gb = x.shape[0]
        return x.reshape(num_micro, gb // num_micro, *x.shape[1:])
    return {k: r(v) for k, v in batch.items()}


def _validate_schedule_stages(batch_calc, pp: int, vpp: int,
                              order_policy: str) -> None:
    """Fail at startup (not hours into a ramp) when any batch-size stage
    produces a microbatch count the interleaved pipeline can't schedule
    (spmd_pipeline requires M % pp == 0 for vpp>1 'dfc')."""
    if pp > 1 and vpp > 1 and order_policy == "dfc":
        for gbs_i, m_i in batch_calc.stages():
            if m_i % pp:
                raise ValueError(
                    f"batch size {gbs_i} in the schedule gives {m_i} "
                    f"microbatches, not divisible by pipeline_parallel="
                    f"{pp} as the interleaved (dfc) pipeline requires; "
                    "adjust the rampup schedule or use order_policy "
                    "'bfc'")


class _RowBuffer:
    """Takes exactly-n sample rows from a fixed-size batch stream without
    dropping any (batch-size rampup consumes fewer rows than the stream's
    batch size; leftovers carry into the next step so consumed-samples
    bookkeeping matches the stream position exactly)."""

    def __init__(self, batch_iter):
        self._iter = batch_iter
        self._buf: Optional[Dict[str, np.ndarray]] = None

    def take(self, n: int) -> Dict[str, np.ndarray]:
        while self._buf is None or                 next(iter(self._buf.values())).shape[0] < n:
            nxt = next(self._iter)
            if self._buf is None:
                self._buf = dict(nxt)
            else:
                self._buf = {k: np.concatenate([self._buf[k], nxt[k]])
                             for k in self._buf}
        out = {k: v[:n] for k, v in self._buf.items()}
        rest = {k: v[n:] for k, v in self._buf.items()}
        self._buf = (rest if next(iter(rest.values())).shape[0] else None)
        return out


def _moe_log_part(sums: Dict[str, float]) -> str:
    """`moe here 0.250 buffer 1.31 max/mean 1.07 router 1.0e-03 | ` of a log
    interval's counters, on a model whose layers count their held experts'
    load (models/gpt.py gpt_loss "sums"): the share of the tokens' picks
    that fell on an expert held here, the rows of the buffers the held
    experts walked over those picks (moe._row_buffer_rungs; 1 / here where
    every call walks all T*k), the most loaded held expert's rows over the
    mean, and the router's loss a layer; "" on every other model."""
    if not sums.get("assignments_here"):
        return ""
    passes = sums["moe_layer_passes"]
    mean_rows = sums["assignments_here"] / sums["experts_here"]
    return (f"moe here {sums['assignments_here'] / sums['assignments']:.3f} "
            f"buffer {sums['row_buffer_rows'] / sums['assignments_here']:.2f} "
            f"max/mean {sums['here_max_rows'] / passes / mean_rows:.2f} "
            f"router {sums['router_loss'] / passes:.1e} | ")


def gpt_rank_kernels(cfg: TransformerConfig):
    """({path: (rank axis, dtype)}, None) of the kernels `gpt_loss`
    multiplies (``ops/per_rank.dense``) or looks up (``per_rank.take``)
    one copy a data-parallel rank of, or (None, why it has none): what
    ``train_step.make_train_step`` may hand the loss such copies of. The
    plain layer stack's five kernels, the rank axis behind the layers' and
    the copy in the type the layer multiplies in; the word embedding (and
    an untied head), the rank axis first, in the parameters' own type (its
    rows are added to the positions' before anything is rounded)."""
    if cfg.is_moe:
        return None, "the experts' grouped products are not per-rank ones"
    if getattr(cfg, "tp_comm_overlap", False):
        return None, ("--tp-comm-overlap's rings multiply inside their own "
                      "full-manual regions")
    if cfg.multi_latent_attention or cfg.hybrid_stack \
            or getattr(cfg, "hetero_block_specs", None) \
            or cfg.mtp_num_layers:
        return None, ("latent attention, hybrid, heterogeneous and multi "
                      "token prediction stacks: not tried")
    kernels = {("block", part, name): (1, cfg.compute_dtype)
               for part, name in (
                   ("attention", "q_kernel"), ("attention", "kv_kernel"),
                   ("attention", "out_kernel"), ("mlp", "fc1_kernel"),
                   ("mlp", "fc2_kernel"))}
    kernels["embedding", "word"] = (0, None)
    if cfg.untie_embeddings_and_output_weights:
        kernels["output",] = (0, cfg.compute_dtype)
    return kernels, None


def gpt_microbatch_loss(cfg: TransformerConfig, ctx=None):
    def loss_fn(params, micro, fp8=None):
        loss, metrics = gpt_loss(params, micro["tokens"], micro["labels"],
                                 micro["loss_mask"], cfg, ctx=ctx,
                                 segment_ids=micro.get("segment_ids"),
                                 fp8=fp8)
        return loss, metrics
    loss_fn.rank_kernels, loss_fn.no_rank_kernels = gpt_rank_kernels(cfg)
    if cfg.params_dtype != cfg.compute_dtype:
        loss_fn.compute_copies = functools.partial(
            compute_dtype_kernels, dtype=cfg.compute_dtype)
    return loss_fn


# The kernels every layer that has them casts to the compute type where it
# multiplies them: attention's and MLA's projections, a dense MLP's and the
# experts' two, the state-space and short-convolution mixers' projections,
# the per-head output gate. NOT a router's (multiplied in float32) nor a
# mixer's "conv_kernel" (its taps are float32).
_COMPUTE_DTYPE_KERNELS = frozenset({
    "q_kernel", "kv_kernel", "out_kernel", "fc1_kernel", "fc2_kernel",
    "in_kernel", "gate_kernel"})


def compute_dtype_kernels(params, dtype):
    """`params` with the kernels that the model multiplies in `dtype` cast
    to it: the leaves named in _COMPUTE_DTYPE_KERNELS and an untied head
    ("output"). Where the parameters' type is not the compute type and the
    loss gets no copy a data-parallel rank (``_PerRankKernels``, which does
    the same a rank), ``train_step.make_train_step`` differentiates the loss
    by these copies, made once a step: each is used once a micro-batch, so
    the gradients are those by the float32 leaves rounded to `dtype` (which
    their casts' cotangents are too, where XLA does not keep excess
    precision), a micro-batch's gradient tree is half the bytes beside the
    float32 accumulator, and XLA has no cast of the stacks to hoist out of
    the micro-batch loop and keep beside them."""
    from megatronapp_tpu.training.optimizer import leaf_name

    def one(path, leaf):
        name = leaf_name(path)
        multiplied = name in _COMPUTE_DTYPE_KERNELS or name == "output"
        return leaf.astype(dtype) if multiplied else leaf

    return jax.tree_util.tree_map_with_path(one, params)


def pretrain_gpt(
    model_cfg: TransformerConfig,
    parallel_cfg: ParallelConfig,
    train_cfg: TrainingConfig,
    opt_cfg: OptimizerConfig,
    batch_iter: Optional[Iterator[Dict[str, np.ndarray]]] = None,
    ctx: Optional[MeshContext] = None,
    log_fn: Callable[[str], None] = print,
    batch_iter_factory: Optional[Callable] = None,
    eval_batch_iter: Optional[Iterator[Dict[str, np.ndarray]]] = None,
) -> TrainResult:
    """End-to-end GPT pretraining loop. Returns final state + stats."""
    # fp8 delayed-scaling training (ISSUE 13, --fp8): reject ineligible
    # layouts HERE too (programmatic callers bypass the parse-time
    # check; fp8_ineligible_reason covers the FBD/DPP exclusions) —
    # checked before the FBD early-return so a silent no-op fp8 run is
    # impossible on any path.
    fp8_on = bool(getattr(model_cfg, "fp8", False))
    if fp8_on:
        from megatronapp_tpu.training.fp8 import fp8_ineligible_reason
        reason = fp8_ineligible_reason(model_cfg, parallel_cfg)
        if reason is not None:
            raise ValueError(reason)

    if parallel_cfg.forward_backward_disaggregating:
        # The FBD executor runs its own legacy schedule — a non-default
        # schedule program or the planner would be silently ignored,
        # which is worse than an error (same policy as the --use-dpp
        # parse-time check; this covers programmatic callers too).
        if getattr(parallel_cfg, "pp_schedule", "1f1b") != "1f1b" or \
                getattr(parallel_cfg, "pp_plan_from_trace", False):
            raise ValueError(
                "--pp-schedule/--pp-plan-from-trace do not compose "
                "with forward_backward_disaggregating (the FBD "
                "executor runs its own schedule); drop one")
        # The FBD executor path has no resilience wiring yet (ROADMAP
        # follow-up) — say so loudly instead of silently dropping the
        # protection the operator asked for.
        if (train_cfg.exit_signal_handler or train_cfg.heartbeat_dir
                or train_cfg.ft_timeouts
                or train_cfg.non_persistent_save_interval
                or train_cfg.simulated_fault):
            log_fn("WARNING: fault-tolerance flags (--exit-signal-"
                   "handler/--heartbeat-dir/--ft-timeouts/--non-"
                   "persistent-save-interval/--simulated-fault) are "
                   "NOT wired into the forward_backward_disaggregating "
                   "path yet — running without them")
        return _pretrain_gpt_fbd(model_cfg, parallel_cfg, train_cfg,
                                 opt_cfg, batch_iter, log_fn,
                                 batch_iter_factory=batch_iter_factory)

    # --- resilience wiring (ISSUE 6) ----------------------------------
    # Heartbeat monitor with section timeouts (training/ft_integration):
    # sections setup → step → checkpointing around the loop below; the
    # on-disk heartbeat lets an external supervisor (read_heartbeat)
    # catch a wedged process even when the in-process watchdog is hung
    # with it.
    ft = None
    if train_cfg.heartbeat_dir or train_cfg.ft_timeouts:
        from megatronapp_tpu.training.ft_integration import (
            FTConfig, HeartbeatMonitor,
        )
        ft_cfg = FTConfig(heartbeat_dir=train_cfg.heartbeat_dir)
        if train_cfg.ft_timeouts:
            (ft_cfg.setup_timeout, ft_cfg.step_timeout,
             ft_cfg.checkpointing_timeout) = train_cfg.ft_timeouts
            ft_cfg.check_interval = min(5.0,
                                        min(train_cfg.ft_timeouts) / 2)

        def _on_timeout(section, idle):
            log_fn(f"ft: section {section!r} hung for {idle:.0f}s "
                   "(timeout exceeded) — rank appears wedged")

        ft = HeartbeatMonitor(ft_cfg, on_timeout=_on_timeout).start()
        ft.start_section("setup")
    # Simulated fault for FT drills (--simulated-fault KIND:DELAY):
    # 'exit' hard-kills the process after DELAY (inside ft_integration);
    # 'hang' sets this event and the loop wedges on it — the watchdog /
    # external supervisor must catch and recover.
    sim_hang = threading.Event()
    if train_cfg.simulated_fault:
        from megatronapp_tpu.training.ft_integration import (
            maybe_setup_simulated_fault,
        )
        kind, delay = train_cfg.simulated_fault
        maybe_setup_simulated_fault(kind, delay, target=sim_hang.set)
        log_fn(f"ft: simulated fault {kind!r} armed (fires in {delay}s)")

    if ctx is None:
        ctx = build_mesh(parallel_cfg)
    log_fn(device_line(ctx.mesh))
    dp_total = ctx.dp * ctx.ep
    num_micro = train_cfg.num_microbatches(dp_total)
    from megatronapp_tpu.training.num_microbatches_calculator import (
        build_calculator,
    )
    batch_calc = build_calculator(
        train_cfg.global_batch_size, train_cfg.micro_batch_size, dp_total,
        train_cfg.rampup_batch_size)
    vpp = parallel_cfg.virtual_pipeline_parallel
    _validate_schedule_stages(batch_calc, ctx.pp, vpp,
                              parallel_cfg.pipeline_order_policy)

    # ZeRO-1 distributed optimizer (--use-distributed-optimizer): the
    # wrapper dp-shards m/v/master state; fsdp keeps the param-sharding
    # rules instead (the two compose poorly — fsdp already owns dp).
    optimizer = get_optimizer(
        opt_cfg, train_cfg.train_iters,
        distributed=(parallel_cfg.distributed_optimizer
                     and not parallel_cfg.fsdp))
    rng = jax.random.PRNGKey(train_cfg.seed)

    # fp8 amax-history state (validated above) threads through the
    # train state so checkpoints carry it and resume is bitwise.
    fp8_state = None
    if fp8_on:
        from megatronapp_tpu.training.fp8 import init_fp8_state
        fp8_state = init_fp8_state(model_cfg)

    def params_and_axes(rng):
        return init_gpt_params(rng, model_cfg, pp=ctx.pp, vpp=vpp)

    state, shardings, params_axes = setup_train_state(
        rng, params_and_axes, optimizer, ctx,
        sharded_init=train_cfg.sharded_init, fp8_state=fp8_state)

    # Checkpointing: restore from load_dir (or save_dir when resuming the
    # same run), save only to save_dir — reference --load/--save semantics
    # (training/checkpointing.py).
    ckpt = None
    start_step = 0
    # Pipeline layout metadata saved with (and consulted by) checkpoints
    # so cross-layout restores derive the stacked-leaf split instead of
    # shape-guessing (reference resharding.py source-parallelism record).
    ckpt_layout = {"pp": ctx.pp, "vpp": vpp,
                   "num_layers": model_cfg.num_layers}
    if train_cfg.save_dir:
        ckpt = CheckpointManager(train_cfg.save_dir,
                                 save_interval=train_cfg.save_interval)
    # Fast non-persistent local checkpoints (LocalCheckpointManager,
    # --non-persistent-save-interval): latest-only .npz saved every few
    # steps for quick preemption restarts, independent of the durable
    # Orbax saves.
    local_ckpt = None
    if (train_cfg.non_persistent_save_interval
            or train_cfg.non_persistent_ckpt_dir):
        np_dir = train_cfg.resolved_non_persistent_dir()
        if np_dir is None:
            log_fn("local checkpoints disabled: pass "
                   "--non-persistent-ckpt-dir or --save")
        else:
            local_ckpt = LocalCheckpointManager(np_dir)
    restore_dir = train_cfg.load_dir or train_cfg.save_dir
    loader = None
    if restore_dir:
        if train_cfg.load_dir and train_cfg.load_dir != train_cfg.save_dir:
            loader = CheckpointManager(train_cfg.load_dir)
        else:
            loader = ckpt
    # Restore prefers the FRESHEST of (local, durable); a tie goes to
    # the local copy (one flat read vs a tensorstore restore). A
    # corrupt/partial local file degrades to the durable path, and a
    # corrupt durable step walks back to the previous saved step
    # (CheckpointManager.restore fallback).
    side_state = None
    restored = None
    local_step = local_ckpt.latest_step if local_ckpt is not None else None
    durable_step = loader.latest_step if loader is not None else None
    # The restore paths are collectives under multi-host: every rank
    # must take the SAME one (one rank entering the durable restore
    # alone wedges the job — same invariant as the emergency-save
    # agreement). Local wins only when EVERY rank prefers it, and a
    # local-restore failure on ANY rank sends every rank to the durable
    # path together. (Ranks whose local files sit at different steps
    # would still diverge — per-rank local saves happen at the same
    # iterations, so differing steps imply a torn save, which shows up
    # as a corrupt/missing file and fails this agreement.)
    from megatronapp_tpu.training.signals import any_process_flag
    want_local = (local_step is not None
                  and (durable_step is None or local_step >= durable_step))
    if not any_process_flag(not want_local):
        out = local_ckpt.restore(state, return_extra=True)
        usable = out is not None
        if jax.process_count() > 1:
            # Bool agreement alone is step-BLIND: a rank whose earlier
            # local save failed (best-effort warn-and-continue) holds a
            # valid-but-STALE file, and with no durable checkpoint to
            # outvote it the ranks would restore divergent steps.
            # Gather the actual restored step (every rank participates
            # — -1 for a failed local restore) and require unanimity.
            from jax.experimental import multihost_utils
            mine = (int(jax.device_get(out[0]["step"])) if usable
                    else -1)
            steps_all = np.asarray(multihost_utils.process_allgather(
                np.asarray([mine])))
            usable = bool((steps_all == steps_all.flat[0]).all()
                          and steps_all.flat[0] >= 0)
        if any_process_flag(not usable):
            if out is not None:
                log_fn("local checkpoint unusable or stale on another "
                       "process; using the durable path")
        else:
            restored, side_state = out
            log_fn(f"restoring from local checkpoint (step {local_step})")
    if restored is None and loader is not None:
        restored = loader.restore(state, layout=ckpt_layout)
        if restored is not None:
            side_state = read_side_state(
                restore_dir, int(jax.device_get(restored["step"])))
    if restored is not None:
        state = restored
        start_step = int(jax.device_get(state["step"]))
        log_fn(f"resumed from checkpoint at step {start_step}")
    if loader is not None and loader is not ckpt:
        loader.close()
    if side_state is not None and \
            int(side_state.get("step", -1)) != start_step:
        side_state = None    # sidecar from a different step: stale

    # Consumed-samples bookkeeping honors the rampup schedule on resume
    # (reference consumed_train_samples accumulates ACTUAL batch sizes).
    # The checkpoint's side-state is authoritative when present (exact
    # stream position incl. _RowBuffer carry-over); the O(start_step)
    # schedule replay only runs for pre-side-state checkpoints.
    if side_state is not None and "consumed" in side_state:
        consumed = int(side_state["consumed"])
    else:
        consumed = 0
        for _ in range(start_step):
            consumed += batch_calc.get(consumed)[0]
    if batch_iter is None:
        # Fast-forward the data stream past already-consumed samples on
        # resume (reference consumed_train_samples bookkeeping) — via the
        # caller's factory for real datasets, the mock stream otherwise.
        if batch_iter_factory is not None:
            batch_iter = batch_iter_factory(consumed)
        else:
            batch_iter = mock_batches(
                train_cfg.seq_length, model_cfg.vocab_size,
                train_cfg.global_batch_size, seed=train_cfg.seed,
                start_idx=consumed)

    pp_schedule = getattr(parallel_cfg, "pp_schedule", "1f1b")
    if ctx.pp > 1:
        def make_pp_loss_fn(schedule):
            """Pipelined loss bound to one schedule program — the
            planner re-plan path rebuilds through this (ISSUE 15)."""
            def loss_fn(params, batch_mb):
                return gpt_pipeline_loss(
                    params, batch_mb["tokens"], batch_mb["labels"],
                    batch_mb["loss_mask"], model_cfg, ctx, vpp=vpp,
                    order_policy=parallel_cfg.pipeline_order_policy,
                    segment_ids_mb=batch_mb.get("segment_ids"),
                    schedule=schedule)
            return loss_fn

        loss_fn = make_pp_loss_fn(pp_schedule)
        if pp_schedule != "1f1b":
            log_fn(f"pipeline schedule: {pp_schedule} (instruction "
                   "program executor, parallel/schedule.py)")
    else:
        loss_fn = gpt_microbatch_loss(model_cfg, ctx=ctx)
    eval_step_fn = None
    if train_cfg.eval_interval:
        # Held-out evaluation (reference evaluate_and_print_results,
        # training.py eval loop): the caller-provided eval stream when
        # given (real validation data), else a distinct mock stream
        # (different seed). Works under pp>1 via the pipelined eval step.
        from megatronapp_tpu.training.train_step import make_eval_step
        eval_step_fn = make_eval_step(loss_fn, ctx, shardings,
                                      pipeline=ctx.pp > 1, fp8=fp8_on)
        if eval_batch_iter is None:
            eval_batch_iter = mock_batches(
                train_cfg.seq_length, model_cfg.vocab_size,
                train_cfg.global_batch_size, seed=train_cfg.seed + 1)

    # MegaDPP dynamic runtime in the training path (reference transport
    # init inside pretrain_body, training.py:746-783): with --use-dpp and
    # a pure-pp layout the step runs host-driven through the
    # DppPipelineRunner (fwd+bwd dynamic scheduling, runtime/dpp_train.py)
    # instead of the jitted SPMD schedule (one host pipeline per dp
    # replica). Layouts the host runner cannot place (tp/cp/ep > 1)
    # fall back to the static bfc SPMD order.
    use_dpp_runtime = False
    if getattr(parallel_cfg, "use_dpp", False) and ctx.pp > 1:
        if (ctx.tp == ctx.cp == ctx.ep == 1
                and not model_cfg.mtp_num_layers):
            use_dpp_runtime = True
        else:
            log_fn("dpp: layout has tp/cp/ep > 1 (or MTP) — host "
                   "runner needs one stage per device per replica; "
                   "falling back to static bfc SPMD ordering")
    if use_dpp_runtime:
        from megatronapp_tpu.runtime.dpp_train import make_dpp_train_step
        # Mesh axis order (pp, dp, ep, cp, tp): with ep=cp=tp=1 the
        # device array reshapes to a [pp][dp] grid — each dp column is
        # one replica's stage chain.
        device_grid = ctx.mesh.devices.reshape(ctx.pp, ctx.dp)
        step_fn = make_dpp_train_step(
            optimizer, opt_cfg, model_cfg, device_grid,
            train_cfg.train_iters, vpp=vpp,
            policy=parallel_cfg.pipeline_order_policy,
            check_nan=train_cfg.check_for_nan_in_loss,
            state_shardings=shardings)
        log_fn(f"dpp: dynamic runtime active (pp={ctx.pp}, dp={ctx.dp}, "
               f"vpp={vpp}, "
               f"policy={parallel_cfg.pipeline_order_policy})")
        if getattr(opt_cfg, "dist_opt_comm", "gspmd") in ("ring", "bulk") \
                and getattr(optimizer, "zero1", False):
            # The host-driven step has no manual-update hook; say so
            # instead of letting an A/B silently measure the wrong mode
            # (same loud-fallback policy as the FBD path).
            log_fn(f"dpp: --dist-opt-comm {opt_cfg.dist_opt_comm} is not "
                   "wired into the host-driven runtime — the ZeRO-1 "
                   "update runs in gspmd mode here")
    def _build_step(loss_fn_, trace_phases=False, donate=True):
        """The ONE build site for the jitted SPMD step — startup, the
        phase-traced variant, and the planner's _apply_schedule rebuild
        all go through it so they can never drift apart."""
        from megatronapp_tpu.trace.scope_map import noted
        # Registered for trace/scope_map.scope_maps(): the abstract
        # arguments of its first call with a batch shape, nothing lowered.
        return noted(
            make_train_step(
                loss_fn_, optimizer, opt_cfg, ctx, shardings,
                train_cfg.train_iters,
                check_nan=train_cfg.check_for_nan_in_loss,
                pipeline=ctx.pp > 1, trace_phases=trace_phases,
                donate=donate, fp8=fp8_on),
            kind="train", key=lambda state, batch: batch["tokens"].shape,
            guard=lambda: ctx.mesh, mesh=ctx.mesh)

    if not use_dpp_runtime:
        step_fn = _build_step(loss_fn)
    # Non-donating variant for rerun replay (compiles only if a failure is
    # ever classified; the donating step would delete the live state's
    # buffers on replay). The DPP step never donates, so it replays as-is.
    replay_step_fn = step_fn if use_dpp_runtime else \
        _build_step(loss_fn, donate=False)

    # Trace-driven dynamic pipeline planning (ISSUE 15 — closing the
    # MegaScan → MegaDPP loop): per-(stage, vstage) step-time EWMAs fed
    # by the pipeline's ring-hop trace spans and the whole-step
    # straggler signal drive a planner that models every candidate
    # schedule's bubble and re-plans with hysteresis; a re-plan rebuilds
    # the jitted step family below (loudly).
    planner = None
    saw_packed = False  # one packed batch freezes planning for the run
    if (getattr(parallel_cfg, "pp_plan_from_trace", False) and ctx.pp > 1
            and not use_dpp_runtime):
        import dataclasses as _dc_plan

        from megatronapp_tpu.parallel.overlap import tp_stage_eligible
        from megatronapp_tpu.parallel.schedule import Planner

        # Mirror pipeline.py's zb_switch: the planner may auto-apply
        # zero-bubble only where the executor realizes it with the
        # per-slot switch backward. On masked-dispatch meshes
        # (tp-sharded / cp-ring / moe-ep stage bodies) both vjps run
        # every slot — the modeled bubble win is paid back ~2x in
        # redundant backward compute, so switching there would make
        # real steps slower while the model claims improvement.
        zb_realizable = (ctx.cp == 1 and ctx.ep == 1 and not (
            ctx.tp > 1
            and tp_stage_eligible(model_cfg, ctx,
                                  train_cfg.seq_length)))
        planner = Planner(ctx.pp, vpp=vpp, model_cfg=model_cfg,
                          allow_zero_bubble=zb_realizable)
        if not zb_realizable:
            log_fn("pp-planner: zero-bubble candidate DISABLED on this "
                   "mesh — the stage body carries collectives "
                   "(tp-sharded rings / cp ring / moe ep), so the "
                   "executor runs zero-bubble as masked dual-vjp "
                   "compute that costs more than the bubble saves; "
                   "planning stays among the remaining schedules")
        _plan0 = planner.plan(num_micro)
        # Pin "current" to the CONFIGURED schedule so re-plans measure
        # improvement against what is actually running (plan() alone
        # would seed with the modeled winner before any signal exists).
        # Under vpp > 1 the candidate is named 'vpp' and '1f1b' is the
        # same interleaved schedule — seed with the alias so the
        # planner never "switches" between two names for one program.
        _seed = ("vpp" if (vpp > 1 and pp_schedule == "1f1b")
                 else pp_schedule)
        planner.current = _dc_plan.replace(
            _plan0, schedule=_seed,
            bubble_fraction=_plan0.candidates.get(
                _seed, _plan0.bubble_fraction))
        log_fn(f"pp-planner: active (schedule {pp_schedule!r}, modeled "
               f"bubble {planner.current.bubble_fraction:.4f}, "
               "candidates "
               f"{ {k: round(v, 4) for k, v in _plan0.candidates.items()} }"
               f", stage costs "
               f"{[round(c, 3) for c in _plan0.stage_costs]})")

    tracer = get_tracer()
    traced_step_fn = step_fn
    phase_traced = False
    if train_cfg.trace:
        tracer.configure(
            enabled=True, trace_dir=train_cfg.trace_dir,
            interval=train_cfg.trace_interval,
            continuous_iterations=train_cfg.continuous_trace_iterations,
            granularity=train_cfg.trace_granularity, mesh_ctx=ctx)
        # Separate compiled step with in-graph phase markers — selected only
        # on traced iterations so untraced steps carry zero overhead (the
        # reference's per-window tracing achieves this by skipping event
        # creation; under jit the instrumentation must be traced in).
        from megatronapp_tpu.trace.tracer import callbacks_supported
        if use_dpp_runtime:
            # The host-driven step has its own per-phase observability
            # (runner transfer/stall metrics in the step metrics dict);
            # in-graph phase markers only apply to the SPMD step.
            log_fn("trace: dpp runtime active — schedule-phase spans come "
                   "from the runner's per-phase metrics")
        elif callbacks_supported():
            phase_traced = True
            traced_step_fn = _build_step(loss_fn, trace_phases=True)
        else:
            # No in-graph phase markers without host callbacks: a traced
            # window keeps the host scopes, and its profiled iteration's
            # collectives and device operations by part (below).
            log_fn("trace: backend lacks host callbacks; no schedule-phase "
                   "spans (host scopes, collectives and device operations "
                   "by part remain)")

    def _apply_schedule(new_schedule: str) -> None:
        """Planner re-plan: swap the pipeline schedule program and
        rebuild the jitted step family (one recompile, loudly logged).
        Grads are schedule-invariant
        (zero-bubble parity pinned ≤1e-6), so switching mid-run never
        perturbs the optimizer trajectory beyond accumulation order."""
        nonlocal loss_fn, step_fn, replay_step_fn, traced_step_fn
        nonlocal pp_schedule
        log_fn(f"pp-planner: APPLYING schedule {new_schedule!r} "
               f"(was {pp_schedule!r}) — rebuilding the train step "
               "(one-time recompile)")
        pp_schedule = new_schedule
        loss_fn = make_pp_loss_fn(new_schedule)
        step_fn = _build_step(loss_fn)
        replay_step_fn = _build_step(loss_fn, donate=False)
        traced_step_fn = step_fn
        if phase_traced:
            traced_step_fn = _build_step(loss_fn, trace_phases=True)

    # Per-collective and per-operator events via the XLA profiler
    # (reference mappings.py:27-60 group+bytes instrumentation; here
    # synthesized post-hoc since SPMD inserts the collectives — see
    # trace/profiler_collectives.py — and every device operation gets the
    # part of the model it belongs to from the step's scope map,
    # trace/scope_map.py). One profiled iteration per trace window keeps
    # the profiler overhead off the steady state.
    _coll = {"window": -1}

    def run_step_maybe_profiled(active_fn, state, batch, it):
        # Host-driven (DPP) steps have no single lowered HLO at all — the
        # runner's metrics cover them.
        scoped = getattr(active_fn, "scope_step", None)
        if (not tracer.active or scoped is None or
                train_cfg.trace_granularity not in ("full", "collective")):
            return active_fn(state, batch)
        window = it // tracer.interval
        if window == _coll["window"]:
            return active_fn(state, batch)
        _coll["window"] = window
        from megatronapp_tpu.trace.profiler_collectives import (
            collective_events, device_op_events, profile_run,
        )
        # The step's own scope map (trace/scope_map.py), made once a batch
        # shape: under batch-size rampup a later window recompiles the
        # step, and joining profiler events against the first shape's
        # table would silently misattribute bytes/bandwidth per collective.
        key = active_fn.key_of(state, batch)
        # The call below shares this compile, as it always did (a compile
        # inside the capture would drown the device events).
        smap = scoped.map_for(key, about_to_call=(state, batch))
        by_part = train_cfg.trace_granularity == "full"
        if smap is None or not (smap.collectives or by_part):
            return active_fn(state, batch)
        result = {}

        def run():
            result["out"] = active_fn(state, batch)
            return result["out"]

        # Anchor BEFORE the capture so events land where the collectives
        # ran, not after the profile-parse delay (which varies per host
        # and would skew cross-process stage-2 comparisons).
        offset_us = tracer.now_in_iteration_us()
        try:
            raw = profile_run(run)
            tracer.add_collective_records(
                collective_events(raw, smap.collectives, iteration=it)
                + (device_op_events(raw, smap, iteration=it)
                   if by_part else []),
                offset_us=offset_us)
        except Exception as e:  # pragma: no cover — profiler optional
            log_fn(f"trace: profiler capture failed ({e})")
            if "out" not in result:  # failed before the step ran
                result["out"] = active_fn(state, batch)
        return result["out"]

    from megatronapp_tpu.training.rerun_state_machine import (
        get_rerun_state_machine,
    )
    from megatronapp_tpu.utils.straggler import get_straggler_detector

    from megatronapp_tpu.training.metrics import MetricsLogger
    metrics_logger = MetricsLogger()
    if jax.process_index() == 0:  # rank-0 writer (reference tb gating)
        if train_cfg.metrics_jsonl:
            metrics_logger.add_jsonl(train_cfg.metrics_jsonl)
        if train_cfg.tensorboard_dir:
            metrics_logger.add_tensorboard(train_cfg.tensorboard_dir,
                                           warn=log_fn)

    rerun = get_rerun_state_machine()
    rerun.mode = train_cfg.rerun_mode
    rerun.loss_spike_factor = train_cfg.loss_spike_factor
    rerun.error_injection_rate = train_cfg.error_injection_rate
    if side_state is not None and side_state.get("rerun"):
        # Resume the fault-classification statistics exactly (EMA, step
        # and injection counters); mode stays with THIS run's config.
        sd = dict(side_state["rerun"])
        sd.pop("mode", None)
        rerun.load_state_dict(sd)
    straggler = get_straggler_detector()
    if train_cfg.log_straggler:
        straggler.enable()
    inspector = None
    if train_cfg.run_workload_inspector_server and jax.process_index() == 0:
        from megatronapp_tpu.utils.inspector import get_inspector
        inspector = get_inspector()
        port = inspector.start(train_cfg.workload_inspector_port)
        log_fn(f"workload inspector: http://127.0.0.1:{port}/status")

    scribe = _CheckpointScribe(ckpt, local_ckpt, train_cfg, ckpt_layout,
                               ft, rerun, log_fn)
    losses = []
    window_tokens = 0
    window_start = time.perf_counter()
    step_time_ms = 0.0
    tokens_per_sec = 0.0

    # E2E run-health metrics (reference one_logger_utils.py parity —
    # utils/one_logger.py flushes through the standard metrics sinks).
    from megatronapp_tpu.utils.one_logger import get_e2e_tracker
    e2e = get_e2e_tracker()
    e2e.reset()
    e2e.on_train_start(start_step, consumed, train_cfg.train_iters,
                       train_cfg.seq_length)
    window_start_iter = start_step   # first iteration of the open window

    last_sync_iter = start_step
    # The loop's own phases, through the serving engine's one emission point
    # (trace/request_trace.py span): `mta.train.step` around a step's
    # dispatch, `mta.train.sync` around the device_get of a log interval's
    # metrics. The second carries its last step's loss and gradient norm
    # unrounded and the interval's counters (what the steps returned under
    # metrics["sums"], kept on the device until this one sync) summed over
    # its steps.
    from megatronapp_tpu.trace.request_trace import get_request_tracer
    spans = get_request_tracer()
    pending_sums = []
    rows = _RowBuffer(batch_iter)
    interrupted = False
    # Exit-signal sync cadence: should_exit() is a host-level collective
    # under multi-host (process_allgather) — running it every iteration
    # would put a blocking sync point in the hot loop for an event that
    # happens at most once. All ranks share the same schedule, so the
    # agreement still holds; a preemption notice drains within 8 steps.
    # Single-process keeps the cheap every-step local check.
    exit_sync_every = 1 if jax.process_count() <= 1 else 8
    if ft is not None:
        ft.start_section("step")
    with _signal_exit_context(train_cfg, log_fn) as sig, ctx.mesh:
        for it in range(start_step, train_cfg.train_iters):
            if ft is not None:
                ft.beat()
            if sim_hang.is_set():
                # FT drill: wedge the step section — heartbeats stop,
                # the watchdog flags the hang, and the external
                # supervisor (read_heartbeat) sees a stale file.
                log_fn(f"ft: simulated hang at iteration {it + 1} — "
                       "wedging the step section")
                while True:          # pragma: no cover — drill only
                    time.sleep(3600)
            tracer.iteration_begin(it)
            cur_gbs, cur_micro = batch_calc.get(consumed)
            # Rampup consumes exactly cur_gbs rows from the stream (each
            # distinct size is its own compiled step shape; leftovers
            # carry over — no samples dropped).
            batch = globalize_batch(
                reshape_global_batch(rows.take(cur_gbs), cur_micro), ctx)
            consumed += cur_gbs
            if (ctx.pp > 1 and not use_dpp_runtime
                    and "segment_ids" in batch):
                # Packed batches cannot run the zero-bubble program
                # (per-microbatch aux inputs). The stream may MIX packed
                # and unpacked batches, so one packed batch freezes
                # planning for the rest of the run, and a zero-bubble
                # schedule — planner-applied OR statically configured —
                # reverts to 1f1b BEFORE the step instead of crashing
                # mid-stream (grads are schedule-invariant, so the
                # revert is a perf-only change; a crash hours in is
                # not).
                if planner is not None and not saw_packed:
                    saw_packed = True
                    log_fn("pp-planner: packed batch in the stream — "
                           "planning frozen (zero-bubble does not "
                           "compose with packed sequences)")
                if pp_schedule == "zero-bubble":
                    log_fn("zero-bubble does not compose with packed "
                           "sequences (segment_ids in batch) — "
                           "reverting to 1f1b (grads are schedule-"
                           "invariant; perf-only change)")
                    _apply_schedule("1f1b")
                    if planner is not None and \
                            planner.current is not None:
                        planner.current = _dc_plan.replace(
                            planner.current, schedule="1f1b",
                            bubble_fraction=planner.current.candidates
                            .get("1f1b",
                                 planner.current.bubble_fraction))
            tokens_per_step = cur_gbs * train_cfg.seq_length
            straggler.start()
            with tracer.scope("train-step"):
                active_fn = traced_step_fn if tracer.active else step_fn
                with spans.span("train.step", ring="train-step",
                                iteration=it + 1,
                                micro_batches=cur_micro,
                                tokens=tokens_per_step):
                    state, metrics = run_step_maybe_profiled(
                        active_fn, state, batch, it)
                if "sums" in metrics:
                    pending_sums.append(metrics.pop("sums"))
                # Block for accurate per-step timing only when tracing or
                # logging this step; otherwise let steps pipeline.
                should_log = ((it + 1) % train_cfg.log_interval == 0 or
                              it + 1 == train_cfg.train_iters)
                if tracer.active or should_log:
                    with spans.span("train.sync", ring="train-sync") as sync:
                        metrics, got = jax.device_get(
                            (metrics, pending_sums))
                        interval_sums = {
                            k: sum(float(g[k]) for g in got)
                            for k in (got[0] if got else ())}
                        sync.set(steps=it + 1 - last_sync_iter,
                                 loss=float(metrics["loss"]),
                                 grad_norm=float(metrics["grad_norm"]),
                                 **interval_sums)
                    pending_sums = []
                    # Straggler sampling: normalize the sync-to-sync window
                    # by the number of pipelined steps it covers, so traced
                    # (1-step) and logged (log_interval-step) samples share
                    # a baseline.
                    steps_in_span = max(it + 1 - last_sync_iter, 1)
                    outlier = straggler.stop(steps=steps_in_span)
                    last_sync_iter = it + 1
                    if outlier is not None:
                        log_fn(f"straggler: step {it+1} averaged "
                               f"{outlier.elapsed_s*1e3:.0f} ms/step "
                               f"(>{straggler.z_threshold} sigma)")
                    # Result validation runs at sync points; the in-graph
                    # NaN guard (lax.cond skip) protects params on EVERY
                    # step regardless — only the host-side classification
                    # is sampled (vs the reference's per-step check).
                    loss_val = float(metrics["loss"])
                    ok, eff_loss = rerun.validate(loss_val)
                    if not ok:
                        # The step's lax.cond already skipped the param
                        # update on non-finite losses, so `state` still
                        # holds the pre-update params — replaying the same
                        # (state, batch) via the NON-donating step
                        # classifies transient vs persistent (reference
                        # rerun-to-classify; spikes with finite loss did
                        # update, so those are report-only).
                        import math as _math
                        if not _math.isfinite(eff_loss):
                            diag = rerun.classify_failure(
                                replay_step_fn, state, batch, eff_loss)
                            log_fn(f"rerun: invalid loss {eff_loss} at step "
                                   f"{it+1} — {diag.value}")
                        else:
                            log_fn(f"rerun: loss spike {eff_loss:.4f} at "
                                   f"step {it+1} (report-only)")
            was_traced = tracer.active
            # Fence on the updated params so in-flight phase callbacks
            # (e.g. the optimizer span) land inside this iteration window.
            tracer.iteration_end(
                it, fence=state["params"] if was_traced else None)
            if was_traced:
                if planner is not None:
                    # MegaScan → planner: mine the traced iteration's
                    # ring-hop spans for per-stage compute gaps BEFORE
                    # save() drains the buffer to disk.
                    planner.ingest_trace_events(tracer.peek())
                tracer.save()
            window_tokens += tokens_per_step

            if should_log:
                loss = float(metrics["loss"])
                losses.append(loss)
                now = time.perf_counter()
                dt = now - window_start
                # Iteration-indexed window length (a modulo formula
                # overcounts the first window after a mid-interval
                # checkpoint resume).
                steps_in_window = it + 1 - window_start_iter
                tokens_per_sec = window_tokens / dt
                step_time_ms = dt / max(steps_in_window, 1) * 1e3
                tflops = (tokens_per_sec *
                          flops_per_token(model_cfg, train_cfg.seq_length)
                          / ctx.num_devices / 1e12)
                log_fn(
                    f"iter {it+1:6d}/{train_cfg.train_iters} | "
                    f"loss {loss:.4f} | grad_norm "
                    f"{float(metrics['grad_norm']):.3f} | "
                    f"lr {float(metrics['lr']):.2e} | "
                    f"skipped {int(metrics['skipped'])} | "
                    f"{_moe_log_part(interval_sums)}"
                    f"{step_time_ms:.1f} ms/step | "
                    f"{tokens_per_sec:,.0f} tok/s | "
                    f"{tflops:.1f} TFLOP/s/dev")
                if inspector is not None:
                    inspector.update(
                        step=it + 1, loss=loss,
                        tokens_per_sec=round(tokens_per_sec, 1),
                        step_time_ms=round(step_time_ms, 2),
                        tflops_per_device=round(tflops, 2),
                        consumed_samples=consumed)
                metrics_logger.log(it + 1, {
                    **metrics,
                    "tokens_per_sec": tokens_per_sec,
                    "step_time_ms": step_time_ms,
                    "tflops_per_device": tflops,
                })
                # Telemetry registry (ISSUE 12): step-time histogram +
                # throughput gauge land in the SAME registry the serving
                # stack exports at /metrics — one signal substrate.
                telemetry.observe("train_step_time_ms", step_time_ms,
                                  lo=1e-2, hi=1e7)
                telemetry.set_gauge("train_tokens_per_sec",
                                    round(tokens_per_sec, 1))
                if fp8_on and telemetry.enabled():
                    # fp8 scale-drift observability (ISSUE 13): per-site
                    # current scale / worst amax gauges + saturation
                    # counters, one small device_get per logged step.
                    from megatronapp_tpu.training.fp8 import (
                        export_fp8_metrics,
                    )
                    export_fp8_metrics(state["fp8"], model_cfg)
                if planner is not None:
                    # Whole-step sample keeps the per-stage EWMAs alive
                    # between traced iterations; the gauges make the
                    # planner's input signal observable at /metrics
                    # (ISSUE 15 satellite). Re-plan with hysteresis —
                    # frozen once ANY packed batch has been seen
                    # (zero-bubble does not compose with per-microbatch
                    # aux inputs, and the stream may mix).
                    planner.observe_step(step_time_ms / 1e3)
                    planner.export_metrics()
                    if not saw_packed:
                        newp = planner.maybe_replan(cur_micro)
                        if newp is not None:
                            _apply_schedule(newp.schedule)
                e2e.track_iterations(
                    steps_in_window, dt,
                    window_tokens // train_cfg.seq_length)
                window_tokens = 0
                window_start = now
                window_start_iter = it + 1

            if eval_step_fn is not None and \
                    (it + 1) % train_cfg.eval_interval == 0:
                t_eval = time.perf_counter()
                totals = []
                for _ in range(train_cfg.eval_iters):
                    ebatch = globalize_batch(
                        reshape_global_batch(next(eval_batch_iter),
                                             num_micro), ctx)
                    totals.append(eval_step_fn(state, ebatch))
                eval_loss = float(jax.device_get(
                    jnp.mean(jnp.stack(totals))))
                eval_dt = time.perf_counter() - t_eval
                e2e.track_validation(eval_dt)
                # Keep eval time out of the next train window (it is
                # reported under validation_* instead).
                window_start += eval_dt
                log_fn(f"eval @ iter {it+1}: loss {eval_loss:.4f} over "
                       f"{train_cfg.eval_iters} batches")

            if ckpt is not None and train_cfg.save_interval and \
                    (it + 1) % train_cfg.save_interval == 0:
                with scribe.section():
                    t_save = time.perf_counter()
                    scribe.save_durable(it + 1, state, consumed)
                    save_dt = time.perf_counter() - t_save
                e2e.on_save_checkpoint(save_dt)
                # Save dispatch time is reported under save_checkpoint_*,
                # not the next train window.
                window_start += save_dt

            if local_ckpt is not None and \
                    train_cfg.non_persistent_save_interval and \
                    (it + 1) % train_cfg.non_persistent_save_interval == 0:
                with scribe.section():
                    scribe.save_local(it + 1, state, consumed)

            # Graceful signal exit (--exit-signal-handler): the in-
            # flight step above already finished; agree the decision
            # across processes (one rank must never enter the collective
            # emergency save alone), force-save durable + local
            # checkpoints with resumable side state, and exit cleanly.
            if sig is not None and (it + 1) % exit_sync_every == 0 \
                    and sig.should_exit():
                log_fn(f"signal: exit requested — emergency checkpoint "
                       f"at iteration {it + 1}")
                with scribe.section():
                    t_save = time.perf_counter()
                    # A SIGTERM landing on a save-interval boundary
                    # already has this step on disk (skip_if_current).
                    scribe.save_durable(it + 1, state, consumed,
                                        force=True, skip_if_current=True)
                    scribe.save_local(it + 1, state, consumed,
                                      what="local emergency")
                    if ckpt is not None:
                        ckpt.wait()   # durability before exit
                log_fn(f"signal: emergency save done in "
                       f"{time.perf_counter() - t_save:.2f}s; exiting "
                       "cleanly")
                interrupted = True
                break

            if train_cfg.exit_interval and \
                    (it + 1) % train_cfg.exit_interval == 0:
                break

    if ckpt is not None:
        final_step = int(jax.device_get(state["step"]))
        if train_cfg.save_interval and ckpt.latest_step != final_step:
            with scribe.section():
                scribe.save_durable(final_step, state, consumed,
                                    force=True)
        ckpt.wait()
        ckpt.close()
    if ft is not None:
        ft.stop()
    if train_cfg.trace:
        tracer.finalize()
    if inspector is not None:
        inspector.stop()
    # Flush a partial window (exit_interval or final iterations not
    # aligned to log_interval) so the summary covers every step run.
    final_iter = int(jax.device_get(state["step"]))
    if final_iter > window_start_iter:
        e2e.track_iterations(final_iter - window_start_iter,
                             time.perf_counter() - window_start,
                             window_tokens // train_cfg.seq_length)
    e2e.finish(metrics_logger, log_fn=log_fn, step=final_iter)
    metrics_logger.close()

    return TrainResult(state=state, losses=losses,
                       tokens_per_sec=tokens_per_sec,
                       step_time_ms=step_time_ms,
                       interrupted=interrupted,
                       consumed_samples=consumed)


def _pretrain_gpt_fbd(model_cfg, parallel_cfg, train_cfg, opt_cfg,
                      batch_iter=None, log_fn=print,
                      batch_iter_factory=None) -> TrainResult:
    """MegaFBD training path: forward and backward on disjoint sub-meshes
    (parallel/fbd.py). DP is halved on each mesh; per microbatch the
    forward mesh runs the vjp forward pass and ships the residuals to the
    backward mesh, which applies the transposed pass and the optimizer
    update — dispatches overlap across the two meshes. Composes with
    tp/pp/cp (each half-mesh runs the same loss_fn as the main path,
    including the SPMD pipeline)."""
    from megatronapp_tpu.parallel.fbd import FBDExecutor, split_fbd_meshes
    from megatronapp_tpu.training.num_microbatches_calculator import (
        build_calculator,
    )

    fwd_ctx, bwd_ctx = split_fbd_meshes(parallel_cfg)
    log_fn(f"FBD: forward mesh {dict(fwd_ctx.mesh.shape)} | backward mesh "
           f"{dict(bwd_ctx.mesh.shape)}")
    if parallel_cfg.distributed_optimizer:
        # The executor ships state between half-meshes with its own
        # shardings; the ZeRO-1 wrapper is not validated there yet
        # (ROADMAP follow-up) — the legacy dp-sharded-param rules apply.
        log_fn("FBD: ZeRO-1 distributed optimizer is not wired into the "
               "forward_backward_disaggregating path; using the legacy "
               "dp-sharded-param (fsdp-style) state rules")
    # Batch-size rampup composes: the executor's microbatch loop takes any
    # M (non-pipelined — no recompiles; pipelined — one compile per ramp
    # stage, same bound as the main path).
    batch_calc = build_calculator(
        train_cfg.global_batch_size, train_cfg.micro_batch_size,
        bwd_ctx.dp * bwd_ctx.ep, train_cfg.rampup_batch_size)
    vpp = parallel_cfg.virtual_pipeline_parallel
    _validate_schedule_stages(batch_calc, bwd_ctx.pp, vpp,
                              parallel_cfg.pipeline_order_policy)

    optimizer = get_optimizer(opt_cfg, train_cfg.train_iters)
    rng = jax.random.PRNGKey(train_cfg.seed)
    with bwd_ctx.mesh:
        state, shardings, _ = setup_train_state(
            rng,
            lambda k: init_gpt_params(k, model_cfg, pp=bwd_ctx.pp, vpp=vpp),
            optimizer, bwd_ctx, sharded_init=train_cfg.sharded_init)

    if bwd_ctx.pp > 1:
        # Pipelined loss on each half-mesh: the executor feeds the WHOLE
        # microbatched batch per fwd call (the pipeline schedules
        # microbatches internally), so grad accumulation degenerates to a
        # single fwd/bwd pair per step.
        def loss_fn(params, batch_whole, _ctx):
            return gpt_pipeline_loss(
                params, batch_whole["tokens"], batch_whole["labels"],
                batch_whole["loss_mask"], model_cfg, _ctx, vpp=vpp,
                order_policy=parallel_cfg.pipeline_order_policy)
    else:
        def loss_fn(params, micro, _ctx):
            loss, metrics = gpt_loss(params, micro["tokens"],
                                     micro["labels"], micro["loss_mask"],
                                     model_cfg, ctx=_ctx)
            return loss, metrics
    executor = FBDExecutor(loss_fn, optimizer, fwd_ctx, bwd_ctx, state,
                           shardings, pipeline=bwd_ctx.pp > 1)

    # Checkpointing on the backward-mesh master state (reference FBD's
    # save_checkpoint_legacy analogue — ours reuses the standard manager).
    ckpt = None
    start_step = 0
    ckpt_layout = {"pp": bwd_ctx.pp, "vpp": 1,
                   "num_layers": model_cfg.num_layers}
    if train_cfg.save_dir:
        ckpt = CheckpointManager(train_cfg.save_dir,
                                 save_interval=train_cfg.save_interval)
    restore_dir = train_cfg.load_dir or train_cfg.save_dir
    if restore_dir:
        loader = (CheckpointManager(train_cfg.load_dir)
                  if train_cfg.load_dir and
                  train_cfg.load_dir != train_cfg.save_dir else ckpt)
        restored = (loader.restore(executor.state, layout=ckpt_layout)
                    if loader else None)
        if restored is not None:
            executor.set_state(restored)
            start_step = int(jax.device_get(restored["step"]))
            log_fn(f"resumed from checkpoint at step {start_step}")
        if loader is not None and loader is not ckpt:
            loader.close()

    # Fast-forward the data stream past consumed samples on resume (same
    # bookkeeping as the main path — rampup makes consumed step-nonlinear,
    # so replay the schedule).
    consumed = 0
    for _ in range(start_step):
        consumed += batch_calc.get(consumed)[0]
    if batch_iter is None:
        if batch_iter_factory is not None:
            batch_iter = batch_iter_factory(consumed)
        else:
            batch_iter = mock_batches(
                train_cfg.seq_length, model_cfg.vocab_size,
                train_cfg.global_batch_size, seed=train_cfg.seed,
                start_idx=consumed)

    from megatronapp_tpu.training.metrics import MetricsLogger
    metrics_logger = MetricsLogger()
    if jax.process_index() == 0:
        if train_cfg.metrics_jsonl:
            metrics_logger.add_jsonl(train_cfg.metrics_jsonl)
        if train_cfg.tensorboard_dir:
            metrics_logger.add_tensorboard(train_cfg.tensorboard_dir,
                                           warn=log_fn)
    tracer = get_tracer()
    if train_cfg.trace:
        # Host-side scopes only: FBD spans two meshes; in-graph phase
        # markers are a per-mesh concept (the bwd mesh carries the
        # schedule), so trace covers dispatch-level timing.
        tracer.configure(
            enabled=True, trace_dir=train_cfg.trace_dir,
            interval=train_cfg.trace_interval,
            continuous_iterations=train_cfg.continuous_trace_iterations,
            granularity=train_cfg.trace_granularity, mesh_ctx=bwd_ctx)

    losses = []
    t0 = time.perf_counter()
    rows = _RowBuffer(batch_iter)
    start_consumed = consumed
    for it in range(start_step, train_cfg.train_iters):
        tracer.iteration_begin(it)
        cur_gbs, cur_micro = batch_calc.get(consumed)
        batch = reshape_global_batch(rows.take(cur_gbs), cur_micro)
        consumed += cur_gbs
        with tracer.scope("train-step"):
            out = executor.step(batch)
        if (it + 1) % train_cfg.log_interval == 0 or \
                it + 1 == train_cfg.train_iters:
            loss = float(jax.device_get(out["loss"]))
            fwd_loss = float(jax.device_get(out["fwd_loss"]))
            grad_norm = float(jax.device_get(out["grad_norm"]))
            losses.append(loss)
            log_fn(f"iter {it+1:6d}/{train_cfg.train_iters} | "
                   f"loss {loss:.4f} | fwd-mesh loss {fwd_loss:.4f} | "
                   f"grad_norm {grad_norm:.3f}")
            metrics_logger.log(it + 1, {"loss": loss, "fwd_loss": fwd_loss,
                                        "grad_norm": grad_norm})
        tracer.iteration_end(it)
        if tracer.active:
            tracer.save()
        if ckpt is not None and train_cfg.save_interval and \
                (it + 1) % train_cfg.save_interval == 0:
            ckpt.save(it + 1, jax.device_get(executor.state),
                      layout=ckpt_layout)
    dt = time.perf_counter() - t0
    if ckpt is not None:
        final_step = int(jax.device_get(executor.state["step"]))
        if train_cfg.save_interval and ckpt.latest_step != final_step:
            ckpt.save(final_step, jax.device_get(executor.state),
                      force=True, layout=ckpt_layout)
        ckpt.wait()
        ckpt.close()
    if train_cfg.trace:
        tracer.finalize()
    metrics_logger.close()
    tokens = (consumed - start_consumed) * train_cfg.seq_length
    return TrainResult(state=executor.state, losses=losses,
                       tokens_per_sec=tokens / max(dt, 1e-9),
                       step_time_ms=dt / max(
                           train_cfg.train_iters - start_step, 1) * 1e3,
                       consumed_samples=consumed)
