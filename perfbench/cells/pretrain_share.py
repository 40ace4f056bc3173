"""``"runner": "pretrain_share"``: ``cells/pretrain.py``'s loop, window and
first-loss check for a model that trains ONE CHIP'S SHARE of an
expert-parallel job, held to three things more:

(a) the FIRST STEP'S GRADIENT, every element of it, against the float32
    reference's (``model.reference_loss_and_grads``, ``jax.vjp`` of the
    plain equations) meaned over the same micro-batches at the timed sizes.
    Its norm as the program records it (unrounded, in the first
    ``train-sync`` record of its span ring; the log line has three decimals
    of it) against the reference's norm; and the gradient itself,
    ``|g_program - g_reference| / |g_reference|`` over all the parameters:
    the program's own step is run once more from the same seed on the same
    first batch, and the first moment its optimizer keeps after one step is
    the step's gradient but for a factor, so scaled to the norm the step
    logged it IS the gradient of the timed run's first step (the repeat's
    loss and norm are held to the timed run's, bit for bit). A norm is
    second order in a rounding error and this gap is first order: it is
    what holds the arithmetic to bf16 (operands in float8 read four times
    the program's own distance; a bf16 accumulator it cannot tell: see
    FIRST_GRAD_GAP_TOL);
(b) the identities of the routing counters over the window, from the
    ``train-sync`` records of that ring
    (``megatronapp_tpu/trace/request_trace.py``; the same numbers ride the
    ``mta.train.sync`` annotations of a traced run): every token's picks are
    counted (``assignments`` = tokens x top-k x layers), each lands on a held
    expert or on an absent one (``assignments_here`` + ``assignments_absent``
    = ``assignments``), and the layers hold what the file says
    (``experts_here`` a layer pass = ``num_experts``);
(c) what ``cells/pretrain.py`` holds every training cell to: no skipped step,
    no compilation inside the window, the first loss.

``cells/pretrain.py`` is used whole and as it is: this file hands it a model
that remembers the reference's gradients and a generator that remembers the
last batches' ``segment_ids`` (the traced steps' allowed pairs are counted
from them: ``mellum_flops.window_pairs``), and adds its checks to the
result.
"""

from __future__ import annotations

import collections
import gc
import math
import time

import jax
import jax.numpy as jnp

from perfbench import manifest, mellum_flops, xplane_stats

pretrain = manifest.load_module("cells", "pretrain")

# The first step's gradient norm, program over reference, less 1. A scalar
# norm is second order in a rounding error: it holds the layer equations (a
# dropped band, segment mask, renormalisation or YaRN factor reads 1e-2 to
# 5e-2) and says nothing of the arithmetic's type (the reference run in bf16
# reads 3.6e-4 to 4.4e-4, inside the program's own 2.1e-4 to 4.0e-4). See
# PERF.md (PR 48).
FIRST_GRAD_NORM_TOL = 2e-3
# |g_program - g_reference| / |g_reference| over every parameter of the
# first step: first order in the error. The configuration states bf16
# arithmetic (products of bf16 arrays summed in float32) over float32
# parameters; the reference is float32 at precision "highest", and the
# program's own distance from it is the bf16 rounding of every operand:
# 1.3e-2 to 1.4e-2 on the chip, whichever seed (PERF.md, PR 48, has every
# reading). The limit lies between that and what the reference reads with
# its products' operands rounded to float8_e4m3fn, the precision below the
# one the configuration states (5.9e-2: `"correct": false` by this limit and
# by no other; tools/share_train_control.py runs it through this runner).
# It cannot tell a bf16 ACCUMULATOR from a float32 one, and no norm of the
# gradient can: the reference on bf16 arrays reads 1.39e-2 against the
# program and with its attention's and experts' sums carried in bf16
# 1.44e-2, a bf16 accumulator adding a third of the program's own distance
# in quadrature. A dropped band, segment mask, renormalisation or YaRN
# factor reads 0.14 to 0.96.
FIRST_GRAD_GAP_TOL = 2.8e-2


class _Recording:
    """The cell's model as ``cells/pretrain.py`` sees it: its
    ``reference_loss`` also keeps the sum of the micro-batches' reference
    gradients."""

    def __init__(self, model):
        self._model = model
        self.grad_sum = None
        self.micro_batches = 0

    def __getattr__(self, name):
        return getattr(self._model, name)

    def reference_loss(self, params, config, micro):
        loss, grads = self._model.reference_loss_and_grads(
            params, config, micro)
        self.grad_sum = grads if self.grad_sum is None else jax.tree.map(
            jnp.add, self.grad_sum, grads)
        self.micro_batches += 1
        return loss

    def grad_norm(self) -> float:
        """Norm of the mean over micro-batches, which is what the step
        clips and logs."""
        total = sum(float(jnp.sum(jnp.square(g)))
                    for g in jax.tree.leaves(self.grad_sum))
        return math.sqrt(total) / self.micro_batches


class _Remembering:
    """The cell's generator, remembering the first global batch and the last
    batches' segment_ids (a traced interval is ``log_interval`` steps: 2 in
    the cell)."""

    def __init__(self, generator):
        self._generator = generator
        self.first_batch = None
        self.segment_ids = collections.deque(maxlen=16)

    def batches(self, *args, **kw):
        for batch in self._generator.batches(*args, **kw):
            if self.first_batch is None:
                self.first_batch = batch
            self.segment_ids.append(batch["segment_ids"])
            yield batch


def _window_counters(syncs, steps: int):
    """The window's ``train-sync`` records summed, or None if they do not
    cover exactly its `steps`: the first two syncs are steps 1 and 2
    (``cells/pretrain.py`` logs them one by one; the second opens the
    window), the window's follow."""
    total = collections.Counter()
    for args in syncs[2:]:
        if total["steps"] >= steps:
            break
        total.update({k: v for k, v in args.items()
                      if k not in ("loss", "grad_norm")})
    return dict(total) if total["steps"] == steps else None


def _first_moment(state):
    """The first-moment tree of a training state's optimizer: ``"mu"`` of the
    ZeRO-1 wrapper's dict, ``.mu`` of an optax chain's Adam state."""
    found = []

    def visit(node):
        if isinstance(node, dict) and "mu" in node:
            found.append(node["mu"])
        elif hasattr(node, "mu"):
            found.append(node.mu)
        elif isinstance(node, (tuple, list)):
            for child in node:
                visit(child)

    visit(state["opt_state"])
    if len(found) != 1:
        raise SystemExit("perfbench: the training state's optimizer keeps "
                         f"{len(found)} first moments, not one")
    return found[0]


def _first_step_again(env, first_batch, ring):
    """The program's first step once more, alone: the same seed, sizes and
    first global batch through ``pretrain_gpt`` with ``exit_interval`` 1.
    -> (the optimizer's first moment after it, the step's ``train-sync``
    record). The configuration objects are ``cells/pretrain.py``'s, line for
    line; the step compiles to the same program (a cache read)."""
    from megatronapp_tpu.config.parallel_config import ParallelConfig
    from megatronapp_tpu.config.training_config import (
        OptimizerConfig, TrainingConfig,
    )
    from megatronapp_tpu.training.train import pretrain_gpt
    config, job = env["config"], env["traffic"]
    tr = config["train"]
    seq = min(job["seq_length"], config["max_position_embeddings"])
    model_cfg = env["model"].model_config(config, tr["params_dtype"],
                                          remat_policy=tr["remat_policy"])
    parallel = ParallelConfig(
        tensor_parallel=tr.get("tensor_parallel", 1),
        data_parallel=tr.get("data_parallel"),
        distributed_optimizer=tr.get("distributed_optimizer", True))
    train_cfg = TrainingConfig(
        micro_batch_size=tr["micro_batch_size"],
        global_batch_size=job["sequences_per_step"], seq_length=seq,
        train_iters=10 ** 7, seed=pretrain.weights_seed(env) % (2 ** 31),
        log_interval=1, exit_interval=1,
        sharded_init=tr.get("sharded_init", False))
    opt_cfg = OptimizerConfig(lr=job["lr"], min_lr=job["min_lr"],
                              lr_warmup_iters=job["lr_warmup_iters"],
                              lr_decay_iters=job["lr_decay_iters"])
    before = len(ring.dump())
    result = pretrain_gpt(model_cfg, parallel, train_cfg, opt_cfg,
                          batch_iter=iter([first_batch, first_batch]),
                          log_fn=lambda msg: None)
    moment = _first_moment(result.state)
    jax.block_until_ready(moment)
    del result
    gc.collect()
    syncs = [r["args"] for r in ring.dump()[before:]
             if r["name"] == "train-sync" and r["ph"] == "E"]
    return moment, (syncs[0] if syncs else {})


def _gradient_gap(moment, norm: float, reference, micro_batches: int):
    """(|g - r| / |r| over every parameter, the same of the leaf that reads
    worst, that leaf's path) for g = `moment` scaled to `norm` (the
    program's gradient of the step) and r = `reference` / `micro_batches`
    (the reference's mean gradient; host arrays, put on the device a leaf at
    a time)."""
    moments = jax.tree_util.tree_leaves_with_path(moment)
    size = math.sqrt(sum(float(jnp.sum(jnp.square(m.astype(jnp.float32))))
                         for _, m in moments))
    scale = norm / size
    gap2 = ref2 = 0.0
    worst = (0.0, "")
    for (path, m), r in zip(moments, jax.tree.leaves(reference),
                            strict=True):
        r = jnp.asarray(r) / micro_batches
        d2 = float(jnp.sum(jnp.square(m.astype(jnp.float32) * scale - r)))
        r2 = float(jnp.sum(jnp.square(r)))
        gap2, ref2 = gap2 + d2, ref2 + r2
        worst = max(worst, (math.sqrt(d2 / r2) if r2 else math.inf,
                            jax.tree_util.keystr(path)))
    return math.sqrt(gap2 / ref2), worst[0], worst[1]


def run_cell(env) -> dict:
    from megatronapp_tpu.trace.request_trace import get_request_tracer
    config, job = env["config"], env["traffic"]
    model, generator = _Recording(env["model"]), _Remembering(env["generator"])
    ring = get_request_tracer()
    ring.reset()
    ring.configure(enabled=True)
    try:
        run = pretrain.run_cell(dict(env, model=model, generator=generator))
        syncs = [r["args"] for r in ring.dump()
                 if r["name"] == "train-sync" and r["ph"] == "E"]
        # The reference's gradients wait on the host while the program's
        # step has the chip once more.
        t_again = time.perf_counter()
        ref_norm = model.grad_norm()
        reference = jax.device_get(model.grad_sum)
        model.grad_sum = None
        moment, again = _first_step_again(env, generator.first_batch, ring)
    finally:
        ring.configure(enabled=False)
    problems = run["problems"]

    # ---- (a) the first step's gradient norm ------------------------------
    first = syncs[0] if syncs else {}
    gap = abs(first.get("grad_norm", math.nan) / ref_norm - 1)
    env["say"](f"perfbench: first step grad norm {first.get('grad_norm')}, "
               f"float32 reference {ref_norm:.6f} over {model.micro_batches} "
               f"micro-batches, relative gap {gap:.2e} (tolerance "
               f"{FIRST_GRAD_NORM_TOL}); unrounded first loss "
               f"{first.get('loss')}")
    if not gap <= FIRST_GRAD_NORM_TOL:
        problems.append(f"first grad norm {first.get('grad_norm')} vs "
                        f"reference {ref_norm}: relative gap {gap:.3e} > "
                        f"{FIRST_GRAD_NORM_TOL}")

    # ---- (a) the first step's gradient, every element ---------------------
    same = all(again.get(k) == first.get(k) for k in ("loss", "grad_norm"))
    if not same:
        problems.append(
            "the first step run once more reads loss "
            f"{again.get('loss')} and grad norm {again.get('grad_norm')}, "
            f"the timed run's first step {first.get('loss')} and "
            f"{first.get('grad_norm')}")
    grad_gap, leaf_gap, leaf = _gradient_gap(
        moment, again.get("grad_norm", math.nan), reference,
        model.micro_batches)
    del moment, reference
    env["say"](f"perfbench: first step gradient against the float32 "
               f"reference, |g - r| / |r| over every parameter "
               f"{grad_gap:.3e} (tolerance {FIRST_GRAD_GAP_TOL}); the leaf "
               f"that reads worst {leaf_gap:.3e} {leaf}; the step run once "
               f"more and the comparison took "
               f"{time.perf_counter() - t_again:.1f}s")
    if not grad_gap <= FIRST_GRAD_GAP_TOL:
        problems.append(f"first gradient against the reference: |g - r| / "
                        f"|r| = {grad_gap:.3e} > {FIRST_GRAD_GAP_TOL}")

    # ---- (b) the counters' identities over the window --------------------
    steps = run["notes"]["steps"]
    counters = _window_counters(syncs, steps)
    layers = config["num_hidden_layers"]
    if counters is None:
        problems.append("the program's train-sync records do not cover the "
                        f"window's {steps} steps")
        counters = {}
    else:
        want = (steps * run["tokens_per_step"]
                * config["num_experts_per_tok"] * layers)
        passes = counters["moe_layer_passes"]
        for what, got, wanted in (
                ("assignments", counters["assignments"], want),
                ("assignments_here + assignments_absent",
                 counters["assignments_here"]
                 + counters["assignments_absent"], counters["assignments"]),
                ("experts_here a layer pass",
                 counters["experts_here"] / passes, config["num_experts"])):
            if got != wanted:
                problems.append(f"{what} = {got:g} over the window, not "
                                f"{wanted:g}")

    # ---- what the readers of a traced run take ---------------------------
    if env["trace_dir"]:
        run["xplane_stats"] = xplane_stats.load(env["trace_dir"])
        traced = list(generator.segment_ids)[-run["traced_steps"]:] \
            if run["traced_steps"] else []
        run["window_pairs_traced"] = sum(
            mellum_flops.window_pairs(seg, config["sliding_window"])
            for seg in traced)
    run["correct"] = not problems
    run["notes"].update(
        first_grad_norm=first.get("grad_norm"),
        reference_grad_norm=ref_norm, first_loss_unrounded=first.get("loss"),
        first_grad_gap=grad_gap, first_grad_gap_worst_leaf=[leaf, leaf_gap],
        window_counters={k: counters[k] for k in sorted(counters)},
        micro_batch_size=config["train"]["micro_batch_size"],
        seq_length=job["seq_length"],
        # ms a step of each log interval of the window: a slow interval is
        # told from a slow step (PERF.md section 7, the cell's spread)
        interval_ms_step=[round(ms, 1) for ms in run["step_intervals_ms"]])
    return run
