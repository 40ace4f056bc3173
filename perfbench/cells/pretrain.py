"""``"runner": "pretrain"``: ``pretrain_gpt`` fed the harness's batches, timed
from its own log lines.

The harness reaches the loop only through ``batch_iter``, ``log_fn`` and its
own ``TrainingConfig`` object. A log line follows ``device_get`` of the
step's metrics, so the time between two log lines is device time for the
steps between them. Warm-up: steps 1 and 2 are logged one by one (step 1
compiles and gives the first loss, which is checked against the plain
reference); step 2's log line opens the window and ``log_interval`` goes to
the job's value; the window closes at the first log line at or after
``--seconds``. A traced run profiles one more log interval after the window,
so the window's numbers are taken with the profiler off.

The model comes from the configuration's ``model`` module and the batches
from the traffic file's ``kind`` generator (``env["model"]``,
``env["generator"]``): this file knows neither by name.
"""

from __future__ import annotations

import gc
import math
import re
import time

import jax
import numpy as np

from perfbench import common

ITER_RE = re.compile(r"iter\s+(\d+)/\s*\d+ \| loss (\S+) \| grad_norm (\S+) "
                     r"\| lr \S+ \| skipped (\d+) \|")
# First-step loss against the float32 reference. The program computes in
# bf16 with float32 accumulation and float32 softmax/loss, and logs the loss
# to four decimals; at initialisation it is ln(V) + ~0.1 (about 10.9). On the
# chip the gap was 1.3e-5 to 7.9e-5 in the one-chip cell and 4.3e-5 to
# 2.3e-4 over five seeds of the four-chip cell (PERF.md, PR 25); the limit is
# four times the worst, and far below what a wrong mask, a dropped bias or a
# misplaced position does (each moves the loss by 1e-2 or more at these
# widths). The initial loss answers little to precision: this check is for
# structure, not for the type of the arithmetic.
FIRST_LOSS_TOL = 1e-3


def weights_seed(env) -> int:
    """The seed the program draws its weights from: the traffic file's
    ``weights_seed`` where it names one, else ``--seed``. A mix names one
    where the WEIGHTS decide how much work a step is (a seeded router sends
    a share of the picks to the held experts that is a property of the
    draw: PERF.md section 6, PR 63); ``--seed`` then draws the token ids
    alone, as it does for every serving cell's replayed trace."""
    return int(env["traffic"].get("weights_seed", env["seed"]))


class _Loop:
    """The harness's side of the training loop: the log sink that opens
    and closes the window, and the batch source that remembers the first
    global batch for the reference."""

    def __init__(self, env, train_cfg, job, batches, compiles):
        self.env, self.cfg, self.job = env, train_cfg, job
        self.batches = batches
        self.compiles = compiles
        self.first_batch = None
        self.lines = []            # (t, iteration, loss, skipped)
        self.first_loss = None
        self.t_open = None
        self.it_open = None
        self.compiles_at_open = None
        self.compiles_at_close = None
        self.closed = False
        self.tracing = False
        self.traced_steps = 0
        self._span = None

    def __iter__(self):
        return self

    def __next__(self):
        batch = next(self.batches)
        if self.first_batch is None:
            self.first_batch = batch
        return batch

    def log(self, msg: str) -> None:
        now = time.perf_counter()
        m = ITER_RE.search(msg)
        if not m:
            return
        it, loss, skipped = int(m[1]), float(m[2]), int(m[4])
        if self.t_open is None:
            self.env["say"](msg.strip())
        if it == 1:
            self.first_loss = loss
            self.lines.append((now, it, loss, skipped))
            return
        if self.t_open is None:
            # Step 2 ran compiled; its log line (a sync) opens the window.
            self.cfg.log_interval = self.job["log_interval"]
            self.t_open, self.it_open = now, it
            self.compiles_at_open = self.compiles.count
            self.lines.append((now, it, loss, skipped))
            return
        if self.tracing:
            self._span.__exit__(None, None, None)
            jax.profiler.stop_trace()
            self.tracing = False
            self.traced_steps = it - self.lines[-1][1]
            self.cfg.exit_interval = it
            return
        self.lines.append((now, it, loss, skipped))
        if not self.closed and now - self.t_open >= self.env["seconds"]:
            self.closed = True
            self.compiles_at_close = self.compiles.count
            if self.env["trace_dir"]:
                common.start_trace(self.env["trace_dir"])
                self._span = jax.profiler.TraceAnnotation("bench.window")
                self._span.__enter__()
                self.tracing = True
            else:
                self.cfg.exit_interval = it


def run_cell(env) -> dict:
    from megatronapp_tpu.config.parallel_config import ParallelConfig
    from megatronapp_tpu.config.training_config import (
        OptimizerConfig, TrainingConfig,
    )
    from megatronapp_tpu.training.train import pretrain_gpt

    config, job, say = env["config"], env["traffic"], env["say"]
    model = env["model"]
    tr = config["train"]
    chips = len(env["devices"])
    seq = min(job["seq_length"], config["max_position_embeddings"])
    model_cfg = model.model_config(config, tr["params_dtype"],
                                   remat_policy=tr["remat_policy"])
    parallel = ParallelConfig(
        tensor_parallel=tr.get("tensor_parallel", 1),
        data_parallel=tr.get("data_parallel"),
        distributed_optimizer=tr.get("distributed_optimizer", True))
    if parallel.tensor_parallel * (parallel.data_parallel or 1) != chips \
            and chips > 1:
        raise SystemExit("perfbench: the configuration's layout does not "
                         f"cover {chips} chips")
    train_cfg = TrainingConfig(
        micro_batch_size=tr["micro_batch_size"],
        global_batch_size=job["sequences_per_step"], seq_length=seq,
        train_iters=10 ** 7, seed=weights_seed(env) % (2 ** 31),
        log_interval=1, sharded_init=tr.get("sharded_init", False))
    opt_cfg = OptimizerConfig(lr=job["lr"], min_lr=job["min_lr"],
                              lr_warmup_iters=job["lr_warmup_iters"],
                              lr_decay_iters=job["lr_decay_iters"])
    compiles = common.CompileCounter()
    loop = _Loop(env, train_cfg, job,
                 env["generator"].batches(job, env["seed"],
                                          config["vocab_size"], seq),
                 compiles)
    result = pretrain_gpt(model_cfg, parallel, train_cfg, opt_cfg,
                          batch_iter=loop, log_fn=loop.log)
    jax.block_until_ready(result.state)
    del result
    gc.collect()

    problems = []
    window = [ln for ln in loop.lines
              if loop.t_open is not None and ln[1] >= loop.it_open]
    if len(window) < 2:
        raise SystemExit("perfbench: the window holds no whole log interval")
    steps = window[-1][1] - window[0][1]
    span = window[-1][0] - window[0][0]
    tokens_per_step = job["sequences_per_step"] * seq
    intervals_ms = [(b[0] - a[0]) / (b[1] - a[1]) * 1e3
                    for a, b in zip(window, window[1:])]
    losses = [ln[2] for ln in loop.lines]
    if not all(math.isfinite(x) for x in losses):
        problems.append("a logged loss is not finite")
    skipped = sum(ln[3] for ln in loop.lines)
    if skipped:
        problems.append(f"{skipped} skipped steps")
    in_window = loop.compiles_at_close - loop.compiles_at_open
    if in_window:
        problems.append(f"{in_window} compilations inside the window")

    # ---- the first step's loss against the plain reference ----------------
    t_ref = time.perf_counter()
    params = model.init_params(model_cfg, weights_seed(env),
                               env["devices"][0])
    rows_per_micro = tr["micro_batch_size"] * (parallel.data_parallel or 1)
    micro_losses = []
    with jax.default_device(env["devices"][0]):
        for lo in range(0, job["sequences_per_step"], rows_per_micro):
            micro = {k: v[lo:lo + rows_per_micro]
                     for k, v in loop.first_batch.items()}
            micro_losses.append(model.reference_loss(params, config, micro))
    ref_loss = float(np.mean(micro_losses))
    del params
    gap = abs(loop.first_loss - ref_loss)
    say(f"perfbench: first step loss {loop.first_loss:.4f}, float32 "
        f"reference {ref_loss:.4f}, gap {gap:.2e} (tolerance "
        f"{FIRST_LOSS_TOL}); reference took "
        f"{time.perf_counter() - t_ref:.1f}s")
    if not gap <= FIRST_LOSS_TOL:
        problems.append(f"first loss {loop.first_loss} vs reference "
                        f"{ref_loss}: gap {gap:.3e} > {FIRST_LOSS_TOL}")

    tok_s_chip = steps * tokens_per_step / span / chips
    return {
        "kind": "train",
        "correct": not problems, "problems": problems,
        "attempted": steps, "failed": skipped,
        "end_to_end": {
            "train_tok_s_chip": tok_s_chip,
            "setup_s": loop.t_open - env["t_start"],
        },
        "step_intervals_ms": intervals_ms,
        "tokens_per_step": tokens_per_step,
        "seq_length": seq,
        "tok_s_chip": tok_s_chip,
        "traced_steps": loop.traced_steps,
        "notes": {"steps": steps, "window_s": span,
                  "first_loss": loop.first_loss, "reference_loss": ref_loss,
                  "last_loss": losses[-1], "compile_s": compiles.seconds,
                  "compilations": compiles.count},
    }
