"""A/B benchmark: ZeRO-1 distributed optimizer vs replicated baseline
(megatronapp_tpu/training/distributed_optimizer.py).

Measures, on a dp-only CPU mesh (dp2 by default), for the full jitted
train step (fwd + bwd + weight update):

  memory   per-rank bytes of the Adam m/v state, replicated vs sharded
           (the ZeRO-1 claim: ~1/dp per rank; with bf16 moments another
           2x on top). Deterministic — read off addressable shards.
  step     wall-clock step time of every ZeRO-1 comm mode (gspmd = XLA
           sharding propagation inserts the grad slice / param
           all-gather; ring = full-manual update with the overlap.py
           latency-hiding ring all-gather; bulk = full-manual tiled
           gather) as PAIRED interleaved ratios vs the replicated
           baseline — the acceptance gate is ratio <= 1.05 (the update
           must not get slower for its memory win).
  parity   sharded-vs-replicated loss curves over >= 5 train steps, for
           BOTH moments dtypes: fp32 mode compares against the plain
           optax chain (arithmetic is delegated to the same transforms,
           so the diff is exactly 0.0), bf16 mode compares against the
           wrapper with a replicated layout (same math, layout off).

Runs on a CPU mesh out of the box:

  python tools/dist_opt_benchmark.py --dp 2


Note on CPU numbers: the ring's latency hiding and the reduce-scatter's
bandwidth win need the TPU async collective engine; on XLA:CPU all legs
serialize, so the wall-clock ratio mostly shows that the sharded update
does not ADD cost at these shapes. The per-rank state-bytes cut and the
loss parity are backend-independent.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _ensure_devices(n: int):
    """Must run before jax import: give the host enough virtual devices."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n}").strip()


def _learnable_batches(seq_length, vocab_size, batch_size, seed=0):
    """tokens[i+1] = (tokens[i]+1) % vocab — the training-parity batch
    family (kept local: tools do not import tests)."""
    import numpy as np
    rng = np.random.default_rng(seed)
    while True:
        start = rng.integers(0, vocab_size, size=(batch_size, 1))
        ramp = np.arange(seq_length + 1)[None, :]
        seq = ((start + ramp) % vocab_size).astype(np.int32)
        tokens, labels = seq[:, :-1], seq[:, 1:]
        yield {
            "tokens": tokens,
            "labels": labels,
            "loss_mask": np.ones_like(tokens, dtype=np.float32),
            "position_ids": np.tile(np.arange(seq_length, dtype=np.int32),
                                    (batch_size, 1)),
        }


def _moment_bytes_per_rank(opt_state) -> int:
    """Bytes of the Adam m/v leaves resident on device 0 — the per-rank
    optimizer-state footprint the sharding is supposed to cut."""
    import jax
    dev0 = jax.devices()[0]
    total = 0
    for key in ("mu", "nu"):
        node = opt_state.get(key) if isinstance(opt_state, dict) else None
        if node is None:
            # Plain optax chain: walk the whole state for ScaleByAdamState.
            import optax
            for s in jax.tree.leaves(
                    opt_state, is_leaf=lambda x: isinstance(
                        x, optax.ScaleByAdamState)):
                if isinstance(s, optax.ScaleByAdamState):
                    node = {"mu": s.mu, "nu": s.nu}
                    for leaf in jax.tree.leaves(node):
                        for sh in leaf.addressable_shards:
                            if sh.device == dev0:
                                total += (sh.data.size *
                                          sh.data.dtype.itemsize)
            return total
        for leaf in jax.tree.leaves(node):
            for sh in leaf.addressable_shards:
                if sh.device == dev0:
                    total += sh.data.size * sh.data.dtype.itemsize
    return total


def run(dp: int = 2, batch: int = 4, seq: int = 64, hidden: int = 128,
        layers: int = 2, heads: int = 4, vocab: int = 256,
        iters: int = 7, warmup: int = 2, train_steps: int = 6):
    """Measure all legs; returns a JSON-ready dict."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from megatronapp_tpu.config.parallel_config import ParallelConfig
    from megatronapp_tpu.config.training_config import (
        OptimizerConfig, TrainingConfig,
    )
    from megatronapp_tpu.config.transformer_config import TransformerConfig
    from megatronapp_tpu.models.gpt import init_gpt_params
    from megatronapp_tpu.parallel.mesh import build_mesh
    from megatronapp_tpu.training.distributed_optimizer import (
        DistributedOptimizer,
    )
    from megatronapp_tpu.training.optimizer import get_optimizer
    from megatronapp_tpu.training.train import (
        gpt_microbatch_loss, reshape_global_batch,
    )
    from megatronapp_tpu.training.train_state import setup_train_state
    from megatronapp_tpu.training.train_step import make_train_step

    if len(jax.devices()) < dp:
        raise RuntimeError(
            f"need {dp} devices for dp={dp}, have {len(jax.devices())} "
            "(run via the CLI, which forces virtual host devices)")
    # fp32 compute so the 1e-6 parity pins are meaningful.
    cfg = TransformerConfig(
        num_layers=layers, hidden_size=hidden, num_attention_heads=heads,
        vocab_size=vocab, max_position_embeddings=max(seq, 64),
        compute_dtype=jnp.float32, remat_policy="none")
    train_cfg = TrainingConfig(micro_batch_size=batch // dp,
                               global_batch_size=batch, seq_length=seq,
                               train_iters=train_steps)
    # distributed_optimizer=False on the mesh config: the replicated
    # baseline leg must be PLAIN data parallelism (params and state
    # replicated over dp), not the legacy fsdp-style param sharding the
    # flag selects for plain optax chains. The zero1 legs carry their
    # own layout via the wrapper regardless of this flag.
    ctx = build_mesh(ParallelConfig(data_parallel=dp,
                                    distributed_optimizer=False),
                     devices=jax.devices()[:dp])
    loss_fn = gpt_microbatch_loss(cfg, ctx=ctx)
    rng = jax.random.PRNGKey(0)
    num_micro = train_cfg.num_microbatches(dp)

    batches = []
    gen = _learnable_batches(seq, vocab, batch)
    for _ in range(train_steps):
        batches.append(reshape_global_batch(next(gen), num_micro))

    def make_leg(opt_cfg, distributed, shard_state=True):
        """(step_fn, fresh state, per-rank m/v bytes, losses fn)."""
        if distributed:
            optimizer = DistributedOptimizer(opt_cfg, train_cfg.train_iters,
                                             shard_state=shard_state)
        else:
            optimizer = get_optimizer(opt_cfg, train_cfg.train_iters)
        state, shardings, _ = setup_train_state(
            rng, lambda k: init_gpt_params(k, cfg), optimizer, ctx)
        step = make_train_step(loss_fn, optimizer, opt_cfg, ctx, shardings,
                               train_cfg.train_iters, check_nan=False)
        return step, state, _moment_bytes_per_rank(state["opt_state"])

    def losses_of(step, state):
        out = []
        with ctx.mesh:
            for b in batches:
                state, metrics = step(state, b)
                out.append(float(jax.device_get(metrics["loss"])))
        return out, state

    res = {"dp": dp, "batch": batch, "seq": seq, "hidden": hidden,
           "layers": layers, "train_steps": train_steps, "iters": iters,
           "environment": jax.devices()[0].platform}

    legs = {}
    base_opt = OptimizerConfig(lr=1e-3)
    legs["replicated"] = make_leg(base_opt, distributed=False)
    for comm in ("gspmd", "ring", "bulk"):
        legs[f"zero1_{comm}"] = make_leg(
            OptimizerConfig(lr=1e-3, dist_opt_comm=comm), distributed=True)
    bf16_opt = OptimizerConfig(lr=1e-3, exp_avg_dtype="bf16",
                               exp_avg_sq_dtype="bf16")
    legs["replicated_bf16"] = make_leg(bf16_opt, distributed=True,
                                       shard_state=False)
    legs["zero1_bf16"] = make_leg(bf16_opt, distributed=True)

    # ---- memory (deterministic) --------------------------------------
    rep_bytes = legs["replicated"][2]
    res["memory"] = {
        "replicated_mv_bytes_per_rank": rep_bytes,
        "zero1_mv_bytes_per_rank": legs["zero1_gspmd"][2],
        "zero1_bf16_mv_bytes_per_rank": legs["zero1_bf16"][2],
        "ratio": round(legs["zero1_gspmd"][2] / rep_bytes, 4),
        "bf16_ratio": round(legs["zero1_bf16"][2] / rep_bytes, 4),
    }

    # ---- loss parity over >= 5 steps ---------------------------------
    curves = {}
    states = {}
    for name, (step, state, _) in legs.items():
        curves[name], states[name] = losses_of(step, state)
    res["loss"] = {k: v for k, v in curves.items()}
    fp32_diff = max(
        max(abs(a - b) for a, b in zip(curves["replicated"],
                                       curves[f"zero1_{comm}"]))
        for comm in ("gspmd", "ring", "bulk"))
    bf16_diff = max(abs(a - b) for a, b in zip(curves["replicated_bf16"],
                                               curves["zero1_bf16"]))
    res["parity"] = {"fp32_max_loss_diff": fp32_diff,
                     "bf16_max_loss_diff": bf16_diff}

    # ---- step time: interleaved PAIRED rounds ------------------------
    # (pp_tp_benchmark pattern: each round times every leg back-to-back
    # so machine-wide slow windows hit all legs equally; the reported
    # ratio is the median of per-round baseline/leg ratios.) States were
    # consumed by the parity run — donation — so rebuild per leg.
    timed = ("replicated", "zero1_gspmd", "zero1_ring", "zero1_bulk")
    steps, tstates = {}, {}
    for name in timed:
        opt_cfg = (base_opt if name == "replicated" else OptimizerConfig(
            lr=1e-3, dist_opt_comm=name.split("_", 1)[1]))
        step, state, _ = make_leg(opt_cfg, distributed=name != "replicated")
        steps[name], tstates[name] = step, state
    times = {k: [] for k in timed}
    with ctx.mesh:
        for name in timed:    # compile + warmup
            for i in range(warmup + 1):
                tstates[name], m = steps[name](tstates[name], batches[0])
            jax.block_until_ready(m["loss"])
        for r in range(iters):
            # Rotate the starting leg each round: a monotonic load ramp
            # inside a round would otherwise systematically bias the
            # legs timed later (the paired ratio only cancels noise
            # that hits a whole round equally).
            order = timed[r % len(timed):] + timed[:r % len(timed)]
            for name in order:
                t0 = time.perf_counter()
                tstates[name], m = steps[name](tstates[name], batches[0])
                jax.block_until_ready(m["loss"])
                times[name].append((time.perf_counter() - t0) * 1e3)
    ratios = {k: float(np.median([x / b for b, x in
                                  zip(times["replicated"], times[k])]))
              for k in timed if k != "replicated"}
    res["step"] = {
        **{f"{k}_ms": round(float(np.median(v)), 3)
           for k, v in times.items()},
        **{f"ratio_{k.split('_', 1)[1]}": round(v, 4)
           for k, v in ratios.items()},
        # The headline gate is the DEFAULT mode's ratio — a best-of-modes
        # min would mask a regression in ring/bulk behind a healthy
        # gspmd (the per-mode ratios above are the A/B record).
        "ratio": round(ratios["zero1_gspmd"], 4),
        "ratio_best": round(min(ratios.values()), 4),
    }
    return res


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--dp", type=int, default=2)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--hidden", type=int, default=128)
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--iters", type=int, default=7)
    ap.add_argument("--train-steps", type=int, default=6)
    args = ap.parse_args()
    _ensure_devices(max(args.dp, 2))
    res = run(dp=args.dp, batch=args.batch, seq=args.seq,
              hidden=args.hidden, layers=args.layers, iters=args.iters,
              train_steps=args.train_steps)
    print(json.dumps(res, indent=1))


if __name__ == "__main__":
    main()
