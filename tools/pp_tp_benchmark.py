"""A/B benchmark: tp-SHARDED pipeline stage bodies vs the tp-replicated
baseline (megatronapp_tpu/parallel/pipeline.py ``tp_shard``).

Times the pipelined GPT forward (and fwd+bwd) on a tp x pp mesh both ways:

  replicated:  --no-tp-sharded-stage — every tp rank redundantly computes
               the whole stage body (the pre-tp-shard behavior)
  sharded:     tp-sharded activations between stages, stage projections
               through the parallel/overlap.py ring all-gather-matmul /
               matmul-reduce-scatter primitives (tp x fewer stage FLOPs,
               tp x smaller pp ppermute hops)

Also checks logits parity of the sharded pipeline against a single-device
dense forward, and 2-step train-loss parity vs single-device training.

Runs on a CPU mesh out of the box:

  python tools/pp_tp_benchmark.py --tp 2 --pp 2


Note on CPU numbers: the ring's latency hiding needs the TPU async
collective engine, but the FLOP cut is backend-independent — each tp rank
computes 1/tp of every stage GEMM instead of all of it. Each mode
therefore reports TWO kinds of number:

  flops_ratio   per-device FLOPs of the compiled step from XLA's cost
                model (replicated / sharded, ~1.99x at tp2) — exact and
                deterministic, the CI gate
  speedup       wall clock. The fwd+bwd step wins consistently on CPU
                (1.5-1.9x at tp2 x pp2 — the >=1.3x acceptance number).
                Pure-fwd at CI shapes is collective-sync dominated
                (the entire per-device FLOP cut is worth ~5 ms inside a
                ~100 ms step) and hostage to the shared container's
                scheduling — recorded for the trend, not gated.

The sharded body is measured BOTH ways tp_comm_overlap picks its
in-stage collectives — ring (chunked, latency-hiding) and bulk — and the
headline `speedup` is the better of the two: on an oversubscribed
virtual-device CPU host the ring's extra synchronization points cost
more than they hide, so bulk usually shows the FLOP cut most cleanly
there, while on chip the ring is the fast variant. Timed iterations are
INTERLEAVED round-robin and each round contributes a PAIRED
replicated/sharded ratio, so machine-wide slow windows hit every leg
equally instead of poisoning one median.
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
    """tokens[i+1] = (tokens[i]+1) % vocab — same generator family the
    training parity tests use (kept local: tools do not import tests)."""
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


def run(tp: int = 2, pp: int = 2, batch: int = 2, seq: int = 64,
        hidden: int = 128, layers: int = 4, heads: int = 4,
        vocab: int = 256, microbatches: int = 4, iters: int = 5,
        warmup: int = 1, include_grad: bool = True,
        include_train: bool = True):
    """Measure both stage-body modes; returns a JSON-ready dict."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from megatronapp_tpu.config.parallel_config import ParallelConfig
    from megatronapp_tpu.config.transformer_config import TransformerConfig
    from megatronapp_tpu.models.gpt import (
        gpt_forward, gpt_loss, gpt_pipeline_loss, init_gpt_params,
    )
    from megatronapp_tpu.parallel.mesh import build_mesh
    from megatronapp_tpu.parallel.overlap import tp_stage_eligible

    ndev = tp * pp
    if len(jax.devices()) < ndev:
        raise RuntimeError(
            f"need {ndev} devices for tp={tp} x pp={pp}, have "
            f"{len(jax.devices())} (run via the CLI, which forces virtual "
            "host devices)")
    # fp32 compute so the <=1e-5 parity pins are meaningful.
    cfg = TransformerConfig(
        num_layers=layers, hidden_size=hidden, num_attention_heads=heads,
        vocab_size=vocab, max_position_embeddings=max(seq, 64),
        compute_dtype=jnp.float32, remat_policy="none",
        tp_comm_overlap=True)
    cfg_rep = dataclasses.replace(cfg, tp_sharded_stage=False)
    cfg_bulk = dataclasses.replace(cfg, tp_comm_overlap=False)
    par = ParallelConfig(pipeline_parallel=pp, tensor_parallel=tp)
    ctx = build_mesh(par, devices=jax.devices()[:ndev])

    rng = jax.random.PRNGKey(0)
    p_pipe, _ = init_gpt_params(rng, cfg, pp=pp)
    p_flat, _ = init_gpt_params(rng, cfg)
    M, mb = microbatches, batch
    tokens = jax.random.randint(jax.random.PRNGKey(1), (M, mb, seq), 0,
                                vocab)
    labels = jnp.roll(tokens, -1, axis=-1)
    mask = jnp.ones(labels.shape, jnp.float32)

    def time_legs(legs, *args):
        """legs: {name: fn}. Compile + warm every leg, then interleave
        the timed iterations round-robin: each round times every leg
        back-to-back, so a slow scheduling window (this host is a 2-core
        container with unobservable neighbors) hits the whole round, and
        per-round PAIRED ratios vs the first leg cancel it out. Returns
        ({name: median_ms}, {name: median of per-round base/leg ratios})
        — the ratio medians are the noise-robust speedups."""
        names = list(legs)
        for fn in legs.values():
            jax.block_until_ready(fn(*args))  # compile
            for _ in range(warmup):
                jax.block_until_ready(fn(*args))
        times = {k: [] for k in names}
        for _ in range(iters):
            for k in names:
                t0 = time.perf_counter()
                jax.block_until_ready(legs[k](*args))
                times[k].append((time.perf_counter() - t0) * 1e3)
        base = names[0]
        ratios = {k: float(np.median([b / x for b, x in
                                      zip(times[base], times[k])]))
                  for k in names[1:]}
        return {k: float(np.median(v)) for k, v in times.items()}, ratios

    eligible = bool(tp_stage_eligible(cfg, ctx, seq))
    if not eligible:
        # Without eligibility every "sharded" leg would silently fall
        # back to the replicated body (a replicated-vs-replicated ~1.0x
        # non-measurement) and the tp_shard=True logits-parity pipeline
        # below would abort mid-trace. Fail up front instead.
        raise ValueError(
            f"tp={tp} x pp={pp} at seq={seq}/heads={heads}/"
            f"hidden={hidden} is not tp_stage_eligible (need tp>1, "
            "pp>1, and seq/heads/ffn divisible by tp) — nothing to A/B")
    res = {"tp": tp, "pp": pp, "batch": batch, "seq": seq,
           "hidden": hidden, "layers": layers,
           "microbatches": microbatches, "iters": iters,
           "sharded_eligible": eligible,
           "environment": jax.devices()[0].platform}

    def loss_with(c):
        return jax.jit(lambda p, t, l, m: gpt_pipeline_loss(
            p, t, l, m, c, ctx)[0])

    def compiled_flops(jitted, *args):
        """AOT-compile and read the per-device FLOP count from XLA's
        cost model — the DETERMINISTIC half of the A/B (wall clock on
        the shared CI container is hostage to invisible neighbors; the
        compiled FLOP count is exactly the tp× stage-work cut the
        tp-sharded body claims, and never jitters). Returns
        (callable, flops or None)."""
        with ctx.mesh:
            comp = jitted.lower(*args).compile()
        try:
            ca = comp.cost_analysis()
            if isinstance(ca, list):
                ca = ca[0] if ca else {}
            fl = float(ca["flops"])
        except Exception:
            fl = None
        return comp, fl

    rep_f, rep_fl = compiled_flops(loss_with(cfg_rep), p_pipe, tokens,
                                   labels, mask)
    ring_f, ring_fl = compiled_flops(loss_with(cfg), p_pipe, tokens,
                                     labels, mask)
    bulk_f, bulk_fl = compiled_flops(loss_with(cfg_bulk), p_pipe, tokens,
                                     labels, mask)
    with ctx.mesh:
        t, r = time_legs({"replicated": rep_f, "sharded_ring": ring_f,
                          "sharded_bulk": bulk_f},
                         p_pipe, tokens, labels, mask)
        res["fwd"] = {"replicated_ms": round(t["replicated"], 3),
                      "sharded_ms": round(t["sharded_ring"], 3),
                      "sharded_bulk_ms": round(t["sharded_bulk"], 3),
                      "speedup_ring": round(r["sharded_ring"], 3),
                      "speedup_bulk": round(r["sharded_bulk"], 3),
                      "speedup": round(max(r.values()), 3),
                      "flops_per_device": {
                          "replicated": rep_fl, "sharded_ring": ring_fl,
                          "sharded_bulk": bulk_fl},
                      "flops_ratio": (round(rep_fl / ring_fl, 3)
                                      if rep_fl and ring_fl else None)}

        # Loss-level parity: replicated vs both sharded variants vs the
        # dense single-mesh reference on identical params/data.
        l_rep = float(rep_f(p_pipe, tokens, labels, mask))
        l_sh = float(ring_f(p_pipe, tokens, labels, mask))
        l_bulk = float(bulk_f(p_pipe, tokens, labels, mask))
        l_ref = float(jnp.mean(jnp.stack([
            gpt_loss(p_flat, tokens[i], labels[i], mask[i], cfg)[0]
            for i in range(M)])))
        res["loss"] = {"replicated": l_rep, "sharded": l_sh,
                       "sharded_bulk": l_bulk, "dense_ref": l_ref}
        res["loss_max_abs_diff"] = float(max(abs(l_sh - l_ref),
                                             abs(l_sh - l_rep),
                                             abs(l_bulk - l_ref)))

        if include_grad:
            def grad_with(c):
                return jax.jit(jax.grad(lambda p: gpt_pipeline_loss(
                    p, tokens, labels, mask, c, ctx)[0]))
            grep_f, grep_fl = compiled_flops(grad_with(cfg_rep), p_pipe)
            gring_f, gring_fl = compiled_flops(grad_with(cfg), p_pipe)
            gbulk_f, gbulk_fl = compiled_flops(grad_with(cfg_bulk),
                                               p_pipe)
            g, gr = time_legs({"replicated": grep_f,
                               "sharded_ring": gring_f,
                               "sharded_bulk": gbulk_f}, p_pipe)
            res["fwd_bwd"] = {"replicated_ms": round(g["replicated"], 3),
                              "sharded_ms": round(g["sharded_ring"], 3),
                              "sharded_bulk_ms": round(g["sharded_bulk"],
                                                       3),
                              "speedup_ring": round(gr["sharded_ring"],
                                                    3),
                              "speedup_bulk": round(gr["sharded_bulk"],
                                                    3),
                              "speedup": round(max(gr.values()), 3),
                              "flops_per_device": {
                                  "replicated": grep_fl,
                                  "sharded_ring": gring_fl,
                                  "sharded_bulk": gbulk_fl},
                              "flops_ratio": (round(grep_fl / gring_fl, 3)
                                              if grep_fl and gring_fl
                                              else None)}

    # Logits parity of the sharded pipeline vs the dense forward (per
    # microbatch; the pipeline's last-stage outputs feed the same head).
    import megatronapp_tpu.models.gpt as gpt_mod
    from megatronapp_tpu.parallel.pipeline import spmd_pipeline
    from megatronapp_tpu.transformer.block import block_forward

    def pipeline_logits(p, toks):
        h = gpt_mod.gpt_embed(p, toks.reshape(M * mb, seq), cfg,
                              dtype=jnp.float32)
        h = h.reshape(M, mb, seq, -1)
        cos, sin = gpt_mod.gpt_rope_tables(cfg, seq)

        def stage_fn(chunk_params, x, layer_offset):
            return block_forward(chunk_params, x, cfg, cos, sin, None,
                                 layer_offset=layer_offset, ctx=ctx,
                                 tp_sharded=True)

        out_mb, _ = spmd_pipeline(stage_fn, p["block"], h, ctx, M,
                                  compute_dtype=cfg.compute_dtype,
                                  tp_shard=True)
        return gpt_mod.gpt_head(p, out_mb, cfg)

    with ctx.mesh:
        lg_pipe = jax.jit(pipeline_logits)(p_pipe, tokens)
    lg_ref = jnp.stack([gpt_forward(p_flat, tokens[i], cfg)[0]
                        for i in range(M)])
    res["logits_max_abs_diff"] = float(jnp.max(jnp.abs(
        lg_pipe - lg_ref)))

    if include_train:
        # 2-step train-loss parity vs single-device training.
        from megatronapp_tpu.config.training_config import (
            OptimizerConfig, TrainingConfig,
        )
        from megatronapp_tpu.training.train import pretrain_gpt

        def train(c, p_cfg, nd):
            tctx = build_mesh(p_cfg, devices=jax.devices()[:nd])
            tc = TrainingConfig(micro_batch_size=mb,
                                global_batch_size=mb * M,
                                seq_length=seq, train_iters=2,
                                log_interval=1)
            r = pretrain_gpt(c, p_cfg, tc,
                             OptimizerConfig(lr=1e-3, lr_decay_iters=2),
                             ctx=tctx,
                             batch_iter=_learnable_batches(
                                 seq, vocab, mb * M))
            return [float(x) for x in r.losses]
        single = train(cfg, ParallelConfig(), 1)
        shard = train(cfg, par, ndev)
        res["train_parity"] = {
            "single": single, "tp_pp_sharded": shard,
            "max_abs_diff": float(max(abs(a - b)
                                      for a, b in zip(single, shard)))}
    return res


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--tp", type=int, default=2)
    ap.add_argument("--pp", type=int, default=2)
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--hidden", type=int, default=128)
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--heads", type=int, default=4)
    ap.add_argument("--microbatches", type=int, default=4)
    ap.add_argument("--iters", type=int, default=5)
    ap.add_argument("--no-grad", action="store_true")
    ap.add_argument("--no-train", action="store_true")
    ap.add_argument("--local", action="store_true",
                    help="force the CPU backend (virtual device mesh)")
    args = ap.parse_args(argv)

    if args.local:
        os.environ["JAX_PLATFORMS"] = "cpu"
    _ensure_devices(max(args.tp * args.pp, 8))
    res = run(tp=args.tp, pp=args.pp, batch=args.batch, seq=args.seq,
              hidden=args.hidden, layers=args.layers, heads=args.heads,
              microbatches=args.microbatches, iters=args.iters,
              include_grad=not args.no_grad,
              include_train=not args.no_train)
    print(json.dumps(res))
    return 0


if __name__ == "__main__":
    sys.exit(main())
