"""A/B benchmark: zero-bubble pipeline schedule vs 1F1B + the
pp x cp x tp sharded-stage composition (ISSUE 15,
megatronapp_tpu/parallel/schedule.py + parallel/pipeline.py).

Three evidence classes, all deterministic and none needing a chip:

  bubble    simulated-timeline bubble fractions off the instruction
            programs (parallel/schedule.simulate_timeline) at the bench
            shapes — uniform pp4 x M8 / pp2 x M4 and the heterogeneous
            2x-slow-stage shape. GATE: zero-bubble strictly below 1F1B
            at every shape (`gates.bubble`).
  train_ab  2-step pp2 train A/B, --pp-schedule 1f1b vs zero-bubble on
            identical seeds/data: per-step CPU wall (informational —
            the SPMD realization runs the same collective count; the
            bubble win needs an MPMD runtime / real per-stage clocks)
            and the loss-parity pin. GATE: max |loss_zb - loss_1f1b|
            <= 1e-6 (`gates.train_parity`).
  pp_cp_tp  the composed pp2 x cp2 x tp2 mesh with tp-sharded stage
            bodies: compiled per-device FLOPs ratio vs the
            tp-replicated baseline (XLA cost model — exact) and loss
            parity vs the dense single-device reference. GATES:
            ratio > 1.8 (`gates.flops_ratio`), parity <= 1e-5
            (`gates.composition_parity`).

Runs on a CPU mesh out of the box:

  python tools/pipeline_benchmark.py

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


def bubble_model():
    """Deterministic bubble fractions off the instruction programs."""
    from megatronapp_tpu.parallel.schedule import simulate_timeline
    shapes = {
        "pp4_m8_uniform": (4, 8, None),
        "pp2_m4_uniform": (2, 4, None),
        "pp4_m8_slow2x": (4, 8, [1.0, 2.0, 1.0, 1.0]),
    }
    out = {}
    ok = True
    for name, (pp, M, costs) in shapes.items():
        b1 = simulate_timeline("1f1b", pp, M,
                               stage_costs=costs)["bubble_fraction"]
        bz = simulate_timeline("zero-bubble", pp, M,
                               stage_costs=costs)["bubble_fraction"]
        out[name] = {"pp": pp, "microbatches": M,
                     "stage_costs": costs or [1.0] * pp,
                     "bubble_1f1b": round(b1, 4),
                     "bubble_zero_bubble": round(bz, 4),
                     "improvement": round(b1 - bz, 4)}
        ok &= bz < b1
    out["gate_zb_strictly_lower"] = ok
    return out


def train_ab(pp=2, mb=2, microbatches=4, seq=32, hidden=64, layers=4,
             vocab=128, steps=2):
    """2-step pp2 train A/B: 1f1b vs zero-bubble, identical seeds/data.
    Loss parity is the gate; wall time is recorded for the trend."""
    import jax
    import jax.numpy as jnp

    from megatronapp_tpu.config.parallel_config import ParallelConfig
    from megatronapp_tpu.config.training_config import (
        OptimizerConfig, TrainingConfig,
    )
    from megatronapp_tpu.config.transformer_config import TransformerConfig
    from megatronapp_tpu.parallel.mesh import build_mesh
    from megatronapp_tpu.training.train import pretrain_gpt

    cfg = TransformerConfig(
        num_layers=layers, hidden_size=hidden, num_attention_heads=4,
        vocab_size=vocab, max_position_embeddings=max(seq, 64),
        compute_dtype=jnp.float32, remat_policy="none")
    tc = TrainingConfig(micro_batch_size=mb,
                        global_batch_size=mb * microbatches,
                        seq_length=seq, train_iters=steps, log_interval=1)
    oc = OptimizerConfig(lr=1e-3, lr_decay_iters=steps)

    out = {"pp": pp, "steps": steps, "losses": {}, "wall_ms_per_step": {}}
    for sched in ("1f1b", "zero-bubble"):
        par = ParallelConfig(pipeline_parallel=pp, pp_schedule=sched)
        ctx = build_mesh(par, devices=jax.devices()[:pp])
        t0 = time.perf_counter()
        r = pretrain_gpt(cfg, par, tc, oc, ctx=ctx,
                         batch_iter=_learnable_batches(
                             seq, vocab, mb * microbatches),
                         log_fn=lambda *_a, **_k: None)
        wall = (time.perf_counter() - t0) * 1e3 / steps
        out["losses"][sched] = [float(x) for x in r.losses]
        out["wall_ms_per_step"][sched] = round(wall, 1)
    out["loss_max_abs_diff"] = float(max(
        abs(a - b) for a, b in zip(out["losses"]["1f1b"],
                                   out["losses"]["zero-bubble"])))
    return out


def pp_cp_tp(pp=2, cp=2, tp=2, mb=2, microbatches=4, seq=32, hidden=64,
             heads=4, layers=4, vocab=128):
    """Composed pp x cp x tp mesh: compiled per-device FLOPs ratio
    (sharded vs tp-replicated stage bodies) + dense loss parity."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from megatronapp_tpu.config.parallel_config import ParallelConfig
    from megatronapp_tpu.config.transformer_config import TransformerConfig
    from megatronapp_tpu.models.gpt import (
        gpt_loss, gpt_pipeline_loss, init_gpt_params,
    )
    from megatronapp_tpu.parallel.mesh import build_mesh
    from megatronapp_tpu.parallel.overlap import tp_stage_ineligible_reason

    cfg = TransformerConfig(
        num_layers=layers, hidden_size=hidden, num_attention_heads=heads,
        vocab_size=vocab, max_position_embeddings=max(seq, 64),
        compute_dtype=jnp.float32, remat_policy="none",
        tp_comm_overlap=True)
    cfg_rep = dataclasses.replace(cfg, tp_sharded_stage=False)
    par = ParallelConfig(pipeline_parallel=pp, tensor_parallel=tp,
                         context_parallel=cp)
    ndev = pp * cp * tp
    ctx = build_mesh(par, devices=jax.devices()[:ndev])
    reason = tp_stage_ineligible_reason(cfg, ctx, seq)
    if reason is not None:
        raise ValueError(
            f"pp{pp} x cp{cp} x tp{tp} at seq={seq} is not "
            f"tp_stage_eligible ({reason}) — nothing to A/B")

    rng = jax.random.PRNGKey(0)
    p_flat, _ = init_gpt_params(rng, cfg)
    p_pipe, _ = init_gpt_params(rng, cfg, pp=pp)
    M = microbatches
    tokens = jax.random.randint(jax.random.PRNGKey(1), (M, mb, seq), 0,
                                vocab)
    labels = jnp.roll(tokens, -1, axis=-1)
    mask = jnp.ones(labels.shape, jnp.float32)

    def flops_and_loss(c, schedule="1f1b"):
        f = jax.jit(lambda p: gpt_pipeline_loss(
            p, tokens, labels, mask, c, ctx, schedule=schedule)[0])
        with ctx.mesh:
            comp = f.lower(p_pipe).compile()
            loss = float(comp(p_pipe))
        try:
            ca = comp.cost_analysis()
            if isinstance(ca, list):
                ca = ca[0] if ca else {}
            fl = float(ca["flops"])
        except Exception:
            fl = None
        return fl, loss

    fl_sh, l_sh = flops_and_loss(cfg)
    fl_rep, l_rep = flops_and_loss(cfg_rep)
    _, l_zb = flops_and_loss(cfg, schedule="zero-bubble")
    ref = float(jnp.mean(jnp.stack([
        gpt_loss(p_flat, tokens[i], labels[i], mask[i], cfg)[0]
        for i in range(M)])))
    return {
        "pp": pp, "cp": cp, "tp": tp, "seq": seq,
        "flops_per_device": {"replicated": fl_rep, "sharded": fl_sh},
        "flops_ratio": (round(fl_rep / fl_sh, 3)
                        if fl_rep and fl_sh else None),
        "loss": {"sharded": l_sh, "replicated": l_rep,
                 "zero_bubble": l_zb, "dense_ref": ref},
        "loss_max_abs_diff": float(max(abs(l_sh - ref),
                                       abs(l_rep - ref))),
        "zb_vs_1f1b_abs_diff": float(abs(l_zb - l_sh)),
    }


def run(steps: int = 2):
    """All three evidence classes + the gate summary."""
    res = {"bubble": bubble_model()}
    res["train_ab"] = train_ab(steps=steps)
    res["pp_cp_tp"] = pp_cp_tp()
    res["gates"] = {
        "bubble": bool(res["bubble"]["gate_zb_strictly_lower"]),
        "train_parity": res["train_ab"]["loss_max_abs_diff"] <= 1e-6,
        "flops_ratio": (res["pp_cp_tp"]["flops_ratio"] or 0) > 1.8,
        "composition_parity":
            res["pp_cp_tp"]["loss_max_abs_diff"] <= 1e-5
            and res["pp_cp_tp"]["zb_vs_1f1b_abs_diff"] <= 1e-6,
    }
    res["ok"] = all(res["gates"].values())
    import jax
    res["environment"] = jax.devices()[0].platform
    return res


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--steps", type=int, default=2)
    ap.add_argument("--devices", type=int, default=8)
    args = ap.parse_args(argv)
    _ensure_devices(args.devices)
    print(json.dumps(run(steps=args.steps), indent=2))


if __name__ == "__main__":
    main()
