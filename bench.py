"""Benchmark: GPT-2 125M-class training throughput on one TPU chip.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.
Metric of record (BASELINE.md): tokens/sec/chip; vs_baseline is MFU relative
to the 40% MFU north-star target (reference publishes no absolute numbers —
BASELINE.json published: {}).

A chip belongs to one process at a time, so the parent imports no JAX and
runs one child per attention implementation, one after the other. A child
that finds no TPU, or a TPU whose peak rate it does not know, fails; the
parent then exits non-zero and prints no record.
"""

import json
import os
import subprocess
import sys
import time

BENCH_TIMEOUT_S = 600   # one compile + two timed windows per child


def _run_child(impl: str):
    """Run this file's --bench child for one attention impl; return its
    JSON record, or an error string."""
    env = dict(os.environ, BENCH_ATTENTION_IMPL=impl)
    try:
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--bench"],
            capture_output=True, text=True, timeout=BENCH_TIMEOUT_S, env=env)
    except subprocess.TimeoutExpired:
        return None, f"bench[{impl}] timed out after {BENCH_TIMEOUT_S}s"
    lines = [ln for ln in proc.stdout.strip().splitlines() if ln.strip()]
    if proc.returncode != 0 or not lines:
        tail = (proc.stderr or "").strip().splitlines()[-8:]
        return None, (f"bench[{impl}] rc={proc.returncode}: "
                      + " | ".join(tail))
    try:
        return json.loads(lines[-1]), None
    except json.JSONDecodeError:
        return None, f"bench[{impl}] emitted non-JSON: {lines[-1][:200]}"


def parent_main() -> int:
    by_impl = {}
    # `auto` (what a user gets: at this shape the flash kernels) and the
    # path it did not take
    for impl in ("auto", "reference"):
        rec, err = _run_child(impl)
        if rec is None:
            print(err, file=sys.stderr)
        else:
            by_impl[impl] = rec
    if not by_impl:
        print("bench: no run on a TPU succeeded; no record", file=sys.stderr)
        return 1
    best = max(by_impl, key=lambda k: by_impl[k]["value"])
    res = by_impl[best]
    res["extra"]["attention_impl"] = best
    res["extra"]["tok_s_by_impl"] = {
        k: v["value"] for k, v in by_impl.items()}
    print(json.dumps(res))
    return 0


def _measure_impl(attention_impl: str):
    """tokens/s for one attention implementation, timed to
    block_until_ready."""
    import jax
    import numpy as np

    from megatronapp_tpu.config.parallel_config import ParallelConfig
    from megatronapp_tpu.config.training_config import OptimizerConfig
    from megatronapp_tpu.config.transformer_config import TransformerConfig
    from megatronapp_tpu.models.gpt import gpt_loss, init_gpt_params
    from megatronapp_tpu.parallel.mesh import build_mesh
    from megatronapp_tpu.training.optimizer import get_optimizer
    from megatronapp_tpu.training.train_state import setup_train_state
    from megatronapp_tpu.training.train_step import make_train_step

    # GPT-2 125M (reference run_single_gpt.sh class model).
    cfg = TransformerConfig(
        num_layers=12, hidden_size=768, num_attention_heads=12,
        vocab_size=50304, max_position_embeddings=1024,
        remat_policy="selective", attention_impl=attention_impl,
    )
    seq, micro_bs, n_micro = 1024, 4, 1
    par = ParallelConfig()
    ctx = build_mesh(par, devices=jax.devices()[:1])

    opt_cfg = OptimizerConfig(lr=1e-4)
    optimizer = get_optimizer(opt_cfg, 100)
    state, shardings, _ = setup_train_state(
        jax.random.PRNGKey(0), lambda k: init_gpt_params(k, cfg),
        optimizer, ctx)

    def loss_fn(params, micro):
        loss, m = gpt_loss(params, micro["tokens"], micro["labels"],
                           micro["loss_mask"], cfg)
        return loss, m

    step_fn = make_train_step(loss_fn, optimizer, opt_cfg, ctx, shardings,
                              100, check_nan=False)

    rng = np.random.default_rng(0)
    tokens = rng.integers(0, cfg.vocab_size,
                          (n_micro, micro_bs, seq)).astype(np.int32)
    batch = {
        "tokens": tokens,
        "labels": np.roll(tokens, -1, axis=-1),
        "loss_mask": np.ones_like(tokens, dtype=np.float32),
        "position_ids": np.tile(np.arange(seq, dtype=np.int32),
                                (n_micro, micro_bs, 1)),
    }

    with ctx.mesh:
        state, metrics = step_fn(state, batch)  # compile + warmup
        jax.block_until_ready(state)
        n_steps = 20
        t0 = time.perf_counter()
        for _ in range(n_steps):
            state, metrics = step_fn(state, batch)
        jax.block_until_ready((state, metrics))
        dt = time.perf_counter() - t0

    tokens_per_step = micro_bs * n_micro * seq
    return cfg, seq, tokens_per_step * n_steps / dt, dt / n_steps


def bench_main():
    """One attention impl per invocation (BENCH_ATTENTION_IMPL env; the
    parent runs one child per impl and reports the faster — the
    flash/dense crossover at this shape was set from one noisy round-2
    sample, so the bench self-selects)."""
    import jax

    from megatronapp_tpu.utils.flops import TPU_PEAK_FLOPS, flops_per_token
    from megatronapp_tpu.utils.platform import enable_compile_cache

    enable_compile_cache()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"bench: needs a TPU, JAX found {dev.platform!r}")
    kind = dev.device_kind.lower()
    peaks = [v for k, v in TPU_PEAK_FLOPS.items() if k in kind]
    if not peaks:
        raise SystemExit(f"bench: no peak FLOP/s known for device_kind "
                         f"{dev.device_kind!r} (utils/flops.py)")

    impl = os.environ.get("BENCH_ATTENTION_IMPL", "auto")
    cfg, seq, tok_per_sec, step_s = _measure_impl(impl)
    mfu = tok_per_sec * flops_per_token(cfg, seq) / peaks[0]

    print(json.dumps({
        "metric": "gpt2_125m_tokens_per_sec_per_chip",
        "value": round(tok_per_sec, 1),
        "unit": "tokens/s",
        "vs_baseline": round(mfu / 0.40, 4),
        "extra": {"mfu": round(mfu, 4), "device": kind,
                  "step_ms": round(step_s * 1e3, 2),
                  "attention_impl": impl},
    }))


if __name__ == "__main__":
    if "--bench" in sys.argv:
        bench_main()
    else:
        sys.exit(parent_main())
