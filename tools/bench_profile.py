"""Component-wise MFU profiling on the real chip.

Decomposes the GPT-2 125M train step into isolated measurements so the MFU
gap (BASELINE.md: ~18-20% measured vs 40% target) can be attributed:

  1. peak-proxy matmul (8192³) — the chip's practical ceiling
  2. model-shaped matmul chain (the layer's 4 big GEMMs, no glue)
  3. flash attention kernel alone (fwd / fwd+bwd)
  4. reference (XLA-fused dense) attention alone
  5. one full layer fwd+bwd
  6. full model fwd+bwd (the bench.py number)

All timings are differential two-window, each window ending in a
device_get (written before block_until_ready could be trusted to wait; not
yet re-timed — ROADMAP S2).

Usage:  timeout 900 python tools/bench_profile.py [--seq 1024] [--bs 4]
Prints one JSON report; each entry carries achieved TFLOP/s and % of the
peak-proxy.
"""

import argparse
import json
import sys
import time

sys.path.insert(0, __file__.rsplit("/tools/", 1)[0])


def _time_fn(fn, *args, steps=(3, 13)):
    """Differential timing: run n1 and n2 dispatch windows, subtract."""
    import jax
    out = fn(*args)  # compile + warmup
    jax.device_get(jax.tree.leaves(out)[0].ravel()[0])
    times = {}
    for n in steps:
        t0 = time.perf_counter()
        o = None
        for _ in range(n):
            o = fn(*args)
        jax.device_get(jax.tree.leaves(o)[0].ravel()[0])
        times[n] = time.perf_counter() - t0
    return (times[steps[1]] - times[steps[0]]) / (steps[1] - steps[0])


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seq", type=int, default=1024)
    ap.add_argument("--bs", type=int, default=4)
    ap.add_argument("--hidden", type=int, default=768)
    ap.add_argument("--heads", type=int, default=12)
    ap.add_argument("--layers", type=int, default=12)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from megatronapp_tpu.config.transformer_config import TransformerConfig
    from megatronapp_tpu.utils.flops import TPU_PEAK_FLOPS, flops_per_token

    S, B, H, NH, L = (args.seq, args.bs, args.hidden, args.heads,
                      args.layers)
    D = H // NH
    report = {"device": str(jax.devices()[0]), "config":
              {"seq": S, "bs": B, "hidden": H, "heads": NH, "layers": L}}

    def entry(name, seconds, flops):
        tf = flops / seconds / 1e12
        report[name] = {"ms": round(seconds * 1e3, 3),
                        "tflops": round(tf, 1)}
        return tf

    # 1. peak proxy
    n = 8192
    a = jnp.ones((n, n), jnp.bfloat16)
    mm = jax.jit(lambda a: a @ a)
    peak_tf = entry("peak_matmul_8192", _time_fn(mm, a), 2 * n ** 3)

    # 2. layer-shaped GEMM chain (qkv, out, fc1, fc2) without glue
    x = jnp.ones((B * S, H), jnp.bfloat16)
    w_qkv = jnp.ones((H, 3 * H), jnp.bfloat16)
    w_out = jnp.ones((H, H), jnp.bfloat16)
    w_fc1 = jnp.ones((H, 4 * H), jnp.bfloat16)
    w_fc2 = jnp.ones((4 * H, H), jnp.bfloat16)

    @jax.jit
    def gemm_chain(x):
        y = x @ w_qkv
        y = y[:, :H] @ w_out
        y = y @ w_fc1
        return y @ w_fc2
    chain_flops = 2 * B * S * H * (3 * H + H + 4 * H + 4 * H)
    entry("layer_gemm_chain", _time_fn(gemm_chain, x), chain_flops)

    # 3/4. attention alone: pallas flash vs XLA-fused dense
    from megatronapp_tpu.ops.attention import dot_product_attention
    from megatronapp_tpu.ops.pallas.flash_attention import flash_attention
    q = jnp.ones((B, S, NH, D), jnp.bfloat16)
    attn_flops = 2 * 2 * B * NH * S * S * D / 2  # causal ≈ half

    fl = jax.jit(lambda q: flash_attention(q, q, q, causal=True))
    entry("flash_attn_fwd", _time_fn(fl, q), attn_flops)
    flb = jax.jit(jax.grad(lambda q: flash_attention(
        q, q, q, causal=True).astype(jnp.float32).sum()))
    entry("flash_attn_fwd_bwd", _time_fn(flb, q), attn_flops * 3.5)

    # 3b. orientation A/B: the straight-orientation kernels (pre-round-5)
    # via the FLASH_STRAIGHT_ORIENTATION knob — attributes the
    # transposed orientation's win directly (PERF.md round-5 item 1).
    import os as _os
    _os.environ["FLASH_STRAIGHT_ORIENTATION"] = "1"
    try:
        fl_st = jax.jit(lambda q: flash_attention(q, q, q, causal=True))
        entry("flash_attn_fwd_straight", _time_fn(fl_st, q), attn_flops)
        flb_st = jax.jit(jax.grad(lambda q: flash_attention(
            q, q, q, causal=True).astype(jnp.float32).sum()))
        entry("flash_attn_fwd_bwd_straight", _time_fn(flb_st, q),
              attn_flops * 3.5)
    finally:
        del _os.environ["FLASH_STRAIGHT_ORIENTATION"]

    dn = jax.jit(lambda q: dot_product_attention(q, q, q))
    entry("dense_attn_fwd", _time_fn(dn, q), attn_flops)
    dnb = jax.jit(jax.grad(lambda q: dot_product_attention(
        q, q, q).astype(jnp.float32).sum()))
    entry("dense_attn_fwd_bwd", _time_fn(dnb, q), attn_flops * 3.5)

    # 4b. flash-vs-dense crossover sweep over sequence length (PERF.md
    # lever #2: locates the auto-select threshold flash_min_seq).
    for s_len in (1024, 2048, 4096):
        qs = jnp.ones((max(B * S // s_len, 1), s_len, NH, D), jnp.bfloat16)
        fl_s = jax.jit(jax.grad(lambda q: flash_attention(
            q, q, q, causal=True).astype(jnp.float32).sum()))
        dn_s = jax.jit(jax.grad(lambda q: dot_product_attention(
            q, q, q).astype(jnp.float32).sum()))
        sweep_flops = (2 * 2 * qs.shape[0] * NH * s_len * s_len * D / 2
                       * 3.5)
        entry(f"flash_fwd_bwd_S{s_len}", _time_fn(fl_s, qs), sweep_flops)
        entry(f"dense_fwd_bwd_S{s_len}", _time_fn(dn_s, qs), sweep_flops)

    # 5. one layer fwd+bwd (both attention impls)
    import dataclasses

    from megatronapp_tpu.models.gpt import gpt_loss, init_gpt_params
    for impl in ("pallas", "reference"):
        cfg1 = TransformerConfig(
            num_layers=1, hidden_size=H, num_attention_heads=NH,
            vocab_size=256, max_position_embeddings=S,
            attention_impl=impl, remat_policy="none")
        p1, _ = init_gpt_params(jax.random.PRNGKey(0), cfg1)
        toks = jnp.zeros((B, S), jnp.int32)
        g1 = jax.jit(jax.grad(lambda p: gpt_loss(
            p, toks, toks, None, cfg1)[0]))
        # ~3x forward flops per token for fwd+bwd, minus the head (vocab
        # 256 keeps the head negligible).
        lf = 3 * (chain_flops + attn_flops)
        entry(f"layer1_fwd_bwd_{impl}", _time_fn(g1, p1), lf)

    # 6. full model step (bench.py shape)
    cfg = TransformerConfig(
        num_layers=L, hidden_size=H, num_attention_heads=NH,
        vocab_size=50304, max_position_embeddings=S,
        remat_policy="selective")
    p, _ = init_gpt_params(jax.random.PRNGKey(0), cfg)
    toks = jnp.zeros((B, S), jnp.int32)
    gm = jax.jit(jax.grad(lambda p: gpt_loss(p, toks, toks, None, cfg)[0]))
    full_flops = B * S * flops_per_token(cfg, S)
    sec = _time_fn(gm, p)
    entry("full_model_fwd_bwd", sec, full_flops)

    kind = getattr(jax.devices()[0], "device_kind", "cpu").lower()
    peak = next((v for k, v in TPU_PEAK_FLOPS.items() if k in kind), None)
    for k, v in report.items():
        if isinstance(v, dict) and "tflops" in v:
            v["pct_of_peak_proxy"] = round(v["tflops"] / peak_tf * 100, 1)
            if peak:
                v["pct_of_spec_peak"] = round(v["tflops"] / (peak / 1e12)
                                              * 100, 1)
    print(json.dumps(report, indent=1))


if __name__ == "__main__":
    main()
