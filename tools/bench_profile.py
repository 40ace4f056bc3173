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
peak-proxy. `--sweep-only` runs the attention sweep instead (dense against
the flash kernels, forward + backward, by shape and tile): what
`ops/pallas/flash_attention.py choose_attention` was decided from.
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


# The shapes `choose_attention` (ops/pallas/flash_attention.py) was decided
# from: S x D, with and without packed segments, at the per-device batch and
# heads of the two training cells (4 x 16 heads on one chip, 1 x 16 a chip
# at tp2 x dp2), and the tiles tried at each.
SWEEP_SEQ = (512, 1024, 2048)
SWEEP_HEAD_DIM = (64, 80, 128)
SWEEP_BATCH_HEADS = ((4, 16), (1, 16))
SWEEP_TILES = ((512, 512), (256, 512), (512, 256), (256, 256), (1024, 512),
               (512, 1024), (1024, 1024))
SWEEP_LAYERS = 24
# Where dense stops winning: XLA keeps the float32 [B, H, S, S] scores of a
# call on the chip while they fit (108 MiB do) and goes to HBM for them when
# they do not (128 MiB do not). (batch, heads, S) with 4 * B * H * S * S
# bytes from 64 to 256 MiB, so that the same bytes come from several S.
SWEEP_CLIFF = ((4, 16, 512), (2, 16, 768), (6, 16, 512), (3, 16, 768),
               (8, 16, 512), (2, 16, 1024), (1, 16, 1536), (4, 16, 768),
               (10, 16, 512), (12, 16, 512), (3, 16, 1024), (6, 16, 768),
               (16, 16, 512), (4, 16, 1024),
               (1, 4, 2048), (1, 6, 2048), (1, 8, 2048))


def _packed_segments(batch, seq, seed=0):
    """[batch, seq] segment ids of log-normal documents (median 400, sigma
    1.2) packed end to end: the training cells' traffic."""
    import numpy as np
    rng = np.random.default_rng(seed)
    rows = []
    for _ in range(batch):
        ids, n = [], 0
        while len(ids) < seq:
            ids += [n] * max(1, int(rng.lognormal(np.log(400), 1.2)))
            n += 1
        rows.append(ids[:seq])
    return np.asarray(rows, np.int32)


def sweep_grid():
    """(batch, heads, S, D, causal, segments) of the sweep's grid."""
    cases = [(b, h, s, d, True, seg)
             for b, h in SWEEP_BATCH_HEADS for s in SWEEP_SEQ
             for d in SWEEP_HEAD_DIM for seg in (False, True)]
    b, h = SWEEP_BATCH_HEADS[0]     # bidirectional and unpacked
    return cases + [(b, h, s, d, False, False)
                    for s in SWEEP_SEQ for d in SWEEP_HEAD_DIM]


def sweep_cliff():
    """The same of SWEEP_CLIFF: heads of 64 and of 128, packed."""
    return [(b, h, s, d, True, True)
            for d in (64, 128) for b, h, s in SWEEP_CLIFF]


def attention_sweep(cases):
    """Forward + backward under the training cells' recomputation (the
    `selective` policy saves no attention matmul: forward, forward again,
    backward) of XLA's dense attention and of the flash kernels at each of
    SWEEP_TILES (and at one tile a sequence), ms a call, for every case.
    bf16."""
    import jax
    import jax.numpy as jnp

    from megatronapp_tpu.config.transformer_config import AttnMaskType
    from megatronapp_tpu.ops.attention import dot_product_attention
    from megatronapp_tpu.ops.pallas.flash_attention import flash_attention

    policy = jax.checkpoint_policies.dots_with_no_batch_dims_saveable

    def fwd_bwd_ms(attn, *args):
        def layer(q, k, v, g, *rest):
            out, vjp = jax.vjp(jax.checkpoint(
                lambda q_, k_, v_: attn(q_, k_, v_, *rest), policy=policy),
                q, k, v)
            # the output is kept: three passes, not two
            return sum(x.astype(jnp.float32).sum() for x in (out, *vjp(g)))

        # SWEEP_LAYERS calls in one program, as the layer scan makes them:
        # a call alone is under the host's ~0.5 ms a dispatch at S 512.
        @jax.jit
        def fn(q, *rest):
            return jax.lax.scan(
                lambda acc, scale: (acc + layer(q * scale, *rest), None),
                jnp.float32(0), jnp.linspace(0.9, 1.1, SWEEP_LAYERS,
                                             dtype=q.dtype))[0]
        return round(_time_fn(fn, *args, steps=(2, 6)) / SWEEP_LAYERS * 1e3,
                     4)

    rows = []
    for b, h, s, d, causal, seg in cases:
        keys = jax.random.split(jax.random.PRNGKey(s + d), 4)
        q, k, v, g = (jax.random.normal(kk, (b, s, h, d), jnp.bfloat16)
                      for kk in keys)
        args = (q, k, v, g) + ((jnp.asarray(_packed_segments(b, s)),)
                               if seg else ())
        mask_type = (AttnMaskType.causal if causal
                     else AttnMaskType.bidirectional)

        def dense(q_, k_, v_, seg_=None):
            mask = None if seg_ is None else (
                seg_[:, None, :, None] == seg_[:, None, None, :])
            return dot_product_attention(q_, k_, v_, mask_type=mask_type,
                                         attention_mask=mask)

        row = {"batch": b, "heads": h, "seq": s, "head_dim": d,
               "causal": causal, "segments": seg,
               "dense_ms": fwd_bwd_ms(dense, *args), "flash_ms": {}}
        for bq, bkv in sorted({(s, s), *SWEEP_TILES}):
            if bq > s or bkv > s or bq * bkv > 1024 * 1024:
                continue

            def flash(q_, k_, v_, seg_=None, bq=bq, bkv=bkv):
                return flash_attention(q_, k_, v_, causal=causal,
                                       block_q=bq, block_kv=bkv,
                                       segment_ids=seg_)
            try:
                row["flash_ms"][f"{bq}x{bkv}"] = fwd_bwd_ms(flash, *args)
            except Exception as e:  # noqa: BLE001 - Mosaic refused the tiles
                row["flash_ms"][f"{bq}x{bkv}"] = (
                    "refused: " + str(e).splitlines()[0][:120])
        print("sweep: " + json.dumps(row), flush=True)
        rows.append(row)
    return rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--sweep-only", action="store_true",
                    help="instead of the report: the sweep `auto` attention "
                         "rests on (dense against the flash kernels by "
                         "shape and tile, ~15 min), written to "
                         "chiprun_out/attention_sweep.json")
    ap.add_argument("--seq", type=int, default=1024)
    ap.add_argument("--bs", type=int, default=4)
    ap.add_argument("--hidden", type=int, default=768)
    ap.add_argument("--heads", type=int, default=12)
    ap.add_argument("--layers", type=int, default=12)
    args = ap.parse_args()

    if args.sweep_only:
        import os
        rows = attention_sweep(sweep_grid() + sweep_cliff())
        os.makedirs("chiprun_out", exist_ok=True)
        with open("chiprun_out/attention_sweep.json", "w") as f:
            json.dump(rows, f, indent=1)
        return

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
