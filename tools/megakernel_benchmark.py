"""A/B microbenchmark: megakernel decode + dispatch levers (ISSUE 11;
ops/pallas/kernel_gen.py, utils/dispatch.py).

Two measurements, deterministic-first (nothing here has run on the
chip — wall numbers here are CPU, the dispatch/cost numbers are
compiled-module facts):

  decode:  plain vs FUSED decode step on the same engine config &
           requests. Gates: greedy streams EXACT, and the estimated
           kernel launches per decode step (utils/dispatch.py
           jaxpr_launch_stats — each pallas_call is one TPU custom
           call; the CPU HLO text inlines interpret-mode kernels and
           cannot be the gate) measurably REDUCED. The compiled
           cost-model flops/bytes and CPU tokens/s ride along for the
           record.
  decode_quantized / decode_tiled (ISSUE 16): the same A/B on resident
           int8 weights (fused leg dequantizes in-register), and a
           large-shape leg whose fused MLP body exceeds the VMEM
           budget — formerly a logged fallback, now grid-tiled, gated
           on the trace-only launch ratio + stream parity.
  mla / mla_int8 (ISSUE 17): the A/B on a multi-latent config — fused
           latent prologue + absorbed-q latent kernel vs the unfused
           step — plus the latent-vs-dense attention byte gate at the
           paper shape (klat=512/dpe=64/nq=16: ~0.14x, gate 0.25x).
  train:   fwd+bwd wall with the two staged PERF levers ON — flash
           backward head-fold (lever 1, --flash-head-fold) + a
           scan-unroll sweep (lever 3, --scan-unroll ∈ {1, 2, 4}) —
           vs the baseline kernels at unroll 1, attention_impl=pallas
           so the flash kernels actually run (interpret mode on CPU).
           Paired interleaved timing with per-round leg rotation;
           gates: loss parity EXACT across all legs and best-lever
           wall ratio >= 1.0.

Runs on CPU out of the box.

  python tools/megakernel_benchmark.py --max-new 6
"""

import argparse
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

DISPATCH_RATIO_GATE = 0.85   # fused launches must be <= 0.85x plain
MLA_BYTES_GATE = 0.25        # latent layout <= 0.25x dense-gather bytes
TRAIN_RATIO_GATE = 1.0       # levers-on fwd+bwd must not be slower
LOSS_ATOL = 1e-6


def _make_cfg(**over):
    import jax.numpy as jnp

    from megatronapp_tpu.config.transformer_config import TransformerConfig
    kw = dict(num_layers=2, hidden_size=128, num_attention_heads=4,
              num_query_groups=2, vocab_size=128,
              max_position_embeddings=128, compute_dtype=jnp.bfloat16,
              remat_policy="none")
    kw.update(over)
    return TransformerConfig(**kw)


def _build(cfg, params, fused, **kw):
    from megatronapp_tpu.inference.dynamic_engine import (
        DynamicInferenceEngine,
    )
    return DynamicInferenceEngine(
        params, cfg, max_batch=4, max_seq_len=96, prefill_buckets=(32, 64),
        paged=True, block_size=8, fused_decode=fused, **kw)


def _run_requests(engine, prompts, max_new):
    from megatronapp_tpu.inference.engine import SamplingParams
    ids = [engine.add_request(p, max_new, SamplingParams(greedy=True))
           for p in prompts]
    t0 = time.perf_counter()
    results = engine.run_to_completion()
    dt = time.perf_counter() - t0
    return [results[r].tolist() for r in ids], dt, len(prompts) * max_new


def run_decode_ab(max_new: int = 6, kv_dtype: str = "bf16",
                  scan_unroll: int = 2, quantized: bool = False):
    """Plain vs fused decode step: dispatch-count gate + stream parity
    + compiled cost model + CPU wall (record). quantized=True runs BOTH
    legs on resident int8 weights (the fused leg dequantizes in-register
    — ISSUE 16)."""
    import jax
    import numpy as np

    from megatronapp_tpu.models.gpt import init_gpt_params

    cfg = _make_cfg()
    fused_cfg = dataclasses.replace(cfg, scan_unroll=scan_unroll)
    params, _ = init_gpt_params(jax.random.PRNGKey(0), cfg)
    if quantized:
        from megatronapp_tpu.inference.quantization import (
            quantize_params, residentize_params,
        )
        qp, _ = quantize_params(params, resident_only=True)
        params = residentize_params(qp)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in (4, 9, 17, 26, 34, 41)]

    plain = _build(cfg, params, fused=False, kv_cache_dtype=kv_dtype)
    p_toks, p_dt, n_new = _run_requests(plain, prompts, max_new)
    fused = _build(fused_cfg, params, fused=True, kv_cache_dtype=kv_dtype)
    f_toks, f_dt, _ = _run_requests(fused, prompts, max_new)
    fused.pool.audit()
    assert fused.megakernel, "fused engine fell back to the unfused step"

    sp = plain.dispatch_stats()
    sf = fused.dispatch_stats()
    ratio = sf["dispatches_per_step"] / sp["dispatches_per_step"]
    out = {
        "kv_dtype": kv_dtype,
        "quantized_weights": quantized,
        "scan_unroll_fused": scan_unroll,
        "greedy_match": p_toks == f_toks,
        "dispatches_per_step": {"plain": sp["dispatches_per_step"],
                                "fused": sf["dispatches_per_step"]},
        "pallas_kernels_per_step": {"plain": sp["kernels"],
                                    "fused": sf["kernels"]},
        "loop_steps": {"plain": sp["loop_steps"],
                       "fused": sf["loop_steps"]},
        "dispatch_ratio": round(ratio, 4),
        "dispatch_ratio_gate": DISPATCH_RATIO_GATE,
        "within_gate": ratio <= DISPATCH_RATIO_GATE,
        "plain_tok_s": round(n_new / p_dt, 1),
        "fused_tok_s": round(n_new / f_dt, 1),
    }
    for name, st in (("plain", sp), ("fused", sf)):
        cost = st.get("compiled", {}).get("cost")
        if cost:
            out.setdefault("compiled_cost", {})[name] = cost
    return out


def run_tiled_ab(max_new: int = 2):
    """Large-shape leg (ISSUE 16): a shape whose fused MLP body exceeds
    the VMEM budget (768*6144 fp32 fc1 weights ≈ 18.9 MB > 12 MiB) used
    to fall back to the unfused step; it now grid-tiles. Gates: the
    shape is ELIGIBLE at the default budget, the traced decode step
    launches <= DISPATCH_RATIO_GATE x the unfused engine's kernels
    (launch_stats traces only — no AOT compile at this size), and a
    short greedy stream stays exact."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from megatronapp_tpu.models.gpt import init_gpt_params
    from megatronapp_tpu.ops.pallas import kernel_gen as kg
    from megatronapp_tpu.utils.dispatch import launch_stats

    cfg = _make_cfg(num_layers=1, hidden_size=768,
                    num_attention_heads=12, num_query_groups=4,
                    ffn_hidden_size=3072)
    budget = kg.get_megakernel_vmem_budget()
    tiled_plan = kg._mlp_tiles(768, 3072, True, 32, 4, 4, 2, False,
                               False, budget) is not None
    eligible = kg.megakernel_ineligible_reason(cfg, batch=2) is None
    params, _ = init_gpt_params(jax.random.PRNGKey(3), cfg)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in (4, 9)]

    def leg(fused):
        from megatronapp_tpu.inference.dynamic_engine import (
            DynamicInferenceEngine,
        )
        eng = DynamicInferenceEngine(
            params, cfg, max_batch=2, max_seq_len=64,
            prefill_buckets=(16,), paged=True, block_size=8,
            fused_decode=fused)
        toks, _, _ = _run_requests(eng, prompts, max_new)
        spec = lambda a: jax.ShapeDtypeStruct(  # noqa: E731
            a.shape, a.dtype)
        p_spec = jax.tree.map(spec, eng.params)
        pages_spec = jax.tree.map(spec, eng.pool.pages)
        scales_spec = jax.tree.map(spec, eng.pool.scales)
        mb = eng.pool.page_table.shape[1]
        args = (p_spec,
                jax.ShapeDtypeStruct((eng.max_batch, 1), jnp.int32),
                pages_spec, scales_spec,
                jax.ShapeDtypeStruct((eng.max_batch, mb), jnp.int32),
                jax.ShapeDtypeStruct((eng.max_batch,), jnp.int32),
                jax.ShapeDtypeStruct((eng.max_batch,), jnp.bool_))
        return toks, launch_stats(eng._decode, *args), eng.megakernel

    p_toks, sp, _ = leg(False)
    f_toks, sf, f_mk = leg(True)
    ratio = sf["dispatches_per_step"] / sp["dispatches_per_step"]
    return {
        "hidden_size": 768, "ffn_hidden_size": 3072,
        "vmem_budget": budget,
        "mlp_plan_tiled": tiled_plan,
        "eligible": eligible,
        "fused_engine_megakernel": f_mk,
        "greedy_match": p_toks == f_toks,
        "dispatches_per_step": {"plain": sp["dispatches_per_step"],
                                "fused": sf["dispatches_per_step"]},
        "dispatch_ratio": round(ratio, 4),
        "dispatch_ratio_gate": DISPATCH_RATIO_GATE,
        "within_gate": ratio <= DISPATCH_RATIO_GATE,
    }


def run_mla_ab(max_new: int = 6, kv_dtype: str = "bf16"):
    """MLA leg (ISSUE 17): plain vs FUSED decode on a multi-latent
    config — the fused latent prologue + absorbed-q latent kernel vs
    the unfused mla_forward step (which runs the SAME latent kernel, so
    streams gate EXACT). Gates: greedy parity, launch ratio <=
    DISPATCH_RATIO_GATE, and the latent-vs-dense attention byte ratio
    at the paper shape (klat=512, dpe=64, nq=16, dqk=dv=128) <=
    MLA_BYTES_GATE — the latent pool reads klat+dpe per cached token
    where the replaced dense gather materialized nq*(dqk+dv)+dpe.
    Compiled cost-model bytes of both kernels ride along for the
    record (totals include the shared w_v operand, so the layout ratio
    is the gate)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from megatronapp_tpu.models.gpt import init_gpt_params

    cfg = _make_cfg(multi_latent_attention=True, kv_lora_rank=32,
                    qk_head_dim=16, qk_pos_emb_head_dim=8,
                    v_head_dim=16)
    fused_cfg = dataclasses.replace(cfg, scan_unroll=2)
    params, _ = init_gpt_params(jax.random.PRNGKey(1), cfg)
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in (4, 9, 17, 26)]

    plain = _build(cfg, params, fused=False, kv_cache_dtype=kv_dtype)
    p_toks, p_dt, n_new = _run_requests(plain, prompts, max_new)
    fused = _build(fused_cfg, params, fused=True,
                   kv_cache_dtype=kv_dtype)
    f_toks, f_dt, _ = _run_requests(fused, prompts, max_new)
    fused.pool.audit()
    assert fused.megakernel, \
        "MLA fused engine fell back to the unfused step"

    sp = plain.dispatch_stats()
    sf = fused.dispatch_stats()
    ratio = sf["dispatches_per_step"] / sp["dispatches_per_step"]

    # Per-cached-token attention byte table at the paper shape. This is
    # a layout fact: the latent pool holds [klat] + [dpe] per token; the
    # dense path the kernel replaced re-expanded through kv_up to
    # nq*(dqk+dv) (+ the shared roped key) every decode step.
    klat, dpe, nq, dqk, dv = 512, 64, 16, 128, 128
    item = 2 if kv_dtype != "int8" else 1
    scale_bytes = 2 * 4 if kv_dtype == "int8" else 0  # per-row fp32 x2
    lat_tok = (klat + dpe) * item + scale_bytes
    dense_tok = (nq * (dqk + dv) + dpe) * 2   # compute dtype (bf16)
    layout_ratio = lat_tok / dense_tok

    # Compiled cost-model cross-check at the same shape, one decode
    # token over 128 cached tokens (record, not gate — totals fold in
    # the shared w_v read).
    from megatronapp_tpu.ops.pallas.kernel_gen import (
        paged_attention_latent,
    )
    from megatronapp_tpu.ops.pallas.paged_attention import (
        paged_attention_latent_reference,
    )
    from megatronapp_tpu.utils.dispatch import compiled_stats
    b, bs, mb = 1, 16, 8
    nb = b * mb + 1
    ks = jax.random.split(jax.random.PRNGKey(2), 5)
    scale = 1.0 / ((dqk + dpe) ** 0.5)
    args = (jax.random.normal(ks[0], (b, nq, klat), jnp.bfloat16),
            jax.random.normal(ks[1], (b, nq, dpe), jnp.bfloat16),
            jax.random.normal(ks[2], (nb, bs, klat), jnp.bfloat16),
            jax.random.normal(ks[3], (nb, bs, dpe), jnp.bfloat16),
            jnp.arange(1, b * mb + 1, dtype=jnp.int32).reshape(b, mb),
            jnp.full((b,), bs * mb, jnp.int32),
            jax.random.normal(ks[4], (klat, nq, dv), jnp.bfloat16))
    cost = {}
    for name, fn in (("latent_kernel", paged_attention_latent),
                     ("dense_reference",
                      paged_attention_latent_reference)):
        st = compiled_stats(
            jax.jit(lambda *a, _f=fn: _f(*a, softmax_scale=scale)),
            *args)
        if st.get("cost"):
            cost[name] = st["cost"]

    out = {
        "kv_dtype": kv_dtype,
        "kv_lora_rank": cfg.kv_lora_rank,
        "greedy_match": p_toks == f_toks,
        "dispatches_per_step": {"plain": sp["dispatches_per_step"],
                                "fused": sf["dispatches_per_step"]},
        "pallas_kernels_per_step": {"plain": sp["kernels"],
                                    "fused": sf["kernels"]},
        "dispatch_ratio": round(ratio, 4),
        "dispatch_ratio_gate": DISPATCH_RATIO_GATE,
        "within_gate": ratio <= DISPATCH_RATIO_GATE,
        "bytes_per_token": {"latent": lat_tok, "dense": dense_tok,
                            "shape": {"klat": klat, "dpe": dpe,
                                      "nq": nq, "dqk": dqk, "dv": dv}},
        "bytes_ratio": round(layout_ratio, 4),
        "bytes_ratio_gate": MLA_BYTES_GATE,
        "bytes_within_gate": layout_ratio <= MLA_BYTES_GATE,
        "plain_tok_s": round(n_new / p_dt, 1),
        "fused_tok_s": round(n_new / f_dt, 1),
    }
    if cost:
        out["compiled_cost"] = cost
    for name, st in (("plain", sp), ("fused", sf)):
        c = st.get("compiled", {}).get("cost")
        if c:
            out.setdefault("compiled_step_cost", {})[name] = c
    return out


def run_train_levers(iters: int = 6, seq: int = 256, batch: int = 2,
                     unrolls=(1, 2, 4)):
    """fwd+bwd wall: baseline kernels/unroll=1 vs head-fold + each
    scan-unroll (paired interleaved, per-round leg rotation, min-of-
    rounds). Loss parity across ALL legs gated exact (<= LOSS_ATOL)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from megatronapp_tpu.config.transformer_config import TransformerConfig
    from megatronapp_tpu.models.gpt import gpt_loss, init_gpt_params

    base_cfg = TransformerConfig(
        num_layers=4, hidden_size=128, num_attention_heads=4,
        vocab_size=512, max_position_embeddings=512,
        attention_impl="pallas", flash_block_q=128, flash_block_kv=128,
        remat_policy="none")
    params, _ = init_gpt_params(jax.random.PRNGKey(0), base_cfg)
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, base_cfg.vocab_size,
                                      (batch, seq)), jnp.int32)
    labels = jnp.roll(tokens, -1, axis=-1)
    mask = jnp.ones((batch, seq), jnp.float32)

    def make(cfg):
        return jax.jit(jax.value_and_grad(
            lambda p: gpt_loss(p, tokens, labels, mask, cfg)[0]))

    legs = {"base": make(base_cfg)}
    for u in unrolls:
        legs[f"fold_u{u}"] = make(dataclasses.replace(
            base_cfg, flash_head_fold=True, scan_unroll=u))

    losses = {}
    for name, f in legs.items():
        loss, g = f(params)            # compile + warmup
        jax.block_until_ready(g)
        losses[name] = float(loss)
    base_loss = losses["base"]
    loss_dev = max(abs(v - base_loss) for v in losses.values())

    times = {k: [] for k in legs}
    names = list(legs)
    for r in range(iters):
        for name in names[r % len(names):] + names[:r % len(names)]:
            f = legs[name]
            t0 = time.perf_counter()
            loss, g = f(params)
            jax.block_until_ready(g)
            times[name].append(time.perf_counter() - t0)
    mins = {k: min(v) for k, v in times.items()}
    lever_names = [k for k in legs if k != "base"]
    best = min(lever_names, key=lambda k: mins[k])
    ratio = mins["base"] / mins[best]
    return {
        "seq": seq, "batch": batch, "layers": base_cfg.num_layers,
        "losses": losses,
        "loss_max_dev": loss_dev,
        "loss_parity": loss_dev <= LOSS_ATOL,
        "wall_ms_min": {k: round(v * 1e3, 2) for k, v in mins.items()},
        "ratio_by_unroll": {
            k: round(mins["base"] / mins[k], 4) for k in lever_names},
        "best_lever": best,
        "fwd_bwd_ratio": round(ratio, 4),
        "ratio_gate": TRAIN_RATIO_GATE,
        "within_gate": ratio >= TRAIN_RATIO_GATE,
    }


def run(**kw):
    """Both measurements; returns a JSON-ready dict."""
    import jax

    return {
        "environment": jax.devices()[0].platform,
        "decode": run_decode_ab(
            max_new=kw.get("max_new", 6),
            scan_unroll=kw.get("scan_unroll", 2)),
        "decode_int8": run_decode_ab(
            max_new=kw.get("max_new", 6), kv_dtype="int8",
            scan_unroll=kw.get("scan_unroll", 2)),
        "decode_quantized": run_decode_ab(
            max_new=kw.get("max_new", 6),
            scan_unroll=kw.get("scan_unroll", 2), quantized=True),
        "decode_tiled": run_tiled_ab(max_new=kw.get("max_new_tiled", 2)),
        "mla": run_mla_ab(max_new=kw.get("max_new", 6)),
        "mla_int8": run_mla_ab(max_new=kw.get("max_new", 6),
                               kv_dtype="int8"),
        "train": run_train_levers(iters=kw.get("iters", 6)),
    }


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--max-new", type=int, default=6)
    ap.add_argument("--scan-unroll", type=int, default=2,
                    help="decode-side unroll for the fused leg")
    ap.add_argument("--iters", type=int, default=6)
    ap.add_argument("--local", action="store_true",
                    help="force the CPU backend")
    args = ap.parse_args(argv)

    if args.local:
        os.environ["JAX_PLATFORMS"] = "cpu"
    res = run(max_new=args.max_new, scan_unroll=args.scan_unroll,
              iters=args.iters)
    print(json.dumps(res))
    return 0


if __name__ == "__main__":
    sys.exit(main())
