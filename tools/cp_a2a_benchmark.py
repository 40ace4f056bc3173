"""A/B microbenchmark: GSPMD baselines vs the full-manual latency-hiding
context-parallel ring attention and MoE chunked all-to-all dispatch
(ISSUE 2; megatronapp_tpu/ops/context_parallel.py, transformer/moe.py).

Two pairs, timed on the same mesh with the same inputs:

  ring:  dense dot_product_attention with q/k/v seq-sharded over cp (XLA
         all-gathers K/V and every rank computes its S/cp x S score strip)
     vs  context_attention 'p2p' — the overlapped custom_vjp ring
         (pre-issued ppermute hops, causal block skip, fused reverse-ring
         backward).
  a2a:   moe_forward with ctx=None (GSPMD compiler-sharded dispatch:
         XLA reshards token-sharded <-> expert-sharded layouts)
     vs  moe_forward with ctx (full-manual chunked all-to-all,
         _chunked_a2a_ffn — token exchange decomposed into per-peer hops
         issued under the expert GEMMs).

Runs on a CPU mesh out of the box (forces 8 virtual host devices when too
few are visible) and on real TPU meshes unchanged. Reports both pairs plus
fwd+bwd timings and the numeric diffs, as one JSON line:

  python tools/cp_a2a_benchmark.py --cp 4 --ep 4 --seq 512


Note on CPU numbers: XLA:CPU executes collectives synchronously, so the
latency hiding itself contributes nothing here — the CPU-mesh win comes
from the causal block skip (ring) and from avoiding the GSPMD
rematerialization churn (a2a); the hop/GEMM overlap needs the TPU async
collective engine (PERF.md round-7 section).
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


def _timer(iters, warmup):
    import jax
    import numpy as np

    def time_fn(fn, *args):
        out = fn(*args)  # compile
        jax.block_until_ready(out)
        times = []
        for _ in range(warmup):
            jax.block_until_ready(fn(*args))
        for _ in range(iters):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*args))
            times.append((time.perf_counter() - t0) * 1e3)
        return float(np.median(times)), out

    return time_fn


def run_ring(cp: int = 4, batch: int = 2, seq: int = 512, heads: int = 8,
             kv_heads: int = 4, head_dim: int = 64, iters: int = 10,
             warmup: int = 2, include_grad: bool = True):
    """Overlapped causal ring attention vs the GSPMD dense baseline."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from megatronapp_tpu.config.parallel_config import CP_AXIS, ParallelConfig
    from megatronapp_tpu.ops.attention import dot_product_attention
    from megatronapp_tpu.ops.context_parallel import context_attention
    from megatronapp_tpu.parallel.mesh import build_mesh

    ctx = build_mesh(ParallelConfig(context_parallel=cp),
                     devices=jax.devices()[:cp])
    mesh = ctx.mesh
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(batch, seq, heads, head_dim)),
                    jnp.float32)
    k = jnp.asarray(rng.normal(size=(batch, seq, kv_heads, head_dim)),
                    jnp.float32)
    v = jnp.asarray(rng.normal(size=(batch, seq, kv_heads, head_dim)),
                    jnp.float32)
    shard = NamedSharding(mesh, P(None, CP_AXIS))
    qs, ks, vs = (jax.device_put(x, shard) for x in (q, k, v))

    def gspmd(q, k, v):
        return dot_product_attention(q, k, v)

    def overlap(q, k, v):
        return context_attention(q, k, v, mesh, "p2p", causal=True)

    def loss_of(pair):
        return lambda q, k, v: jnp.sum(pair(q, k, v) ** 2)

    time_fn = _timer(iters, warmup)
    res = {"cp": cp, "batch": batch, "seq": seq, "heads": heads,
           "kv_heads": kv_heads, "head_dim": head_dim, "iters": iters}
    with mesh:
        g_ms, g_out = time_fn(jax.jit(gspmd), qs, ks, vs)
        o_ms, o_out = time_fn(jax.jit(overlap), qs, ks, vs)
        res["fwd"] = {"gspmd_ms": round(g_ms, 3),
                      "overlap_ms": round(o_ms, 3),
                      "speedup": round(g_ms / o_ms, 3) if o_ms else None}
        res["max_abs_diff"] = float(jnp.max(jnp.abs(
            g_out.astype(jnp.float32) - o_out.astype(jnp.float32))))
        if include_grad:
            gg = jax.jit(jax.grad(loss_of(gspmd), argnums=(0, 1, 2)))
            og = jax.jit(jax.grad(loss_of(overlap), argnums=(0, 1, 2)))
            g_ms, g_gr = time_fn(gg, qs, ks, vs)
            o_ms, o_gr = time_fn(og, qs, ks, vs)
            res["grad"] = {"gspmd_ms": round(g_ms, 3),
                           "overlap_ms": round(o_ms, 3),
                           "speedup": round(g_ms / o_ms, 3) if o_ms
                           else None}
            res["max_abs_grad_diff"] = float(max(
                jnp.max(jnp.abs(a - b)) for a, b in zip(g_gr, o_gr)))
    return res


def run_a2a(ep: int = 4, batch: int = 8, seq: int = 64, hidden: int = 128,
            moe_ffn: int = 256, experts: int = 8, topk: int = 2,
            iters: int = 10, warmup: int = 2, include_grad: bool = True):
    """Full-manual chunked MoE all-to-all vs the GSPMD-sharded dispatch."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from megatronapp_tpu.config.parallel_config import (
        DP_AXIS, EP_AXIS, ParallelConfig,
    )
    from megatronapp_tpu.config.transformer_config import TransformerConfig
    from megatronapp_tpu.parallel.mesh import build_mesh
    from megatronapp_tpu.transformer.moe import init_moe_params, moe_forward

    cfg = TransformerConfig(
        num_layers=1, hidden_size=hidden, num_attention_heads=4,
        vocab_size=256, max_position_embeddings=seq,
        num_moe_experts=experts, moe_router_topk=topk,
        moe_ffn_hidden_size=moe_ffn, moe_aux_loss_coeff=0.0,
        compute_dtype=jnp.float32, remat_policy="none")
    ctx = build_mesh(ParallelConfig(expert_parallel=ep),
                     devices=jax.devices()[:ep])
    mesh = ctx.mesh
    p, _ = init_moe_params(jax.random.PRNGKey(0), cfg, out_std=0.02)
    x = jax.random.normal(jax.random.PRNGKey(1), (batch, seq, hidden),
                          jnp.float32)
    with mesh:
        xs = jax.device_put(x, NamedSharding(
            mesh, P((DP_AXIS, EP_AXIS), None, None)))
        ps = {
            "router_kernel": jax.device_put(
                p["router_kernel"], NamedSharding(mesh, P())),
            "fc1_kernel": jax.device_put(
                p["fc1_kernel"], NamedSharding(mesh, P(EP_AXIS))),
            "fc2_kernel": jax.device_put(
                p["fc2_kernel"], NamedSharding(mesh, P(EP_AXIS))),
        }

    def gspmd(p_, x_):
        return moe_forward(p_, x_, cfg)[0]

    def overlap(p_, x_):
        return moe_forward(p_, x_, cfg, ctx=ctx)[0]

    def loss_of(pair):
        return lambda p_, x_: jnp.sum(pair(p_, x_) ** 2)

    time_fn = _timer(iters, warmup)
    res = {"ep": ep, "batch": batch, "seq": seq, "hidden": hidden,
           "moe_ffn": moe_ffn, "experts": experts, "topk": topk,
           "iters": iters}
    with mesh:
        g_ms, g_out = time_fn(jax.jit(gspmd), ps, xs)
        o_ms, o_out = time_fn(jax.jit(overlap), ps, xs)
        res["fwd"] = {"gspmd_ms": round(g_ms, 3),
                      "overlap_ms": round(o_ms, 3),
                      "speedup": round(g_ms / o_ms, 3) if o_ms else None}
        res["max_abs_diff"] = float(jnp.max(jnp.abs(g_out - o_out)))
        if include_grad:
            gg = jax.jit(jax.grad(loss_of(gspmd)))
            og = jax.jit(jax.grad(loss_of(overlap)))
            g_ms, g_gr = time_fn(gg, ps, xs)
            o_ms, o_gr = time_fn(og, ps, xs)
            res["grad"] = {"gspmd_ms": round(g_ms, 3),
                           "overlap_ms": round(o_ms, 3),
                           "speedup": round(g_ms / o_ms, 3) if o_ms
                           else None}
            res["max_abs_grad_diff"] = float(max(
                jnp.max(jnp.abs(a - b))
                for a, b in zip(jax.tree.leaves(g_gr),
                                jax.tree.leaves(o_gr))))
    return res


def run(cp: int = 4, ep: int = 4, **kw):
    """Both pairs; returns a JSON-ready dict."""
    import jax

    ring_kw = {k: v for k, v in kw.items()
               if k in ("batch", "seq", "iters", "warmup", "include_grad",
                        "heads", "kv_heads", "head_dim")}
    a2a_kw = {k: v for k, v in kw.items()
              if k in ("iters", "warmup", "include_grad")}
    return {"environment": jax.devices()[0].platform,
            "ring_attention": run_ring(cp=cp, **ring_kw),
            "moe_a2a": run_a2a(ep=ep, **a2a_kw)}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--cp", type=int, default=4)
    ap.add_argument("--ep", type=int, default=4)
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--seq", type=int, default=512)
    ap.add_argument("--heads", type=int, default=8)
    ap.add_argument("--kv-heads", type=int, default=4)
    ap.add_argument("--head-dim", type=int, default=64)
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--no-grad", action="store_true",
                    help="forward-only timing")
    ap.add_argument("--local", action="store_true",
                    help="force the CPU backend (virtual device mesh)")
    args = ap.parse_args(argv)

    if args.local:
        os.environ["JAX_PLATFORMS"] = "cpu"
    _ensure_devices(max(args.cp, args.ep, 8))
    res = run(cp=args.cp, ep=args.ep, batch=args.batch, seq=args.seq,
              heads=args.heads, kv_heads=args.kv_heads,
              head_dim=args.head_dim, iters=args.iters,
              include_grad=not args.no_grad)
    print(json.dumps(res))
    return 0


if __name__ == "__main__":
    sys.exit(main())
