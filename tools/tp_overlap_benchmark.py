"""A/B microbenchmark: GSPMD tensor-parallel matmuls vs the manual ring
overlap path (`--tp-comm-overlap`, megatronapp_tpu/parallel/overlap.py).

Times one column->row projection pair (the MLP fc1 -> activation -> fc2
shape, the hottest per-layer tp pattern) both ways on the same mesh:

  gspmd:    x @ w1 -> gelu -> @ w2      (XLA inserts the tp collectives)
  overlap:  all_gather_matmul -> gelu -> matmul_reduce_scatter

Runs on a CPU mesh out of the box (forces 8 virtual host devices when too
few are visible) and on real TPU meshes unchanged. Reports BOTH paths plus
fwd+bwd timings and the numeric diff, as one JSON line:

  python tools/tp_overlap_benchmark.py --tp 4 --seq 512 --hidden 256


Note on CPU numbers: XLA:CPU executes collectives synchronously, so the
ring path's win there is bounded (it mainly validates correctness + span
emission); the latency hiding this path exists for needs the TPU async
collective engine (PERF.md 'tp-comm-overlap' section).
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


def run(tp: int = 4, batch: int = 4, seq: int = 512, hidden: int = 256,
        ffn: int = 1024, iters: int = 10, warmup: int = 2,
        dtype: str = "float32", include_grad: bool = True):
    """Measure both paths; returns a JSON-ready dict."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from megatronapp_tpu.config.parallel_config import (
        ParallelConfig, TP_AXIS,
    )
    from megatronapp_tpu.parallel.mesh import build_mesh
    from megatronapp_tpu.parallel.overlap import (
        all_gather_matmul, matmul_reduce_scatter,
    )

    if len(jax.devices()) < tp:
        raise RuntimeError(
            f"need {tp} devices for tp={tp}, have {len(jax.devices())} "
            "(run via the CLI, which forces virtual host devices)")
    ctx = build_mesh(ParallelConfig(tensor_parallel=tp),
                     devices=jax.devices()[:tp])
    mesh = ctx.mesh
    dt = jnp.dtype(dtype)

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(batch, seq, hidden)), dtype=dt)
    w1 = jnp.asarray(rng.normal(size=(hidden, ffn)) * 0.02, dtype=dt)
    w2 = jnp.asarray(rng.normal(size=(ffn, hidden)) * 0.02, dtype=dt)
    w1 = jax.device_put(w1, NamedSharding(mesh, P(None, TP_AXIS)))
    w2 = jax.device_put(w2, NamedSharding(mesh, P(TP_AXIS, None)))

    def gspmd_pair(x, w1, w2):
        return jax.nn.gelu(x @ w1) @ w2

    def overlap_pair(x, w1, w2):
        y = jax.nn.gelu(all_gather_matmul(x, w1, mesh))
        return matmul_reduce_scatter(y, w2, mesh)

    def loss_of(pair):
        return lambda x, w1, w2: jnp.sum(pair(x, w1, w2) ** 2)

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

    res = {"tp": tp, "batch": batch, "seq": seq, "hidden": hidden,
           "ffn": ffn, "dtype": dtype, "iters": iters,
           "chunks": tp,  # ring length == chunk count, derived from tp
           "environment": jax.devices()[0].platform}
    with mesh:
        g_ms, g_out = time_fn(jax.jit(gspmd_pair), x, w1, w2)
        o_ms, o_out = time_fn(jax.jit(overlap_pair), x, w1, w2)
        res["fwd"] = {"gspmd_ms": round(g_ms, 3),
                      "overlap_ms": round(o_ms, 3),
                      "speedup": round(g_ms / o_ms, 3) if o_ms else None}
        res["max_abs_diff"] = float(jnp.max(jnp.abs(
            g_out.astype(jnp.float32) - o_out.astype(jnp.float32))))
        if include_grad:
            gg = jax.jit(jax.grad(loss_of(gspmd_pair), argnums=(0, 1, 2)))
            og = jax.jit(jax.grad(loss_of(overlap_pair), argnums=(0, 1, 2)))
            g_ms, g_gr = time_fn(gg, x, w1, w2)
            o_ms, o_gr = time_fn(og, x, w1, w2)
            res["grad"] = {"gspmd_ms": round(g_ms, 3),
                           "overlap_ms": round(o_ms, 3),
                           "speedup": round(g_ms / o_ms, 3) if o_ms
                           else None}
            res["max_abs_grad_diff"] = float(max(
                jnp.max(jnp.abs(a.astype(jnp.float32)
                                - b.astype(jnp.float32)))
                for a, b in zip(g_gr, o_gr)))
    return res


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--tp", type=int, default=4)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=512)
    ap.add_argument("--hidden", type=int, default=256)
    ap.add_argument("--ffn", type=int, default=1024)
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--dtype", default="float32")
    ap.add_argument("--no-grad", action="store_true",
                    help="forward-only timing")
    ap.add_argument("--local", action="store_true",
                    help="force the CPU backend (virtual device mesh)")
    args = ap.parse_args(argv)

    if args.local:
        os.environ["JAX_PLATFORMS"] = "cpu"
    _ensure_devices(max(args.tp, 8))
    res = run(tp=args.tp, batch=args.batch, seq=args.seq,
              hidden=args.hidden, ffn=args.ffn, iters=args.iters,
              dtype=args.dtype, include_grad=not args.no_grad)
    print(json.dumps(res))
    return 0


if __name__ == "__main__":
    sys.exit(main())
