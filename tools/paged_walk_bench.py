"""The paged kernels alone, on the chip, at the serving cells' shapes.

    python tools/paged_walk_bench.py [--tree DIR] [--label NAME] [--out FILE]
                                     [--keys N]

Times one call of `kernel_gen.paged_attention` / `paged_attention_latent`
(bf16 pools in `pool_format`; one query a slot, or a slot's new rows behind
its context) a shape, inside one jitted scan over the pool's planes as the
engine's layer loop runs it, and prints a JSON line a shape: microseconds a
call, the pages the slots hold and their copies (pages x pools),
microseconds a page copy, and the share of the byte roof of the pages read
once (819 GB/s, a v5e; a ragged call's query tiles read them again). A
shape's widths are read from its cell's `perfbench/configs/*.json`; slots,
pool and rows are the cell's traffic as PERF.md section 4 gives it.
`--tree` imports the kernels from another checkout (the parent commit's, to
time its form beside this one's in one chip call); `--out` also writes each
shape's output rows there (.npz), so two trees' results can be compared;
`--keys` times every dense walk at that many keys a step (128, 256) in
place of `dense_key_tile`'s choice.
Needs a TPU: a CPU's numbers are the interpreter's and are refused.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

HBM_BYTES_S = 819e9
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# name: (the cell's configuration, slots, pool blocks, table blocks, rows a
# slot from .. to, planes, queries a slot: 0 is the decode kernel, more the
# ragged one over that many new rows behind the slot's context)
SHAPES = {
    # serve.longcat-flash-chat.agent-closed: 64 slots of ~1.6k rows
    "agent": ("longcat-flash-chat", 64, 16384, 256, (1200, 2000), 8, 0),
    # serve.laguna-xs.2.code-closed: 32 slots of 6-16k rows
    "code": ("laguna-xs.2", 32, 20000, 1216, (6000, 8000), 2, 0),
    # serve.evabyte-6.5b.bytegen-closed
    "byte": ("evabyte-6.5b", 32, 6000, 192, (2000, 3000), 2, 0),
    # serve.lfm2-24b-a2b.assist-closed: 192 slots
    "assist": ("lfm2-24b-a2b", 192, 16384, 128, (300, 900), 2, 0),
    # serve.gpt3-2.7b.batch-closed: 24 slots of up to 2,048 rows
    "batch": ("gpt3-2.7b", 24, 3600, 128, (1000, 2000), 2, 0),
    # serve.jamba2-3b.chat-closed: 128 slots
    "chat": ("jamba2-3b", 128, 16384, 128, (500, 1500), 2, 0),
    # the cells' prefill calls
    "agent-prefill": ("longcat-flash-chat", 1, 16384, 256, (2000, 2001), 8,
                      512),
    "code-prefill": ("laguna-xs.2", 1, 20000, 1216, (8000, 8001), 2, 2048),
    "assist-prefill": ("lfm2-24b-a2b", 1, 16384, 128, (2040, 2041), 2, 2048),
    "byte-prefill": ("evabyte-6.5b", 1, 6000, 192, (2500, 2501), 2, 256),
    "batch-prefill": ("gpt3-2.7b", 1, 3600, 128, (1500, 1501), 2, 256),
    "chat-prefill": ("jamba2-3b", 1, 16384, 128, (1500, 1501), 2, 256),
}


def widths(config: str):
    """(kind, query heads, (key/value heads, head dim) or (latent, roped-key
    columns)) of a cell's configuration file."""
    with open(os.path.join(ROOT, "perfbench", "configs",
                           config + ".json")) as f:
        cfg = json.load(f)
    hq = cfg["num_attention_heads"]
    if "kv_lora_rank" in cfg:
        return "latent", hq, (cfg["kv_lora_rank"], cfg["qk_rope_head_dim"])
    return "dense", hq, (cfg.get("num_key_value_heads", hq),
                         cfg.get("head_dim", cfg["hidden_size"] // hq))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tree", default=None)
    ap.add_argument("--label", default="this")
    ap.add_argument("--out", default=None)
    ap.add_argument("--shapes", default=",".join(SHAPES))
    ap.add_argument("--reps", type=int, default=32)
    ap.add_argument("--keys", type=int, default=None)
    args = ap.parse_args()
    sys.path.insert(0, args.tree or ROOT)

    import jax
    import jax.numpy as jnp
    import numpy as np
    jax.config.update("jax_enable_compilation_cache", False)
    if jax.default_backend() != "tpu":
        print("paged_walk_bench: needs a TPU", file=sys.stderr)
        return 1
    from megatronapp_tpu.inference.paged_cache import pool_format
    from megatronapp_tpu.ops.pallas import kernel_gen as kg
    if args.keys:
        kg.dense_key_tile = lambda *_: args.keys

    bs = 16
    sharding = jnp.zeros(()).sharding
    saved = {}
    for name in args.shapes.split(","):
        config, b, nb, mb, (lo, hi), planes, s_q = SHAPES[name]
        kind, hq, dims = widths(config)
        lead = (b, s_q) if s_q else (b,)
        q_lens = jnp.full((b,), s_q, jnp.int32) if s_q else None
        rng = np.random.default_rng(46)
        lens = rng.integers(lo, hi, b).astype(np.int32)
        held = -(-lens // bs)
        table = np.zeros((b, mb), np.int32)
        blocks = rng.permutation(nb - 1)[:held.sum()] + 1
        at = 0
        for i, n in enumerate(held):
            table[i, :n] = blocks[at:at + n]
            at += n

        def pool(key, cols):
            shape = (planes, nb, bs) + cols
            return jax.jit(
                lambda k: jax.random.normal(k, shape, jnp.bfloat16),
                out_shardings=pool_format(sharding, len(shape)))(key)

        keys = jax.random.split(jax.random.key(46), 4)
        if kind == "latent":
            klat, dpe = dims
            pools = (pool(keys[0], (klat,)), pool(keys[1], (dpe,)))
            qs = (jax.random.normal(keys[2], lead + (hq, klat), jnp.bfloat16),
                  jax.random.normal(keys[3], lead + (hq, dpe), jnp.bfloat16))
            w_v = jnp.ones((klat, hq, 128), jnp.bfloat16) / klat

            def call(qs, pools, table, lens, layer):
                return kg.paged_attention_latent(
                    *qs, *pools, table, lens, w_v, q_lens=q_lens,
                    softmax_scale=(128 + 64) ** -0.5, layer=layer)
        else:
            hkv, d = dims
            pools = (pool(keys[0], (hkv, d)), pool(keys[1], (hkv, d)))
            qs = (jax.random.normal(keys[2], lead + (hq, d), jnp.bfloat16),)

            def call(qs, pools, table, lens, layer):
                return kg.paged_attention(*qs, *pools, table, lens,
                                          q_lens=q_lens, layer=layer)

        @jax.jit
        def run(qs, pools, table, lens):
            layers = jnp.arange(args.reps, dtype=jnp.int32) % planes
            return jax.lax.map(
                lambda layer: call(qs, pools, table, lens, layer), layers)

        table_d, lens_d = jnp.asarray(table), jnp.asarray(lens)
        out = run(qs, pools, table_d, lens_d)
        out.block_until_ready()
        times = []
        for _ in range(7):
            t0 = time.perf_counter()
            run(qs, pools, table_d, lens_d).block_until_ready()
            times.append((time.perf_counter() - t0) / args.reps)
        us = sorted(times)[len(times) // 2] * 1e6
        page_bytes = sum(math.prod(p.shape[2:-1])
                         * -(-p.shape[-1] // 128) * 128 * 2 for p in pools)
        print(json.dumps({
            "shape": name, "form": args.label, "us_call": us,
            "us_call_min": min(times) * 1e6, "pages_read": int(held.sum()),
            "page_copies": int(held.sum()) * len(pools),
            "us_page_copy": us / (int(held.sum()) * len(pools)),
            "roofline_pct": 100 * int(held.sum()) * page_bytes
            / HBM_BYTES_S / (us * 1e-6),
            "device": jax.devices()[0].device_kind}), flush=True)
        saved[name] = np.asarray(out[:2].astype(jnp.float32))
        for a in pools:
            a.delete()
    if args.out:
        np.savez(args.out, **saved)
    return 0


if __name__ == "__main__":
    sys.exit(main())
