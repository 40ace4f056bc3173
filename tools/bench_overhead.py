"""MegaScan tracing overhead: traced vs untraced on-chip comparison.

BASELINE.md requires <10% overhead (the reference claims ≈10%,
/root/reference/README.md:72). Same GPT-2 125M-class config as bench.py;
differential two-window timing, each window ending in a device_get (two
window lengths are differenced to cancel the constant cost of the fence).

Measures the steady-state documented cadence (trace 2 of every 5
iterations, tracer defaults) — the configuration a user actually runs,
amortizing the per-window profiler capture. Prints one JSON line:
  {"untraced_ms", "traced_ms", "overhead_pct", "callbacks_supported"}

Note: where host callbacks are unimplemented ("callbacks_supported":
false), 'traced' covers the host-side scope + profiler-collective path
only; with callbacks the in-graph phase spans add the rest. Never run on
the attached chip yet (ROADMAP S9).
"""

import json
import sys

sys.path.insert(0, __file__.rsplit("/tools/", 1)[0])


def measure(trace: bool, steps=(5, 25)):
    import time

    import jax
    import numpy as np

    from megatronapp_tpu.config.parallel_config import ParallelConfig
    from megatronapp_tpu.config.training_config import (
        OptimizerConfig, TrainingConfig,
    )
    from megatronapp_tpu.config.transformer_config import TransformerConfig
    from megatronapp_tpu.data.mock import mock_batches
    from megatronapp_tpu.parallel.mesh import build_mesh
    from megatronapp_tpu.training.train import (
        pretrain_gpt, reshape_global_batch,
    )

    cfg = TransformerConfig(
        num_layers=12, hidden_size=768, num_attention_heads=12,
        vocab_size=50304, max_position_embeddings=1024,
        remat_policy="selective")
    par = ParallelConfig()
    ctx = build_mesh(par, devices=jax.devices()[:1])
    # Drive the REAL training loop (tracer windows included) for n1/n2
    # iterations at the default tracing cadence.
    times = {}
    for n in steps:
        # Default production cadence (tracer defaults: 2 traced
        # iterations per 5-iteration window) — interval=1 would measure
        # the per-iteration profiler capture, not steady-state MegaScan.
        train = TrainingConfig(
            micro_batch_size=4, global_batch_size=4, seq_length=1024,
            train_iters=n, log_interval=10_000, trace=trace,
            trace_interval=5, continuous_trace_iterations=2,
            trace_dir="/tmp/megascan_overhead_trace")
        t0 = time.perf_counter()
        pretrain_gpt(cfg, par, train, OptimizerConfig(lr=1e-4), ctx=ctx,
                     log_fn=lambda s: None)
        times[n] = time.perf_counter() - t0
    n1, n2 = steps
    return (times[n2] - times[n1]) / (n2 - n1) * 1e3  # ms/iter


def main():
    from megatronapp_tpu.trace.tracer import callbacks_supported

    untraced = min(measure(False) for _ in range(2))
    traced = min(measure(True) for _ in range(2))
    overhead = (traced - untraced) / untraced * 100.0
    print(json.dumps({
        "untraced_ms": round(untraced, 2),
        "traced_ms": round(traced, 2),
        "overhead_pct": round(overhead, 2),
        "callbacks_supported": callbacks_supported(),
    }))


if __name__ == "__main__":
    main()
