"""Compile each cell's real step at published widths for a DESCRIBED v5e:2x2.

No chip, no chip time: the TPU compiler is installed here and compiles for
a topology that is described and not attached. A compile that passes is not
a chip run; what this prints (bytes per chip from ``memory_analysis()``,
compile seconds, counts of kernels and collectives in the compiled text)
goes into PERF.md named as compiles. It fixes what a deployment sizes from
memory: the micro-batch of the training cells and the depth of the
four-chip cell.

    JAX_PLATFORMS=cpu python3 perfbench/tools/compile_rehearsal.py train gpt2-medium packed-1k --micro 4 8 16
    JAX_PLATFORMS=cpu python3 perfbench/tools/compile_rehearsal.py train gpt3-2.7b-tp2dp2 packed-2k --layers 16 20 24
    JAX_PLATFORMS=cpu python3 perfbench/tools/compile_rehearsal.py serve gpt3-2.7b
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(BENCH_DIR))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from perfbench import manifest  # noqa: E402


def _load(kind, name):
    with open(os.path.join(BENCH_DIR, kind, name + ".json")) as f:
        return json.load(f)


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _report(what, compiled, seconds):
    mem = compiled.memory_analysis()
    text = compiled.as_text()
    total = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
             + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    print(json.dumps({
        "compiled": what, "compile_s": round(seconds, 1),
        "argument_GB": round(mem.argument_size_in_bytes / 1e9, 3),
        "temp_GB": round(mem.temp_size_in_bytes / 1e9, 3),
        "output_GB": round(mem.output_size_in_bytes / 1e9, 3),
        "alias_GB": round(mem.alias_size_in_bytes / 1e9, 3),
        "total_GB_per_chip": round(total / 1e9, 3),
        "tpu_custom_calls": text.count("tpu_custom_call"),
        "all_reduce": text.count(" all-reduce("),
        "all_gather": text.count(" all-gather("),
        "reduce_scatter": text.count(" reduce-scatter("),
    }), flush=True)


def _compiled_kernels():
    """Kernels compiled, not interpreted; persistent cache off (an entry
    written for a described chip cannot be read back)."""
    from megatronapp_tpu.ops.pallas import flash_attention, kernel_gen
    flash_attention._interpret = lambda: False
    kernel_gen._interpret = lambda: False
    jax.config.update("jax_enable_compilation_cache", False)


def train(topo, config, job, micro, layers):
    """The jitted step exactly as pretrain_gpt assembles it, on a mesh of
    described devices; the state is only traced, never placed."""
    from megatronapp_tpu.config.parallel_config import ParallelConfig
    from megatronapp_tpu.config.training_config import (
        OptimizerConfig, TrainingConfig,
    )
    from megatronapp_tpu.models.gpt import init_gpt_params
    from megatronapp_tpu.parallel.mesh import build_mesh
    from megatronapp_tpu.training.optimizer import get_optimizer
    from megatronapp_tpu.training.train import gpt_microbatch_loss
    from megatronapp_tpu.training.train_state import setup_train_state
    from megatronapp_tpu.training.train_step import (
        batch_shardings, make_train_step,
    )
    tr = config["train"]
    config = dict(config, num_layers=layers or config["num_layers"])
    micro = micro or tr["micro_batch_size"]
    seq, rows = job["seq_length"], job["sequences_per_step"]
    tp, dp = tr.get("tensor_parallel", 1), tr.get("data_parallel") or 1
    # `auto` asks jax.default_backend(), which is the CPU here; say what it
    # would choose on the chip (transformer/attention.py's rule).
    dense_bytes = 2 * 4 * micro * dp * config["num_attention_heads"] \
        * seq * seq // (tp * dp)
    impl = "pallas" if seq >= 2048 or dense_bytes > 1 << 30 else "reference"
    model = manifest.load_module("models", config["model"]).model_config(
        config, tr["params_dtype"], remat_policy=tr["remat_policy"],
        attention_impl=impl)
    parallel = ParallelConfig(
        tensor_parallel=tp, data_parallel=tr.get("data_parallel"),
        distributed_optimizer=tr.get("distributed_optimizer", True))
    ctx = build_mesh(parallel, devices=topo.devices[:tp * dp])
    train_cfg = TrainingConfig(micro_batch_size=micro,
                               global_batch_size=rows, seq_length=seq,
                               train_iters=10 ** 7)
    opt = OptimizerConfig(lr_decay_iters=job["lr_decay_iters"])
    optimizer = get_optimizer(opt, train_cfg.train_iters,
                              distributed=parallel.distributed_optimizer)
    captured = {}

    def init(rng):
        state, shardings, _ = setup_train_state(
            rng, lambda k: init_gpt_params(k, model), optimizer, ctx,
            sharded_init=True)
        captured["shardings"] = shardings
        return state

    struct = jax.eval_shape(init, jax.random.PRNGKey(0))
    shardings = captured["shardings"]
    state = jax.tree.map(lambda s, sh: _sds(s.shape, s.dtype, sh),
                         struct, shardings)
    step = make_train_step(gpt_microbatch_loss(model, ctx=ctx), optimizer,
                           opt, ctx, shardings, train_cfg.train_iters)
    num_micro = train_cfg.num_microbatches(ctx.dp * ctx.ep)
    shape = (num_micro, rows // num_micro, seq)
    bsh = batch_shardings(ctx)
    batch = {k: _sds(shape, jnp.int32, bsh)
             for k in ("tokens", "labels", "position_ids", "segment_ids")}
    batch["loss_mask"] = _sds(shape, jnp.float32, bsh)
    t0 = time.perf_counter()
    with ctx.mesh:
        compiled = step.lower(state, batch).compile()
    _report(f"train step {config['name']} layers={config['num_layers']} "
            f"micro={micro} x{num_micro} seq={seq} mesh={dict(ctx.mesh.shape)}"
            f" attention={impl}", compiled, time.perf_counter() - t0)


def serve(topo, config):
    """The engine's own decode step at max_batch and its [1, prefill_chunk]
    multi-query step, pool and weights at the configuration's sizes."""
    from megatronapp_tpu.inference.dynamic_engine import (
        DynamicInferenceEngine,
    )
    from megatronapp_tpu.models.gpt import init_gpt_params
    sv = config["serve"]
    one = SingleDeviceSharding(topo.devices[0])
    build = manifest.load_module("models", config["model"]).model_config
    model = build(config, sv["params_dtype"])
    # A tiny engine gives the jits; the real shapes go in as abstract values.
    tiny = build(dict(config, num_layers=1), sv["params_dtype"])
    eng = DynamicInferenceEngine(
        init_gpt_params(jax.random.PRNGKey(0), tiny)[0], model,
        max_batch=sv["max_batch"], max_seq_len=sv["max_seq_len"], paged=True,
        num_blocks=8)
    params = jax.tree.map(
        lambda s: _sds(s.shape, s.dtype, one),
        jax.eval_shape(lambda k: init_gpt_params(k, model)[0],
                       jax.random.PRNGKey(0)))
    nb = sv["num_blocks"]
    pages = tuple(_sds((model.num_layers, nb) + p.shape[2:], p.dtype, one)
                  for p in eng.pool.pages)
    mb = eng.pool.page_table.shape[1]

    def i32(*shape):
        return _sds(shape, jnp.int32, one)

    b = eng.max_batch
    t0 = time.perf_counter()
    compiled = eng._decode.lower(
        params, i32(b, 1), pages, None, i32(b, mb), i32(b),
        _sds((b,), jnp.bool_, one), None).compile()
    _report(f"decode step {config['name']} batch={b} pool={nb} blocks",
            compiled, time.perf_counter() - t0)
    t0 = time.perf_counter()
    compiled = eng._mq_step.lower(
        params, i32(1, eng.prefill_chunk), pages, None, i32(1, mb), i32(1),
        i32(1), _sds((1,), jnp.bool_, one), None).compile()
    _report(f"prefill call [1, {eng.prefill_chunk}] {config['name']}",
            compiled, time.perf_counter() - t0)


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("what", choices=("train", "serve"))
    ap.add_argument("config")
    ap.add_argument("job", nargs="?")
    ap.add_argument("--micro", type=int, nargs="*", default=[None])
    ap.add_argument("--layers", type=int, nargs="*", default=[None])
    ap.add_argument("--blocks", type=int, default=None,
                    help="serve: pool blocks instead of the file's")
    ap.add_argument("--batch", type=int, default=None,
                    help="serve: max_batch instead of the file's")
    args = ap.parse_args()
    from jax.experimental import topologies
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    _compiled_kernels()
    config = _load("configs", args.config)
    if args.blocks:
        config["serve"]["num_blocks"] = args.blocks
    if args.batch:
        config["serve"]["max_batch"] = args.batch
    for layers in args.layers:
        for micro in args.micro:
            try:
                if args.what == "train":
                    train(topo, config, _load("traffic", args.job), micro,
                          layers)
                else:
                    serve(topo, config)
            except Exception as e:  # noqa: BLE001 — the compiler's refusal
                print(json.dumps({"refused": f"layers={layers} micro={micro}",
                                  "error": f"{type(e).__name__}: "
                                           f"{str(e)[:600]}"}), flush=True)


if __name__ == "__main__":
    main()
