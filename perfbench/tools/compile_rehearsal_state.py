"""``compile_rehearsal.py serve`` for a configuration whose engine keeps
recurrent state beside its pages: both paged steps at published widths for
a DESCRIBED v5e:2x2, no chip.

``compile_rehearsal.py`` builds the pools it hands the steps as ``[num_layers,
num_blocks, ...]`` copies of ``engine.pool.pages``; a hybrid stack's KV pools
hold a plane an ATTENTION layer, and its state pools ``[state-space layers,
slots, ...]`` are not pages at all. This takes every pool's shape from the
engine (the page pools' block axis from the configuration's ``num_blocks``)
and hands the prefill call its slot. A compile that passes is not a chip
run; what it prints goes into PERF.md named as a compile.

    JAX_PLATFORMS=cpu python3 perfbench/tools/compile_rehearsal_state.py jamba2-3b
"""

from __future__ import annotations

import argparse
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

_plain = manifest.load_module("tools", "compile_rehearsal")


def serve(topo, config, keep_text=None):
    from megatronapp_tpu.inference.dynamic_engine import (
        DynamicInferenceEngine,
    )
    from megatronapp_tpu.models.gpt import init_gpt_params
    sv = config["serve"]
    one = SingleDeviceSharding(topo.devices[0])
    build = manifest.load_module("models", config["model"]).model_config
    model = build(config, sv["params_dtype"])
    # A one-layer engine gives the jits; the real shapes go in as abstract
    # values. Its state pools have the real slots and widths, one plane.
    tiny = build(dict(config, num_layers=1), sv["params_dtype"])
    eng = DynamicInferenceEngine(
        init_gpt_params(jax.random.PRNGKey(0), tiny)[0], model,
        max_batch=sv["max_batch"], max_seq_len=sv["max_seq_len"], paged=True,
        num_blocks=8)
    sds = _plain._sds
    params = jax.tree.map(
        lambda s: sds(s.shape, s.dtype, one),
        jax.eval_shape(lambda k: init_gpt_params(k, model)[0],
                       jax.random.PRNGKey(0)))
    nb = sv["num_blocks"]
    pools = tuple(sds(p.shape[:1] + (nb,) + p.shape[2:], p.dtype, one)
                  for p in eng.pool.pages) \
        + tuple(sds(p.shape, p.dtype, one) for p in eng.pool.state)
    for p in pools:
        print(f"pool {p.dtype.name}{list(p.shape)}: "
              f"{p.size * p.dtype.itemsize / 1e9:.3f} GB of elements",
              flush=True)
    mb = eng.pool.page_table.shape[1]

    def i32(*shape):
        return sds(shape, jnp.int32, one)

    b = eng.max_batch
    steps = {}
    t0 = time.perf_counter()
    steps["decode"] = eng._decode.lower(
        params, i32(b, 1), pools, None, i32(b, mb), i32(b),
        sds((b,), jnp.bool_, one), None).compile()
    _plain._report(f"decode step {config['name']} batch={b} pool={nb} blocks",
                   steps["decode"], time.perf_counter() - t0)
    t0 = time.perf_counter()
    steps["prefill"] = eng._mq_step.lower(
        params, i32(1, eng.prefill_chunk), pools, None, i32(1, mb), i32(1),
        i32(1), sds((1,), jnp.bool_, one), None, i32(1)).compile()
    _plain._report(f"prefill call [1, {eng.prefill_chunk}] {config['name']}",
                   steps["prefill"], time.perf_counter() - t0)
    if keep_text:
        os.makedirs(keep_text, exist_ok=True)
        for name, compiled in steps.items():
            with open(os.path.join(keep_text, f"{config['name']}.{name}.hlo"),
                      "w") as f:
                f.write(compiled.as_text())
    return steps


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("config")
    ap.add_argument("--keep-text", default=None, metavar="DIR",
                    help="write the compiled steps' HLO text into DIR")
    args = ap.parse_args()
    from jax.experimental import topologies
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    _plain._compiled_kernels()
    # ops/pallas/ssm_update.py asks kernel_gen._interpret(), switched above.
    serve(topo, _plain._load("configs", args.config), args.keep_text)


if __name__ == "__main__":
    main()
