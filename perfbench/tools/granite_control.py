"""The controls ``serve.granite-4.0-h-small.rag-closed``'s ``correct`` is
sized by: the cell's own run (``perfbench/run.py``'s ``main``, ``--trace 0``:
its set-up, traffic, runner, comparison and limits) with ONE thing wrong,
which has to come out ``"correct": false`` by at least one of the cell's
limits.

    python3 perfbench/tools/granite_control.py --control C --workload W --seed N --seconds S

- ``--control weights-3bit``: ``tools/share_control.py``'s control (the
  engine serves the seed's matrices rounded to 3 stored bits of mantissa,
  the reference reads the stated ones) for a runner that calls the
  reference in two halves: the stated weights are made again from the seed
  at ``reference_hidden``'s first call, AFTER the engine's rounded ones are
  deleted (the chip holds one set), and the state's probes, which follow,
  are served from the stated ones;
- ``--control state-bf16``: the program keeps the recurrent state at bf16's
  precision in its float32 pool (the decode kernel's and the chunked scan's
  new state rounded from here; nothing of the program is edited): the
  pool's size does not tell it, the state's fine share does;
- ``--control attention-scale``: the program takes the softmax scale as
  1 / sqrt(head size) (``attention_multiplier`` of the model's configuration
  dropped from here), the reference as the published 1/128;
- ``--control residual-1``: the program adds each half's output to the
  stream unscaled (``residual_multiplier`` 1), the reference times 0.22;
- ``--control no-renorm``: the program weighs the ten chosen experts by
  their shares of a softmax over all 72 (``moe_router_norm_topk_prob``
  false), the reference by a softmax over the ten.

The exit code is ``run.py``'s; the last line is its line. Needs the chip,
as ``run.py`` does, except under ``PERFBENCH_REHEARSAL=1`` (control flow
only: tiny seeded logits do not reach the limits).
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

FACTS = {
    "attention-scale": ({"attention_multiplier": None},
                        "the program scales q.k by 1 / sqrt(head size); the "
                        "reference by attention_multiplier"),
    "residual-1": ({"residual_multiplier": 1.0},
                   "the program adds each half unscaled; the reference "
                   "times residual_multiplier"),
    "no-renorm": ({"moe_router_norm_topk_prob": False},
                  "the program does not renormalise the chosen experts' "
                  "weights; the reference takes the softmax over them alone"),
}


def _say(msg: str) -> None:
    print("perfbench: CONTROL: " + msg, file=sys.stderr, flush=True)


def wrong_fact(model, control: str) -> None:
    import dataclasses
    fields, said = FACTS[control]
    model_config = model.model_config

    def with_the_fact_wrong(config, params_dtype, **extra):
        return dataclasses.replace(
            model_config(config, params_dtype, **extra), **fields)

    model.model_config = with_the_fact_wrong
    _say(said)


def weights_3bit(model) -> None:
    import jax
    from perfbench import manifest as mf
    round_weights = mf.load_module("tools", "precision_control").round_weights
    state_cell = mf.load_module("cells", "serve_closed_state")
    init_params, probe = model.init_params, state_cell._probe
    halves = {"reference_hidden": model.reference_hidden,
              "reference_head": model.reference_head}
    made = {}

    def init_rounded(model_cfg, seed, device=None):
        made.update(args=(model_cfg, seed, device))
        params, moved = round_weights(init_params(model_cfg, seed, device))
        _say(f"the engine's matrices lie {moved:.4f} of their size from "
             "the stated ones")
        if not moved > 1e-3:
            raise SystemExit("perfbench: CONTROL: the rounding moved "
                             "nothing: this is the run, not its control")
        return params

    def of_the_stated(name):
        def half(params, *a, **kw):
            if "params" not in made:
                for leaf in jax.tree.leaves(params):
                    leaf.delete()           # the chip holds one set
                made["params"] = init_params(*made["args"])
            return halves[name](made["params"], *a, **kw)
        return half

    def probe_on_the_stated(env, driver, page_specs):
        if "params" in made:
            driver.engine.params = made["params"]
        return probe(env, driver, page_specs)

    model.init_params = init_rounded
    for name in halves:
        setattr(model, name, of_the_stated(name))
    state_cell._probe = probe_on_the_stated
    _say("the engine serves weights rounded to 3 bits of mantissa; the "
         "reference reads the stated ones")


def state_bf16() -> None:
    import jax
    from megatronapp_tpu.ops.pallas import ssm_update as kernel
    from megatronapp_tpu.transformer import ssm
    update, chunked = kernel.ssm_update, ssm.ssd_chunked

    def rounded_update(pool, *a):
        y, pool = update(pool, *a)
        return y, jax.lax.reduce_precision(pool, 8, 7)

    def rounded_chunked(*a, **kw):
        y, h = chunked(*a, **kw)
        return y, jax.lax.reduce_precision(h, 8, 7)

    kernel.ssm_update, ssm.ssd_chunked = rounded_update, rounded_chunked
    _say("the program keeps the recurrent state at bf16's precision in its "
         "float32 pool")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--control", required=True,
                    choices=["weights-3bit", "state-bf16"] + sorted(FACTS))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", required=True)
    ap.add_argument("--seconds", required=True)
    args = ap.parse_args(argv)

    from perfbench import manifest as mf, run as bench
    manifest = mf.load_manifest()
    model = mf.load_module(
        "models", mf.load_config(manifest, mf.find_cell(
            manifest, args.workload))["model"])
    if args.control == "weights-3bit":
        weights_3bit(model)
    elif args.control == "state-bf16":
        state_bf16()
    else:
        wrong_fact(model, args.control)
    return bench.main(["--workload", args.workload, "--seed", args.seed,
                       "--seconds", args.seconds, "--trace", "0"])


if __name__ == "__main__":
    sys.exit(main())
