"""The controls ``serve.lfm2-24b-a2b.assist-closed``'s ``correct`` is sized
by: the cell's own run (``perfbench/run.py``'s ``main``, ``--trace 0``: its
set-up, traffic, runner, comparison and limits) with ONE thing wrong, which
has to come out ``"correct": false``.

    python3 perfbench/tools/conv_control.py --control C --workload W --seed N --seconds S

- ``--control weights-3bit``: ``tools/share_control.py``'s control (the
  engine serves the seed's matrices rounded to 3 stored bits of mantissa,
  the reference reads the stated ones);
- ``--control softmax``: the program scores its router by a softmax over the
  64 experts (``moe_router_score`` of the model's configuration patched from
  here; nothing of the program is edited), the reference by the sigmoid;
- ``--control seeded-bias``: no fault, the ground the next stands on: both
  sides read a NON-ZERO selection bias drawn from the seed (the
  configuration's is zeros, which hides a program that drops it), and the
  run has to come out ``"correct": true``;
- ``--control no-bias``: that seeded bias, and a program whose selection
  does not see it (``transformer/moe._router`` handed the router without
  its bias); the reference selects on ``s + b``.

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

BIAS_STD = 0.1      # of the seeded bias; the sigmoid scores spread by ~0.2


def _say(msg: str) -> None:
    print("perfbench: CONTROL: " + msg, file=sys.stderr, flush=True)


def softmax_scores(model) -> None:
    import dataclasses
    model_config = model.model_config

    def scored_by_softmax(config, params_dtype, **extra):
        return dataclasses.replace(model_config(config, params_dtype, **extra),
                                   moe_router_score="softmax")

    model.model_config = scored_by_softmax
    _say("the program scores its router by softmax; the reference by "
         "sigmoid")


def seeded_bias(model) -> None:
    import jax
    init_params = model.init_params

    def init_with_bias(model_cfg, seed, device=None):
        params = init_params(model_cfg, seed, device)
        moe = params["block"]["ffn"]["moe"]
        bias = BIAS_STD * jax.random.normal(
            jax.random.PRNGKey((seed + 1) % (2 ** 31)),
            moe["router_bias"].shape, moe["router_bias"].dtype)
        params["block"]["ffn"]["moe"] = dict(
            moe, router_bias=jax.device_put(bias, device))
        return params

    model.init_params = init_with_bias
    _say(f"the selection bias is a seeded draw of std {BIAS_STD} on both "
         "sides")


def bias_dropped() -> None:
    from megatronapp_tpu.transformer import moe
    router = moe._router

    def unbiased(p, *a, **kw):
        if "router_bias" not in p:
            raise SystemExit("perfbench: CONTROL: this router has no "
                             "selection bias to drop")
        return router({k: v for k, v in p.items() if k != "router_bias"},
                      *a, **kw)

    moe._router = unbiased
    _say("the program's selection does not see the bias; the reference's "
         "does")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--control", required=True, choices=[
        "weights-3bit", "softmax", "seeded-bias", "no-bias"])
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
        mf.load_module("tools", "share_control").weights_3bit(model)
    elif args.control == "softmax":
        softmax_scores(model)
    else:
        seeded_bias(model)
        if args.control == "no-bias":
            bias_dropped()
    return bench.main(["--workload", args.workload, "--seed", args.seed,
                       "--seconds", args.seconds, "--trace", "0"])


if __name__ == "__main__":
    sys.exit(main())
