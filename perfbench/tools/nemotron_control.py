"""The controls ``serve.nemotron-3-nano-30b-a3b.reason-closed``'s ``correct``
is sized by: the cell's own run (``perfbench/run.py``'s ``main``, ``--trace
0``: its set-up, traffic, runner, comparison and limits) with ONE thing
wrong, which has to come out ``"correct": false`` by at least one of the
cell's limits.

    python3 perfbench/tools/nemotron_control.py --control C --workload W
        --seed N --seconds S

- ``--control weights-3bit``: the REFERENCE reads the seed's matrices
  rounded to 3 stored bits of mantissa while the engine serves the stated
  ones. (``tools/granite_control.py`` rounds the engine's and makes the
  stated ones again for the reference: here that second initialisation
  fails beside the state pools, by 10 MB of the chip's 16 GB; the distance
  between the two sides is the same);
- ``--control state-bf16``: ``tools/granite_control.py``'s (the program
  keeps the recurrent state at bf16's precision in its float32 pool);
- ``--control one-group``: every head of the program's Mamba-2 mixers reads
  group 0's B and C (the chunked scan's and the decode kernel's operands
  replaced from here), the reference its own group's;
- ``--control norm-all``: the program's gated norm runs over all E columns
  (the grouped call's operands reshaped from here), the reference's over
  each group's;
- ``--control relu``: the program's experts and shared expert apply relu
  (``activation`` of the model's configuration replaced from here), the
  reference relu squared;
- ``--control no-scale``: the program leaves the routed experts' factor out
  (``moe_routed_scaling_factor`` 1), the reference multiplies by 2.5.

Nothing of the program is edited. The exit code is ``run.py``'s; the last
line is its line, whose ``notes`` carry the emitted tokens' largest and mean
gap and the share of them that are not the reference's argmax
(``reference_not_argmax_share``: what shows that the seeded model is no
degenerate one). Needs the chip, as ``run.py`` does, except under
``PERFBENCH_REHEARSAL=1`` (control flow only).
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

MIXER = ("one-group", "norm-all")


def facts():
    from megatronapp_tpu.config.transformer_config import ActivationKind
    return {
        "relu": ({"activation": ActivationKind.relu},
                 "the program's experts apply relu; the reference relu "
                 "squared"),
        "no-scale": ({"moe_routed_scaling_factor": 1.0},
                     "the program leaves the routed experts' factor out; "
                     "the reference multiplies by routed_scaling_factor"),
    }


def weights_3bit(model, say) -> None:
    import jax
    init_params = model.init_params

    def round_matrices(a):
        a = a.astype(model.F32)
        return jax.lax.reduce_precision(a, 8, 3) if a.ndim >= 2 else a

    def then_round(*args, **kw):
        # after the set-up's own pass (the routers' bias is levelled on the
        # stated weights, for both sides alike)
        params = init_params(*args, **kw)
        model._f32 = round_matrices
        return params

    model.init_params = then_round
    say("the reference reads matrices rounded to 3 bits of mantissa; the "
        "engine serves the stated ones")


def one_group(say) -> None:
    import jax.numpy as jnp
    from megatronapp_tpu.ops.pallas import ssm_update as kernel
    from megatronapp_tpu.transformer import ssm
    update, chunked = kernel.ssm_update, ssm.ssd_chunked

    def first(t, axis):
        """Group 0's row in every group's place."""
        return jnp.broadcast_to(jnp.take(t, jnp.asarray([0]), axis=axis),
                                t.shape)

    def update_one(pool, layer, dt, u, b, c, *rest):
        return update(pool, layer, dt, u, first(b, 1), first(c, 1), *rest)

    def chunked_one(x, dt, a, b, c, *rest, **kw):
        return chunked(x, dt, a, first(b, 2), first(c, 2), *rest, **kw)

    kernel.ssm_update, ssm.ssd_chunked = update_one, chunked_one
    say("every head of the program's mixers reads group 0's B and C")


def norm_all(say) -> None:
    from megatronapp_tpu.transformer import ssm
    rms_norm = ssm.rms_norm

    def over_all_columns(x, scale, eps):
        if x.ndim != 4:
            return rms_norm(x, scale, eps)
        flat = x.reshape(x.shape[:2] + (1, -1))
        return rms_norm(flat, scale.reshape(1, -1), eps).reshape(x.shape)

    ssm.rms_norm = over_all_columns
    say("the program's gated norm runs over all E columns")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--control", required=True,
                    choices=["weights-3bit", "state-bf16", "relu",
                             "no-scale"] + list(MIXER))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", required=True)
    ap.add_argument("--seconds", required=True)
    args = ap.parse_args(argv)

    from perfbench import manifest as mf, run as bench
    shared = mf.load_module("tools", "granite_control")
    manifest = mf.load_manifest()
    model = mf.load_module(
        "models", mf.load_config(manifest, mf.find_cell(
            manifest, args.workload))["model"])
    if args.control == "weights-3bit":
        weights_3bit(model, shared._say)
    elif args.control == "state-bf16":
        shared.state_bf16()
    elif args.control == "one-group":
        one_group(shared._say)
    elif args.control == "norm-all":
        norm_all(shared._say)
    else:
        shared.FACTS.update(facts())
        shared.wrong_fact(model, args.control)
    return bench.main(["--workload", args.workload, "--seed", args.seed,
                       "--seconds", args.seconds, "--trace", "0"])


if __name__ == "__main__":
    sys.exit(main())
