"""The controls ``serve.solar-open2-250b.doc-closed``'s ``correct`` is sized
by: the cell's own run (``perfbench/run.py``'s ``main``, ``--trace 0``: its
set-up, traffic, runner, comparison and limits) with ONE thing wrong, which
has to come out ``"correct": false`` by at least one of the cell's limits.

    python3 perfbench/tools/solar_control.py --control C --workload W
        --seed N --seconds S

- ``--control weights-3bit``: the REFERENCE reads the seed's matrices
  rounded to 3 stored bits of mantissa while the engine serves the stated
  ones (``tools/nemotron_control.py``'s control, after the set-up's own
  pass has levelled the routers' bias on the stated weights);
- ``--control state-bf16``: the program keeps the recurrent state at bf16's
  precision in its float32 pool (the decode kernel's and the chunked pass's
  new state rounded from here; nothing of the program is edited);
- ``--control beta-1``: the reference's delta rule takes b = sigmoid(.) in
  (0, 1), without the factor 2 of ``kda_allow_neg_eigval``;
- ``--control one-decay``: every key channel of a head of the reference
  decays by the head's mean log decay: one decay a head in place of a
  channel's;
- ``--control no-conv``: the reference's q, k and v skip their short
  convolutions;
- ``--control no-gate``: the reference's GQA layer leaves its output gate
  out.

The four wrong models are the reference's (``models/solar_open2.py``'s
``CONTROLS``): the logits of the sample AND the probes' states are compared
with them. The exit code is ``run.py``'s; the last line is its line, whose
``notes`` carry the emitted tokens' largest and mean gap, the share of them
that are not the reference's argmax and the probes' state gap. Needs the
chip, as ``run.py`` does, except under ``PERFBENCH_REHEARSAL=1`` (control
flow only).
"""

from __future__ import annotations

import argparse
import functools
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

WRONG_MODELS = {
    "beta-1": ({"beta_factor": 1.0},
               "the reference's delta rule takes b in (0, 1): no factor 2"),
    "one-decay": ({"one_decay": True},
                  "the reference's key channels decay by their head's mean "
                  "log decay: one decay a head"),
    "no-conv": ({"no_conv": True},
                "the reference's q, k and v skip their convolutions"),
    "no-gate": ({"no_gate": True},
                "the reference's GQA layer leaves its output gate out"),
}


def _say(msg: str) -> None:
    print("perfbench: CONTROL: " + msg, file=sys.stderr, flush=True)


def wrong_model(model, control: str) -> None:
    fields, said = WRONG_MODELS[control]
    for name in ("reference_hidden", "reference_state"):
        setattr(model, name, functools.partial(getattr(model, name),
                                               **fields))
    _say(said)


def weights_3bit(model) -> None:
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
    _say("the reference reads matrices rounded to 3 bits of mantissa; the "
         "engine serves the stated ones")


def state_bf16() -> None:
    import jax
    from megatronapp_tpu.ops.pallas import kda_update as kernel
    from megatronapp_tpu.transformer import kda
    update, chunked = kernel.kda_update, kda.kda_chunked

    def rounded_update(pool, *a):
        o, pool = update(pool, *a)
        return o, jax.lax.reduce_precision(pool, 8, 7)

    def rounded_chunked(*a, **kw):
        o, s = chunked(*a, **kw)
        return o, jax.lax.reduce_precision(s, 8, 7)

    kernel.kda_update, kda.kda_chunked = rounded_update, rounded_chunked
    _say("the program keeps the recurrent state at bf16's precision in its "
         "float32 pool")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--control", required=True,
                    choices=["weights-3bit", "state-bf16"]
                    + sorted(WRONG_MODELS))
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
        wrong_model(model, args.control)
    return bench.main(["--workload", args.workload, "--seed", args.seed,
                       "--seconds", args.seconds, "--trace", "0"])


if __name__ == "__main__":
    sys.exit(main())
