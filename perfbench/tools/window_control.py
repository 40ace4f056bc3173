"""The controls ``serve.laguna-xs.2.code-closed``'s ``correct`` is sized by:
the cell's own run (``perfbench/run.py``'s ``main``, ``--trace 0``: its
set-up, traffic, runner, comparison and limits) with ONE thing wrong, which
has to come out ``"correct": false``.

    python3 perfbench/tools/window_control.py --control C --workload W --seed N --seconds S

- ``--control weights-3bit``: ``tools/share_control.py``'s control (the
  engine serves the seed's matrices rounded to 3 stored bits of mantissa,
  the reference reads the stated ones): the nearest precision below the one
  the configuration states;
- ``--control window-1`` / ``window+1``: the PROGRAM's sliding layers see
  511 or 513 keys (``program_window``: its configuration patched from here),
  the reference's 512: what the runner's edge probe has to tell, since no
  limit on an emitted token's gap does;
- ``--control one-table``: the reference rotates its sliding layers by the
  full layers' table (YaRN on half a head), the program by their own;
- ``--control no-gate``: the reference leaves the per-head output gate out.

The last two hand the REFERENCE the wrong model
(``models/laguna.reference_hidden(control=)``): what the comparison reads is
the distance between the two models, whichever side is wrong, and nothing of
the program is edited. The exit code is ``run.py``'s; the last line is its
line. Needs the chip, as ``run.py`` does, except under
``PERFBENCH_REHEARSAL=1`` (control flow only).
"""

from __future__ import annotations

import argparse
import functools
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

WRONG = ("window-1", "window+1", "one-table", "no-gate")


def _say(msg: str) -> None:
    print("perfbench: CONTROL: " + msg, file=sys.stderr, flush=True)


def wrong_reference(model, control: str) -> None:
    model.reference_hidden = functools.partial(model.reference_hidden,
                                               control=control)
    _say(f"the reference computes the model with `{control}`; the program "
         "the model")


def weights_3bit(model) -> None:
    """``tools/share_control.py``'s control for a runner that reads the
    reference as ``reference_hidden`` + ``reference_head``: the engine
    serves the seed's matrices at 3 stored bits of mantissa; the stated
    weights are made again from the seed at the reference's first call,
    AFTER the engine's rounded ones are deleted."""
    import jax
    from perfbench import manifest as mf
    round_weights = mf.load_module("tools", "precision_control").round_weights
    init_params = model.init_params
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

    def stated(params):
        if "params" not in made:
            for leaf in jax.tree.leaves(params):
                leaf.delete()               # the chip holds one set
            made["params"] = init_params(*made["args"])
        return made["params"]

    model.init_params = init_rounded
    for name in ("reference_hidden", "reference_head"):
        fn = getattr(model, name)
        setattr(model, name, functools.partial(
            lambda fn, params, *a, **kw: fn(stated(params), *a, **kw), fn))
    _say("the engine serves weights rounded to 3 bits of mantissa; the "
         "reference reads the stated ones")


def program_window(model, by: int) -> None:
    """The PROGRAM's window off by `by` keys (its configuration patched from
    here; nothing of the program is edited); the reference's is the
    model's."""
    import dataclasses
    model_config = model.model_config

    def off_by(config, params_dtype, **extra):
        cfg = model_config(config, params_dtype, **extra)
        return dataclasses.replace(cfg,
                                   sliding_window=cfg.sliding_window + by)

    model.model_config = off_by
    _say(f"the program's window is {by:+d} keys off the model's; the "
         "reference's is the model's")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--control", required=True,
                    choices=("weights-3bit",) + WRONG)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", required=True)
    ap.add_argument("--seconds", required=True)
    ap.add_argument("--keep-trace", default=None, metavar="DIR",
                    help="passed on to run.py (the edge probe's rows)")
    args = ap.parse_args(argv)

    from perfbench import manifest as mf, run as bench
    manifest = mf.load_manifest()
    model = mf.load_module(
        "models", mf.load_config(manifest, mf.find_cell(
            manifest, args.workload))["model"])
    if args.control == "weights-3bit":
        weights_3bit(model)
    elif args.control.startswith("window"):
        program_window(model, {"window-1": -1, "window+1": 1}[args.control])
    else:
        wrong_reference(model, args.control)
    return bench.main(["--workload", args.workload, "--seed", args.seed,
                       "--seconds", args.seconds, "--trace", "0"]
                      + (["--keep-trace", args.keep_trace]
                         if args.keep_trace else []))


if __name__ == "__main__":
    sys.exit(main())
