"""The two controls ``serve.longcat-flash-chat.agent-closed``'s ``correct``
is sized by: the cell's own run (``perfbench/run.py``'s ``main``, ``--trace
0``: its set-up, traffic, runner, comparison and limits) with ONE thing
wrong, which has to come out ``"correct": false``.

    python3 perfbench/tools/share_control.py --control C --workload W --seed N --seconds S

- ``--control weights-3bit``: ``tools/precision_control.py``'s control (the
  engine serves the seed's matrices rounded to ``float8_e4m3``'s 3 stored
  bits of mantissa by ``lax.reduce_precision``; the reference reads the
  stated ones) for a model whose weights do not fit the chip twice: the
  stated weights are made again from the seed at the reference's first
  call, AFTER the engine's rounded ones are deleted (the window is over, the
  stepper parked, the pool freed);
- ``--control no-s-q`` / ``no-s-kv``: the program runs without one of the
  two latent scale corrections (``transformer/mla.latent_scales`` patched
  from here; nothing of the program is edited), the reference with both.

The exit code is ``run.py``'s; the last line is its line. Needs the chip,
as ``run.py`` does, except under ``PERFBENCH_REHEARSAL=1`` (control flow
only: tiny seeded logits do not reach the limit).
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def _say(msg: str) -> None:
    print("perfbench: CONTROL: " + msg, file=sys.stderr, flush=True)


def weights_3bit(model) -> None:
    import jax
    from perfbench import manifest as mf
    round_weights = mf.load_module("tools", "precision_control").round_weights
    init_params, reference_logits = model.init_params, model.reference_logits
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

    def reference_of_the_stated(params, *a, **kw):
        if "params" not in made:
            for leaf in jax.tree.leaves(params):
                leaf.delete()               # the chip holds one set
            made["params"] = init_params(*made["args"])
        return reference_logits(made["params"], *a, **kw)

    model.init_params = init_rounded
    model.reference_logits = reference_of_the_stated
    _say("the engine serves weights rounded to 3 bits of mantissa; the "
         "reference reads the stated ones")


def no_scale(which: int) -> None:
    from megatronapp_tpu.transformer import mla
    scales = mla.latent_scales

    def dropped(cfg):
        got = list(scales(cfg))
        if got[which] is None:
            raise SystemExit("perfbench: CONTROL: this model has no such "
                             "scale correction to drop")
        got[which] = None
        return tuple(got)

    mla.latent_scales = dropped
    _say(f"the program runs without {('s_q', 's_kv')[which]}; the "
         "reference with both corrections")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--control", required=True,
                    choices=["weights-3bit", "no-s-q", "no-s-kv"])
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
    else:
        no_scale(("no-s-q", "no-s-kv").index(args.control))
    return bench.main(["--workload", args.workload, "--seed", args.seed,
                       "--seconds", args.seconds, "--trace", "0"])


if __name__ == "__main__":
    sys.exit(main())
