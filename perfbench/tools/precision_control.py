"""The control a serving cell's ``correct`` is sized by: the same run with
the program's weights in the nearest precision below the stated one.

    python3 perfbench/tools/precision_control.py --workload W --seed N --seconds S

Calls ``perfbench/run.py``'s own ``main`` with ``--trace 0`` (its set-up,
its traffic, its runner, its comparison and its limits) after two changes
to the cell's model module, and to nothing of the harness or the program:

- ``init_params`` hands the engine the weights of the seed ROUNDED: every
  matrix to ``float8_e4m3``'s mantissa, 3 stored bits where bfloat16 stores
  7, with its own exponent kept: what a tensor stored in fp8 with a scale
  keeps (vectors, norm scales among them, stay). By
  ``lax.reduce_precision``, which no compiler pass may drop: a convert to
  ``float8_e4m3fn`` and back is removed on the TPU as excess precision, and
  such a control served the stated weights (PERF.md, PR 34). The tool
  prints how far the rounding moved the weights and stops if it did not.
  The engine then serves at the cell's load as it does in a run: its own
  steps, kernels and cache.
- ``reference_logits`` reads the weights of the seed as the configuration
  states them (made again from the seed at its first call, when the window
  is over and the pool is freed), so the comparison is the run's own:
  every emitted token's float32 reference logit against the maximum.

A cell's limits are sound if this comes out ``"correct": false`` by one of
them, and the run itself (``run.py``) ``true`` with room to spare: PERF.md
gives both readings (``notes.reference_worst_gap``). The exit code is
``run.py``'s; the last line is its line. Needs the chip, as ``run.py``
does, except under ``PERFBENCH_REHEARSAL=1``.
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

MANTISSA_BITS = 3   # float8_e4m3's; bfloat16 stores 7


def round_weights(params):
    """Every floating matrix of the tree at MANTISSA_BITS stored bits of
    mantissa, in its own type and range, a buffer at a time, donated; and
    the mean relative change of those matrices."""
    import jax
    import jax.numpy as jnp

    def one(a):
        wide = a.astype(jnp.float32)
        low = jax.lax.reduce_precision(wide, exponent_bits=8,
                                       mantissa_bits=MANTISSA_BITS)
        moved = jnp.sum(jnp.abs(low - wide)) / jnp.maximum(
            jnp.sum(jnp.abs(wide)), 1e-30)
        return low.astype(a.dtype), moved

    rounded = jax.jit(one, donate_argnums=0)
    moved = []

    def leaf(a):
        if a.ndim < 2 or not jnp.issubdtype(a.dtype, jnp.floating):
            return a
        low, share = rounded(a)
        moved.append(float(share))
        return low

    params = jax.tree.map(leaf, params)
    return params, sum(moved) / max(len(moved), 1)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", required=True)
    ap.add_argument("--seconds", required=True)
    args = ap.parse_args(argv)

    from perfbench import manifest as mf, run as bench
    manifest = mf.load_manifest()
    model = mf.load_module(
        "models", mf.load_config(manifest, mf.find_cell(
            manifest, args.workload))["model"])
    init_params, reference_logits = model.init_params, model.reference_logits
    made = {}

    def init_rounded(model_cfg, seed, device=None):
        made.update(args=(model_cfg, seed, device))
        params, moved = round_weights(init_params(model_cfg, seed, device))
        print(f"perfbench: CONTROL: the engine's matrices lie {moved:.4f} "
              f"of their size from the stated ones", file=sys.stderr,
              flush=True)
        if not moved > 1e-3:
            raise SystemExit("perfbench: CONTROL: the rounding moved "
                             "nothing: this is the run, not its control")
        return params

    def reference_of_the_stated(params, *a, **kw):
        if "params" not in made:
            made["params"] = init_params(*made["args"])
        return reference_logits(made["params"], *a, **kw)

    model.init_params = init_rounded
    model.reference_logits = reference_of_the_stated
    print("perfbench: CONTROL: the engine serves weights rounded to "
          f"{MANTISSA_BITS} bits of mantissa; the reference reads the "
          "stated ones",
          file=sys.stderr, flush=True)
    return bench.main(["--workload", args.workload, "--seed", args.seed,
                       "--seconds", args.seconds, "--trace", "0"])


if __name__ == "__main__":
    sys.exit(main())
