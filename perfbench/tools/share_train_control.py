"""The controls ``train.mellum2-12b-a2.5b.packed-8k``'s ``correct`` is sized
by, each through the benchmark's own command: ``perfbench/run.py`` runs the
cell as the driver does (the program trains, the runner compares, the last
line is validated), but the MODEL it is handed computes its plain reference
with ONE thing wrong. What the runner's comparisons read is the distance
between the program and the reference, whichever side is wrong, so a control
that comes out ``"correct": false`` here is a fault that a program with it
would be refused for, and ``problems`` says by which limit:

    python3 perfbench/tools/share_train_control.py --workload W --seed N

- ``float8``: the reference's equations on bf16 arrays with every product's
  operands rounded to float8_e4m3fn (the precision below the bf16 the
  configuration states for its arithmetic): HAS to come out not correct;
- ``bf16``, ``bf16-accumulate``: the reference on bf16 arrays with one-pass
  products, which is the program's own arithmetic, and the same with the
  sums of the attention's and the experts' products carried in bf16. Neither
  is expected to be refused: the program is itself ~1e-2 of the float32
  gradient away and a bf16 accumulator adds a third of that in quadrature
  (PERF.md, PR 48). They are run so that this is a reading and not a claim;
- ``no-band``, ``no-segments``, ``no-renorm``, ``no-yarn-factor``: the
  window layers see every earlier key, attention crosses documents, the
  top-k weights are not divided by their sum, YaRN's attention factor is
  left off the full layers' tables (``models/mellum.CONTROLS`` has these and
  the off-by-one and share controls that the CPU tests hold per leaf).

One run of the cell a control, one after the other in this process (one
process holds the chip), ``--seconds`` of window each; a JSON line a
control: ``correct``, ``problems``, and the numbers the runner compared.
Needs the chip for the cell's sizes; under ``PERFBENCH_REHEARSAL=1`` it runs
tiny widths on the CPU (control flow only).
"""

from __future__ import annotations

import argparse
import contextlib
import io
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

DEFAULT = ("float8", "bf16", "bf16-accumulate", "no-band", "no-segments",
           "no-renorm", "no-yarn-factor")
COMPUTE = {"float8": "float8", "bf16": "bfloat16",
           "bf16-accumulate": "bfloat16-accumulate"}
COMPARED = ("first_loss_unrounded", "reference_loss", "first_grad_norm",
            "reference_grad_norm", "first_grad_gap",
            "first_grad_gap_worst_leaf")


class _Wrong:
    """A model module whose plain reference has `control` wrong."""

    def __init__(self, model, control: str):
        self._model = model
        self._kw = ({"compute": COMPUTE[control]} if control in COMPUTE
                    else {"control": control})

    def __getattr__(self, name):
        return getattr(self._model, name)

    def reference_loss_and_grads(self, params, config, micro):
        return self._model.reference_loss_and_grads(params, config, micro,
                                                    **self._kw)

    def reference_loss(self, params, config, micro):
        return self.reference_loss_and_grads(params, config, micro)[0]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--controls", default=",".join(DEFAULT))
    args = ap.parse_args(argv)

    from perfbench import manifest as mf, run
    manifest = mf.load_manifest()
    name = mf.load_config(manifest, mf.find_cell(manifest, args.workload)
                          )["model"]
    load = mf.load_module
    for control in filter(None, args.controls.split(",")):
        mf.load_module = lambda kind, which, control=control, **kw: (
            _Wrong(load(kind, which), control)
            if (kind, which) == ("models", name)
            else load(kind, which, **kw))
        out, said = io.StringIO(), io.StringIO()
        try:
            with contextlib.redirect_stdout(out), \
                    contextlib.redirect_stderr(said):
                rc = run.main(["--workload", args.workload, "--seed",
                               str(args.seed), "--seconds",
                               str(args.seconds), "--trace", "0"])
        finally:
            mf.load_module = load
            sys.stderr.write(said.getvalue())
        line = json.loads(out.getvalue().strip().splitlines()[-1])
        mark = "perfbench: not correct: "
        print(json.dumps({
            "control": control, "seed": args.seed, "rc": rc,
            "correct": line["correct"],
            "problems": [ln[len(mark):] for ln in said.getvalue(
                ).splitlines() if ln.startswith(mark)],
            **{k: line["notes"].get(k) for k in COMPARED}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
