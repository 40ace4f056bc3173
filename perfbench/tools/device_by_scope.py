"""A traced run's device seconds by part of the model.

    python3 perfbench/tools/device_by_scope.py --workload W --seed N \
        --seconds S

Calls ``perfbench/run.py``'s own ``main`` with ``--trace 1`` (its set-up, its
checks, its result line) and keeps the ``run`` that the cell's runner hands
back, in which ``perfbench/scope_time.py`` has joined the device trace to the
program's scope maps by then. After ``run.py``'s line it prints one JSON
object more, the last line:

- ``by_scope``: device seconds of the traced window by module kind, part and
  pass, averaged over the chips, with ``unmatched`` (what no map explains)
  and ``leaf_s`` (their sum: the window's summed leaf seconds);
- ``by_module``: seconds and unmatched seconds of every module that ran;
- ``collectives``: the collectives' seconds by the part that owns them;
- ``other_top``, ``unmatched_top``: the ten largest instructions in no named
  part (with the ``op_name`` the compiled text gives them) and the ten
  largest that no map explains;
- ``compile_s``: what making each map cost (a second lowering and compile
  of the step, after the window);
- ``per``: the divisor a cell's ``*_ms_step`` / ``*_ms_round`` metrics use.

Needs the chip, as ``run.py`` does, except under ``PERFBENCH_REHEARSAL=1``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def _top(rows: dict, n: int = 10) -> list:
    return [[*key, sec] for key, sec in
            sorted(rows.items(), key=lambda kv: -kv[1])[:n]]


def report(run: dict) -> dict:
    from perfbench import program_spans as ps, scope_time
    t = scope_time.table(run)
    if t is None:
        raise SystemExit("device_by_scope: the run holds no device trace")
    window = run["device_summary"]["window"]
    return {
        "by_scope": _top(t["seconds"], len(t["seconds"]))
        + [[scope_time.UNMATCHED, "", "", t["unmatched_s"]]],
        "leaf_s": t["leaf_s"],
        "by_module": t["by_module"],
        "collectives": _top(t["collectives"], len(t["collectives"])),
        "other_top": _top(t["other"]),
        "unmatched_top": _top(t["unmatched"]),
        "compile_s": t["compile_s"],
        "per": {"traced_steps": run.get("traced_steps"),
                "rounds": ps.rounds_in(ps.program_spans(run), window)},
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", required=True)
    ap.add_argument("--seconds", required=True)
    args = ap.parse_args(argv)

    from perfbench import manifest as mf, run as bench
    cell = mf.find_cell(mf.load_manifest(), args.workload)
    # run.py loads the runner by the same name and gets this module object.
    runner = mf.load_module("cells", mf.load_traffic(cell)["runner"])
    run_cell, kept = runner.run_cell, {}

    def keeping(env):
        kept["run"] = run_cell(env)
        return kept["run"]

    runner.run_cell = keeping
    rc = bench.main(["--workload", args.workload, "--seed", args.seed,
                     "--seconds", args.seconds, "--trace", "1"])
    if "run" in kept:
        print(json.dumps({"workload": cell["name"], "seed": int(args.seed),
                          **report(kept["run"])}), flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
