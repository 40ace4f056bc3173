"""The engine's flight recorder after one untraced run of a serving cell.

    python3 perfbench/tools/slowest_rounds.py --workload W --seed N --seconds S

Calls ``perfbench/run.py``'s own ``main`` with ``--trace 0`` (its set-up,
its checks, its result line) and keeps the ``run`` that the cell's runner
hands back, of which ``run.py`` prints only the metrics. After ``run.py``'s
line it prints one JSON object more, the last line: the run's end-to-end
metrics, the harness's own count of slow decode rounds
(``notes.decode_hiccups``) and the engine's always-on
``stats_snapshot()["steps"]``: every phase's count, total and longest, and
``slowest``, the eight longest pure decode rounds since the engine started
with the seconds each phase took inside them. A round that is long in
``decode.wait`` waited for the device or the runtime; one long in
``decode.stage``, ``decode.record``, ``capacity`` or ``retire`` lost its
time on the host. The first compile of the decode step is among them by
nature (``step`` 1 or 2). Needs the chip, as ``run.py`` does, except under
``PERFBENCH_REHEARSAL=1``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


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
                     "--seconds", args.seconds, "--trace", "0"])
    run = kept.get("run", {})
    steps = (run.get("engine_stats") or {}).get("steps")
    if steps is None:
        raise SystemExit("slowest_rounds: this cell's engine keeps no step "
                         "counters (stats_snapshot()['steps'])")
    notes = run["notes"]
    print(json.dumps({
        "workload": cell["name"], "seed": int(args.seed),
        "correct": run["correct"], "end_to_end": run["end_to_end"],
        "decode_hiccups": notes.get("decode_hiccups"),
        "decode_hiccup_s": notes.get("decode_hiccup_s"),
        "gc_runs": notes.get("gc_runs"),
        "steps": steps}), flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
