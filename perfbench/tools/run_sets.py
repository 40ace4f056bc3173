"""Run a cell's two sets of runs (same seeds in both), one process after the
other, and print each metric's quartile spread: what the bounds in
BENCHMARK.json are set from (about five times the widest spread).

    python3 perfbench/tools/run_sets.py <workload> <seconds> <runs per set> [out.jsonl [notrace]]

The parent touches no JAX, so each run gets the chip. Seeds are large, as
the driver's are. A traced run follows the sets.
"""

import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from perfbench.stats import median, quartile_spread  # noqa: E402

SEEDS = [3000000019, 2147483659, 1000000007, 4000000007, 2500000001,
         3500000011]


def run(workload, seed, seconds, trace):
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "perfbench", "run.py"),
         "--workload", workload, "--seed", str(seed), "--seconds", seconds,
         "--trace", str(trace)], capture_output=True, text=True, cwd=ROOT)
    if out.returncode != 0:
        return {"rc": out.returncode, "stderr": out.stderr[-1500:]}
    return json.loads(out.stdout.strip().splitlines()[-1])


def main():
    workload, seconds, n = sys.argv[1], sys.argv[2], int(sys.argv[3])
    sink = open(sys.argv[4], "a") if len(sys.argv) > 4 else None
    sets = []
    for which in ("A", "B"):
        rows = []
        for seed in SEEDS[:n]:
            line = run(workload, seed, seconds, 0)
            line.update(set=which, seed=seed, workload=workload)
            print(json.dumps(line), flush=True)
            if sink:
                sink.write(json.dumps(line) + "\n")
                sink.flush()
            rows.append(line)
        sets.append(rows)
    if "notrace" not in sys.argv[5:]:
        traced = run(workload, SEEDS[0], seconds, 1)
        traced.update(set="traced", seed=SEEDS[0], workload=workload)
        print(json.dumps(traced), flush=True)
        if sink:
            sink.write(json.dumps(traced) + "\n")
    names = sorted({k for rows in sets for r in rows
                    for k in r.get("metrics", {})})
    for name in names:
        row = {"workload": workload, "metric": name}
        for which, rows in zip("AB", sets):
            xs = [r["metrics"][name]["value"] for r in rows
                  if "metrics" in r]
            # setup_s: the first run of a call may compile; it is recorded
            # apart, as the driver does.
            if name == "setup_s":
                xs = xs[1:] if which == "A" else xs
            if len(xs) >= 2:
                row[f"median_{which}"] = median(xs)
                row[f"spread_{which}"] = quartile_spread(xs)
                row[f"values_{which}"] = [round(x, 4) for x in xs]
        print("SPREAD " + json.dumps(row), flush=True)
        if sink:
            sink.write("SPREAD " + json.dumps(row) + "\n")


if __name__ == "__main__":
    main()
