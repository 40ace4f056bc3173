"""The per-layer table's twins, and the fold that ends them (ISSUE 63).

A twin is a file under ``perfbench/metrics/`` whose ``read`` is another
file's object: one reader under a second name, from the time when a cell's PR
could add entries but not append its cell to an entry's ``workloads``. The
fold gives a twin's cells to the entry whose reader it borrows (each list in
the order of the manifest's cells) and drops the twin's entry and file. The
same ``read`` then runs on the same run under the surviving name, so a cell
prints as many per-layer readings as before and no number changes.

    python3 perfbench/tools/fold_aliases.py            # what would fold; changes nothing
    python3 perfbench/tools/fold_aliases.py --apply    # rewrites BENCHMARK.json, deletes the twins' files

Only a PR of kind ``benchmark`` may apply it, and only once ``tests/`` no
longer pins the table to the parents' hashes and calls no twin by name
(PERF.md, section 7, first entry): that PR deletes this file with the twins.
``perfbench/tests/test_benchmark_manifest.py`` holds the fold to what
ISSUE 63 asks of it, on the table as it stands.
"""

from __future__ import annotations

import copy
import json
import os
import sys
from typing import Dict, List

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from perfbench import manifest as mf  # noqa: E402

METRICS_DIR = os.path.join(mf.BENCH_DIR, "metrics")
# What a twin must share with the entry it folds into.
SAME = ("unit", "better", "source", "layer", "moves")


def reader_files() -> List[str]:
    return sorted(f[:-3] for f in os.listdir(METRICS_DIR)
                  if f.endswith(".py"))


def twins() -> Dict[str, str]:
    """``{twin: the file whose read it is}``, by the objects themselves and
    not by the files' text: a reader defined elsewhere under ``perfbench/``
    and imported by one file is that file's own."""
    mods = {name: mf.load_module("metrics", name) for name in reader_files()}
    owner = {mod.__name__: name for name, mod in mods.items()}
    return {name: owner[mod.read.__module__] for name, mod in mods.items()
            if mod.read.__module__ != mod.__name__
            and mod.read.__module__ in owner}


def fold(manifest: dict, twin_of: Dict[str, str]) -> dict:
    """`manifest` with every twin's cells in its target's ``workloads`` and
    the twins' entries gone; everything else as it was."""
    out = copy.deepcopy(manifest)
    entries = {m["name"]: m for m in out["per_layer"]}
    order = [cell["name"] for cell in out["workloads"]]
    for twin, target in twin_of.items():
        if target in twin_of:
            raise SystemExit(f"fold: {twin} borrows from {target}, a twin")
        mine, theirs = entries[twin], entries[target]
        differ = [key for key in SAME if mine[key] != theirs[key]]
        both = set(mine["workloads"]) & set(theirs["workloads"])
        if differ or both:
            raise SystemExit(f"fold: {twin} is not {target} under another "
                             f"name: {differ} differ, both list {both}")
        cells = set(mine["workloads"]) | set(theirs["workloads"])
        theirs["workloads"] = [cell for cell in order if cell in cells]
    out["per_layer"] = [m for m in out["per_layer"]
                        if m["name"] not in twin_of]
    return out


def readings(manifest: dict) -> List[int]:
    """Per-layer readings a cell prints, in the manifest's order of cells."""
    return [len(mf.cell_metrics(manifest, cell["name"], "per_layer"))
            for cell in manifest["workloads"]]


def main() -> None:
    was, twin_of = mf.load_manifest(), twins()
    now = fold(was, twin_of)
    for target in sorted(set(twin_of.values())):
        gone = sorted(t for t in twin_of if twin_of[t] == target)
        entry, = (m for m in now["per_layer"] if m["name"] == target)
        print(f"{target} <- {', '.join(gone)}: "
              f"{len(entry['workloads'])} cells")
    print(f"per_layer {len(was['per_layer'])} -> {len(now['per_layer'])}; "
          f"readings a cell {readings(was)} -> {readings(now)}")
    if "--apply" not in sys.argv[1:]:
        return
    with open(os.path.join(ROOT, "BENCHMARK.json"), "w") as f:
        f.write(json.dumps(now, indent=1) + "\n")
    for twin in twin_of:
        os.remove(os.path.join(METRICS_DIR, twin + ".py"))


if __name__ == "__main__":
    main()
