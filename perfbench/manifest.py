"""Finds a cell's files by the names the data gives them.

The harness knows no configuration, model, traffic mix, generator, cell
runner or per-layer metric by name:

- a cell in ``BENCHMARK.json`` names its configuration and its traffic:
  ``configs/<config>.json`` and ``traffic/<traffic>.json``;
- a configuration file's ``model`` names ``models/<model>.py``: the builder
  of the program's model from the file's sizes, with its plain reference and
  its count of operations;
- a traffic file's ``kind`` names ``generators/<kind>.py`` and its ``runner``
  names ``cells/<runner>.py``, the loop that offers that traffic to the
  program and times it;
- a per-layer metric is a reader in ``metrics/<name>.py``.

A later PR adds files and entries, and edits none (PERF.md, section 4).
"""

from __future__ import annotations

import importlib.util
import json
import os
import sys
from typing import Callable, Dict, List, Optional

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


def load_manifest() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def find_cell(manifest: dict, name: str) -> dict:
    for cell in manifest["workloads"]:
        if cell["name"] == name:
            return cell
    raise SystemExit(f"perfbench: no workload {name!r} in BENCHMARK.json "
                     f"(have {[c['name'] for c in manifest['workloads']]})")


def load_config(manifest: dict, cell: dict) -> dict:
    for cfg in manifest["configs"]:
        if cfg["name"] == cell["config"]:
            with open(os.path.join(ROOT, cfg["file"])) as f:
                return json.load(f)
    raise SystemExit(f"perfbench: no config {cell['config']!r}")


def load_traffic(cell: dict) -> dict:
    path = os.path.join(BENCH_DIR, "traffic", cell["traffic"] + ".json")
    with open(path) as f:
        return json.load(f)


def cell_metrics(manifest: dict, cell_name: str, group: str) -> List[dict]:
    """The metrics of `group` ('end_to_end' or 'per_layer') that this cell
    reports: those without a ``workloads`` key, and those that list it."""
    return [m for m in manifest[group]
            if "workloads" not in m or cell_name in m["workloads"]]


def load_module(subdir: str, name: str, required: bool = True):
    """``<subdir>/<name>.py`` under perfbench/ as a module (a name may hold
    dots and dashes, so it is loaded by path), or None if it is not there
    and not `required`."""
    path = os.path.join(BENCH_DIR, subdir, name + ".py")
    if not os.path.exists(path):
        if required:
            raise SystemExit(f"perfbench: no perfbench/{subdir}/{name}.py "
                             f"(have {sorted(os.listdir(os.path.dirname(path)))})")
        return None
    modname = "perfbench_%s_%s" % (
        subdir, "".join(c if c.isalnum() else "_" for c in name))
    if modname in sys.modules:
        return sys.modules[modname]
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[modname] = mod
    spec.loader.exec_module(mod)
    return mod


def load_reader(name: str) -> Optional[Callable[[dict], Optional[float]]]:
    """``metrics/<name>.py``'s ``read(run)``, or None if there is no such
    file (an end-to-end metric, which the cell's runner takes itself)."""
    mod = load_module("metrics", name, required=False)
    return None if mod is None else mod.read


def load_peaks(device_kind: str) -> Dict[str, float]:
    """The published peaks of this device kind; a kind that is not in the
    table is an error, never a default."""
    with open(os.path.join(BENCH_DIR, "peaks.json")) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise SystemExit(f"perfbench: device kind {device_kind!r} is not in "
                         f"perfbench/peaks.json ({sorted(table)})")
    return table[device_kind]
