"""BENCHMARK.json's command: one run of one cell, one JSON line at the end.

    python3 perfbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process holds the chip(s) and does everything: weights from the seed on
the device, warm-up of the cell's own shapes (set-up), the measured window,
the check against the plain reference, and, with ``--trace 1``, a profiler
trace of a short sub-window. The last line is validated (``lastline.py``)
before it is printed; a line that fails is not printed and the exit code is
not 0. Without a TPU (or with fewer chips than the cell asks for) nothing
is printed and the exit code is not 0, except under ``PERFBENCH_REHEARSAL=1``:
tiny widths on the CPU, to rehearse control flow; its line says
``"platform": "cpu"`` and is never a result.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time

T_START = time.perf_counter()

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
# `python3 perfbench/run.py` puts this directory first on the path; the
# benchmark is imported as the package `perfbench` from the repo's root.
sys.path[:] = [p for p in sys.path if os.path.abspath(p or ".") != BENCH_DIR]
sys.path.insert(0, ROOT)

OUT_DIR = os.path.join(BENCH_DIR, ".out")      # traces; listed in .gitignore


def _say(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None, metavar="DIR",
                    help="also copy the raw profiler trace and its outline, "
                         "and a serving run's engine steps, into DIR (for "
                         "reading a run by hand)")
    args = ap.parse_args(argv)

    from perfbench import lastline, manifest as mf
    manifest = mf.load_manifest()
    cell = mf.find_cell(manifest, args.workload)
    config = mf.load_config(manifest, cell)
    traffic = mf.load_traffic(cell)
    # The files name their own code: nothing here knows a model, a traffic
    # kind or a runner by name (manifest.py).
    model = mf.load_module("models", config["model"])
    generator = mf.load_module("generators", traffic["kind"])
    runner = mf.load_module("cells", traffic["runner"])
    rehearsal = os.environ.get("PERFBENCH_REHEARSAL") == "1"

    import jax
    from megatronapp_tpu.utils.platform import enable_compile_cache
    cache_dir = enable_compile_cache()
    if rehearsal:
        # On the CPU jnp.asarray may share a numpy buffer that the engine
        # then updates in place (lengths, page table) while the dispatched
        # step has yet to read it; seen as single wrong tokens under load
        # (PERF.md, PR 25). A TPU copies host arrays at the call. The CPU
        # client reads this switch when it is made, so it is set first.
        jax.config.update("jax_cpu_enable_async_dispatch", False)
    devices = jax.devices()
    dev0 = devices[0]
    if rehearsal:
        if dev0.platform == "tpu":
            raise SystemExit("perfbench: PERFBENCH_REHEARSAL=1 is for the "
                             "CPU; refusing to rehearse on a TPU")
        from perfbench.rehearsal import shrink
        config, traffic = shrink(config, traffic, model, runner)
        # Any row will do: a rehearsal's numbers mean nothing.
        peaks = mf.load_peaks("TPU v5 lite")
    else:
        if dev0.platform != "tpu":
            raise SystemExit(f"perfbench: JAX found platform "
                             f"{dev0.platform!r}, not a TPU")
        peaks = mf.load_peaks(dev0.device_kind)
    if len(devices) < cell["chips"]:
        raise SystemExit(f"perfbench: the cell asks for {cell['chips']} "
                         f"chip(s), JAX found {len(devices)}")
    devices = devices[:cell["chips"]]
    _say(f"perfbench: {cell['name']} seed {args.seed} on {len(devices)} x "
         f"{dev0.device_kind} ({dev0.platform}); compile cache {cache_dir}")

    # The seed may exceed 32 signed bits; PRNGKey and numpy both take 2**32.
    seed = args.seed % (2 ** 32)
    trace_dir = None
    if args.trace:
        trace_dir = os.path.join(OUT_DIR, "trace", cell["name"])
        shutil.rmtree(trace_dir, ignore_errors=True)
        os.makedirs(trace_dir, exist_ok=True)

    env = {"cell": cell, "config": config, "traffic": traffic, "seed": seed,
           "seconds": args.seconds, "trace_dir": trace_dir,
           "devices": devices, "peaks": peaks, "t_start": T_START,
           "rehearsal": rehearsal, "say": _say, "model": model,
           "generator": generator, "keep_dir": args.keep_trace}
    run = runner.run_cell(env)

    # ---- device facts ---------------------------------------------------
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    if rehearsal and not peak:
        import resource
        peak = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024
    device = {"platform": dev0.platform, "kind": dev0.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    breakdown = None
    if args.trace:
        from perfbench import trace_reduce
        trace = trace_reduce.load_xplane(trace_dir)
        # Only a rehearsal may read host threads in a device plane's place.
        trace["rehearsal"] = rehearsal
        run["trace"] = trace
        summary = trace_reduce.device_summary(trace)
        run["device_summary"] = summary
        if args.keep_trace:
            _keep_trace(args.keep_trace, cell["name"], trace_dir, trace)
        shutil.rmtree(trace_dir, ignore_errors=True)    # tens of MB a run
        if summary is not None:
            device["window_s"] = summary["window_s"]
            device["busy_s"] = summary["busy_s"]
            breakdown = {"device_ops": summary["device_ops"],
                         "idle_gaps": summary["idle_gaps"]}

    # ---- metrics ----------------------------------------------------------
    groups = ["end_to_end"] + (["per_layer"] if args.trace else [])
    wanted, metrics = {}, {}
    run["peaks"], run["config"], run["traffic"] = peaks, config, traffic
    run["chips"], run["model"] = len(devices), model
    for group in groups:
        for m in mf.cell_metrics(manifest, cell["name"], group):
            wanted[m["name"]] = m["unit"]
            reader = mf.load_reader(m["name"])
            value = (run["end_to_end"].get(m["name"]) if reader is None
                     else reader(run))
            if value is not None:
                metrics[m["name"]] = {"value": float(value),
                                      "unit": m["unit"]}
            elif rehearsal and m["source"] == "device_trace":
                del wanted[m["name"]]   # a CPU trace has no device plane
    line = {"correct": bool(run["correct"]), "attempted": run["attempted"],
            "failed": run["failed"], "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["notes"] = run.get("notes", {})
    bad = lastline.faults(line, wanted, cell["chips"], bool(args.trace))
    if rehearsal:
        line["rehearsal"] = True
    if bad:
        _say("perfbench: refusing to print a last line that misses the "
             "contract:\n  " + "\n  ".join(bad))
        _say("the line was: " + json.dumps(line, default=str)[:4000])
        return 3
    for problem in run.get("problems", []):
        _say(f"perfbench: not correct: {problem}")
    print(json.dumps(line, allow_nan=False), flush=True)
    return 0


def _keep_trace(dest: str, cell_name: str, trace_dir: str, trace: dict):
    import glob
    from perfbench import trace_reduce
    os.makedirs(dest, exist_ok=True)
    with open(os.path.join(dest, cell_name + ".outline.txt"), "w") as f:
        f.write(trace_reduce.outline(trace))
    for path in glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                          recursive=True):
        if os.path.getsize(path) < 48 << 20:
            shutil.copy(path, os.path.join(dest, cell_name + ".xplane.pb"))


if __name__ == "__main__":
    sys.exit(main())
