"""Serving engine: device seconds of the prefill step's module(s) over the
traced window's seconds, per cent: the WINDOW's own prefill share, from the
device's side (``prefill_share.*`` covers the engine's life, ramp included)
(``perfbench/scope_time.py``). 0.0 on a program that registers no prefill
step."""
from perfbench import scope_time


def read(run):
    summary = run.get("device_summary")
    if not summary or not summary["window_s"]:
        return 0.0
    return 100.0 * scope_time.part_s(run, "prefill") / summary["window_s"]
