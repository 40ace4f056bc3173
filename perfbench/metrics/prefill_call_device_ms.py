"""Serving engine: device milliseconds a prefill call: the seconds of the
window in the prefill step's operations (``scope_time.part_s(run,
"prefill")``) over the window's ``mta.engine.prefill_call`` spans, one across
an edge counted by its share inside. A call's own cost, which stands still
when decode rounds get shorter (``prefill_device_share`` does not). 0.0 on a
program that registers no prefill step or names no call."""
from perfbench import admission_spans, scope_time


def read(run):
    calls = admission_spans.of(run)["prefill_calls_in"]
    return scope_time.part_s(run, "prefill") * 1e3 / calls if calls else 0.0
