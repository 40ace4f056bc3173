"""Kernels: device milliseconds a prefill call in the Mamba-2 layers'
chunked scans: the seconds of the window in the prefill step's operations
under the program's ``ssm/ssd_chunk`` scope (``granite_bytes.sub_s``) over
the window's ``mta.engine.prefill_call`` spans. None on a program that
names no such scope: the line leaves it out."""
from perfbench import admission_spans, granite_bytes


def read(run):
    if not run.get("device_summary"):
        return None
    seconds = granite_bytes.sub_s(run, "prefill", "ssm", "ssd_chunk")
    calls = admission_spans.of(run)["prefill_calls_in"]
    return seconds * 1e3 / calls if seconds and calls else None
