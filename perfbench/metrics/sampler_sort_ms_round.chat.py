"""Serving engine: device milliseconds per decode round in the sampler's
sorts, from the profiler trace: ``sort`` instructions whose result holds
float32 keys (``_sample_batched`` orders the ``[rows, vocabulary]`` logits
twice, for top-k and top-p, whatever the sampling; the steps' other sorts
order a few hundred flags). A prefill's first sample sorts one row and is
counted with the rounds' 128. What greedy requests pay for a sampler whose
result ignores the order. 0 when no such sort or no round is in the window."""
from perfbench import program_spans as ps
from perfbench import trace_reduce


def read(run):
    summary = run.get("device_summary")
    if not summary:
        return None
    s = trace_reduce.summed_s(
        run["trace"], summary["window"],
        lambda ev: ev[3].get("op") == "sort"
        and "f32[" in ev[3].get("shape", ""))
    if s is None:
        return None
    rounds = ps.rounds_in(ps.program_spans(run), summary["window"])
    return s * 1e3 / rounds if rounds else 0.0
