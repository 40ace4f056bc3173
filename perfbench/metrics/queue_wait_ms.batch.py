"""Serving engine: mean time a request waits in the engine's queue before a
slot takes it, from the engine's always-on ``steps.queue_wait`` (a
preempted request's second wait counts again), over the engine's LIFE, not
the window, and over every admitted request: in a closed loop it is set by
the harness's callers per slot (the second caller of a slot waits for the
first's whole answer), so read it against ``clients_per_slot``.
``ttft_p50_ms.batch`` less this is prefill."""
from perfbench import program_spans as ps


def read(run):
    steps = ps.step_counters(run)
    if steps is None:
        return None
    wait = steps.get("queue_wait", {})
    if not wait.get("count"):
        return 0.0
    return wait["total_s"] * 1e3 / wait["count"]
