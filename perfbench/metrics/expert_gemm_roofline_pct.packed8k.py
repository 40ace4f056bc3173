"""Kernels: the held experts' grouped products' share of the chip's bf16
peak, in percent: ``assignments_here`` of the traced interval
(``mta.train.sync``) x 3 x 2 x hidden x width x three passes
(``mellum_flops.expert_gemm_flops``) at the peak, over the device seconds
of the operations XLA:TPU lowers ``lax.ragged_dot`` and its two gradients
to (``ragged-dot*`` by name: the program runs no kernel of its own there)
in the traced window. The recomputed forward products are in the time and
not in the count. None on a program without the counter."""
from perfbench import mellum_flops, trace_reduce, train_spans


def read(run):
    here = train_spans.sync_sum(run, "assignments_here")
    if not here:
        return None
    seconds = trace_reduce.summed_s(
        run["trace"], run["device_summary"]["window"],
        lambda ev: "ragged-dot" in ev[0])
    if not seconds:
        return None
    least_s = (mellum_flops.expert_gemm_flops(run["config"], here)
               / run["peaks"]["bf16_flops_per_s"])
    return 100.0 * least_s / seconds
