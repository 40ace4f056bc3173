"""Device: per cent of the first chip's idle in the window whose innermost
``mta.*`` span is a container itself (``mta.engine.step``, ``.decode_round``,
``.prefill``, ``.admit``, ``.decode.stage``) or none: idle the program gives
no phase for. The yardstick's own health, as ``scope_unmatched_share.serve``
is the scope maps' (``perfbench/admission_spans.py``). 0.0 where the chip
never idles."""
from perfbench import admission_spans


def read(run):
    return float(admission_spans.of(run)["idle_unnamed_share"])
