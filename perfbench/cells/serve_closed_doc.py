"""``"runner": "serve_closed_doc"``: ``serve_closed_reason``'s composition,
unchanged (``serve_closed``'s loop; the logits of a seed-drawn sample of the
window's completed requests, packed as segments; the state's size and the
probes' fine share; the probes' state rows against the reference's; the
share's counters), for a model whose recurrent mixers are Kimi delta
attention (``models/solar_open2.py``), with this cell's own table of limits
and this configuration's keys. It brings no loop and no check of its own.

THE LIMITS ON AN EMITTED TOKEN'S GAP, ``LOGIT_TOL`` (the largest) and
``MEAN_TOL`` (the mean over the checked tokens) below, and ``STATE_TOL`` on
the probes' states, each set between two readings: the run's own and the
controls' (``tools/solar_control.py``).

THE SHARE. ``assignments_here + assignments_absent`` equals the rounds'
tokens x ``num_experts_per_tok`` x the layers that are run (every layer
routes), both terms above 0, and ``experts_here`` is the configuration's
``n_routed_experts``.
"""

from __future__ import annotations

from perfbench import manifest

_reason = manifest.load_module("cells", "serve_closed_reason")
_rag = _reason._rag
_share_problems = _reason._share_problems
REHEARSAL = _reason.REHEARSAL

# Real positions of one run's reference passes: four passes of 18,432
# positions, each a sequential scan of three KDA layers and 40 held experts
# of four layers over every position.
SAMPLE_POSITIONS = 73_728
# Tokens every probe's state has read at its end: prompts 2 past one prefill
# call's edge, 1 past the second's, and nearly the whole length (calls of
# 1,024 positions hold sixteen chunks of 64).
PROBE_TOKENS = 2560
# An emitted (greedy) token's reference logit may lie this far below the
# reference's own maximum. The logits have a standard deviation of ~1 (a
# unit-RMS stream into an untied head of std 0.02 over 4,096 columns); with
# the routers' bias levelled the eighth and ninth scores of 320 lie close
# in every router, and where the engine's bf16 stream flips a pick a whole
# expert's term changes: one emitted token in sixteen is not the
# reference's argmax, and the tail of the gap is made of those flips. The
# two readings (my chip runs, PR 59; 4,900-7,700 checked tokens a run of 8-15
# sampled requests): the run's largest gap 0.50 to 0.84 in thirteen runs and
# 1.24 and 1.25 in two, of fifteen seeds; the controls'
# (``tools/solar_control.py``) 1.66 with b in (0, 1), 1.76 with the
# reference's matrices at 3 bits of mantissa, 2.49 with one decay a head,
# 5.99 without the GQA gate, 9.52 without the convolutions. The limit rides
# the tail of one token in ~6,000 and one run that is not correct refuses a
# PR, so the room is on the run's side: 2.4 times the largest reading, half
# the no-gate control and a third of the no-convolution one; the other three
# controls pass it, and it is the mean and the state below that part every
# control from the run by a wide margin.
LOGIT_TOL = 3.0
# ... and their MEAN may be this large: the sharper reading, because it does
# not ride the tail. The run's mean gap 0.0030 to 0.0042 over those runs
# (93-94% of the tokens are the reference's own argmax); the controls' 0.062
# and 0.067 (3 bits, two seeds; 69% argmax), 0.185 (b in (0, 1); 50%),
# 0.381 (one decay a head; 35%), 1.35
# (no gate; 10%), 4.04 (no convolutions; 0.04%). Between the two: 4.7
# times the largest reading, a third of the weakest control. (A state kept
# at bf16 passes both, at 0.78 and 0.0032: the state's fine share tells it,
# 0.0.)
MEAN_TOL = 0.02
# The probes' states may lie this far from the reference's, as a share of
# its norm. The two readings (my chip runs, PR 59): the run's 0.028 to 0.069
# over those runs (bf16 activations into a float32 recurrence, and behind
# the first expert layer the picks the bf16 stream flips); the controls'
# 0.93 without the
# GQA gate (the layer before the first KDA layer), 1.00 without the
# convolutions, 1.01 with b in (0, 1), 1.24 with one decay a head: 5.8
# times the largest reading, 0.43 of the weakest of those. (The reference's
# matrices at 3 bits read 0.185 and 0.187: the mean above tells them.)
STATE_TOL = 0.4


def share_problems(moe: dict, config: dict) -> list:
    """``serve_closed_rag.share_problems`` under this file's keys: every
    layer that is run routes."""
    return _share_problems(moe, {
        "layer_types": ["E"] * config["num_hidden_layers"],
        "num_experts_per_tok": config["num_experts_per_tok"],
        "num_local_experts": config["n_routed_experts"]})


def run_cell(env) -> dict:
    mine = dict(SAMPLE_POSITIONS=SAMPLE_POSITIONS, PROBE_TOKENS=PROBE_TOKENS,
                LOGIT_TOL=LOGIT_TOL, MEAN_TOL=MEAN_TOL, STATE_TOL=STATE_TOL,
                share_problems=share_problems)
    theirs = {k: getattr(_reason, k) for k in mine}
    for k, v in mine.items():
        setattr(_reason, k, v)
    try:
        return _reason.run_cell(env)
    finally:
        for k, v in theirs.items():
            setattr(_reason, k, v)
