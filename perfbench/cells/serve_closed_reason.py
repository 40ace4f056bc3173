"""``"runner": "serve_closed_reason"``: ``serve_closed_rag``'s composition,
unchanged (``serve_closed``'s loop; the logits of a seed-drawn sample of the
window's completed requests, packed as segments; the state's size and the
probes' fine share; the share's counters), for a model whose stack is a
pattern of single-sublayer layers (``models/nemotron_h.py``), with this
cell's own table of limits. It brings no loop and no check of its own.

THE LIMITS ON AN EMITTED TOKEN'S GAP, ``LOGIT_TOL`` (the largest) and
``MEAN_TOL`` (the mean over the checked tokens) below, each set between two
readings: the run's own and the controls' (``tools/nemotron_control.py``).

THE STATE'S VALUES. ``serve_closed_state``'s probes are read back for their
precision alone; a mixer whose heads read another group's B and C keeps a
state as fine, and moves the logits by less than the limits above (the
recurrence is a small term beside the mixer's skip path: ``tools/
nemotron_control.py --control one-group``). So the probes' rows are also
held to ``model.reference_state`` of the tokens each has read: the norm of
the difference over the norm of the reference, the largest of the probes,
under ``STATE_TOL``.

THE SHARE. ``assignments_here + assignments_absent`` equals the rounds'
tokens x ``num_experts_per_tok`` x the pattern's ``E`` layers (an ``M`` or
``*`` layer routes nothing), both terms above 0, and ``experts_here`` is the
configuration's ``n_routed_experts``.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from perfbench import manifest

_rag = manifest.load_module("cells", "serve_closed_rag")
_state = manifest.load_module("cells", "serve_closed_state")
_share_problems = _rag.share_problems
REHEARSAL = _rag.REHEARSAL

# Real positions of one run's reference passes: a dozen passes of 6,144
# positions, each a sequential scan of six Mamba-2 layers and 64 held
# experts of five layers over every position.
SAMPLE_POSITIONS = 73_728
# Tokens every probe's state has read at its end: prompts 2 past one prefill
# call's edge, 1 past the second's, and nearly the whole length (calls of
# 1,024 positions hold eight chunks of 128).
PROBE_TOKENS = 2560
# An emitted (greedy) token's reference logit may lie this far below the
# reference's own maximum. The logits have a standard deviation of ~1 (a
# unit-RMS stream into an untied head of std 0.02 over 2,688 columns) and
# the two largest of 65,536 lie ~0.2 apart; with the routers' bias levelled
# every expert is as likely as the next, so the sixth and seventh scores of
# 128 lie close in every router, and where the engine's bf16 stream flips a
# pick a whole expert's term changes: one emitted token in eight is not the
# reference's argmax, and the tail of the gap is made of those flips. The
# two readings (my chip runs, PR 54; 42,000-49,000 checked tokens a run):
# the run's largest gap 1.17 to 1.92 over fifteen runs of thirteen seeds;
# the controls' (``tools/nemotron_control.py``) 2.81 with the reference's
# matrices at 3 bits of mantissa and 2.77 with relu for relu^2. The limit is
# 1.4 times the largest reading and just under those two: one run that is
# not correct refuses a PR, so the room is on the run's side, and it is the
# mean below that parts the controls from the run by a wide margin. (The norm over all
# 4,096 columns, 1.78, the routed scale left out, 2.22, one group for all
# heads, 1.45, and a state kept at bf16, 1.40, ride this limit's tail: the
# mean, the state's values and the state's fine share tell them, below.)
LOGIT_TOL = 2.7
# ... and their MEAN may be this large: the sharper reading, because it does
# not ride the tail. The run's mean gap 0.0082 to 0.0332 over those runs
# (83-95% of the tokens are the reference's own argmax); the controls' 0.161
# (the norm over all columns; 48% argmax), 0.230 (no routed scale; 43%),
# 0.274 (relu; 53%), 0.391 (3 bits; 31%). Between 0.0332 and 0.161: 2.4
# times the largest reading, half the weakest control. (One group for all
# heads and a state kept at bf16 pass it, at 0.0178 and 0.0181: the
# recurrence is a small term beside the mixer's skip path.)
MEAN_TOL = 0.08
# The probes' states may lie this far from the reference's, as a share of
# its norm. The two readings (my chip runs, PR 54): the run's 0.050 to 0.222
# over eight seeds (0.132 the second largest) (bf16 activations into a float32 recurrence, and behind the
# first expert layer the picks the bf16 stream flips); the controls' 1.37
# with every head on group 0's B and C and 0.65 with the reference's
# matrices at 3 bits. 2.3 times the largest reading, 0.36 of the control it
# is there for (one group), under the other.
STATE_TOL = 0.5


def state_gap(env, engine, rids) -> float:
    """The largest, over the finished probes `rids`, of |held - reference|
    / |reference| over all Mamba-2 layers' rows of the probe's slot, the
    reference's recurrence run over the tokens the slot has read (all but
    the last it emitted), the probes side by side in one pass."""
    reqs = [engine.requests[rid] for rid in rids]
    tokens = np.stack([r.tokens[:-1] for r in reqs])
    want = env["model"].reference_state(engine.params, env["config"],
                                        jnp.asarray(tokens))
    held = jnp.stack([engine.pool.state[0][:, r.slot] for r in reqs], axis=1)
    gaps = jnp.sqrt(jnp.sum(jnp.square(held - want), axis=(0, 2, 3))
                    / jnp.sum(jnp.square(want), axis=(0, 2, 3)))
    return float(jnp.max(gaps))


def share_problems(moe: dict, config: dict) -> list:
    """``serve_closed_rag.share_problems`` under this file's keys: the
    layers that route are the pattern's ``E`` layers."""
    return _share_problems(moe, {
        "layer_types": ["E"] * config["hybrid_override_pattern"].count("E"),
        "num_experts_per_tok": config["num_experts_per_tok"],
        "num_local_experts": config["n_routed_experts"]})


def run_cell(env) -> dict:
    probe, read = _state._probe, {}

    def probe_and_compare(env, driver, page_specs):
        before = set(driver.engine.requests)
        out = probe(env, driver, page_specs)
        read["gap"] = state_gap(env, driver.engine, sorted(
            set(driver.engine.requests) - before))
        return out

    _rag.SAMPLE_POSITIONS = SAMPLE_POSITIONS
    _rag.PROBE_TOKENS = PROBE_TOKENS
    _rag.LOGIT_TOL, _rag.MEAN_TOL = LOGIT_TOL, MEAN_TOL
    _rag.share_problems = share_problems
    _state._probe = probe_and_compare
    try:
        run = _rag.run_cell(env)
    finally:
        _state._probe = probe
    gap = read.get("gap")
    run["notes"]["state_gap"] = gap
    if gap is not None:
        env["say"](f"perfbench: the probes' states lie {gap:.3e} of their "
                   f"norm from the reference's (limit {STATE_TOL})")
    if gap is None or not gap <= STATE_TOL:
        run["problems"].append(
            f"a finished probe's recurrent state lies {gap} of its norm "
            f"from the reference's (> {STATE_TOL})")
        run["correct"] = False
    return run
