"""``"runner": "serve_closed_rag"``: ``serve_closed``'s loop, unchanged, for a
model whose engine keeps a recurrent state a slot beside its KV pages AND
holds a share of the published experts (``models/granite_moe_hybrid.py``).
It brings no loop and no check of its own: it composes what
``serve_closed_state`` (the state's size and precision),
``serve_closed_share`` (the packed reference pass, the share's counters) and
``serve_closed_conv`` (the seeded sample) hold their cells to.

THE LOGITS, ON A SAMPLE. The float32 reference runs the recurrence a
position at a time and every held expert over every position: a window's
~170 completed requests of ~2,300 positions would take minutes, and a run
has to end inside the driver's six. So a SAMPLE of the window's completed
requests is checked, every emitted token of each: drawn AFTER the window from
``--seed`` (no step can know it), the longest request always in it, filled up
to ``SAMPLE_POSITIONS`` positions. Whole requests are packed end to end into
passes of one shape ``[1, max_total_len]`` as segments, longest first, the
head a block of rows at a time (``packed_gaps``): the reference's attention, convolution
AND recurrence stay inside a segment (its state starts from zero at a
segment's first position). The run's ``notes`` say how many requests and
positions were checked of how many.

THE LIMITS ON AN EMITTED TOKEN'S GAP, ``LOGIT_TOL`` (the largest) and
``MEAN_TOL`` (the mean over the checked tokens) below, each set between two
readings: the run's own and the controls' (``tools/granite_control.py``).

THE STATE. ``stats_snapshot()["state"]``: ``kind`` ``"ssm"`` and
``bytes_per_slot`` against the model module's ``state_bytes_per_slot(config,
serve.state_dtype)``; then ``serve_closed_state``'s probes: after the window
the measured engine serves a few more requests alone (prompts that end just
past a prefill call's edge and past a chunk's, then decoded by the state
kernel), their slots' rows are read back, and ``state_fine_share`` (the share
of their elements that bf16 cannot hold) stays above its ``FINE_SHARE``: a
state rounded to bf16 anywhere on its way to the pool reads 0.

THE SHARE. The engine's ``moe`` counters over its plain decode rounds:
``assignments_here + assignments_absent`` equals the rounds' tokens x
experts a token x layers (each counted from the indices inside the step, the
tokens on the host), both terms above 0 (a router over the held experts
alone would read ``assignments_absent`` 0), and ``experts_here`` is the
configuration's ``num_local_experts``.

The pool's bytes a block are held to the cache type by ``serve_closed``
itself. A program without such counters or pools is not correct here.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from perfbench import manifest, xplane_stats

_closed = manifest.load_module("cells", "serve_closed")
_state = manifest.load_module("cells", "serve_closed_state")
_share = manifest.load_module("cells", "serve_closed_share")
_conv = manifest.load_module("cells", "serve_closed_conv")
REHEARSAL = _closed.REHEARSAL
HEAD_ROWS = 1024        # positions the reference's head runs at a time

# Real positions of one run's reference passes (PERF.md, PR 52: what a pass
# of 7,168 positions costs, warm and on an empty compile cache).
SAMPLE_POSITIONS = 71_680
# Tokens every probe's state has read at its end: prompts 2 past one prefill
# call's edge, 1 past the second's, and nearly the whole length (calls of 512
# positions hold two chunks of 256).
PROBE_TOKENS = 1280
# An emitted (greedy) token's reference logit may lie this far below the
# reference's own maximum. No other cell's limit carries over: the logits are
# divided by 16 and the tied embedding's rows are made at 0.02 / 96
# (``models/granite_moe_hybrid.py``), so they have a standard deviation of
# ~1e-3 and the two largest of 50,176 lie ~2e-4 apart; the engine's bf16
# stream meets ten routers whose 10th and 11th logits of 72 lie close, and
# about one emitted token in seventeen is not the reference's own argmax. The
# two readings (my chip runs, PR 52; 7,700-12,400 checked tokens a run): the
# run's largest gap 8.3e-5 to 1.51e-4 over fourteen seeds (1.51e-4 once, the
# rest under 1.3e-4); the controls' (``tools/granite_control.py``) 1.11e-3
# with the engine's matrices at 3 bits of mantissa, 9.4e-4 with the residual
# multiplier 1, 5.0e-4 with the ten weights not renormalised. The limit is
# twice the largest reading and 0.6 of the weakest of those three. (The
# fourth wrong fact, the softmax scale taken as 1 / sqrt(128) in the ONE
# attention layer of ten, reads 2.4e-4 and rides this limit's tail: the mean
# tells it, below.)
LOGIT_TOL = 3.0e-4
# ... and their MEAN may be this large: the sharper reading, because it does
# not ride the tail. The run's mean gap 0.98e-6 to 1.23e-6 over those seeds
# (94% of the tokens are the reference's own argmax); the controls' 5.1e-6
# (scale; 88% argmax), 2.7e-5 (not renormalised; 74%), 3.6e-5 (residual; 70%),
# 9.3e-5 (3 bits; 55%). Between 1.23e-6 and 5.1e-6: 1.9 times the largest
# reading, under half the weakest control. (A state kept at bf16 passes both
# limits, at 8.3e-5 and 1.07e-6: the state's fine share is what tells it,
# 0.0.)
MEAN_TOL = 2.3e-6


def share_problems(moe: dict, config: dict) -> list:
    """What the engine's `moe` counters say against the configuration."""
    layers = len(config["layer_types"])
    picks = moe.get("tokens", 0) * config["num_experts_per_tok"] * layers
    here, absent = (moe.get(k, 0) for k in ("assignments_here",
                                            "assignments_absent"))
    problems = []
    if not picks or here + absent != picks \
            or moe.get("assignments") != picks:
        problems.append(
            f"the router's picks do not add up: here + absent = {here} + "
            f"{absent} against {moe.get('tokens', 0)} tokens x "
            f"{config['num_experts_per_tok']} x {layers} = {picks} "
            f"(assignments {moe.get('assignments')})")
    elif not (here and absent):
        problems.append(
            "a router as wide as published picks held and absent experts "
            f"alike; counted here {here}, absent {absent}")
    if moe.get("experts_here") != config["num_local_experts"]:
        problems.append(
            f"the engine holds {moe.get('experts_here')} experts a layer, "
            f"the configuration {config['num_local_experts']}")
    return problems


@jax.jit
def _gaps(logits, emitted):
    """logits [1, rows, V], emitted [rows] -> [rows]: how far each row's
    logit of `emitted` lies below the row's maximum."""
    rows = logits[0]
    picked = jnp.take_along_axis(rows, emitted[:, None], axis=-1)[:, 0]
    return jnp.max(rows, axis=-1) - picked


def packed_gaps(model, params, recs, config: dict, size: int, dev):
    """``serve_closed_share._reference_gaps`` (whole requests packed into
    passes of one shape ``[1, size]`` as segments, in the order given; in a
    request's segment position P-1+i predicts answer token i) with the head
    ``HEAD_ROWS`` positions at a time: a pass's float32 logits over 50,176
    columns are 1.44 GB beside 13 GB of weights and state (15.37 GB at the
    peak of my first chip runs, PR 52)."""
    seqs = [np.concatenate([r.prompt, np.asarray(r.toks[:-1], np.int32)])
            for r in recs]
    gaps = [None] * len(recs)
    rows = min(HEAD_ROWS, size)
    with jax.default_device(dev):
        for some in _share.pack([len(s) for s in seqs], size):
            tokens = np.zeros((1, size), np.int32)
            # the tail's padding is a segment of its own
            segments = np.full((1, size), len(some), np.int32)
            emitted = np.zeros((size,), np.int32)
            firsts, at = [], 0
            for j, i in enumerate(some):
                r, n = recs[i], len(seqs[i])
                tokens[0, at:at + n] = seqs[i]
                segments[0, at:at + n] = j
                firsts.append(at + len(r.prompt) - 1)
                emitted[firsts[-1]:firsts[-1] + r.n] = r.toks
                at += n
            x = model.reference_hidden(params, config, jnp.asarray(tokens),
                                       jnp.asarray(segments))
            below = np.zeros((size,), np.float32)
            for lo in range(0, size, rows):
                lo = min(lo, size - rows)
                below[lo:lo + rows] = np.asarray(_gaps(
                    model.reference_head(params, config, x, (lo, rows)),
                    jnp.asarray(emitted[lo:lo + rows])))
            for first, i in zip(firsts, some):
                gaps[i] = below[first:first + recs[i].n]
    return gaps


def sampled_gaps(model, params, recs, config: dict, size: int, dev,
                 seed: int, budget: int):
    """``serve_closed_conv.sampled_gaps`` over ``packed_gaps``: {index of
    recs: its emitted tokens' gaps} for the seed's sample, handed over in the
    order that makes the passes the fullest."""
    lengths = [len(r.prompt) + r.n - 1 for r in recs]
    chosen = _conv.draw_sample(lengths, seed, budget)
    order = [chosen[j] for some in _conv.pack_longest_first(
        [lengths[i] for i in chosen], size) for j in some]
    return dict(zip(order, packed_gaps(
        model, params, [recs[i] for i in order], config, size, dev)))


def run_cell(env) -> dict:
    from megatronapp_tpu.inference import server
    kept, sample = [], {}
    model, config, say = env["model"], env["config"], env["say"]

    class Driver(server.DynamicBatchingDriver):
        def __init__(self, engine, *a, **kw):
            super().__init__(engine, *a, **kw)
            kept.append((self, [(p.shape, p.dtype)
                                for p in engine.pool.pages]))

    def reference_gaps(model, params, recs, config, limit, dev):
        budget = SAMPLE_POSITIONS if not env["rehearsal"] else 1 << 30
        sample["gaps"] = sampled_gaps(model, params, recs, config,
                                      max(limit, 1), dev, env["seed"], budget)
        positions = [len(r.prompt) + r.n - 1 for r in recs]
        sample.update(total=len(recs), positions_total=sum(positions),
                      positions=sum(positions[i] for i in sample["gaps"]))
        # serve_closed takes a row a request: the unchecked ones get one
        # zero each, and the statistics are made again below from the
        # checked ones alone.
        return [sample["gaps"].get(i, np.zeros(1)) for i in range(len(recs))]

    real, server.DynamicBatchingDriver = server.DynamicBatchingDriver, Driver
    _closed.REF_BATCH = 1 << 30         # one call: the sample is drawn there
    _closed._reference_gaps = reference_gaps
    _closed.LOGIT_TOL = LOGIT_TOL
    _state.PROBE_TOKENS = PROBE_TOKENS
    try:
        run = _closed.run_cell(env)
    finally:
        server.DynamicBatchingDriver = real
    if env["trace_dir"]:    # as serve_closed_rows: the spans' attributes
        run["xplane_stats"] = xplane_stats.load(env["trace_dir"])
    problems, notes = run["problems"], run["notes"]

    # ---- the logits, over the sample ------------------------------------
    gaps = list(sample.get("gaps", {}).values())
    checked = np.concatenate(gaps) if gaps else np.zeros(1)
    notes.update(
        reference_checked=len(gaps),
        reference_checked_of=sample.get("total", 0),
        reference_positions=sample.get("positions", 0),
        reference_positions_of=sample.get("positions_total", 0),
        reference_tokens=int(sum(len(g) for g in gaps)),
        reference_worst_gap=float(checked.max()),
        reference_mean_gap=float(checked.mean()),
        reference_not_argmax_share=float((checked > 0).mean()))
    say(f"perfbench: checked {len(gaps)} of {sample.get('total', 0)} "
        f"requests, {notes['reference_positions']} of "
        f"{notes['reference_positions_of']} positions, "
        f"{notes['reference_tokens']} emitted tokens, drawn from the seed "
        f"after the window: largest gap {notes['reference_worst_gap']:.3e} "
        f"(limit {LOGIT_TOL}), mean gap {notes['reference_mean_gap']:.3e} "
        f"(limit {MEAN_TOL})")
    if not notes["reference_mean_gap"] <= MEAN_TOL:
        problems.append(
            f"the emitted tokens' reference logits lie "
            f"{notes['reference_mean_gap']:.3e} below the maximum on "
            f"average (> {MEAN_TOL})")

    # ---- the state: size, then precision ----------------------------------
    stats = run.get("engine_stats") or {}
    state = stats.get("state") or {}
    stated = model.state_bytes_per_slot(config, config["serve"]["state_dtype"])
    if state.get("kind") != "ssm" or state.get("bytes_per_slot") != stated:
        problems.append(
            f"a slot's second tenant is {state.get('kind')!r} of "
            f"{state.get('bytes_per_slot')} B where a recurrent state in "
            f"{config['serve']['state_dtype']} takes {stated}")
    probe = {}
    if kept and getattr(kept[0][0].engine.pool, "state", None):
        with jax.default_device(env["devices"][0]):
            probe = _state._probe(env, *kept[0])
        say(f"perfbench: {probe['probes']} probes' states read back: "
            f"{probe['fine']:.5f} of their elements are finer than "
            f"{probe['below']} (limit {_state.FINE_SHARE})")
    if not probe.get("fine", 0.0) > _state.FINE_SHARE:
        problems.append(
            f"a slot's recurrent state is held no finer than "
            f"{probe.get('below')}: {probe.get('fine')} of its elements "
            f"(<= {_state.FINE_SHARE})")

    # ---- the share's counters ---------------------------------------------
    moe = stats.get("moe") or {}
    problems += share_problems(moe, config)
    run["correct"] = not problems
    notes.update(
        state_bytes_per_slot=state.get("bytes_per_slot"),
        state_mixer=state.get("mixer"), state_resets=state.get("resets"),
        state_dropped=state.get("dropped"),
        prefill_scans=state.get("prefill_scans"),
        state_probes=probe.get("probes"),
        state_fine_share=probe.get("fine"),
        moe={k: moe.get(k, 0) for k in (
            "decode_rounds", "tokens", "assignments", "assignments_here",
            "assignments_absent", "expert_pairs_touched",
            "expert_pairs_possible", "here_max_rows")})
    return run
