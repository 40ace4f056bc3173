"""``"runner": "serve_closed_share"``: ``serve_closed``'s loop, unchanged, for a
model whose expert layer holds a share of the published experts
(``models/longcat_flash.py``: the reference is given the same share, and what
the absent experts would have added is left out on both sides). It wraps
``serve_closed`` the way ``serve_closed_rows`` does and brings only what
``correct`` needs here:

THE REFERENCE PASS. Every completed request is checked against the float32
reference, every emitted token. A pass is ONE shape, ``[1, max_total_len]``
positions: whole requests are packed into it end to end as segments (the
reference's causal mask is restricted to a segment, positions restart at
each), 2-3 requests a pass at this mix, about 90% of a pass real. One shape
is one set of compiled programs: padding each request to a multiple of 1024
positions (``serve_closed_rows``) compiled four lengths x the answers'
row counts, 100 s of a cold run's 172 s check, and computed 138k positions
for 100k real ones (my chip run, PR 38).

THE LIMITS ON AN EMITTED TOKEN'S GAP, ``LOGIT_TOL`` (the largest) and
``MEAN_TOL`` (the mean over the run's tokens) below, each set between two
readings: the run's own and the controls' (``tools/share_control.py``).

THE SHARE, from the engine's own ``moe`` counters over its plain decode
rounds:

- every pick is accounted for: ``assignments_zero + assignments_here +
  assignments_absent`` equals the rounds' tokens x top-k x layers (each
  counted from the indices inside the step, the tokens on the host), so no
  pick is silently dropped or computed twice;
- the router is as wide as published: picks fall on the held experts, on
  the absent ones and on the zero-compute ones at all (a router over the
  held experts alone would read ``assignments_absent`` 0).

The pool's bytes a block are held to the configuration's cache type by
``serve_closed`` itself (two planes a layer: ``kv_bytes_per_token``).
"""

from __future__ import annotations

from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from perfbench import manifest, xplane_stats

_closed = manifest.load_module("cells", "serve_closed")
REHEARSAL = _closed.REHEARSAL

# An emitted (greedy) token's reference logit may lie this far below the
# reference's own maximum. ``serve_closed.LOGIT_TOL`` (0.25) was sized on
# logits of standard deviation ~1 whose error is rounding alone. Here the
# head's columns (std 0.02 over 6144 inputs) give logits of std 1.57, and the
# engine's bf16 stream meets a DISCONTINUITY the other cells lack at this
# weight: a 768-way router over seeded weights is nearly uniform, the 12th
# and 13th largest of its probabilities lie ~4% apart, so bf16's rounding
# of the router's input flips a near-tie pick in some layer for about one
# token in thirteen, and a flipped pick of an identity expert moves that
# layer's output by gamma x p = 0.08 of a unit-RMS vector at once. The two
# readings (my chip runs, PR 38; ~36,000 emitted tokens a run): the run's
# largest gap 0.415, 0.503, 0.558 over three seeds, a third of the requests
# over 0.25 and the tail falling e-fold every ~0.08; the controls'
# (``tools/share_control.py``) 3.19 with the engine's matrices at 3 bits of
# mantissa, 10.5 without s_q, 11.6 without s_kv. The limit is the geometric
# middle of 0.56 and 3.19, 2.7 times the largest reading and under half the
# smallest control.
LOGIT_TOL = 1.5
# ... and their MEAN may be this large: the sharper reading of precision,
# because it does not ride the tail. The run's mean gap 0.0041, 0.0042,
# 0.0041 (92.4% of the tokens are the reference's own argmax, gap 0); the
# 3-bit control's 0.35 (43% argmax), without s_q 3.9, without s_kv 5.3.
# Five times the reading, a seventeenth of the smallest control.
MEAN_TOL = 0.02


def share_problems(moe: dict, config: dict) -> list:
    """What the engine's `moe` counters say against the configuration."""
    picks = moe.get("tokens", 0) * config["moe_topk"] * config["num_layers"]
    parts = [moe.get(k, 0) for k in ("assignments_zero", "assignments_here",
                                     "assignments_absent")]
    problems = []
    if not picks or sum(parts) != picks or moe.get("assignments") != picks:
        problems.append(
            f"the router's picks do not add up: zero + here + absent = "
            f"{parts} against {moe.get('tokens', 0)} tokens x "
            f"{config['moe_topk']} x {config['num_layers']} = {picks} "
            f"(assignments {moe.get('assignments')})")
    elif not all(parts):
        problems.append(
            f"a {config['router_width']}-way router's picks fall on held, "
            f"absent and zero-compute experts alike; counted {parts}")
    if moe.get("experts_here") != config["n_routed_experts"]:
        problems.append(
            f"the engine holds {moe.get('experts_here')} experts a layer, "
            f"the configuration {config['n_routed_experts']}")
    return problems


def pack(lengths: List[int], size: int) -> List[List[int]]:
    """Indices of `lengths` in order, cut into passes of at most `size`
    positions in all (a request is never split)."""
    passes, room = [], 0
    for i, n in enumerate(lengths):
        if n > size:
            raise ValueError(f"a request of {n} positions in a pass of "
                             f"{size}")
        if not passes or n > room:
            passes.append([])
            room = size
        passes[-1].append(i)
        room -= n
    return passes


@jax.jit
def _gaps(logits, emitted):
    """logits [1, S, V], emitted [S] -> [S]: how far each row's logit of
    `emitted` lies below the row's maximum (one program for every pass; a
    slice a request would compile one a request)."""
    rows = logits[0]
    picked = jnp.take_along_axis(rows, emitted[:, None], axis=-1)[:, 0]
    return jnp.max(rows, axis=-1) - picked


def _reference_gaps(model, params, recs, config: dict, limit: int,
                    dev) -> List[np.ndarray]:
    """``serve_closed._reference_gaps`` with whole requests packed into
    passes of one shape: how far below the reference's maximum logit each
    emitted token lies. In a request's segment, position P-1+i predicts
    answer token i."""
    seqs = [np.concatenate([r.prompt, np.asarray(r.toks[:-1], np.int32)])
            for r in recs]
    size = max(limit, 1)
    gaps = [None] * len(recs)
    with jax.default_device(dev):
        for some in pack([len(s) for s in seqs], size):
            tokens = np.zeros((1, size), np.int32)
            # the tail's padding is a segment of its own
            segments = np.full((1, size), len(some), np.int32)
            positions = np.zeros((1, size), np.int32)
            emitted = np.zeros((size,), np.int32)
            firsts, at = [], 0
            for j, i in enumerate(some):
                r, n = recs[i], len(seqs[i])
                tokens[0, at:at + n] = seqs[i]
                segments[0, at:at + n] = j
                positions[0, at:at + n] = np.arange(n)
                firsts.append(at + len(r.prompt) - 1)
                emitted[firsts[-1]:firsts[-1] + r.n] = r.toks
                at += n
            lg = model.reference_logits(
                params, config, jnp.asarray(tokens), jnp.asarray(segments),
                jnp.asarray(positions % config["max_position_embeddings"]))
            below = np.asarray(_gaps(lg, jnp.asarray(emitted)))
            for first, i in zip(firsts, some):
                gaps[i] = below[first:first + recs[i].n]
    return gaps


def run_cell(env) -> dict:
    _closed.REF_BATCH = 1 << 30         # one call: the packing is ours
    _closed._reference_gaps = _reference_gaps
    _closed.LOGIT_TOL = LOGIT_TOL
    run = _closed.run_cell(env)
    if env["trace_dir"]:
        run["xplane_stats"] = xplane_stats.load(env["trace_dir"])
    moe = run["engine_stats"].get("moe", {})
    mean = run["notes"]["reference_mean_gap"]
    if not mean <= MEAN_TOL:
        run["problems"].append(
            f"the emitted tokens' reference logits lie {mean:.4f} below the "
            f"maximum on average (> {MEAN_TOL})")
    run["problems"] += share_problems(moe, env["config"])
    run["correct"] = not run["problems"]
    run["notes"]["moe"] = {k: moe.get(k, 0) for k in (
        "decode_rounds", "tokens", "assignments_zero", "assignments_here",
        "assignments_absent", "expert_pairs_touched",
        "expert_pairs_possible", "here_max_rows")}
    return run
