"""``"runner": "serve_closed_conv"``: ``serve_closed``'s loop, unchanged, for a
model whose engine keeps a convolution's tail a slot beside its KV pages and
whose feed-forwards are experts that are ALL held here
(``models/lfm2_moe.py``). It wraps ``serve_closed`` the way
``serve_closed_state`` does and brings only what ``correct`` needs here:

THE LOGITS, ON A SAMPLE. The float32 reference runs every expert over every
position, 9.66 GFLOP a position at the published widths: a window's ~400
completed requests of ~1,200 positions would take three minutes, and a run
has to end inside the driver's six. So a SAMPLE of the window's completed
requests is checked, every emitted token of each: drawn AFTER the window
from ``--seed`` (no step can know it), the longest request always in it,
filled up to ``SAMPLE_POSITIONS`` positions (about a quarter of a window's).
Whole requests are packed end to end into passes of one shape ``[1,
max_total_len]`` as segments (``serve_closed_share``'s pass: the
reference's convolution and attention stay inside a segment, positions
restart at each), longest first, so that the passes are full. The run's
``notes`` say how many requests and positions were checked of how many.

THE LIMITS ON AN EMITTED TOKEN'S GAP, ``LOGIT_TOL`` (the largest) and
``MEAN_TOL`` (the mean over the checked tokens) below, each set between two
readings: the run's own and the controls' (``tools/conv_control.py``).

THE TENANT'S SIZE. ``stats_snapshot()["state"]``: ``kind`` ``"conv"`` and
``bytes_per_slot`` against the model module's ``state_bytes_per_slot(config,
serve.state_dtype)``; the pool's bytes a block are held to the cache type by
``serve_closed`` itself.

THE TENANT'S CONTENT. After the window and the drain, the engine that was
measured (its stepper, compiled steps and tail pool) serves a few more
requests, alone: prompts that end 1 and 2 tokens past a prefill call's edge
(the tail's columns come from both sides of it) and one inside a call, each
followed by a few decoded tokens. A finished request's slot keeps its
columns until the next admission, so they are read back and held to
``model.reference_state`` of the tokens the slot has read: the largest
distance of a layer's two columns from the reference's, as a share of their
size, stays under ``TAIL_TOL``.

THE COUNTERS' IDENTITY. The engine's ``moe`` counters over its plain decode
rounds: ``assignments`` (counted from the indices inside the step) equals
the rounds' tokens (counted on the host) x experts a token x MoE layers,
and ``experts_here`` is the configuration's ``num_experts``.

A program without such counters or pool is not correct here.
"""

from __future__ import annotations

from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from perfbench import manifest, xplane_stats

_closed = manifest.load_module("cells", "serve_closed")
_share = manifest.load_module("cells", "serve_closed_share")
REHEARSAL = _closed.REHEARSAL

# Real positions of one run's reference passes: ~45 passes, 24 s warm and
# ~50 s on an empty compile cache (my chip runs, PR 41), of a traced cold
# run's 251 s.
SAMPLE_POSITIONS = 120_000
PROBE_DECODED = 4               # tokens every probe decodes behind its prompt
# An emitted (greedy) token's reference logit may lie this far below the
# reference's own maximum. ``serve_closed.LOGIT_TOL`` (0.25) was sized on a
# dense model whose error is rounding alone. Here the tied head gives logits
# of std 0.9 over 65536 columns (the two largest lie ~0.2 apart), and the
# engine's bf16 stream meets 8 routers whose 4th and 5th largest of 64
# sigmoid scores lie a few per cent apart: rounding flips near-tie picks, a
# flipped pick swaps a quarter of that layer's output, which at seeded
# weights is most of the stream, and about one emitted token in five is not
# the reference's own argmax. The
# two readings (my chip runs, PR 41; ~45,000 checked tokens a run): the
# run's largest gap 0.89-1.25 over twenty-two seeds (their maxima spread like
# a Gumbel of scale 0.08); the controls' (``tools/conv_control.py``) 2.08
# with the engine's matrices at 3 bits of mantissa and 2.49 with a seeded
# selection bias that the program drops. The limit is their geometric
# middle, 1.3 times the largest reading and 0.77 of the weaker control. (A
# program that scores by softmax reads 1.30 here: it selects the same
# experts and weighs them otherwise, so no single token tells it; the mean
# does.)
LOGIT_TOL = 1.6
# ... and their MEAN may be this large: the sharper reading, because it does
# not ride the tail. The run's mean gap 0.0249-0.0268 over those seeds
# (0.0206 with a seeded bias; 79% of the tokens are the reference's own
# argmax); the controls' 0.098 with softmax scores (56% argmax), 0.27 at 3
# bits, 0.45 with the bias dropped. The geometric middle of 0.027 and 0.098:
# 1.9 times the reading, half the weakest control.
MEAN_TOL = 0.05
# A slot's two columns a layer may lie this far from the reference's, as a
# share of their size (the largest over the probes). In the FIRST layer,
# whose input is an embedding row and nothing else, the engine's columns are
# the reference's to bf16's rounding of a projection and a product:
# 0.0036-0.0040 over those seeds; columns kept at 3 bits of mantissa lie
# 0.027 away (tests/test_lfm2_moe.py), columns one position late 1.0-1.4.
TAIL_FIRST_TOL = 0.01
# In any layer: deeper columns inherit the stream's flipped picks, 0.04-0.29
# over fifteen seeds (mean 0.17; each flipped pick upstream of a probe's last
# two positions adds its tenth or two); a tail that was not advanced, not
# reset or another slot's lies 1.0-1.4 away whatever the layer (unrelated
# columns: the root of 2). Nearer the controls than the readings, because a
# correct run's reading has a tail and a wrong tail's has none.
TAIL_TOL = 0.7


def draw_sample(lengths: List[int], seed: int, budget: int) -> List[int]:
    """Indices of the requests to check, from the seed: the longest always,
    then the others in the seed's order, each taken if it still fits into
    `budget` positions in all."""
    if not lengths:
        return []
    longest = int(np.argmax(lengths))
    order = np.random.default_rng([seed, len(lengths)]).permutation(
        len(lengths))
    taken, used = [longest], lengths[longest]
    for i in map(int, order):
        if i != longest and used + lengths[i] <= budget:
            taken.append(i)
            used += lengths[i]
    return taken


def pack_longest_first(lengths: List[int], size: int) -> List[List[int]]:
    """Indices of `lengths` cut into passes of at most `size` positions in
    all, longest first, each into the first pass that has room (a request
    is never split)."""
    passes, room = [], []
    for i in sorted(range(len(lengths)), key=lambda i: -lengths[i]):
        n = lengths[i]
        if n > size:
            raise ValueError(f"a request of {n} positions in a pass of "
                             f"{size}")
        for j, left in enumerate(room):
            if n <= left:
                passes[j].append(i)
                room[j] -= n
                break
        else:
            passes.append([i])
            room.append(size - n)
    return passes


def sampled_gaps(model, params, recs, config: dict, size: int, dev,
                 seed: int, budget: int):
    """{index of recs: how far below the reference's maximum logit each of
    its emitted tokens lies} for the seed's sample: ``serve_closed_share``'s
    pass (whole requests packed as segments into one shape ``[1, size]``),
    handed the sample in the order that makes its passes the fullest."""
    lengths = [len(r.prompt) + r.n - 1 for r in recs]
    chosen = draw_sample(lengths, seed, budget)
    # its packer fills a pass in the order given: longest-first passes, one
    # after the other, come out of it as they went in
    order = [chosen[j] for some in pack_longest_first(
        [lengths[i] for i in chosen], size) for j in some]
    return dict(zip(order, _share._reference_gaps(
        model, params, [recs[i] for i in order], config, size, dev)))


def counter_problems(moe: dict, config: dict) -> list:
    """What the engine's `moe` counters say against the configuration."""
    layers = config["num_hidden_layers"] - config["num_dense_layers"]
    picks = moe.get("tokens", 0) * config["num_experts_per_tok"] * layers
    problems = []
    if not picks or moe.get("assignments") != picks:
        problems.append(
            f"the router's picks do not add up: assignments "
            f"{moe.get('assignments')} against {moe.get('tokens', 0)} tokens "
            f"x {config['num_experts_per_tok']} x {layers} = {picks}")
    if moe.get("experts_here") != config["num_experts"]:
        problems.append(
            f"the engine holds {moe.get('experts_here')} experts a layer, "
            f"the configuration {config['num_experts']}")
    return problems


def probe_prompts(chunk: int, longest: int) -> List[int]:
    """Prompt lengths of the probes: 1 and 2 tokens past a prefill call's
    edge, and one inside a call."""
    return [p for p in (chunk + 1, chunk + 2, max(2, chunk // 2 + 3))
            if p + PROBE_DECODED <= longest]


def tail_distance(held, want) -> np.ndarray:
    """held, want [layers, probes, columns, H] -> [layers]: the largest
    distance over the probes of a layer's columns from the reference's, as
    a share of the reference's size."""
    held, want = np.asarray(held, np.float32), np.asarray(want, np.float32)
    err = np.sqrt(((held - want) ** 2).sum(axis=(2, 3)))
    return (err / np.sqrt((want ** 2).sum(axis=(2, 3)))).max(axis=1)


def _probe(env, driver, page_specs, params) -> dict:
    from megatronapp_tpu.inference.engine import SamplingParams
    engine, config, model = driver.engine, env["config"], env["model"]
    # serve_closed freed the page pools for its reference pass.
    engine.pool.pages = tuple(jnp.zeros(s, d) for s, d in page_specs)
    rng = np.random.default_rng([env["seed"], PROBE_DECODED])
    subs = [driver.submit(
        rng.integers(0, config["vocab_size"], p).astype(np.int32),
        PROBE_DECODED, SamplingParams(greedy=True))
        for p in probe_prompts(engine.prefill_chunk,
                               config["serve"]["max_seq_len"])]
    for _, done in subs:
        if not done.wait(timeout=600):
            raise SystemExit("perfbench: a tail probe did not finish")
    if any(rid not in engine.requests for rid, _ in subs):
        # (a control that deleted the engine's weights ends here)
        return {"probes": len(subs), "failed": True}
    hidden = config["hidden_size"]
    # a finished request keeps its slot's number, and has read all but its
    # last token (reading the tokens pops the engine's record)
    slots = [engine.requests[rid].slot for rid, _ in subs]
    read = [np.asarray(driver.result_tokens(rid))[:-1] for rid, _ in subs]
    width = max(len(t) for t in read)
    tokens = np.zeros((len(read), width), np.int32)
    for row, t in enumerate(read):
        tokens[row, :len(t)] = t
    pool = engine.pool.state[0]                 # [layers, slots, 2 * H]
    held = jnp.stack([pool[:, slot] for slot in slots], axis=1)
    held = held.reshape(held.shape[0], len(subs), -1, hidden)
    want = model.reference_state(
        params, config, jnp.asarray(tokens),
        lengths=jnp.asarray([len(t) for t in read], jnp.int32))
    by_layer = tail_distance(held, want)
    return {"probes": len(subs), "first": float(by_layer[0]),
            "distance": float(by_layer.max())}


def run_cell(env) -> dict:
    from megatronapp_tpu.inference import server
    kept, sample = [], {}
    model, config, say = env["model"], env["config"], env["say"]

    class Driver(server.DynamicBatchingDriver):
        def __init__(self, engine, *a, **kw):
            super().__init__(engine, *a, **kw)
            kept.append((self, [(p.shape, p.dtype)
                                for p in engine.pool.pages], engine.params))

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
        f"after the window: mean gap "
        f"{notes['reference_mean_gap']:.5f} (limit {MEAN_TOL})")
    if not notes["reference_mean_gap"] <= MEAN_TOL:
        problems.append(
            f"the emitted tokens' reference logits lie "
            f"{notes['reference_mean_gap']:.4f} below the maximum on "
            f"average (> {MEAN_TOL})")

    # ---- the tenant: size, then content ---------------------------------
    stats = run.get("engine_stats") or {}
    state = stats.get("state") or {}
    stated = model.state_bytes_per_slot(config, config["serve"]["state_dtype"])
    if state.get("kind") != "conv" or state.get("bytes_per_slot") != stated:
        problems.append(
            f"a slot's second tenant is {state.get('kind')!r} of "
            f"{state.get('bytes_per_slot')} B where a convolution's tails "
            f"in {config['serve']['state_dtype']} take {stated}")
    probe = {}
    if kept and getattr(kept[0][0].engine.pool, "state", None):
        with jax.default_device(env["devices"][0]):
            probe = _probe(env, *kept[0])
    if probe.get("failed"):
        problems.append("the engine did not serve the tail probes")
    elif probe:
        say(f"perfbench: {probe['probes']} probes' columns read back: "
            f"{probe['first']:.5f} of their size from the reference's in "
            f"the first layer (limit {TAIL_FIRST_TOL}), at most "
            f"{probe['distance']:.5f} in any (limit {TAIL_TOL})")
    if not probe.get("failed") and not (
            probe.get("first", 1.0) <= TAIL_FIRST_TOL
            and probe.get("distance", 1.0) <= TAIL_TOL):
        problems.append(
            f"a slot's cached columns lie {probe.get('first')} of their "
            f"size from the reference's in the first layer (> "
            f"{TAIL_FIRST_TOL}) or {probe.get('distance')} in some layer "
            f"(> {TAIL_TOL})")

    # ---- the counters' identity -------------------------------------------
    moe = stats.get("moe") or {}
    problems += counter_problems(moe, config)
    run["correct"] = not problems
    notes.update(
        state_bytes_per_slot=state.get("bytes_per_slot"),
        state_resets=state.get("resets"), state_dropped=state.get("dropped"),
        tail_probes=probe.get("probes"), tail_distance=probe.get("distance"),
        tail_distance_first=probe.get("first"),
        moe={k: moe.get(k, 0) for k in (
            "decode_rounds", "tokens", "assignments", "expert_pairs_touched",
            "expert_pairs_possible", "here_max_rows")})
    return run
