"""``"runner": "serve_closed_window"``: ``serve_closed``'s loop, unchanged, for a
model whose engine keeps two kinds of plane in one cache: full planes that
keep every row and sliding-window planes that give a slot's blocks back
(``models/laguna.py``), and whose feed-forwards are experts that are ALL
held here. It wraps ``serve_closed`` the way ``serve_closed_conv`` does and
brings only what ``correct`` needs here:

THE LOGITS, ON A SAMPLE. What is compared is what the timed path produced at
the timed sizes: every emitted token of a sampled request came out of the
window's own prefill calls and decode rounds, through both kinds of plane,
and is held to the float32 reference's full forward over the request's
prompt and the engine's own answer. The reference runs every expert over
every position (7.5 GFLOP a position at the published widths) and full
attention over up to 19,456 keys, so a SAMPLE of the window's completed
requests is checked (``serve_closed_conv.draw_sample``: drawn AFTER the
window from ``--seed``, the longest request always in it, and with it a
prompt far past 8,192 whose answer crosses many block edges of the window
planes), filled up to ``SAMPLE_POSITIONS`` positions. Whole requests are
packed end to end as segments into passes of one shape ``[1,
max_total_len]``; the head over 100,352 columns runs ``HEAD_ROWS`` rows at a
time (a pass's logits would be 7.8 GB). The run's ``notes`` say how many
requests and positions were checked of how many.

THE LIMITS ON AN EMITTED TOKEN'S GAP, ``LOGIT_TOL`` (the largest) and
``MEAN_TOL`` (the mean over the checked tokens) below, each set between two
readings: the run's own and the controls' (``tools/window_control.py``).

THE WINDOW'S EDGE. One key more or less in a window of 512 moves a logit by
less than bf16's rounding does, so no limit on an emitted token's gap tells
a window of 511 or 513 from 512. After the window and the drain, the engine
that was measured (its stepper, its compiled steps, both kinds of plane)
serves ``len(PROBE_PROMPTS)`` more requests at once, alone, and every decode
round's logits rows of theirs are kept: prompts past the window, past two
windows, past a prefill call's edge, each followed by ``PROBE_DECODED``
tokens that cross block edges of the window planes. The reference computes
those rows three times: as the model (window w), and with windows w - 1 and
w + 1. With e = engine - reference(w) and d = reference(w +- 1) -
reference(w), a row's share of d in e is ``e . d / d . d``: 0 where e is
rounding, which knows nothing of d, and 1 where the program's window is w
+- 1. The MEDIAN over the rows is held to ``EDGE_TOL``, not the rows' pooled
ratio: a near-tie pick of a router flips under any small change, the
window's and the rounding's alike, and a row in which it did carries a
difference hundreds of times a smooth row's, in the same direction on both
sides (the pooled ratio read 0.43-0.59 whatever the program's window; my
chip runs, PR 45).

THE CACHE'S SIZE AND ITS IDENTITIES. ``serve_closed`` holds the main pool's
bytes a block to the full planes' (``kv_bytes_per_token``); here the window
planes' bytes a block are held to ``window_bytes_per_token``, and
``stats_snapshot()["window"]`` to: blocks taken - given back = held (0 once
drained: nothing leaks); the most blocks one slot held at most the bound
between calls plus one prefill call's; the planes' counts the
configuration's.

THE COUNTERS' IDENTITY. ``serve_closed_conv.counter_problems``' reading of
the engine's ``moe`` counters: picks = rounds' tokens x experts a token x
sparse layers, and ``experts_here`` the configuration's ``num_experts``.

A program without such counters or planes is not correct here.
"""

from __future__ import annotations

from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from perfbench import manifest, xplane_stats

_closed = manifest.load_module("cells", "serve_closed")
_conv = manifest.load_module("cells", "serve_closed_conv")
REHEARSAL = _closed.REHEARSAL

# Real positions of one run's reference passes (~11 requests of this mix, a
# fifth of a window's): 40-60 s of a run, 75 s on an empty compile cache
# (my chip runs, PR 45, at 120,000).
SAMPLE_POSITIONS = 100_000
HEAD_ROWS = 2048                # rows of a pass the head runs at a time
BLOCK_ROWS = 16
# An emitted (greedy) token's reference logit may lie this far below the
# reference's own maximum. ``serve_closed.LOGIT_TOL`` (0.25) was sized on a
# dense model whose error is rounding alone. Here an untied head over 100,352
# columns gives logits whose two largest lie ~0.2 apart, and the engine's
# bf16 stream meets 4 routers whose 8th and 9th largest of 256 sigmoid scores
# lie a per cent apart: rounding flips near-tie picks, and about one emitted
# token in eight is not the reference's own argmax. The two readings (my
# chip runs, PR 45; ~10,000-15,000 checked tokens a run): the run's largest
# gap 0.63-0.97 over sixteen seeds; the controls' (``tools/
# window_control.py``) 1.36 with the engine's matrices at 3 bits of
# mantissa, 1.62 with the full layers' rotary table on the sliding layers,
# 4.42 without the gate. The limit is the geometric middle of 0.97 and 1.62:
# the 3-bit control passes under it by a little on some seeds (1.36 here),
# which is what the mean is for.
LOGIT_TOL = 1.25
# ... and their MEAN may be this large: the sharper reading, because it does
# not ride the tail. The run's mean gap 0.0128-0.0149 over those seeds (88%
# of the tokens are the reference's own argmax); the controls' 0.114 at 3
# bits (52% argmax), 0.217 with one rotary table, 1.55 without the gate. The
# geometric middle of 0.0149 and 0.114: 2.7 times the largest reading, a
# third of the weakest control. (A window of 511 or 513 reads 0.0137: one key
# in 512 is under the rounding, which is what the edge probe is for.)
MEAN_TOL = 0.04
# The window's edge (the module's docstring): prompt lengths of the probes as
# (windows, prefill calls, tokens) past 0, the tokens each decodes, and the
# most of a one-key-off window's difference that the engine's error may hold.
# The two readings (my chip runs, PR 45; 234 rows a run): the median share
# 0.14-0.17 on both sides with the program's window at 512 (not 0: the three
# reference passes share their own rounding, a sixth of a one-key
# difference's size), 0.836 and 0.837 on the side of a program whose window
# is 511 or 513 (0.14 on the other); a row's share spreads by +-0.15.
PROBE_PROMPTS = ((1, 0, 9), (1, 0, 266), (2, 0, 7), (0, 1, 3), (1, 1, 130),
                 (3, 1, 77))
PROBE_DECODED = 40
EDGE_TOL = 0.5


@jax.jit
def _gaps(logits, emitted):
    """logits [1, R, V], emitted [R] -> [R]: how far each row's logit of
    `emitted` lies below the row's maximum."""
    rows = logits[0]
    picked = jnp.take_along_axis(rows, emitted[:, None], axis=-1)[:, 0]
    return jnp.max(rows, axis=-1) - picked


def pack_pass(seqs, size: int):
    """Whole sequences end to end as segments of one pass [1, size]
    (``serve_closed_share``'s packing): tokens, segment ids (the tail's
    padding a segment of its own), positions restarting at each sequence,
    and where each starts."""
    tokens = np.zeros((1, size), np.int32)
    segments = np.full((1, size), len(seqs), np.int32)
    positions = np.zeros((1, size), np.int32)
    starts, at = [], 0
    for j, seq in enumerate(seqs):
        tokens[0, at:at + len(seq)] = seq
        segments[0, at:at + len(seq)] = j
        positions[0, at:at + len(seq)] = np.arange(len(seq))
        starts.append(at)
        at += len(seq)
    return tokens, segments, positions, starts


def reference_gaps(model, params, recs, config: dict, size: int, dev,
                   order: List[List[int]]) -> dict:
    """{index of recs: how far below the reference's maximum logit each of
    its emitted tokens lies}, for the requests `order` names, pass by pass:
    ``serve_closed_share``'s packing (whole requests as segments of one
    shape ``[1, size]``, positions restarting at each), with the head taken
    ``HEAD_ROWS`` rows at a time over the rows that predict an emitted
    token."""
    seqs = {i: np.concatenate([recs[i].prompt,
                               np.asarray(recs[i].toks[:-1], np.int32)])
            for some in order for i in some}
    gaps = {}
    rows = min(HEAD_ROWS, size)
    with jax.default_device(dev):
        for some in order:
            tokens, segments, positions, starts = pack_pass(
                [seqs[i] for i in some], size)
            emitted = np.zeros((size,), np.int32)
            firsts = [at + len(recs[i].prompt) - 1
                      for at, i in zip(starts, some)]
            for first, i in zip(firsts, some):
                emitted[first:first + recs[i].n] = recs[i].toks
            x = model.reference_hidden(
                params, config, jnp.asarray(tokens), jnp.asarray(segments),
                jnp.asarray(positions % config["max_position_embeddings"]))
            below = np.zeros((size,), np.float32)
            wanted = np.zeros((size,), bool)
            for first, i in zip(firsts, some):
                wanted[first:first + recs[i].n] = True
            for start in range(0, size, rows):
                start = min(start, size - rows)
                if wanted[start:start + rows].any():
                    below[start:start + rows] = np.asarray(_gaps(
                        model.reference_head(params, config, x, start, rows),
                        jnp.asarray(emitted[start:start + rows])))
            for first, i in zip(firsts, some):
                gaps[i] = below[first:first + recs[i].n]
    return gaps


def probe_lengths(window: int, chunk: int, longest: int) -> List[int]:
    return [n for n in (w * window + c * chunk + t
                        for w, c, t in PROBE_PROMPTS)
            if n + PROBE_DECODED <= longest]


def serve_probes(env, driver, page_specs) -> dict:
    """The measured engine serves the probes, alone and at once; -> their
    token sequences and, a probe, the logits rows of its decode rounds
    (round r's row predicts token r + 1 of its answer)."""
    from megatronapp_tpu.inference.engine import SamplingParams
    engine, config = driver.engine, env["config"]
    # serve_closed freed the full planes' pools for its reference pass.
    engine.pool.pages = tuple(jnp.zeros(s, d) for s, d in page_specs)
    kept, inner = [], getattr(engine, "_decode", None)
    if inner is None or not getattr(engine, "has_window", False):
        return {"failed": "the engine has no window planes or no decode "
                          "step to read logits from"}

    def recording(*a):
        out = inner(*a)
        active = np.flatnonzero(np.asarray(a[6]))
        kept.append((active, np.asarray(out[0][active], np.float32)))
        return out

    engine._decode = recording
    rng = np.random.default_rng([env["seed"], PROBE_DECODED])
    lengths = probe_lengths(config["sliding_window"], engine.prefill_chunk,
                            config["serve"]["max_seq_len"])
    try:
        subs = [driver.submit(
            rng.integers(0, config["vocab_size"], n).astype(np.int32),
            PROBE_DECODED, SamplingParams(greedy=True)) for n in lengths]
        for _, done in subs:
            if not done.wait(timeout=600):
                return {"failed": "a window probe did not finish"}
    finally:
        engine._decode = inner
        engine.pool.pages = None
    if any(rid not in engine.requests for rid, _ in subs):
        return {"failed": "the engine did not serve the window probes"}
    slots = [engine.requests[rid].slot for rid, _ in subs]
    tokens = [np.asarray(driver.result_tokens(rid)) for rid, _ in subs]
    rows = []
    for slot in slots:
        mine = [lg[list(active).index(slot)] for active, lg in kept
                if slot in active]
        rows.append(np.stack(mine[:PROBE_DECODED - 1]))
    return {"lengths": lengths, "tokens": tokens, "rows": rows}


def edge_shares(model, params, config: dict, probe: dict, size: int, dev):
    """(share of the window-1 difference, of the window+1 difference) in the
    engine's error over the probes' decode rounds: the module's docstring."""
    n = sum(len(t) - 1 for t in probe["tokens"])
    if n > size:        # (a rehearsal's passes are shorter than its probes)
        size = -(-n // 128) * 128
    tokens, segments, positions, starts = pack_pass(
        [toks[:-1] for toks in probe["tokens"]], size)
    # decode round r read the token at position length + r
    wanted = [at + length + r
              for at, length, rows in zip(starts, probe["lengths"],
                                          probe["rows"])
              for r in range(len(rows))]
    wanted = jnp.asarray(wanted)
    with jax.default_device(dev):
        def rows_of(control):
            x = model.reference_hidden(
                params, config, jnp.asarray(tokens), jnp.asarray(segments),
                jnp.asarray(positions), control=control)
            return np.asarray(model.reference_head(
                params, config, x[:, wanted]))[0]
        ref = rows_of("")
        err = np.concatenate(probe["rows"]) - ref
        per_row = {"err2": (err * err).sum(-1)}
        for control in ("window-1", "window+1"):
            d = rows_of(control) - ref
            per_row[control] = ((err * d).sum(-1), (d * d).sum(-1))
    shares = [float(np.median(ed / np.maximum(dd, 1e-30)))
              for ed, dd in (per_row["window-1"], per_row["window+1"])]
    return shares, float(np.abs(err).max()), per_row


def window_problems(stats: dict, config: dict, model, pool_blocks: dict,
                    prefill_chunk: int) -> list:
    """What ``stats_snapshot()["window"]`` and the window planes' size say
    against the configuration."""
    w = stats.get("window") or {}
    if not w:
        return ["the engine reports no window planes "
                "(stats_snapshot()['window'])"]
    problems = []
    types = config["layer_types"]
    planes = (types.count("full_attention"), types.count("sliding_attention"))
    if (w.get("planes_full"), w.get("planes_window")) != planes \
            or w.get("window") != config["sliding_window"]:
        problems.append(
            f"the cache has {w.get('planes_full')} full and "
            f"{w.get('planes_window')} window planes of window "
            f"{w.get('window')}; the configuration {planes} of "
            f"{config['sliding_window']}")
    held = w.get("blocks_taken", 0) - w.get("blocks_given_back", 0)
    if not w.get("blocks_taken") or held != w.get("blocks_held") or held:
        problems.append(
            f"the window planes' blocks do not add up once drained: taken "
            f"{w.get('blocks_taken')} - given back "
            f"{w.get('blocks_given_back')} = {held}, held "
            f"{w.get('blocks_held')}")
    most = w.get("blocks_slot_bound", 0) + -(-prefill_chunk // BLOCK_ROWS) + 1
    if not 0 < w.get("max_blocks_slot", 0) <= most:
        problems.append(
            f"a slot held {w.get('max_blocks_slot')} window blocks; the "
            f"window and one prefill call take at most {most}")
    stated = model.window_bytes_per_token(
        config, config["serve"]["kv_cache_dtype"]) * BLOCK_ROWS
    if pool_blocks.get("window_bytes_per_block") != stated:
        problems.append(
            f"a window-plane block takes "
            f"{pool_blocks.get('window_bytes_per_block')} B where "
            f"{config['serve']['kv_cache_dtype']} takes {stated}")
    return problems


def run_cell(env) -> dict:
    from megatronapp_tpu.inference import server
    kept, sample = [], {}
    model, config, say = env["model"], env["config"], env["say"]

    class Driver(server.DynamicBatchingDriver):
        def __init__(self, engine, *a, **kw):
            super().__init__(engine, *a, **kw)
            pool = engine.pool
            window = getattr(pool, "window_pages", None)
            kept.append({
                "driver": self,
                "page_specs": [(p.shape, p.dtype) for p in pool.pages],
                "prefill_chunk": engine.prefill_chunk,
                "window_bytes_per_block": (
                    sum(p.size * p.dtype.itemsize for p in window)
                    // pool.num_window_blocks if window else None)})

    def gaps_of_sample(model, params, recs, config, limit, dev):
        budget = SAMPLE_POSITIONS if not env["rehearsal"] else 1 << 30
        lengths = [len(r.prompt) + r.n - 1 for r in recs]
        size = max(limit, 1)
        # the engine's part of the edge probe first: a control may delete
        # the engine's weights at the reference's first call
        with jax.default_device(dev):
            probe = serve_probes(env, kept[0]["driver"],
                                 kept[0]["page_specs"])
        sample["probe"] = probe
        chosen = _conv.draw_sample(lengths, env["seed"], budget)
        order = [[chosen[j] for j in some] for some in
                 _conv.pack_longest_first([lengths[i] for i in chosen], size)]
        sample["gaps"] = reference_gaps(model, params, recs, config, size,
                                        dev, order)
        if "failed" not in probe:
            sample["edge"], sample["probe_err"], per_row = edge_shares(
                model, params, config, probe, size, dev)
            if env.get("keep_dir"):     # every row's numbers, to read by hand
                import json
                import os
                os.makedirs(env["keep_dir"], exist_ok=True)
                with open(os.path.join(
                        env["keep_dir"], f"{env['cell']['name']}."
                        f"{env['seed']}.edge.json"), "w") as f:
                    json.dump({k: np.asarray(v).tolist()
                               for k, v in per_row.items()}, f)
        sample.update(
            total=len(recs), positions_total=sum(lengths),
            positions=sum(lengths[i] for i in chosen),
            longest_prompt=max((len(recs[i].prompt) for i in chosen),
                               default=0))
        # serve_closed takes a row a request: the unchecked ones get one
        # zero each, and the statistics are made again below from the
        # checked ones alone.
        return [sample["gaps"].get(i, np.zeros(1)) for i in range(len(recs))]

    real, server.DynamicBatchingDriver = server.DynamicBatchingDriver, Driver
    _closed.REF_BATCH = 1 << 30         # one call: the sample is drawn there
    _closed._reference_gaps = gaps_of_sample
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
        reference_longest_prompt=sample.get("longest_prompt", 0),
        reference_tokens=int(sum(len(g) for g in gaps)),
        reference_worst_gap=float(checked.max()),
        reference_mean_gap=float(checked.mean()),
        reference_not_argmax_share=float((checked > 0).mean()))
    say(f"perfbench: checked {len(gaps)} of {sample.get('total', 0)} "
        f"requests (longest prompt {notes['reference_longest_prompt']}), "
        f"{notes['reference_positions']} of "
        f"{notes['reference_positions_of']} positions, "
        f"{notes['reference_tokens']} emitted tokens, drawn from the seed "
        f"after the window: mean gap {notes['reference_mean_gap']:.5f} "
        f"(limit {MEAN_TOL})")
    if not notes["reference_mean_gap"] <= MEAN_TOL:
        problems.append(
            f"the emitted tokens' reference logits lie "
            f"{notes['reference_mean_gap']:.4f} below the maximum on "
            f"average (> {MEAN_TOL})")

    # ---- the window's edge ------------------------------------------------
    probe = sample.get("probe") or {"failed": "no probe was served"}
    edge = sample.get("edge")
    if "failed" in probe:
        problems.append(probe["failed"])
    else:
        say(f"perfbench: {len(probe['lengths'])} probes (prompts "
            f"{probe['lengths']}, {PROBE_DECODED} tokens each): the engine's "
            f"error holds {edge[0]:+.4f} of a window one key shorter's "
            f"difference and {edge[1]:+.4f} of one key longer's (limit "
            f"{EDGE_TOL}); its largest logit error {sample['probe_err']:.4f}")
        if not max(abs(e) for e in edge) <= EDGE_TOL:
            problems.append(
                f"the engine's logits lie {edge[0]:+.3f} / {edge[1]:+.3f} of "
                f"the way to a window one key shorter / longer (> "
                f"{EDGE_TOL}): its window is not "
                f"{config['sliding_window']}")
    notes.update(edge_probes=len(probe.get("lengths", [])),
                 edge_share_shorter=None if edge is None else edge[0],
                 edge_share_longer=None if edge is None else edge[1],
                 edge_logit_error=sample.get("probe_err"))

    # ---- the cache and the counters ---------------------------------------
    stats = run.get("engine_stats") or {}
    facts = kept[0] if kept else {}
    problems += window_problems(stats, config, model, facts,
                                facts.get("prefill_chunk", 0))
    moe = stats.get("moe") or {}
    lead = config["mlp_layer_types"].count("dense")
    problems += _conv.counter_problems(moe, dict(
        config, num_dense_layers=lead))
    run["correct"] = not problems
    window = stats.get("window") or {}
    notes.update(
        window={k: window.get(k, 0) for k in (
            "decode_rounds", "rows_walked", "rows_full_walk", "bytes_held",
            "tokens_in_flight", "blocks_taken", "blocks_given_back",
            "blocks_held", "max_blocks_slot", "peak_blocks_held",
            "num_blocks")},
        prefill=stats.get("prefill"),
        moe={k: moe.get(k, 0) for k in (
            "decode_rounds", "tokens", "assignments", "expert_pairs_touched",
            "expert_pairs_possible", "here_max_rows")})
    return run
