"""``"runner": "serve_closed_eva"``: ``serve_closed``'s loop, unchanged, for
a model whose attention is EVA (an exact window, and behind it one pooled
key/value row for every chunk of older positions), with
``serve_closed_rows``'s reference pass: every completed request against the
float32 reference, one request a pass, padded to a whole number of windows,
the head over the answer's positions only (a multiple of
``serve_closed_rows.ANSWER_STEP``). The windows a pass is padded to are 1,
2, 4 or 8 (``window_size`` x a power of two): four shapes at
``max_total_len`` 16384, not eight, because a shape costs ~13 s to compile
on an empty cache and a whole run has 360 s (PERF.md, PR 34: the first run
took 121 s over its 23 requests, the second 31 s). What is compared and its
limit are ``serve_closed``'s: every emitted token, ``LOGIT_TOL``.

Two more things are held to the configuration, as ``serve_closed_state``
holds a recurrent state's size:

**The cache shrinks.** The engine's own ``stats_snapshot()["eva"]
["max_blocks_slot"]``, the most blocks one slot has held, is at most
``window_size / 16 + (window_size / chunk_size / 16) x
ceil(max_total_len / window_size)`` (192 at the published sizes): the open
window, and eight blocks of summaries for every window the longest request
can close or fill. A cache that keeps its closed windows passes the logit
check, since those rows are masked, and is a full-attention deployment's
memory (1024 blocks a slot at 16384 positions).

**A summary row is a cached row.** Everything the engine caches, exact rows
and chunk summaries alike, lies in the pool's blocks in the stated type:
``pool_bytes_total`` is ``num_blocks`` blocks of ``block_size`` rows of
``kv_bytes_per_token`` and nothing beside them (``serve_closed`` holds one
block's bytes; summaries kept in another array, or another type, would show
here).

A program without such counters (or a model without EVA attention) is not
correct here: this runner is for cells whose shrinking cache is part of the
deployment.
"""

from __future__ import annotations

from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from perfbench import manifest

_closed = manifest.load_module("cells", "serve_closed")
_rows = manifest.load_module("cells", "serve_closed_rows")
# chunk 16 is a block, 16 chunks a window fill one block of summaries: a
# rehearsal's requests close up to three windows of 256
REHEARSAL = {"serve": {"max_batch": 4, "max_seq_len": 1024,
                       "num_blocks": 80}}
BLOCK = 16      # rows a block of the engine's pool holds (its default)


def blocks_slot_limit(config: dict, max_total_len: int) -> int:
    window, chunk = config["window_size"], config["chunk_size"]
    return (window // BLOCK + window // chunk // BLOCK
            * -(-max_total_len // window))


def _reference_gaps(model, params, recs, config: dict, limit: int,
                    dev) -> List[np.ndarray]:
    """``serve_closed_rows._reference_gaps`` with its padding in windows:
    how far below the reference's maximum logit each emitted token lies.
    Position P-1+i predicts answer token i."""
    window, step = config["window_size"], _rows.ANSWER_STEP
    gaps = []
    with jax.default_device(dev):
        for r in recs:
            seq = np.concatenate([r.prompt, np.asarray(r.toks[:-1], np.int32)])
            windows = 1
            while windows * window < len(seq):
                windows *= 2
            padded = windows * window
            rows = min(padded, -(-r.n // step) * step)
            first = min(len(r.prompt) - 1, padded - rows)
            tokens = np.zeros((1, padded), np.int32)
            tokens[0, :len(seq)] = seq
            positions = np.arange(padded, dtype=np.int32)[None] \
                % config["max_position_embeddings"]
            lg = model.reference_logits(
                params, config, jnp.asarray(tokens),
                jnp.zeros(tokens.shape, jnp.int32), jnp.asarray(positions),
                rows=(first, rows))
            # on the host: a slice by a request's own lengths would be a
            # program of its own a request
            skip = len(r.prompt) - 1 - first
            lg = np.asarray(lg)[0, skip:skip + r.n, :config["vocab_size"]]
            gaps.append(lg.max(axis=-1) - lg[np.arange(r.n), r.toks])
    return gaps


def run_cell(env) -> dict:
    config, sv = env["config"], env["config"]["serve"]
    _rows._reference_gaps = _reference_gaps
    run = _rows.run_cell(env)       # one request a pass, the spans' stats
    problems = run["problems"]
    eva = (run.get("engine_stats") or {}).get("eva")
    if not eva:
        problems.append("the engine reports no EVA cache "
                        "(stats_snapshot()['eva'])")
    else:
        scale = sv["max_seq_len"] / env["traffic"]["max_total_len"] \
            if env["rehearsal"] else 1.0
        limit = blocks_slot_limit(
            config, int(round(env["traffic"]["max_total_len"] * scale)))
        if not 0 < eva["max_blocks_slot"] <= limit:
            problems.append(
                f"a slot held {eva['max_blocks_slot']} blocks where windows "
                f"that are freed as they close leave at most {limit}")
        run["notes"].update(
            eva_max_blocks_slot=eva["max_blocks_slot"],
            eva_windows_closed=eva["windows_closed"],
            eva_blocks_freed=eva["blocks_freed"],
            eva_summary_rows_written=eva["summary_rows_written"],
            eva_rows_walked=eva["rows_walked"],
            eva_rows_full_attention=eva["rows_full_attention"])
    pool = (run.get("engine_stats") or {}).get("pool", {})
    stated = (sv["num_blocks"] * BLOCK
              * env["model"].kv_bytes_per_token(config, sv["kv_cache_dtype"]))
    if pool.get("pool_bytes_total") != stated:
        problems.append(
            f"the cache takes {pool.get('pool_bytes_total')} B where "
            f"{sv['num_blocks']} blocks of {BLOCK} rows in "
            f"{sv['kv_cache_dtype']}, chunk summaries among them, take "
            f"{stated}")
    run["correct"] = not problems
    return run
