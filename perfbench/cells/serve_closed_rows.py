"""``"runner": "serve_closed_rows"``: ``serve_closed``'s loop, unchanged, for
a model whose vocabulary is too wide for that runner's reference pass.

``serve_closed._reference_gaps`` asks the reference for ``[8, padded
max_total_len, V]`` float32 logits: 13.4 GB at 8 x 4096 x 102400. Here every
completed request is still checked against the float32 reference, one
request a pass, padded to the next multiple of 1024 positions (four shapes
at ``max_total_len`` 4096, not one shape of 4096 for a mean request of
1,400), and the head runs over the answer's positions only (a multiple of
256 of them): at most ``2048 x 102400 x 4`` B = 0.84 GB of logits. The limit
on an emitted token's gap stays ``serve_closed.LOGIT_TOL``.

A traced run also keeps what ``trace_reduce.load_xplane`` drops and a
per-layer reader of this cell needs: the attributes of the program's spans
(``perfbench/xplane_stats.py``).
"""

from __future__ import annotations

from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from perfbench import manifest, xplane_stats

_closed = manifest.load_module("cells", "serve_closed")
REHEARSAL = _closed.REHEARSAL
SEQ_STEP, ANSWER_STEP = 1024, 256


def _reference_gaps(model, params, recs, config: dict, limit: int,
                    dev) -> List[np.ndarray]:
    """``serve_closed._reference_gaps`` for one request a pass: how far
    below the reference's maximum logit each emitted token lies. Position
    P-1+i predicts answer token i."""
    gaps = []
    with jax.default_device(dev):
        for r in recs:
            seq = np.concatenate([r.prompt, np.asarray(r.toks[:-1], np.int32)])
            padded = -(-len(seq) // SEQ_STEP) * SEQ_STEP
            rows = -(-r.n // ANSWER_STEP) * ANSWER_STEP
            first = min(len(r.prompt) - 1, padded - rows)
            tokens = np.zeros((1, padded), np.int32)
            tokens[0, :len(seq)] = seq
            positions = np.arange(padded, dtype=np.int32)[None] \
                % config["max_position_embeddings"]
            lg = model.reference_logits(
                params, config, jnp.asarray(tokens),
                jnp.zeros(tokens.shape, jnp.int32), jnp.asarray(positions),
                rows=(first, rows))
            skip = len(r.prompt) - 1 - first
            lg = lg[0, skip:skip + r.n, :config["vocab_size"]]
            picked = lg[jnp.arange(r.n), jnp.asarray(r.toks)]
            gaps.append(np.asarray(jnp.max(lg, axis=-1) - picked))
    return gaps


def run_cell(env) -> dict:
    _closed.REF_BATCH = 1
    _closed._reference_gaps = _reference_gaps
    run = _closed.run_cell(env)
    if env["trace_dir"]:
        run["xplane_stats"] = xplane_stats.load(env["trace_dir"])
    return run
