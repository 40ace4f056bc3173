"""The benchmark's own count of the operations of one chip's share of a
Mellum-2 training step (``models/mellum.py``), kept with the benchmark so
that the yardstick does not move when the program does.

``flops_per_token`` is ``mfu``'s yardstick, by ``perfbench/flops.py``'s
convention: 3 x the forward matrix multiplications, recomputed operations
not counted, a full-attention layer counted at S keys a query (so packed
documents over-count it, as they do in the dense cells) and a sliding layer
at min(S, window). Of a token's ``num_experts_per_tok`` picks it counts the
share that lands on the experts held (held / router width: what seeded
weights send here by chance; the cell's ``expert_rows_here_share`` reads
what a run sent), the router over its whole width, and the head over the
rows of the vocabulary held.

The two kernel yardsticks count what the step's masks and routing allowed,
not a convention: ``window_pair_flops`` from the (query, key) pairs of the
sliding layers that the traced batches' ``segment_ids`` let through, and
``expert_gemm_flops`` from the picks that landed here (``assignments_here``
of ``mta.train.sync``).
"""

from __future__ import annotations

import numpy as np

FULL, SLIDING = "full_attention", "sliding_attention"
PASSES = 3                      # forward, and twice its operations backward


def _attention_proj(config: dict) -> float:
    h, d = config["hidden_size"], config["head_dim"]
    nq, nkv = config["num_attention_heads"], config["num_key_value_heads"]
    return 2 * h * nq * d + 2 * h * 2 * nkv * d + 2 * nq * d * h


def _pair_flops(config: dict) -> float:
    """Operations of one (query, key) pair in one pass over every head:
    q.k and p.v, 2 x head_dim each."""
    return 2 * 2 * config["head_dim"] * config["num_attention_heads"]


def expert_flops(config: dict) -> float:
    """One pick's SwiGLU expert, forward: 3 x 2 x hidden x width."""
    return 3 * 2 * config["hidden_size"] * config["moe_intermediate_size"]


def flops_per_token(config: dict, seq_len: int) -> float:
    kinds = config["layer_types"]
    h = config["hidden_size"]
    held_share = config["num_experts"] / config["router_width"]
    keys = {FULL: seq_len, SLIDING: min(seq_len, config["sliding_window"])}
    attention = sum(_attention_proj(config) + _pair_flops(config) * keys[k]
                    for k in kinds)
    moe = len(kinds) * (
        config["num_experts_per_tok"] * held_share * expert_flops(config)
        + 2 * h * config["router_width"])
    return PASSES * (attention + moe + 2 * h * config["vocab_size"])


def window_pairs(segment_ids: np.ndarray, window: int) -> int:
    """The (query, key) pairs a sliding layer's mask allows in rows of
    packed documents, segment_ids [rows, S]: a document of n tokens in a
    row gives the sum over its positions i of min(i + 1, window)."""
    total = 0
    for row in np.asarray(segment_ids):
        cuts = np.flatnonzero(np.diff(row)) + 1
        for n in np.diff(np.concatenate([[0], cuts, [len(row)]])):
            short = min(int(n), window)
            total += short * (short + 1) // 2 + (int(n) - short) * window
    return total


def window_pair_flops(config: dict, pairs: float) -> float:
    """All passes of the sliding layers' attention arithmetic over `pairs`
    allowed pairs a layer (``window_pairs``), the recomputed forward not
    counted."""
    return (PASSES * config["layer_types"].count(SLIDING)
            * _pair_flops(config) * pairs)


def expert_gemm_flops(config: dict, assignments_here: float) -> float:
    """All passes of the grouped products over the picks that landed on a
    held expert (summed over layers, as the counter is)."""
    return PASSES * expert_flops(config) * assignments_here
