"""The benchmark's own count of a dense GPT's operations per token.

A copy of ``megatronapp_tpu/utils/flops.py::flops_per_token`` (dense case)
taken in PR 25, so that the yardstick does not move when the program does;
``tests/test_flops.py`` checks that the two still agree.
"""

from __future__ import annotations


def flops_per_token(model: dict, seq_len: int) -> float:
    """Forward + backward operations per trained token (3 x the forward
    matrix multiplications; recomputed operations do not count).
    `model` is a configuration file's top level."""
    h = model["hidden_size"]
    d = model["head_dim"]
    nq = model["num_attention_heads"]
    nkv = model.get("num_query_groups", nq)
    f = model["ffn_hidden_size"]
    proj = 2 * h * (nq * d) + 2 * h * (2 * nkv * d) + 2 * (nq * d) * h
    attn = 2 * 2 * seq_len * nq * d
    mlp = 2 * 2 * h * f
    logits = 2 * h * model["padded_vocab_size"]
    return 3.0 * (model["num_layers"] * (proj + attn + mlp) + logits)
