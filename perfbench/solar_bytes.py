"""The bytes a Solar Open 2 hybrid's serving steps have to move, from
shapes: the numerator of the doc cell's ``kda_update_roofline_pct.doc``
(``nemotron_bytes``' twin for a Kimi-delta-attention state). Kept with the
benchmark so that the yardstick does not move when the program does."""

from __future__ import annotations

ITEMSIZE = {"bfloat16": 2, "float32": 4}


def kda_layers(config: dict) -> int:
    """The layers that are run and are no GQA layer."""
    return config["num_hidden_layers"] - len(config["gqa_layers"])


def kda_update_bytes(config: dict, rows: float) -> float:
    """What the state's decode kernel must move in decode rounds that
    advance `rows` running sequences in all: each row's matrix states of
    each KDA layer (``heads x K x V`` elements in the state's type) read
    once and written once, and the row's vectors: q, k and the decay over
    the key channels, v and the output over the value columns, and beta a
    head, float32, once each. A kernel cannot do with less whatever
    implements it, so the share cannot pass 100%."""
    la = config["linear_attn_config"]
    heads, d = la["num_heads"], la["head_dim"]
    state = 2 * heads * d * d * ITEMSIZE[config["serve"]["state_dtype"]]
    vectors = (5 * heads * d + heads) * 4
    return rows * kda_layers(config) * (state + vectors)
