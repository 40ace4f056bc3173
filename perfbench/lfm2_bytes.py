"""The bytes an LFM2 mixture-of-experts model's decode round has to move,
from shapes: the numerators of the assist cell's ``*_roofline_pct``
metrics."""

from __future__ import annotations

ITEMSIZE = {"bfloat16": 2, "float32": 4}
BLOCK_ROWS = 16         # rows of a block of the paged pools


def _head_dim(config: dict) -> int:
    return config["hidden_size"] // config["num_attention_heads"]


def paged_decode_read_bytes(config: dict, kv_blocks: float) -> float:
    """What ``paged_decode*`` must read in decode rounds whose running
    slots hold `kv_blocks` blocks in all (the rounds' ``kv_blocks`` span
    attribute, over the attention planes' one table): every row of those
    blocks in each attention layer's plane once, keys and values of the
    ``num_key_value_heads`` heads, in the cache's type. It counts the
    ``head_dim`` (64) columns a head that HOLD something: on the device a
    row pads them to 128 lanes, so a kernel that reads whole rows moves
    twice this, and that padding is part of what keeps the kernel off this
    roof. A slot's last block counts whole (at most 15 rows a slot too
    many: the kernel's tile is a block). No table, no query, no re-read: a
    kernel cannot do with less, so the share cannot pass 100%."""
    attention_layers = config["layer_types"].count("full_attention")
    row = (2 * config["num_key_value_heads"] * _head_dim(config)
           * ITEMSIZE[config["serve"]["kv_cache_dtype"]])
    return kv_blocks * BLOCK_ROWS * attention_layers * row


def moe_stream_bytes(config: dict, rounds: float,
                     touched_share: float) -> float:
    """What the MoE layers' experts must stream in `rounds` decode rounds
    that touch `touched_share` (0..1) of their (layer, expert) pairs: a
    touched expert's three matrices (3 x hidden x moe_intermediate_size)
    once a round, in the weights' type. No router, no activations, no
    second read between the two grouped GEMMs: the experts cannot do with
    less, so the share cannot pass 100%."""
    moe_layers = config["num_hidden_layers"] - config["num_dense_layers"]
    expert = (3 * config["hidden_size"] * config["moe_intermediate_size"]
              * ITEMSIZE[config["serve"]["params_dtype"]])
    return rounds * touched_share * moe_layers * config["num_experts"] * expert
