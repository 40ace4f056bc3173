"""The bytes a Laguna model's decode round has to move, from shapes: the
numerators of the code cell's ``*_roofline_pct`` metrics. Nothing here knows
what implements a kernel: lengths, window, planes and head counts in, bytes
out."""

from __future__ import annotations

ITEMSIZE = {"bfloat16": 2, "float32": 4}
BLOCK_ROWS = 16         # rows of a block of the paged pools


def _row_bytes(config: dict) -> int:
    """Keys and values of one cached row of one plane."""
    return (2 * config["num_key_value_heads"] * config["head_dim"]
            * ITEMSIZE[config["serve"]["kv_cache_dtype"]])


def paged_decode_read_bytes(config: dict, kv_blocks: float) -> float:
    """What the FULL layers' ``paged_decode*`` must read in decode rounds
    whose running slots hold `kv_blocks` blocks in all (the rounds'
    ``kv_blocks`` span attribute, over the full planes' table): every row
    of those blocks in each full layer's plane once, keys and values of the
    8 key/value heads of 128 (a whole lane row: no padding). A slot's last
    block counts whole (at most 15 rows a slot too many: the kernel's tile
    is a block). No table, no query (48 heads x 128 a slot), no re-read: a
    kernel cannot do with less, so the share cannot pass 100%."""
    planes = config["layer_types"].count("full_attention")
    return kv_blocks * BLOCK_ROWS * planes * _row_bytes(config)


def paged_window_read_bytes(config: dict, window_blocks: float) -> float:
    """The same of the SLIDING layers' ``paged_window_decode*``, whose walk
    of a slot starts at the block that holds position length -
    ``sliding_window`` + 1: `window_blocks` (the rounds' ``window_blocks``
    span attribute) are the blocks from there to the slot's last, a plane,
    at most sliding_window / 16 + 1 a slot whatever its length."""
    planes = config["layer_types"].count("sliding_attention")
    return window_blocks * BLOCK_ROWS * planes * _row_bytes(config)


def moe_stream_bytes(config: dict, rounds: float,
                     touched_share: float) -> float:
    """What the sparse layers must stream in `rounds` decode rounds that
    touch `touched_share` (0..1) of their (layer, expert) pairs: a touched
    expert's three matrices (3 x hidden x moe_intermediate_size) once a
    round, and every round the shared expert's three and the router's one,
    in the weights' type. No activations, no second read between the two
    grouped GEMMs: the layers cannot do with less, so the share cannot pass
    100%."""
    sparse = config["mlp_layer_types"].count("sparse")
    item = ITEMSIZE[config["serve"]["params_dtype"]]
    h = config["hidden_size"]
    expert = 3 * h * config["moe_intermediate_size"] * item
    always = (3 * h * config["shared_expert_intermediate_size"]
              + h * config["num_experts"]) * item
    return rounds * sparse * (
        touched_share * config["num_experts"] * expert + always)
