"""The bytes and operations a Nemotron-H hybrid's serving steps have to
move, from shapes: the numerators of the reason cell's ``*_roofline_pct``
metrics (``granite_bytes``' twins for a pattern of single-sublayer layers:
B and C a group, a 2 MiB plane, two-matrix experts, 2 key/value heads).
Kept with the benchmark so that the yardstick does not move when the
program does."""

from __future__ import annotations

from perfbench.granite_bytes import sub_s  # noqa: F401 (the readers' join)

ITEMSIZE = {"bfloat16": 2, "float32": 4}
BLOCK_ROWS = 16         # rows of a block of the paged pools


def _inner(config: dict) -> int:
    return config["mamba_num_heads"] * config["mamba_head_dim"]


def _layers(config: dict, kind: str) -> int:
    return config["hybrid_override_pattern"].count(kind)


def ssd_update_bytes(config: dict, rows: float) -> float:
    """What the state's decode kernel must move in decode rounds that advance
    `rows` running sequences in all: each row's matrix states of each
    Mamba-2 layer (``ssm_state_size x E`` elements in the state's type) read
    once and written once, and nothing else (no dt, x, B, C, A, D, no y): a
    kernel cannot do with less whatever implements it, so the share cannot
    pass 100%."""
    return (rows * _layers(config, "M") * 2 * config["ssm_state_size"]
            * _inner(config) * ITEMSIZE[config["serve"]["state_dtype"]])


def ssd_chunk_flops(config: dict, positions: float) -> float:
    """Matmul operations of the Mamba-2 layers' chunked scans over
    `positions` positions of prefill calls (padding included: a call
    computes its whole width), forward: a position's Q scores of N in each
    of the G groups and Q x P a head inside its chunk, N x E into the
    chunk's state and N x E out of the one that came in; 2 operations a
    multiply-add."""
    q, n, e = (config["chunk_size"], config["ssm_state_size"],
               _inner(config))
    return positions * _layers(config, "M") * 2.0 * (
        q * n * config["n_groups"] + q * e + 2 * n * e)


def ssd_chunk_bytes(config: dict, calls: float, width: float) -> float:
    """What those scans must move in `calls` prefill calls of `width`
    positions: a layer reads x, B and C (E + 2GN columns a position, in the
    weights' type) and dt (a float32 a head), writes y (E columns, the
    weights' type), and reads and writes the call's one state (N x E in the
    state's type). Nothing a chunk keeps to itself (scores, decays) counts:
    an implementation may never write them."""
    e, n = _inner(config), config["ssm_state_size"]
    sv = config["serve"]
    w, st = ITEMSIZE[sv["params_dtype"]], ITEMSIZE[sv["state_dtype"]]
    position = ((2 * e + 2 * config["n_groups"] * n) * w
                + config["mamba_num_heads"] * 4)
    return calls * _layers(config, "M") * (width * position
                                           + 2 * n * e * st)


def moe_stream_bytes(config: dict, rounds: float,
                     touched_share: float) -> float:
    """What the expert layers must stream in `rounds` decode rounds that
    touch `touched_share` (0..1) of their (layer, HELD expert) pairs: a
    touched expert's two matrices (2 x hidden x moe_intermediate_size: no
    gate) once a round, and every round the shared expert's two, in the
    weights' type, and the router's one in float32. No activations, no
    second read between the two grouped GEMMs: the layers cannot do with
    less, so the share cannot pass 100%."""
    item = ITEMSIZE[config["serve"]["params_dtype"]]
    h = config["hidden_size"]
    expert = 2 * h * config["moe_intermediate_size"] * item
    routers = config.get("published", config)["n_routed_experts"]
    always = (2 * h * config["moe_shared_expert_intermediate_size"] * item
              + h * routers * 4)
    return rounds * _layers(config, "E") * (
        touched_share * config["n_routed_experts"] * expert + always)


def paged_decode_read_bytes(config: dict, kv_blocks: float) -> float:
    """What ``paged_decode*`` must read in decode rounds whose running
    slots hold `kv_blocks` blocks in all (the rounds' ``kv_blocks`` span
    attribute): every row of those blocks in each attention layer's plane
    once, keys and values of the ``num_key_value_heads`` heads of
    ``head_dim``, in the cache's type. A slot's last block counts whole (at
    most 15 rows a slot too many: the kernel's tile is a block). No table,
    no query, no re-read: a kernel cannot do with less, so the share cannot
    pass 100%."""
    row = (2 * config["num_key_value_heads"] * config["head_dim"]
           * ITEMSIZE[config["serve"]["kv_cache_dtype"]])
    return kv_blocks * BLOCK_ROWS * _layers(config, "*") * row
