"""The bytes and operations a Granite 4.0-H hybrid's serving steps have to
move, from shapes: the numerators of the rag cell's ``*_roofline_pct``
metrics, and the sub-scope time that two of them divide by. Kept with the
benchmark so that the yardstick does not move when the program does."""

from __future__ import annotations

import dataclasses

ITEMSIZE = {"bfloat16": 2, "float32": 4}


def _inner(config: dict) -> int:
    return config["mamba_expand"] * config["hidden_size"]


def _mamba_layers(config: dict) -> int:
    return config["layer_types"].count("mamba")


def ssd_update_bytes(config: dict, rows: float) -> float:
    """What the state's decode kernel must move in decode rounds that advance
    `rows` running sequences in all: each row's matrix states of each
    Mamba-2 layer (``mamba_d_state x E`` elements in the state's type) read
    once and written once, and nothing else (no dt, x, B, C, A, D, no y): a
    kernel cannot do with less whatever implements it, so the share cannot
    pass 100%."""
    return (rows * _mamba_layers(config) * 2 * config["mamba_d_state"]
            * _inner(config) * ITEMSIZE[config["serve"]["state_dtype"]])


def ssd_chunk_flops(config: dict, positions: float) -> float:
    """Matmul operations of the Mamba-2 layers' chunked scans over
    `positions` positions of prefill calls (padding included: a call
    computes its whole width), forward: a position's Q scores of N and Q x P
    a head inside its chunk, N x E into the chunk's state and N x E out of
    the one that came in; 2 operations a multiply-add."""
    q, n, e = (config["mamba_chunk_size"], config["mamba_d_state"],
               _inner(config))
    return positions * _mamba_layers(config) * 2.0 * (q * n + q * e
                                                      + 2 * n * e)


def ssd_chunk_bytes(config: dict, calls: float, width: float) -> float:
    """What those scans must move in `calls` prefill calls of `width`
    positions: a layer reads x, B and C (E + 2N columns a position, in the
    weights' type) and dt (a float32 a head), writes y (E columns, the
    weights' type), and reads and writes the call's one state (N x E in the
    state's type). Nothing a chunk keeps to itself (scores, decays) counts:
    an implementation may never write them."""
    e, n = _inner(config), config["mamba_d_state"]
    sv = config["serve"]
    w, st = ITEMSIZE[sv["params_dtype"]], ITEMSIZE[sv["state_dtype"]]
    position = (2 * e + 2 * n) * w + config["mamba_n_heads"] * 4
    return calls * _mamba_layers(config) * (width * position
                                            + 2 * n * e * st)


def moe_stream_bytes(config: dict, rounds: float,
                     touched_share: float) -> float:
    """What the HELD experts must stream in `rounds` decode rounds that
    touch `touched_share` (0..1) of their (layer, expert) pairs: a touched
    expert's three matrices (3 x hidden x intermediate_size) once a round,
    in the weights' type. No router, no shared expert, no activations: the
    experts cannot do with less, so the share cannot pass 100%."""
    expert = (3 * config["hidden_size"] * config["intermediate_size"]
              * ITEMSIZE[config["serve"]["params_dtype"]])
    return (rounds * touched_share * len(config["layer_types"])
            * config["num_local_experts"] * expert)


def sub_s(run, kind: str, part: str, sub: str) -> float:
    """Device seconds of the window in `kind` modules' operations that the
    program wrote under sub-scope `sub` of part `part` (``Scoped.sub`` of
    its scope maps): ``scope_time``'s join over maps cut down to those
    instructions. 0.0 on a program that names no such sub-scope."""
    from perfbench import scope_time
    summary = run.get("device_summary")
    if not summary:
        return 0.0
    maps = run["scope_maps"] if "scope_maps" in run \
        else scope_time._program_maps()
    cut = [dataclasses.replace(m, instructions={
        name: ins for name, ins in m.instructions.items()
        if ins.part == part and getattr(ins, "sub", "") == sub})
        for m in maps if m.kind == kind]
    if not any(m.instructions for m in cut):
        return 0.0
    seconds = scope_time.join(run["trace"], summary["window"], cut)["seconds"]
    return sum(s for (k, p, _), s in seconds.items()
               if k == kind and p == part)
