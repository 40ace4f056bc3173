"""The bytes the ``ssm_update`` kernel has to move, from shapes: the
numerator of ``ssm_update_roofline_pct.*``."""

from __future__ import annotations


def ssm_update_bytes(config: dict, rows: float) -> float:
    """What ``ssm_update*`` must move in decode rounds that advance `rows`
    running sequences in all: each row's h ``[mamba_d_state, E]`` float32 of
    each state-space layer read once and written once, and nothing else
    (no dt, u, B, C, A, D, no y): a kernel cannot do with less, so the
    share cannot pass 100%. The layers are those the configuration's
    pattern makes state-space layers."""
    depth = config.get("num_layers", config["num_hidden_layers"])
    ssm_layers = sum(
        i % config["attn_layer_period"] != config["attn_layer_offset"]
        for i in range(depth))
    e = config["mamba_expand"] * config["hidden_size"]
    return rows * ssm_layers * 2 * config["mamba_d_state"] * e * 4
