"""The bytes LongCat-Flash's kernels have to move, from shapes: the
numerators of the agent cell's ``*_roofline_pct`` metrics."""

from __future__ import annotations


def paged_latent_read_bytes(config: dict, kv_tokens: float,
                            itemsize: int = 2) -> float:
    """What ``paged_decode_latent*`` must read in decode rounds whose
    running requests hold `kv_tokens` cached tokens in all
    (``mta.engine.decode_round``'s attribute: the slots' context lengths):
    the kernel walks them once a PLANE, and a shortcut-connected double
    layer owns two planes, so every cached row of 2 x num_layers planes
    once, the scaled latent and the roped key (512 + 64 columns), in the
    cache's type. ``kernel_bytes.paged_latent_read_bytes`` counts one plane
    a layer and would read half. Unpadded rows and nothing else (no page
    table, no query, no kv_up columns, no re-read), so a kernel cannot do
    with less and the share cannot pass 100%."""
    return (kv_tokens * 2 * config["num_layers"]
            * (config["kv_lora_rank"] + config["qk_rope_head_dim"])
            * itemsize)
