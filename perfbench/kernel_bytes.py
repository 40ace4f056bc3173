"""The bytes a named kernel has to move, from shapes: the numerators of the
``*_roofline_pct`` metrics."""

from __future__ import annotations


def paged_latent_read_bytes(config: dict, kv_tokens: float,
                            itemsize: int = 2) -> float:
    """What ``paged_decode_latent*`` must read in decode rounds whose
    running requests hold `kv_tokens` cached tokens in all: every cached
    row of every layer once, the latent and the roped key (512 + 64 columns
    for DeepSeek-V2), in the cache's type. Unpadded rows and nothing else
    (no page table, no query, no re-read), so a kernel cannot do with
    less and the share cannot pass 100%."""
    return (kv_tokens * config["num_layers"]
            * (config["kv_lora_rank"] + config["qk_rope_head_dim"])
            * itemsize)
