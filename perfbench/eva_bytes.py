"""The bytes the kernels of an EVA model's decode round have to move, from
shapes: the numerators of ``paged_decode_roofline_pct.bytegen`` and
``eva_summary_roofline_pct.bytegen``."""

from __future__ import annotations

ITEMSIZE = {"bfloat16": 2, "float32": 4}


def _row_bytes(config: dict) -> int:
    """Keys and values of one cached row of ONE layer, in the cache's
    type."""
    head_dim = config["hidden_size"] // config["num_attention_heads"]
    return (2 * config["num_key_value_heads"] * head_dim
            * ITEMSIZE[config["serve"]["kv_cache_dtype"]])


def _depth(config: dict) -> int:
    return config.get("num_layers", config["num_hidden_layers"])


def paged_decode_read_bytes(config: dict, kv_rows: float) -> float:
    """What ``paged_decode*`` must read in decode rounds whose running
    slots' tables hold `kv_rows` rows in all (the rounds' ``kv_rows`` span
    attribute: R(T) a slot, chunk summaries and the open window's rows):
    every such row of every layer once, keys and values, in the cache's
    type. Nothing else (no table, no query, no re-read, no padding of a
    slot's last block), so a kernel cannot do with less and the share
    cannot pass 100%."""
    return kv_rows * _depth(config) * _row_bytes(config)


def eva_summary_bytes(config: dict, chunks: float) -> float:
    """What ``eva_summary*`` must move to pool `chunks` chunks (a chunk
    counted once, whatever the layers): in every layer ``chunk_size`` rows
    read and one written, keys and values. phi and mu (16 KB a layer) and
    the block ids are left out, so the share cannot pass 100%."""
    return (chunks * _depth(config) * (config["chunk_size"] + 1)
            * _row_bytes(config))
