"""Ragged paged-attention decode entry points (Pallas TPU) + page helpers.

vLLM-style paged KV serving ("Ragged Paged Attention", arXiv 2604.15464,
PAPERS.md): the decode cache lives in a shared block pool shaped
[num_blocks, block_size, Hkv, D]; each slot owns an ordered page table of
block ids, and one query token per active slot gathers K/V through its
table with an online softmax over the blocks it HOLDS, several pages a
step (kernel_gen.emit_paged_kernel: the grid runs over the call's real
steps, not over what the table could hold) — no slot pays for another
slot's length or for the table's width, and admission is per-block
instead of per-S_max row (inference/paged_cache.py is the allocator).

The kernel BODIES live in ops/pallas/kernel_gen.py (ISSUE 11): one
dtype/shard/raggedness-parameterized generator emits the decode and
multi-query variants from a spec — the four hand-written bodies this
module used to carry (decode / multiquery × plain / tp, each × bf16 /
int8) are deleted; the public names below are thin dispatchers over one
layer's pool, kept for the tests and tools that call a kernel alone (the
layer bodies call kernel_gen.paged_attention on the stacked pool). The
emitted kernels are the legacy variants' mathematics folded a tile of
several pages at a time (pinned in tests/test_kernel_gen.py: bitwise to a
replay of the walk, allclose to the frozen legacy bodies).

This module keeps what is NOT kernel-body generation: the jnp parity
oracles, the quantization helper (`quantize_kv_rows` — symmetric
per-(row, kv-head) int8, fused into the engine's write-path jits), the
page write/gather helpers (the steps' appends go through
kernel_gen.paged_append, in place), and the tp eligibility predicate
(`tp_paged_eligible` / `tp_paged_ineligible_reason`).

TP sharding (ISSUE 9): GSPMD cannot partition a pallas_call, so the
tp-mesh serving path places the emitted kernels with a FULL-MANUAL
shard_map over KV heads (kernel_gen._tp_place): q heads and kv heads
slice contiguously together so each shard owns matched GQA groups, the
page table and kv lengths are replicated, and the K/V pools (plus int8
scale pools) shard on their Hkv dim — each device holds 1/tp of the
block pool and does 1/tp of the attention FLOPs/bytes.

Quantized KV (ISSUE 10, `k_scales`/`v_scales`): pools may be stored int8
with a per-(row, kv-head) fp32 scale pool [NB, bs, Hkv] alongside — rows
quantize independently on insert (`quantize_kv_rows`), so CoW copies,
rewind, and stale-row overwrites need no re-scaling. The scale pages
ride the SAME scalar-prefetched page-table indirection as the KV pages
and dequantize in-register; no bf16 pool is ever materialized.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from megatronapp_tpu.ops.pallas.kernel_gen import (  # noqa: F401 (re-export)
    _NEG_INF, _dequant_block, _interpret, paged_attention,
    paged_attention_latent,
)


def quantize_kv_rows(rows: jnp.ndarray, dtype=jnp.int8):
    """Symmetric per-(row, head) quantization of KV rows.

    rows [..., Hkv, D] → (quantized rows [..., Hkv, D], fp32 scales
    [..., Hkv]). Each (token, head) row quantizes independently over D —
    inserts never re-scale already-written rows, so partial blocks,
    copy-on-write copies, and speculative rewinds need no block-level
    bookkeeping. jit-able; fused into the engine's write-path jits.

    dtype selects the storage format (the page pool's dtype — callers
    pass ``pages.dtype`` so the write path follows the pool):
    - int8: round to [-127, 127] with scale = absmax / 127 (the PR-10
      path, bit-identical to before);
    - fp8 (e4m3fn): scale = absmax / 448 and SATURATE-cast — e4m3
      overflow is NaN, not inf, so the clip is load-bearing; the float
      cast rounds natively (no integer rounding step — the "drops the
      scale-pool rounding" half of the fp8 mode)."""
    from megatronapp_tpu.ops.pallas.kernel_gen import quant_qmax_of
    r32 = rows.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(r32), axis=-1)
    qmax = quant_qmax_of(dtype)
    if jnp.dtype(dtype) == jnp.dtype(jnp.int8):
        scales = jnp.maximum(absmax / qmax, 1e-12)
        q = jnp.clip(jnp.round(r32 / scales[..., None]), -qmax, qmax)
    else:
        scales = jnp.maximum(absmax / qmax, 1e-12)
        q = jnp.clip(r32 / scales[..., None], -qmax, qmax)
    return q.astype(dtype), scales.astype(jnp.float32)


# ---------------------------------------------------------------------------
# Public kernel entry points — thin dispatchers over the generator
# ---------------------------------------------------------------------------


def paged_attention_decode(q: jnp.ndarray, k_pages: jnp.ndarray,
                           v_pages: jnp.ndarray, page_table: jnp.ndarray,
                           kv_lens: jnp.ndarray,
                           softmax_scale: Optional[float] = None,
                           k_scales: Optional[jnp.ndarray] = None,
                           v_scales: Optional[jnp.ndarray] = None
                           ) -> jnp.ndarray:
    """One-token-per-slot ragged paged attention.

    q [B, Hq, D]; k_pages/v_pages [num_blocks, block_size, Hkv, D];
    page_table [B, max_blocks_per_seq] int32 (entries beyond a slot's
    allocation may be anything in range — they are masked, not read for
    math); kv_lens [B] int32 valid kv positions per slot (>= 1).
    k_scales/v_scales [num_blocks, block_size, Hkv] fp32: present iff the
    pools are int8 (quantize_kv_rows layout). Returns [B, Hq, D]."""
    return paged_attention(q, k_pages, v_pages, page_table, kv_lens,
                           softmax_scale=softmax_scale,
                           k_scales=k_scales, v_scales=v_scales)


def paged_attention_multiquery(q: jnp.ndarray, k_pages: jnp.ndarray,
                               v_pages: jnp.ndarray,
                               page_table: jnp.ndarray,
                               kv_lens: jnp.ndarray, q_lens: jnp.ndarray,
                               softmax_scale: Optional[float] = None,
                               k_scales: Optional[jnp.ndarray] = None,
                               v_scales: Optional[jnp.ndarray] = None
                               ) -> jnp.ndarray:
    """Ragged multi-query paged attention (speculative verify / chunked
    prefill).

    q [B, S_q, Hq, D] — per-request the first q_lens[b] rows are real
    queries at absolute positions kv_lens[b]-q_lens[b] .. kv_lens[b]-1
    (their K/V must already be written into the pages); the rest are
    padding whose outputs are garbage and must be discarded. kv_lens [B]
    counts ALL valid kv positions including the new tail (>= q_lens >=
    1). At q_len == 1 the emitted body reduces bitwise to the decode
    kernel. Returns [B, S_q, Hq, D]."""
    return paged_attention(q, k_pages, v_pages, page_table, kv_lens,
                           q_lens=q_lens, softmax_scale=softmax_scale,
                           k_scales=k_scales, v_scales=v_scales)


def paged_attention_decode_tp(q: jnp.ndarray, k_pages: jnp.ndarray,
                              v_pages: jnp.ndarray,
                              page_table: jnp.ndarray,
                              kv_lens: jnp.ndarray, mesh,
                              softmax_scale: Optional[float] = None,
                              k_scales: Optional[jnp.ndarray] = None,
                              v_scales: Optional[jnp.ndarray] = None
                              ) -> jnp.ndarray:
    """`paged_attention_decode` head-sharded over the tp axis of `mesh`
    (kernel_gen._tp_place: full-manual shard_map, pools + int8 scale
    pools sharded on Hkv, table/lens replicated). Output is [B, Hq, D]
    head-sharded (callers gather / constrain as needed)."""
    return paged_attention(q, k_pages, v_pages, page_table, kv_lens,
                           softmax_scale=softmax_scale,
                           k_scales=k_scales, v_scales=v_scales,
                           mesh=mesh)


def paged_attention_multiquery_tp(q: jnp.ndarray, k_pages: jnp.ndarray,
                                  v_pages: jnp.ndarray,
                                  page_table: jnp.ndarray,
                                  kv_lens: jnp.ndarray,
                                  q_lens: jnp.ndarray, mesh,
                                  softmax_scale: Optional[float] = None,
                                  k_scales: Optional[jnp.ndarray] = None,
                                  v_scales: Optional[jnp.ndarray] = None
                                  ) -> jnp.ndarray:
    """`paged_attention_multiquery` head-sharded over the tp axis of
    `mesh` (speculative verify / chunked prefill on a tp serving mesh).
    q [B, S_q, Hq, D] sharded on Hq; pools on Hkv (int8 pools: scale
    pools sharded alongside); table/lens/q_lens replicated."""
    return paged_attention(q, k_pages, v_pages, page_table, kv_lens,
                           q_lens=q_lens, softmax_scale=softmax_scale,
                           k_scales=k_scales, v_scales=v_scales,
                           mesh=mesh)


def dequantize_pages(pages: jnp.ndarray, scales: jnp.ndarray
                     ) -> jnp.ndarray:
    """Dense dequant of an int8 pool [..., bs, Hkv, D] with scales
    [..., bs, Hkv] → fp32 (references, prefix-hit gathers, A/B
    baselines — NOT the kernel path, which dequantizes per block)."""
    return pages.astype(jnp.float32) * scales[..., None]


def paged_attention_multiquery_reference(
        q: jnp.ndarray, k_pages: jnp.ndarray, v_pages: jnp.ndarray,
        page_table: jnp.ndarray, kv_lens: jnp.ndarray, q_lens: jnp.ndarray,
        softmax_scale: Optional[float] = None,
        k_scales: Optional[jnp.ndarray] = None,
        v_scales: Optional[jnp.ndarray] = None,
        window: int = 0) -> jnp.ndarray:
    """Pure-jnp oracle for the multi-query kernel (gathers dense,
    masks per-(query, kv) causally; int8 pools dequantize dense; `window`:
    a sliding-window layer's band, a query sees its last `window` keys)."""
    b, s_q, hq, d = q.shape
    nb, bs, hkv, _ = k_pages.shape
    mb = page_table.shape[1]
    group = hq // hkv
    if k_scales is not None:
        k_pages = dequantize_pages(k_pages, k_scales)
        v_pages = dequantize_pages(v_pages, v_scales)
    if softmax_scale is None:
        softmax_scale = 1.0 / (d ** 0.5)
    k = k_pages[page_table].reshape(b, mb * bs, hkv, d)
    v = v_pages[page_table].reshape(b, mb * bs, hkv, d)
    k = jnp.repeat(k, group, axis=2)
    v = jnp.repeat(v, group, axis=2)
    s = jnp.einsum("bqhd,bkhd->bqhk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * softmax_scale
    pos = jnp.arange(mb * bs)
    abs_q = (kv_lens - q_lens)[:, None] + jnp.arange(s_q)[None, :]  # [B,Sq]
    mask = ((pos[None, None, :] <= abs_q[:, :, None])
            & (pos[None, None, :] < kv_lens[:, None, None]))
    if window:
        mask &= abs_q[:, :, None] - pos[None, None, :] < window
    s = jnp.where(mask[:, :, None, :], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bqhk,bkhd->bqhd", p, v.astype(jnp.float32))
    return out.astype(q.dtype)


def paged_attention_reference(q: jnp.ndarray, k_pages: jnp.ndarray,
                              v_pages: jnp.ndarray, page_table: jnp.ndarray,
                              kv_lens: jnp.ndarray,
                              softmax_scale: Optional[float] = None,
                              k_scales: Optional[jnp.ndarray] = None,
                              v_scales: Optional[jnp.ndarray] = None,
                              window: int = 0) -> jnp.ndarray:
    """Pure-jnp oracle with the same signature (gathers dense, masks;
    int8 pools dequantize dense; `window`: a sliding-window layer's band,
    the query at kv_len - 1 sees its last `window` keys)."""
    b, hq, d = q.shape
    nb, bs, hkv, _ = k_pages.shape
    mb = page_table.shape[1]
    group = hq // hkv
    if k_scales is not None:
        k_pages = dequantize_pages(k_pages, k_scales)
        v_pages = dequantize_pages(v_pages, v_scales)
    if softmax_scale is None:
        softmax_scale = 1.0 / (d ** 0.5)
    k = k_pages[page_table].reshape(b, mb * bs, hkv, d)
    v = v_pages[page_table].reshape(b, mb * bs, hkv, d)
    k = jnp.repeat(k, group, axis=2)
    v = jnp.repeat(v, group, axis=2)
    s = jnp.einsum("bhd,bshd->bhs", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * softmax_scale
    pos = jnp.arange(mb * bs)
    mask = pos[None, None, :] < kv_lens[:, None, None]
    if window:
        mask &= pos[None, None, :] >= kv_lens[:, None, None] - window
    s = jnp.where(mask, s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhs,bshd->bhd", p, v.astype(jnp.float32))
    return out.astype(q.dtype)


def dequantize_latent_pages(pages: jnp.ndarray, scales: jnp.ndarray
                            ) -> jnp.ndarray:
    """Dense dequant of a quantized LATENT pool [NB, bs, d] with per-row
    scalar scales [NB, bs] → fp32 (the latent row has no kv-head axis, so
    the scale is one scalar per (block, row) — `quantize_kv_rows` over a
    [..., d] row produces exactly this layout)."""
    return pages.astype(jnp.float32) * scales[..., None]


def paged_attention_latent_reference(
        q_lat: jnp.ndarray, q_pe: jnp.ndarray, lat_pages: jnp.ndarray,
        pe_pages: jnp.ndarray, page_table: jnp.ndarray,
        kv_lens: jnp.ndarray, w_v: jnp.ndarray,
        q_lens: Optional[jnp.ndarray] = None,
        softmax_scale: Optional[float] = None,
        lat_scales: Optional[jnp.ndarray] = None,
        pe_scales: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Pure-jnp oracle for the latent kernel: gathers the latent/pe runs
    DENSE through the page table (the pre-ISSUE-17 `mla_forward` decode
    path: `gather_pages_batched` + `kv_up` re-expansion), masks, and
    applies a plain softmax. Same signature and semantics as
    `paged_attention_latent` — q_lat is already ABSORBED through
    `kv_up`'s k_nope columns (and carries the YaRN mscale² if any), so
    scores are `q_lat·latᵀ + q_pe·peᵀ` and values re-expand dense as
    `lat @ w_v`. Quantized pools dequantize dense (per-row scalar
    scales)."""
    if softmax_scale is None:
        raise ValueError(
            "paged_attention_latent_reference requires softmax_scale — the "
            "MLA scale is 1/sqrt(dqk + dpe), which cannot be derived from "
            "the latent width")
    decode = q_lens is None
    if decode:
        q_lat = q_lat[:, None]
        q_pe = q_pe[:, None]
    b, s_q, nq, klat = q_lat.shape
    bs = lat_pages.shape[1]
    mb = page_table.shape[1]
    dv = w_v.shape[-1]
    if lat_scales is not None:
        lat_pages = dequantize_latent_pages(lat_pages, lat_scales)
        pe_pages = dequantize_latent_pages(pe_pages, pe_scales)
    lat = lat_pages[page_table].reshape(b, mb * bs, klat)
    pe = pe_pages[page_table].reshape(b, mb * bs, -1)
    s = (jnp.einsum("bqnk,bsk->bqns", q_lat.astype(jnp.float32),
                    lat.astype(jnp.float32))
         + jnp.einsum("bqnp,bsp->bqns", q_pe.astype(jnp.float32),
                      pe.astype(jnp.float32))) * softmax_scale
    pos = jnp.arange(mb * bs)
    if decode:
        mask = pos[None, None, :] < kv_lens[:, None, None]      # [B,1,S]
        mask = mask[:, :, None, :]
    else:
        abs_q = (kv_lens - q_lens)[:, None] + jnp.arange(s_q)[None, :]
        mask = ((pos[None, None, :] <= abs_q[:, :, None])
                & (pos[None, None, :] < kv_lens[:, None, None]))
        mask = mask[:, :, None, :]
    s = jnp.where(mask, s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    v = jnp.einsum("bsk,knd->bsnd", lat.astype(jnp.float32),
                   w_v.astype(jnp.float32))
    out = jnp.einsum("bqns,bsnd->bqnd", p, v)
    out = out.astype(q_lat.dtype)
    return out[:, 0] if decode else out


# ---------------------------------------------------------------------------
# Page write / gather helpers (jit-able; `mode="drop"` keeps every invalid
# position out of the pool instead of clamping onto live blocks)
# ---------------------------------------------------------------------------


def write_prompt_pages(pages: jnp.ndarray, rows: jnp.ndarray,
                       table_row: jnp.ndarray, start, count) -> jnp.ndarray:
    """Scatter a prefill's new KV rows into the block pool.

    pages [L, num_blocks, block_size, ...]; rows [L, S_step, ...] where
    row i holds absolute sequence position start + i; table_row
    [max_blocks_per_seq]; count = number of valid rows (the rest are
    bucket padding and are dropped)."""
    nb, bs = pages.shape[1], pages.shape[2]
    s_step = rows.shape[1]
    pos = start + jnp.arange(s_step)
    blocks = jnp.take(table_row, pos // bs, mode="clip")
    blocks = jnp.where(jnp.arange(s_step) < count, blocks, nb)
    return pages.at[:, blocks, pos % bs].set(rows, mode="drop")


def _page_slots(page_table, pos, valid, nb: int, bs: int):
    """(block ids, in-block offsets) of absolute positions `pos` through
    their rows' page tables; an invalid position gets block id `nb`, which
    `kernel_gen.paged_append` drops (never clamps onto a live block)."""
    mb = page_table.shape[1]
    blocks = jnp.take_along_axis(
        page_table, jnp.clip(pos // bs, 0, mb - 1), axis=1)
    return jnp.where(valid, blocks, nb), pos % bs


def append_token_pages(pages: jnp.ndarray, vals: jnp.ndarray,
                       page_table: jnp.ndarray, positions: jnp.ndarray,
                       active: jnp.ndarray, layer=None,
                       mesh=None) -> jnp.ndarray:
    """Write one decode token per slot at its own (block, offset).

    pages [num_blocks, block_size, ...], or with `layer` (int32 scalar)
    the STACKED pool [L, num_blocks, block_size, ...] of which that layer
    is written in place (`kernel_gen.paged_append`: the engine's steps);
    vals [B, ...]; positions [B] (append position per slot); active [B]
    bool — inactive slots' page tables may reference freed blocks, so
    their writes are dropped, not clamped (the dense engine could write
    inactive rows harmlessly; a shared pool cannot). mesh: the pool is
    tp-sharded on its first row dim (see paged_append)."""
    return append_chunk_pages(pages, vals[:, None], page_table, positions,
                              jnp.ones_like(positions), active, layer, mesh)


def append_chunk_pages(pages: jnp.ndarray, vals: jnp.ndarray,
                       page_table: jnp.ndarray, starts: jnp.ndarray,
                       counts: jnp.ndarray, active: jnp.ndarray,
                       layer=None, mesh=None) -> jnp.ndarray:
    """Write a ragged multi-token run per slot (speculative verify /
    chunked prefill): row b's token i lands at absolute position
    starts[b] + i for i < counts[b]; padding rows and inactive slots are
    dropped, never clamped onto live blocks.

    pages [num_blocks, block_size, ...] (with `layer`: the stacked pool
    [L, num_blocks, block_size, ...], that layer written in place); vals
    [B, S, ...]; starts/counts [B] int32; active [B] bool. counts[b] == 1
    is append_token_pages."""
    from megatronapp_tpu.ops.pallas.kernel_gen import paged_append
    pool = pages[None] if layer is None else pages
    nb, bs = pool.shape[1], pool.shape[2]
    b, s = vals.shape[0], vals.shape[1]
    pos = starts[:, None] + jnp.arange(s)[None, :]           # [B, S]
    valid = (jnp.arange(s)[None, :] < counts[:, None]) & active[:, None]
    blocks, offs = _page_slots(page_table, pos, valid, nb, bs)
    pool = paged_append(pool, vals.reshape((b * s,) + vals.shape[2:]),
                        0 if layer is None else layer,
                        blocks.reshape(b * s), offs.reshape(b * s),
                        mesh=mesh)
    return pool[0] if layer is None else pool


def append_kv(kv_cache, kv_scales, rows, page_table, positions, active,
              layer, counts=None, mesh=None):
    """One layer's new rows into its pools: what every paged layer body
    does between its projections and its attention kernel.

    kv_cache: the pool pair, STACKED [L, NB, bs, ...] (K and V; MLA: the
    latent and k_pe pools); rows: the matching pair, [B, ...] for one
    token a slot or, with counts [B], a ragged [B, S, ...] chunk starting
    at positions. kv_scales: the scale-pool pair of a quantised pool —
    the rows then quantize per (row, head) right here, in the step's jit,
    and their scales go through the same page table. Returns (pools,
    scale pools or None), each the buffer it was given (paged_append)."""
    def put(pool, r):
        if counts is None:
            return append_token_pages(pool, r, page_table, positions,
                                      active, layer, mesh)
        return append_chunk_pages(pool, r, page_table, positions, counts,
                                  active, layer, mesh)

    if kv_scales is None:
        return tuple(put(p, r.astype(p.dtype))
                     for p, r in zip(kv_cache, rows)), None
    quant = [quantize_kv_rows(r, dtype=p.dtype)
             for p, r in zip(kv_cache, rows)]
    return (tuple(put(p, q) for p, (q, _) in zip(kv_cache, quant)),
            tuple(put(sp, sc) for sp, (_, sc) in zip(kv_scales, quant)))


def gather_prefix_pages(pages: jnp.ndarray, table_row: jnp.ndarray,
                        num_blocks: int) -> jnp.ndarray:
    """Gather the first `num_blocks` (static) blocks of one slot into a
    contiguous run: pages [L, NB, bs, ...] → [L, num_blocks*bs, ...]
    (prefix-cache hits re-enter the dense bucketed prefill this way)."""
    sel = jnp.take(pages, table_row[:num_blocks], axis=1, mode="clip")
    return sel.reshape((pages.shape[0], num_blocks * pages.shape[2])
                       + pages.shape[3:])


def gather_pages_batched(pages: jnp.ndarray, page_table: jnp.ndarray
                         ) -> jnp.ndarray:
    """pages [NB, bs, ...] + table [B, MB] → [B, MB*bs, ...] (block order
    is sequence order; rows past a slot's length are garbage and must be
    masked by the caller). Used by the MLA paged decode, whose latent →
    kv_up reconstitution needs the contiguous latent run."""
    b, mb = page_table.shape
    bs = pages.shape[1]
    out = jnp.take(pages, page_table.reshape(-1), axis=0, mode="clip")
    return out.reshape((b, mb * bs) + pages.shape[2:])


# ---------------------------------------------------------------------------
# TP-shard eligibility (the placement itself lives in kernel_gen._tp_place)
# ---------------------------------------------------------------------------


def tp_paged_ineligible_reason(cfg, ctx) -> Optional[str]:
    """Why the paged kernels may NOT run sharded on ctx's tp axis —
    None when eligible, otherwise the FIRST failed predicate by name (so
    fallback logs say what to fix instead of a generic "ineligible").
    Standard layout: both head counts divide by tp so each shard owns
    whole, matched GQA groups (q head h reads kv head h // group —
    contiguous slicing of BOTH by tp preserves the grouping per shard,
    the same rule as the flash wrapper). MLA: the latent pool has no
    kv-head axis, so the shard axis is the latent COLUMN dim instead
    (kernel_gen._tp_place_latent) — eligibility is kv_lora_rank % tp."""
    if ctx is None:
        return "no mesh context (ctx is None)"
    if ctx.tp <= 1:
        return f"tp == {ctx.tp} (needs tp > 1 to shard)"
    if cfg.multi_latent_attention:
        if cfg.kv_lora_rank % ctx.tp:
            return (f"kv_lora_rank ({cfg.kv_lora_rank}) % tp ({ctx.tp}) "
                    f"!= 0 (the latent pool shards on latent columns)")
        return None
    if cfg.num_attention_heads % ctx.tp:
        return (f"num_attention_heads ({cfg.num_attention_heads}) % tp "
                f"({ctx.tp}) != 0")
    if cfg.num_query_groups % ctx.tp:
        return (f"num_query_groups ({cfg.num_query_groups}) % tp "
                f"({ctx.tp}) != 0 (shards must own whole GQA groups)")
    return None


def tp_paged_eligible(cfg, ctx) -> bool:
    """True when the paged kernels may run head-sharded on ctx's tp axis
    (see tp_paged_ineligible_reason for the predicate list — it names
    the specific failure for fallback logs)."""
    return tp_paged_ineligible_reason(cfg, ctx) is None
