"""Paged-attention kernel GENERATOR, the pool writer and the batched-LoRA delta.

ISSUE 11 tentpole. Before this module, ops/pallas/paged_attention.py
hand-wrote four kernel variants (decode / multiquery × plain / tp) × two
KV dtypes (bf16, int8 dequant-in-register) — eight bodies that had to be
edited in lockstep. Every variant differed from the others along exactly
three axes, so the bodies are now EMITTED from a spec instead of copied:

  - ``ragged``     one query row per slot (decode) vs a per-request
                   ragged q_len ∈ [1, S_q] window (speculative verify /
                   chunked prefill) with the causal-tail mask and the
                   q_lens scalar-prefetch ref;
  - ``quantized``  bf16 pools vs int8 pools whose per-(row, kv-head)
                   fp32 scale blocks ride the SAME page-table BlockSpec
                   index map and dequantize in-register;
  - tp head-shard  plain single-device placement vs a FULL-MANUAL
                   shard_map over KV heads (``mesh=`` — each shard runs
                   the emitted kernel on its matched GQA groups against
                   its 1/tp slice of the pool).

``paged_attention`` is the one entry point (``paged_attention_latent``
for MLA pools); the legacy names in paged_attention.py are thin wrappers
over it. Both run ONE scaffold, ``emit_paged_kernel`` under
``_walk_call`` (ISSUE 29): a one-dimensional grid over the REAL steps of
a call — a slot is walked for the blocks it holds, ``pages_per_step``
pages a step (a key tile 128 or 256 wide at blocks of 16, 256 for latent
pages), and no step, branch or DMA exists for a table entry past its
length — around the body's tile functions (``_dense_tile``,
``_latent_tile``). A pool whose page is whole tiles stays in HBM and the
kernel starts the copies of a step's pages itself, a step ahead (ISSUE
46); the others are the pipeline's blocked operands. The
mathematics is the legacy bodies' (online softmax in fp32, causal tail
mask, in-register dequant), folded a tile at a time:
tests/test_kernel_gen.py holds the
kernels BITWISE to a jax.numpy replay of the walk and allclose to frozen
copies of the old bodies across {bf16, int8} × {tp1, tp2} × {q_len 1,
ragged} × {GQA, MHA}. New variants (fp8 pools, MLA latent layouts,
token-tree masks) are parameters here, not new copies. The latent family
sums its values IN LATENT SPACE (ISSUE 39): kv_up's value columns do not
depend on the key, so the walk accumulates p · latent over a slot's tiles
and ``paged_attention_latent`` expands the normalised sum through them
once a query row, after the walk.

After the emitter come ``paged_append`` (the in-place pool writer every
paged step and prefill call shares) and the batched-LoRA delta kernels
(``lora_segmented_delta`` / ``apply_lora_delta`` and their jnp oracle).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _dequant_block(k, ks):
    """[rows, Hkv, D] quantized K/V rows × [rows, Hkv] fp32 scales (or
    [rows, d] latent rows × [rows] scales) → fp32 rows: the in-register
    dequant of the pages a step holds."""
    return k.astype(jnp.float32) * ks[..., None]


# ---------------------------------------------------------------------------
# The generator: one spec → one emitted ragged-paged-attention body
# ---------------------------------------------------------------------------


QUANT_DTYPES = {
    # THE canonical quantized-KV storage registry: quant_dtype axis of
    # PagedSpec → (page jnp dtype, TPU min tile (sublane, lane) for the
    # KV block windows, symmetric quantization range bound qmax). Both
    # 1-byte formats want the (32, 128) layout on-chip; bf16 pools tile
    # (16, 128). The tile is PARAMETERIZED (not hard-coded in the body)
    # so the fp8 (32, 128) layout can be flipped on and validated when
    # the chip returns — interpret mode (CPU) imposes no tiling, so the
    # same spec runs everywhere today. quantize_kv_rows derives its
    # range from qmax, and the serving-facing KV_CACHE_DTYPES registry
    # (inference/paged_cache.py) builds its quantized entries FROM this
    # map — one place to add a storage dtype end-to-end.
    "int8": (jnp.int8, (32, 128), 127.0),
    "fp8": (jnp.float8_e4m3fn, (32, 128), 448.0),
}


def quant_dtype_of(pages_dtype) -> Optional[str]:
    """Map a page pool's storage dtype to the PagedSpec quant_dtype axis
    (None = unquantized compute-dtype pool)."""
    for name, (dt, _, _) in QUANT_DTYPES.items():
        if jnp.dtype(pages_dtype) == jnp.dtype(dt):
            return name
    return None


def quant_qmax_of(pages_dtype) -> float:
    """Symmetric quantization range bound for a registered quantized
    page dtype (127 int8, 448 e4m3)."""
    name = quant_dtype_of(pages_dtype)
    if name is None:
        raise ValueError(
            f"{pages_dtype} is not a registered quantized KV storage "
            f"dtype ({sorted(QUANT_DTYPES)})")
    return QUANT_DTYPES[name][2]


def _paged_name(ragged: bool, quant_dtype: Optional[str] = None,
                variant: str = "", window: bool = False) -> str:
    """A paged kernel's ``pallas_call`` name, which the compiled HLO
    instruction and so every device trace carries. The family prefix is
    the contract trace readers match (perfbench/metrics): ``paged_decode``
    for one query a row, ``paged_mq`` for the ragged multi-query kernel
    that chunked prefill and speculative verify run; variant and pool type
    follow (``paged_decode_latent_int8``). A sliding-window layer's walk is
    a family of its own, ``paged_window_decode`` / ``paged_window_mq``
    (no reader of ``paged_decode`` or ``paged_mq`` matches it): it reads
    other planes, and a trace tells the two apart."""
    return (("paged_window_" if window else "paged_")
            + ("mq" if ragged else "decode") + variant
            + (f"_{quant_dtype}" if quant_dtype else ""))


def default_kv_tile(quant_dtype: Optional[str]):
    """Min TPU tile (sublane, lane) of a KV page for this storage dtype:
    what a page's last two dims are padded to in VMEM."""
    if quant_dtype is None:
        return (16, 128)
    return QUANT_DTYPES[quant_dtype][1]


# What a step's pages, held twice (this step's and the next one's: the
# kernel's own two buffers, or the pipeline's for blocked operands), may
# take of VMEM. Mosaic's default scope is 16 MiB and a compute block's
# temporaries (the transposed tile, scores, probabilities) share it.
WALK_VMEM_BUDGET = 8 * 1024 * 1024


def _padded(n: int, to: int) -> int:
    return -(-n // to) * to


def _pages_vmem_bytes(pools, kv_tile) -> int:
    """Bytes ONE page of every pool takes in VMEM together, in a buffer
    of the kernel's own as in a blocked operand. pools: the stacked
    [L, NB, bs, ...] arrays of a call, KV pools (tiled `kv_tile`) first,
    their fp32 scale pools (tiled (8, 128)) after. A page's last dim is
    padded to the tile's lanes, the one before to its sublanes."""
    total = 0
    for i, pool in enumerate(pools):
        sub, lane = kv_tile if i < 2 else (8, 128)
        *lead, rows, cols = (1,) + tuple(pool.shape[2:])
        total += (math.prod(lead) * _padded(rows, sub)
                  * _padded(cols, lane) * pool.dtype.itemsize)
    return total


# Keys a step of the walk folds. A step costs what it does whatever the
# keys (the grid step, a query block's and an output block's bookkeeping,
# the rescaling of the accumulator) and a page copy has a price of its own
# whatever it holds (0.045 us started by the kernel, 0.063-0.078 us as a
# blocked operand of the pipeline), so a walk of small pages wants many of
# them a step and a walk of large ones is at its byte roof at 128 keys
# already. The dense family folds WIDE_KEY_TILE keys where the kernel copies
# the pages itself and a step of that many holds no more than
# WIDE_STEP_BYTES of them as VMEM pads them (`_pages_vmem_bytes`: 8
# key/value heads of 128), else KEY_TILE. Kernels alone on a v5e, us a call
# at 128 -> 256 keys (PERF.md section 6, PR 46), one query a slot: 8 heads
# of 128 copied by the kernel 1,848 -> 1,406 (1,322 at 512; as blocked
# operands 2,225 -> 1,956). Over the cap: 32 heads of 128 take 1,818-1,834
# in every form and 32 heads of 80 are SLOWER at 256, 851-862 -> 872, at
# twice the VMEM, which a ragged call's query tile wants. A walk of blocked
# operands keeps 128, the kernels and the bits it had before ISSUE 46:
# alone its small pages gain at 256 too (8 heads of 64 1,276 -> 1,238, one
# head of 128 1,192 -> 1,103, their prefill calls 1,264 -> 1,164 and 206 ->
# 179), but neither cell's tokens a second moved (4,901.8 -> 4,892.6 and
# 3,475.8 -> 3,462.8, a pair each, inside their spread) and the other sums'
# order gave one of two runs of the first cell an emitted token 1.618 under
# its reference's maximum where the cell allows 1.6 (0.99 at 128 keys on
# the same seed): a re-drawn tail for no gain. A latent page is 20 KiB: its
# decode kernel takes 732 us at 256 keys and 672 at 512 (693 at 1,024), but
# at 512 the [rows, 512] scores take the query tile's VMEM and the ragged
# kernel was slower (PR 39, 16 and 64 heads); a ragged call and a one-query
# call of one slot have to fold the same tiles to give the same bits, so
# both keep 256.
KEY_TILE = 128
WIDE_KEY_TILE = 256
WIDE_STEP_BYTES = 2 * 1024 * 1024
LATENT_KEY_TILE = 256


def dense_key_tile(block_size: int, page_bytes: int, copied) -> int:
    """Keys a step of a dense walk folds, from what the code can see:
    `page_bytes`, one page of every pool of the call as VMEM holds it, and
    `copied`, whether the kernel starts the copies of the key and the
    value pages itself."""
    wide = (all(copied) and WIDE_KEY_TILE // block_size * page_bytes
            <= WIDE_STEP_BYTES)
    return WIDE_KEY_TILE if wide else KEY_TILE


def pages_per_step(block_size: int, page_bytes: int, table_blocks: int,
                   budget: int = WALK_VMEM_BUDGET,
                   key_tile: int = KEY_TILE) -> int:
    """Pages in one compute block of the walk (`PagedSpec.pages`), from
    what the code can see: as many as make the key tile `key_tile` wide (8
    or 16 at block_size 16, 16 for latent pages), fewer if two buffers of
    them (the step's and the next one's, whoever copies them) would pass
    `budget` (`page_bytes`: one page of every pool of the call, as VMEM
    holds it), never more than the table holds."""
    return max(1, min(key_tile // block_size, budget // (2 * page_bytes),
                      table_blocks))


# Mosaic's default scoped VMEM: what one kernel may hold at a time. A
# ragged walk's query tile gets what the page blocks leave of it. (Asking
# Mosaic for more and holding a 256-wide call in one tile was tried: the
# kernel alone gains 13% over tiles of 64 at 1,280 cached rows, 3% of the
# call. PERF.md section 6, PR 35.)
VMEM_SCOPE = 16 * 1024 * 1024


def _query_vmem_budget(pools, kv_tile, pages: int) -> int:
    """What a ragged walk's query tile may take of VMEM: the scope less the
    step's pages, twice (the two buffers); less one more copy
    of the key and the value tile as a step computes on them (transposed in
    the pool's dtype; float32 where the pages are quantized and
    dequantized in-register); less 1 MiB."""
    held = 2 * pages * _pages_vmem_bytes(pools, kv_tile)
    stored = pools[0].dtype.itemsize
    computed = 4 if len(pools) > 2 else stored
    copies = pages * _pages_vmem_bytes(pools[:2], kv_tile) // stored * computed
    return VMEM_SCOPE - held - copies - (1 << 20)


def _query_row_vmem_bytes(queries, out_cols: int, key_tile: int,
                          out_dtype=None) -> int:
    """Bytes ONE query position of a ragged call takes in VMEM, all its
    heads together. queries: the call's query arrays [B, S_q, heads, cols]
    (the lanes hold cols, padded to 128); out_cols, out_dtype: the output
    block's columns and type (the queries' type if None). The accumulator
    is as wide as the output: a head's D columns in the dense family, the
    klat columns of the latent sum, in float32, in the latent one.
    Counted: each query block and the output block twice (the pipeline's
    buffers), a float32 copy of the queries, the float32 accumulator, the
    running max and sum (a lane-padded column each) and a step's float32
    scores. Held against what Mosaic asks for at the serving cells' shapes
    (a search over `vmem_limit_bytes` for a described v5e): 112 KB here for
    the 104 KB a position it takes at 32 heads of 128, 70 for 47-65 at 20
    heads over one key/value head; the latent family's, at 512 + 64
    columns, is in tests/test_chip_compile.py."""
    heads = queries[0].shape[2]
    lanes = sum(_padded(q.shape[3], 128) for q in queries)
    item = queries[0].dtype.itemsize
    out_item = item if out_dtype is None else jnp.dtype(out_dtype).itemsize
    out = _padded(out_cols, 128)
    return heads * (2 * item * lanes + 2 * out_item * out + 4 * lanes
                    + 4 * out + 2 * 4 * 128 + 4 * _padded(key_tile, 128))


def _latent_tp_row_vmem_bytes(heads: int, klat_local: int, key_tile: int,
                              dv: int) -> int:
    """Bytes ONE query position takes in VMEM, all its heads together, in
    the latent-column tp path's two kernels (`_latent_block_scores`,
    `_latent_block_wsum`), whichever holds more, all float32: the scores
    kernel's query block and output block [., key_tile] twice each (the
    pipeline's buffers) and the query cast to the page's dtype; the
    weighted sum's probability block and output twice each, the
    accumulator, the probabilities regrouped by head and the product
    before and after it is regrouped."""
    cols, keys, out = (_padded(n, 128) for n in (klat_local, key_tile, dv))
    return heads * 4 * max(3 * cols + 2 * keys, 3 * keys + 5 * out)


def query_rows_per_step(s_q: int, row_bytes: int, budget: int) -> int:
    """Query positions of one slot that a step of the ragged walk holds
    (the query tile), from what the code can see: all `s_q` of them where
    they fit `budget` (`row_bytes`: one position's, all heads), else the
    fewest equal tiles that do, each a multiple of 8 rows. No caller sets
    it, as none sets `pages_per_step`."""
    fit = max(8, budget // row_bytes // 8 * 8)
    if s_q <= fit:
        return s_q
    return _padded(-(-s_q // -(-s_q // fit)), 8)


def _query_tiled(tile: int, page_table, kv_lens, q_lens, queries):
    """A ragged call whose slots bring more queries than a step holds, as
    the call the walk takes: slot b's S_q queries become cdiv(S_q, tile)
    rows of `tile` queries that share b's table row.

    The walk gives every row its own kv_len and q_len already. Row i of
    slot b holds the slot's queries [i*tile, (i+1)*tile): q_len
    clip(q_lens[b] - i*tile, 0, tile) of them are real, and the last of
    those sits at position kv_len - 1 with
    kv_len = (kv_lens[b] - q_lens[b]) + i*tile + q_len: the slot's own
    rows are in its pages before the kernel runs, so a tile attends the
    context and the new tail up to itself, and the causal mask
    (kv_len - q_len + row) is the kernel's own. A tile past the slot's
    count has kv_len 0: the walk's one step that holds no row and writes
    zeros. -> (page_table, kv_lens, q_lens, queries) of the tiled call and
    the function that gives its output the caller's shape back."""
    b, s_q = queries[0].shape[:2]
    if tile >= s_q:
        return page_table, kv_lens, q_lens, queries, lambda out: out
    n = -(-s_q // tile)
    first = jnp.arange(n, dtype=jnp.int32)[None, :] * tile       # [1, n]
    kv_lens, q_lens = kv_lens.astype(jnp.int32), q_lens.astype(jnp.int32)
    q_tile = jnp.clip(q_lens[:, None] - first, 0, tile)          # [B, n]
    kv_tile = jnp.where(
        q_tile > 0,
        jnp.maximum((kv_lens - q_lens)[:, None] + first + q_tile, 0), 0)

    def split(q):
        q = jnp.pad(q, ((0, 0), (0, n * tile - s_q))
                    + ((0, 0),) * (q.ndim - 2))
        return q.reshape((b * n, tile) + q.shape[2:])

    def join(out):
        return out.reshape((b, n * tile) + out.shape[2:])[:, :s_q]

    return (jnp.repeat(page_table, n, axis=0), kv_tile.reshape(-1),
            q_tile.reshape(-1), [split(q) for q in queries], join)


@dataclasses.dataclass(frozen=True)
class PagedSpec:
    """Everything that selects a paged-attention kernel variant.

    ragged=False requires s_q == 1 (the decode shape); ragged=True adds
    the q_lens scalar-prefetch ref and the causal tail mask over the
    [1, S_q] window. quant_dtype ("int8" | "fp8" | None) adds the
    scale-block refs and the in-register dequant of each DMA'd block —
    the dequant body (cast to fp32 × per-(row, head) scale) is shared by
    both quantized formats, so a new storage dtype is a registry entry
    (QUANT_DTYPES), not a new body. pages is how many pages one step of
    the walk takes (the key tile is pages × block_size wide) and
    kv_tile the (sublane, lane) min tile a KV page is padded to in VMEM
    (dtype-dependent — fp8/int8 want (32, 128)); the entry points
    derive both, and `copied`, from the shapes (`pages_per_step`,
    `default_kv_tile`, `_page_is_tiles`), no caller sets them. The tp
    head-shard axis is NOT part of the body spec — sharding is pure
    placement (``paged_attention(..., mesh=)`` wraps the same emitted
    kernel in a full-manual shard_map)."""

    ragged: bool
    quant_dtype: Optional[str]
    s_q: int
    block_size: int
    num_blocks_seq: int
    hkv: int
    group: int
    scale: float
    kv_tile: tuple = (16, 128)
    pages: int = 1
    # A pool, in the call's order: whether the kernel starts the copies of
    # its pages itself (`_page_is_tiles`) or the pipeline brings them as
    # blocked operands. () is all blocked.
    copied: tuple = ()
    # MLA latent layout (ISSUE 17): pages hold [block, klat] latent +
    # [block, dpe] roped-key blocks with NO per-head axis; hkv carries
    # the QUERY head count (every head attends the one shared latent,
    # group == 1) and the kernel contracts q_lat · latent^T + q_pe ·
    # k_pe^T directly and sums p · latent: its output is the normalised
    # latent sum [., nq, klat] in float32, which the entry point expands
    # through kv_up's v columns after the walk.
    latent: bool = False
    klat: int = 0
    dpe: int = 0
    # A sliding-window layer (window > 0): the query at position t sees the
    # keys t - window + 1 .. t. The walk of a slot then STARTS at the block
    # that holds its first query's oldest key (`_window_first`; scalar-
    # prefetched, the slot's first row in `start_ref`), so no step and no
    # DMA exists for a block wholly behind the window, whose table entries
    # may name blocks the slot has given back; the rows of the first block
    # that lie behind the window are masked.
    window: int = 0

    @property
    def quantized(self) -> bool:
        return self.quant_dtype is not None

    @property
    def pools_copied(self) -> tuple:
        return self.copied or (False,) * (4 if self.quantized else 2)

    def __post_init__(self):
        if not self.ragged and self.s_q != 1:
            raise ValueError(
                f"non-ragged (decode) kernels are single-query: s_q="
                f"{self.s_q} requires ragged=True (pass q_lens)")
        if self.quant_dtype is not None \
                and self.quant_dtype not in QUANT_DTYPES:
            raise ValueError(
                f"quant_dtype must be one of {sorted(QUANT_DTYPES)} or "
                f"None, got {self.quant_dtype!r}")
        if len(self.kv_tile) != 2 or self.kv_tile[1] % 128:
            raise ValueError(
                f"kv_tile must be (sublane, lane) with lane a multiple "
                f"of 128, got {self.kv_tile!r}")
        if self.latent:
            if self.klat <= 0 or self.dpe <= 0:
                raise ValueError(
                    f"latent specs need klat/dpe > 0, got "
                    f"({self.klat}, {self.dpe})")
            if self.group != 1:
                raise ValueError(
                    "latent specs have no GQA grouping (every query "
                    f"head shares the one latent row): group="
                    f"{self.group} must be 1, with hkv carrying the "
                    "query head count")


def emit_paged_kernel(spec: PagedSpec):
    """Emit the kernel for `spec`: ONE walk, written once, around the
    body's tile functions (dense K/V pages, or MLA latent pages).

    The grid is one-dimensional and its bound is the call's count of REAL
    steps (`_walk_steps`): step g belongs to slot ``slot_of[g]`` and is
    that slot's ``step_of[g]``-th compute block of ``spec.pages`` pages, a
    key tile pages × bs wide. A slot is visited for blocks
    0 … cdiv(lens[b], bs) − 1 and nothing else: no grid step, no branch
    and no DMA for a table entry past the slot's length.

    How a step gets its pages is decided a pool (``spec.copied``,
    `_page_is_tiles`). A pool whose page is whole tiles stays in HBM
    (``memory_space=pl.ANY``) and the kernel copies its pages itself
    (ISSUE 46): it owns a buffer [2, pages*bs, ...] a pool and a DMA
    semaphore a half and pool; step g waits for its own pages in half
    g % 2 and, before it computes, starts step g+1's into the other half,
    one ``make_async_copy`` a page (``block_of[(g+1)*pages + p]``, read
    from SMEM), whichever slot the next step belongs to; step 0 starts its
    own first. The pages are a loop's turns, traced once: spelled out page
    by page the four families of a stack took a serving program 20 s
    longer to trace (PERF.md section 6, PR 46). A page that a slot's partial last step lacks is not copied;
    its rows of the buffer are blanked, since the probabilities (0) of its
    masked columns still meet them in the value product. The tile a step
    computes on is the buffer's half as it lies. Any other pool (a head dim
    of 80 or 64, one key/value head, the roped keys' 64 columns, a latent
    page of fewer rows than its dtype's sublane tile, scale pages: Mosaic
    refuses to slice a buffer whose tiled dims are no whole tiles) is
    ``pages`` blocked operands [1, bs, ...] of the pipeline,
    which keeps step g+1's in flight while step g is computed and joins
    them into the tile (`_tile_of`); there a missing page re-names a page
    the pipeline already holds (`_walk_steps`), so nothing is copied for
    it. Both kinds may meet in one call (the latent plane copied, the roped
    keys blocked).

    Online softmax in fp32 over the valid range [0, lens[b]),
    query row i (absolute position kv_len − q_len + i; q_len is 1 where
    the kernel is not ragged) masked causally within the new tail. A
    slot with no cached row gets one step that computes nothing and
    writes zeros. With spec.window the walk starts at the slot's row
    ``start_ref[b]`` (a block's first) in place of row 0, and a key more
    than window - 1 positions behind its query is masked."""
    pages, ragged, window = spec.pages, spec.ragged, spec.window
    bs = spec.block_size
    width = pages * bs
    copied = spec.pools_copied
    n_q, prep, row_q, scores, finish = (
        _latent_tile if spec.latent else _dense_tile)(spec)

    def kernel(lid_ref, lens_ref, slot_ref, step_ref, block_ref, *refs):
        refs = list(refs)
        qlens_ref = refs.pop(0) if ragged else None
        start_ref = refs.pop(0) if window else None
        q_refs, refs = refs[:n_q], refs[n_q:]
        pools = []
        for own in copied:
            pools.append(refs.pop(0) if own else
                         [refs.pop(0) for _ in range(pages)])
        o_ref, acc, m_scr, l_scr, *bufs = refs
        g = pl.program_id(0)
        b, i = slot_ref[g], step_ref[g]
        kv_len = lens_ref[b]

        def span(step):
            """(the first key row of grid step `step`, the pages of it that
            its slot holds)."""
            slot = slot_ref[step]
            first = step_ref[step] * width
            if window:
                first += start_ref[slot]
            held = jnp.minimum(-(-lens_ref[slot] // bs), spec.num_blocks_seq)
            return first, jnp.clip(held - first // bs, 0, pages)

        row0, n = span(g)
        tiles = list(pools)
        if any(copied):
            # Step g computes on half g % 2 of the buffers. Its pages were
            # started a step ago (step 0 starts its own); step g + 1's,
            # whichever slot's they are, start now into the other half.
            sem = bufs.pop()
            half = g % 2
            mine = [k for k, own in enumerate(copied) if own]
            for k, buf in zip(mine, bufs):
                tiles[k] = buf.at[half]

            def rows_of(p):
                return pl.ds(pl.multiple_of(p * bs, bs), bs)

            def page_copies(step, into, p):
                return [pltpu.make_async_copy(
                    pools[k].at[lid_ref[0], block_ref[step * pages + p]],
                    buf.at[into, rows_of(p)], sem.at[into, j])
                    for j, (k, buf) in enumerate(zip(mine, bufs))]

            def pages_between(lo, hi, do, **kw):
                """do(p) for the pages lo <= p < hi of a step: one loop,
                traced once however many pages a step has."""
                jax.lax.fori_loop(lo, hi, lambda p, _: do(p), None, **kw)

            def start(step, into):
                """Start the copies of the pages step `step` holds."""
                def page(p):
                    for copy in page_copies(step, into, p):
                        copy.start()

                # a whole step (all but a slot's last) runs straight
                # through: a loop of `count` turns costs a tenth more
                count = span(step)[1]
                pl.when(count == pages)(
                    lambda: pages_between(0, pages, page, unroll=True))
                pl.when(count < pages)(lambda: pages_between(0, count, page))

            pl.when(g == 0)(lambda: start(0, 0))
            pl.when(g + 1 < pl.num_programs(0))(
                lambda: start(g + 1, 1 - half))

            @pl.when(n == pages)
            def _all_arrived():
                # a semaphore counts what has arrived: a whole step's
                # pages are its half of a buffer, one wait a pool
                for j, buf in enumerate(bufs):
                    pltpu.make_async_copy(buf.at[half], buf.at[half],
                                          sem.at[half, j]).wait()

            @pl.when(n < pages)
            def _some_arrived():
                def arrived(p):
                    for copy in page_copies(g, half, p):
                        copy.wait()

                def blank(p):
                    # a page the slot does not hold was not copied: what
                    # the buffer holds there is masked out of the scores,
                    # and has to be finite where the probabilities (0)
                    # meet it
                    for buf in bufs:
                        rows = buf.at[half, rows_of(p)]
                        rows[...] = jnp.zeros(rows.shape, rows.dtype)

                pages_between(0, n, arrived)
                pages_between(n, pages, blank)

        @pl.when(i == 0)
        def _init():
            acc[...] = jnp.zeros_like(acc)
            m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
            l_scr[...] = jnp.zeros_like(l_scr)

        @pl.when(row0 < kv_len)
        def _fold():
            s, values = scores(prep(*q_refs), tiles)
            pos = row0 + jax.lax.broadcasted_iota(
                jnp.int32, (1, width), 1)
            # the local query of row r sits at kv_len - q_len + row_q(r)
            # (one query a slot: at kv_len - 1, behind every cached row)
            abs_q = (kv_len - (qlens_ref[b] if ragged else 1)) + row_q(
                jax.lax.broadcasted_iota(jnp.int32, (s.shape[-2], 1), 0))
            valid = (pos < kv_len) & (pos <= abs_q)       # [rows, width]
            if window:
                valid &= abs_q - pos < window
            s = jnp.where(valid, s, _NEG_INF)
            m_prev = m_scr[...]
            m_new = jnp.maximum(m_prev,
                                jnp.max(s, axis=-1, keepdims=True))
            m_safe = jnp.maximum(m_new, _NEG_INF / 2)
            p = jnp.exp(s - m_safe)
            p = jnp.where(valid, p, 0.0)
            corr = jnp.exp(jnp.minimum(m_prev - m_new, 0.0))
            corr = jnp.where(m_prev <= _NEG_INF / 2, 0.0, corr)
            l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=-1,
                                                     keepdims=True)
            acc[...] = acc[...] * corr + values(p)
            m_scr[...] = m_new

        @pl.when(row0 + width >= kv_len)
        def _finalize():
            o_ref[0] = finish(
                acc[...] / jnp.maximum(l_scr[...], 1e-20)
            ).reshape(o_ref.shape[1:]).astype(o_ref.dtype)

    return kernel


def _tile_of(pages, scales=None):
    """A step's tile [pages*bs, ...]: the buffer's half that the kernel's
    own copies filled, as it lies, or the pipeline's page blocks [1, bs, ...]
    joined; its scale rows, held either way, dequantize it in-register."""
    def joined(held):
        if not isinstance(held, list):
            return held[...]
        x = jnp.concatenate([r[...] for r in held], axis=0)
        return x.reshape((-1,) + x.shape[2:])
    x = joined(pages)
    return x if scales is None else _dequant_block(x, joined(scales))


def _dense_tile(spec: PagedSpec):
    """The dense body's part of the walk: K and V pages [bs, Hkv, D]
    (int8/fp8 pools: their scale pages [bs, Hkv] beside them). Rows are
    [Hkv, S_q*group] with inner index i = s*group + g, held that way
    from a slot's first tile to its last: the [S_q, Hq] layout is left
    in `prep` and entered again in `finish` only."""
    hkv, group, s_q = spec.hkv, spec.group, spec.s_q
    rows = s_q * group

    def prep(q_ref):
        q = q_ref[0].astype(jnp.float32) * spec.scale
        d = q.shape[-1]
        if s_q == 1:
            return q.reshape(hkv, group, d)
        return jnp.transpose(q.reshape(s_q, hkv, group, d),
                             (1, 0, 2, 3)).reshape(hkv, rows, d)

    def scores(q3, tiles):
        k_ref, v_ref, *scale_refs = tiles
        ks_ref, vs_ref = scale_refs or (None, None)
        k3 = jnp.swapaxes(_tile_of(k_ref, ks_ref), 0, 1)  # [Hkv, w, D]
        s = jax.lax.dot_general(                          # [Hkv, rows, w]
            q3.astype(k3.dtype), k3,
            (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)

        def values(p):
            v3 = jnp.swapaxes(_tile_of(v_ref, vs_ref), 0, 1)
            return jax.lax.dot_general(                   # [Hkv, rows, D]
                p.astype(v3.dtype), v3,
                (((2,), (1,)), ((0,), (0,))),
                preferred_element_type=jnp.float32)

        return s, values

    def finish(a):
        d = a.shape[-1]
        if s_q > 1:
            a = jnp.transpose(a.reshape(hkv, s_q, group, d), (1, 0, 2, 3))
        return a                                  # [(S_q,) Hkv, group, D]

    return 1, prep, lambda r: r // group, scores, finish


def _latent_tile(spec: PagedSpec):
    """The MLA latent body's part of the walk (ISSUE 17): the pool pages
    are the COMPRESSED run ([bs, klat] latent + [bs, dpe] roped shared
    key, no per-head axis; quantized pools: a per-ROW scalar scale [bs]
    each) and the score contraction runs directly in latent space: the
    caller absorbs q_nope through kv_up's k_nope columns, so tile scores
    are q_lat · latent^T + q_pe · k_pe^T. The value path stays in latent
    space too (ISSUE 39): kv_up's value columns are the same for every
    key, so Σ_keys p · (latent · w_v) is (Σ_keys p · latent) · w_v, and a
    tile adds p · latent, the (dequantized) tile the scores were taken
    from, to a [rows, klat] float32 accumulator; the entry point expands
    the normalised sum once a query row. Neither w_v nor a tile's
    [w, nq*dv] value rows exist in the kernel, and a dense
    [B, S_kv, nq, dqk+dv] reconstitution never materializes. Rows are
    nq * s_q with row = h*s_q + s (group == 1: every head shares the
    latent row, so no GQA fold)."""
    nq, s_q = spec.hkv, spec.s_q
    klat, dpe = spec.klat, spec.dpe
    rows = nq * s_q

    def prep(ql_ref, qp_ref):
        def head_major(ref, d):
            q = ref[0].astype(jnp.float32)
            if s_q > 1:
                q = jnp.swapaxes(q, 0, 1)                 # [nq, s_q, d]
            return q.reshape(rows, d) * spec.scale
        return head_major(ql_ref, klat), head_major(qp_ref, dpe)

    def scores(qs, tiles):
        ql, qp = qs
        lat_ref, pe_ref, *scale_refs = tiles
        ls_ref, ps_ref = scale_refs or (None, None)
        lat = _tile_of(lat_ref, ls_ref)                 # [w, klat]
        pe = _tile_of(pe_ref, ps_ref)                   # [w, dpe]
        nt = (((1,), (1,)), ((), ()))                     # a · b^T
        s = (jax.lax.dot_general(ql.astype(lat.dtype), lat, nt,  # [rows, w]
                                 preferred_element_type=jnp.float32)
             + jax.lax.dot_general(qp.astype(pe.dtype), pe, nt,
                                   preferred_element_type=jnp.float32))

        def values(p):
            return jnp.dot(p.astype(lat.dtype), lat,      # [rows, klat]
                           preferred_element_type=jnp.float32)

        return s, values

    def finish(a):
        if s_q > 1:
            a = jnp.swapaxes(a.reshape(nq, s_q, klat), 0, 1)
        return a                                  # [(S_q,) nq, klat]

    return 2, prep, lambda r: r % s_q, scores, finish


def _window_first(kv_lens, q_lens, bs: int, window: int):
    """The first block a window walk visits, a slot: the one that holds the
    oldest key its FIRST query sees, position kv_len - q_len - (window - 1)
    (q_len is 1 where q_lens is None)."""
    q = 1 if q_lens is None else q_lens.astype(jnp.int32)
    return jnp.maximum(kv_lens.astype(jnp.int32) - q - (window - 1), 0) // bs


def _walk_steps(page_table, kv_lens, bs: int, pages: int, first=None):
    """The grid steps of a walk, from the table and the lengths.

    Slot b takes cdiv(kv_lens[b], pages*bs) steps of `pages` pages (one,
    to write its zeros, if it holds no row; never more than its table
    row can name); with `first` [B] (a window walk, `_window_first`) its
    steps start at block first[b] and cover the rows from there on, and no
    entry before first[b] is read. → their count, and for every step g up to the most
    there can be: slot_of[g]; step_of[g], its index within the slot; and
    block_of[g*pages + p], the pool block of its p-th page. A slot's
    steps are consecutive. A page a partial last step does not have
    names the block its operand held a step earlier (a blocked operand's
    pipeline then copies nothing; the kernel's own copies skip such a
    page and never read the name), the slot's last page if the slot has no
    earlier step, block 0 if the slot holds nothing: never a table entry
    past the slot's length. Entries past the count repeat the last slot
    (no grid step reads them)."""
    b, max_blocks = page_table.shape
    max_steps = -(-max_blocks // pages)
    kv_lens = kv_lens.astype(jnp.int32)
    rows = kv_lens if first is None else kv_lens - first * bs
    n = jnp.clip(-(-rows // (pages * bs)), 1, max_steps)
    ends = jnp.cumsum(n)
    g = jnp.arange(b * max_steps, dtype=jnp.int32)
    before = g[:, None] >= ends[None, :]        # slots wholly before step g
    slot_of = jnp.minimum(jnp.sum(before, axis=1, dtype=jnp.int32), b - 1)
    step_of = g - jnp.sum(jnp.where(before, n[None, :], 0), axis=1,
                          dtype=jnp.int32)
    held = jnp.minimum(-(-kv_lens // bs), max_blocks)[slot_of][:, None]
    first_step = step_of[:, None] == 0
    page = step_of[:, None] * pages + jnp.arange(pages, dtype=jnp.int32)
    if first is not None:
        page += first[slot_of][:, None]
    page = jnp.where(page < held, page,
                     jnp.where(first_step, held - 1, page - pages))
    block_of = jnp.where(
        held > 0, page_table[slot_of[:, None], jnp.maximum(page, 0)], 0)
    return ends[-1], slot_of, step_of, block_of.reshape(-1)


def _walk_call(spec: PagedSpec, name, lid, page_table, kv_lens, q_lens,
               queries, pools, out, acc_shape):
    """The one `pallas_call` of the paged family. Scalar-prefetched: the
    layer id, the lengths and the walk's step maps (ragged: q_lens
    last). A slot's query block and its block of the output `out` (a
    ShapeDtypeStruct) follow ``slot_of``. A STACKED pool [L, NB, bs, ...]
    that ``spec.copied`` marks is handed over once, in HBM, with a VMEM
    buffer [2, pages*bs, ...] of the kernel's own (all such pools share
    one array of DMA semaphores [2, pools]); any other is handed over
    ``spec.pages`` times, each a block [1, bs, ...] named by ``block_of``.
    What a step copies goes into the call's metadata, where
    utils/dispatch.page_copies reads it for ``GET /stats``, and is printed
    once a walk (`_announce_walk`)."""
    pages = spec.pages
    first = None
    if spec.window:
        first = _window_first(kv_lens, q_lens, spec.block_size, spec.window)
    total, slot_of, step_of, block_of = _walk_steps(
        page_table, kv_lens, spec.block_size, pages, first)

    def slot_block(shape):
        rest = (0,) * (len(shape) - 1)
        return pl.BlockSpec((1,) + tuple(shape[1:]),
                            lambda g, l, n, slot, *_: (slot[g],) + rest)

    def page_block(pool, p):
        rest = (0,) * (pool.ndim - 2)
        return pl.BlockSpec(
            (None, 1) + tuple(pool.shape[2:]),
            lambda g, l, n, slot, step, block, *_:
            (l[0], block[g * pages + p]) + rest)

    prefetch = [lid, kv_lens, slot_of, step_of, block_of]
    if spec.ragged:
        prefetch.append(q_lens)
    if spec.window:
        prefetch.append(first * spec.block_size)
    copied = spec.pools_copied
    own = [pool for pool, mine in zip(pools, copied) if mine]
    pool_specs, pool_args = [], []
    for pool, mine in zip(pools, copied):
        specs = ([pl.BlockSpec(memory_space=pl.ANY)] if mine else
                 [page_block(pool, p) for p in range(pages)])
        pool_specs += specs
        pool_args += [pool] * len(specs)
    rows = pages * spec.block_size
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(total,),
        in_specs=[slot_block(q.shape) for q in queries] + pool_specs,
        out_specs=slot_block(out.shape),
        scratch_shapes=[
            pltpu.VMEM(acc_shape, jnp.float32),
            pltpu.VMEM(acc_shape[:-1] + (1,), jnp.float32),
            pltpu.VMEM(acc_shape[:-1] + (1,), jnp.float32),
            *(pltpu.VMEM((2, rows) + tuple(pool.shape[3:]), pool.dtype)
              for pool in own),
            *([pltpu.SemaphoreType.DMA((2, len(own)))] if own else [])],
    )
    page_bytes = [math.prod(pool.shape[2:]) * pool.dtype.itemsize
                  for pool in pools]
    _announce_walk(name, pages, page_bytes, copied)
    return pl.pallas_call(
        emit_paged_kernel(spec), grid_spec=grid_spec, out_shape=out,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=_interpret(), name=name,
        metadata=dict(
            page_copies_step=str(pages * len(pools)),
            page_copy_bytes="+".join(map(str, page_bytes)),
            page_copies_kernel=str(pages * len(own))),
    )(*(a.astype(jnp.int32) for a in prefetch), *queries, *pool_args)


_announced = set()


def _announce_walk(name: str, pages: int, page_bytes, copied) -> None:
    """Print, once per distinct walk in this process, how a step of it
    gets its pages."""
    def sizes(which):
        return " + ".join(f"{n:,}" for n, own in zip(page_bytes, copied)
                          if own == which)
    parts = []
    if any(copied):
        parts.append(f"of {sizes(True)} B started by the kernel")
    if not all(copied):
        parts.append(f"of {sizes(False)} B (a page no whole tiles) left to "
                     "the pipeline")
    line = (f"paged walk: {name}, {pages} pages a step, {len(page_bytes)} "
            f"copies a page: {', '.join(parts)} "
            f"({'interpreted' if _interpret() else 'compiled'})")
    if line not in _announced:
        _announced.add(line)
        print(line, flush=True)


def _page_is_tiles(pool) -> bool:
    """Whether the kernel can start the copies of the stacked `pool`'s
    pages [L, NB, bs, ...] itself. Mosaic lets a copy address a slice of
    the kernel's buffer only where the buffer's two tiled dims are whole
    tiles (else "Slice shape along dimension 2 must be aligned to tiling",
    even for a slice that takes those dims whole), and the tile follows the
    dtype. A page of key/value heads [bs, Hkv, D] is cut out along an
    untiled dim: D has to be whole 128 lanes and Hkv a multiple of 8 (8 and
    32 heads of 128 in bf16, int8 and fp8 lower for a described v5e:
    tests/test_chip_compile.py). A page with no head axis [bs, cols] (the
    latent plane) is cut out along the sublanes: cols has to be whole 128
    lanes and bs whole sublane tiles of its dtype (16 rows of bf16, 32 of
    int8 and fp8: `default_kv_tile`). Every other pool (D 80, D 64, one
    key/value head, the roped keys' 64 columns, a latent pool of 8-row
    blocks or of quantized 16-row blocks, scale pages) stays the
    pipeline's blocked operands."""
    if pool.ndim < 4 or pool.shape[-1] % 128:
        return False
    if pool.ndim == 4:
        return pool.shape[2] % default_kv_tile(
            quant_dtype_of(pool.dtype))[0] == 0
    return pool.shape[-2] % 8 == 0


def _call_pools(pages, scales, table_blocks: int, key_tile=None):
    """(a call's pools in the kernel's order, what the spec takes from
    them): the two stacked page pools [L, NB, bs, ...], then their scale
    pools where the pages are quantized (scales not None), and the
    spec's quant_dtype, kv_tile, which pools the kernel copies itself and
    the pages a step (of `key_tile` keys; None: `dense_key_tile`'s)."""
    quant_dtype = None
    if scales[0] is not None:
        quant_dtype = quant_dtype_of(pages[0].dtype)
        if quant_dtype is None:
            raise ValueError(
                f"scales passed but page dtype {pages[0].dtype} is not a "
                f"registered quantized storage format "
                f"({sorted(QUANT_DTYPES)})")
        pages = pages + scales
    kv_tile = default_kv_tile(quant_dtype)
    bs, page_bytes = pages[0].shape[2], _pages_vmem_bytes(pages, kv_tile)
    copied = tuple(_page_is_tiles(pool) for pool in pages)
    return pages, dict(
        quant_dtype=quant_dtype, kv_tile=kv_tile, copied=copied,
        pages=pages_per_step(
            bs, page_bytes, table_blocks, key_tile=key_tile
            or dense_key_tile(bs, page_bytes, copied[:2])))


def _query_tile(queries, out_cols: int, pools, by_pools,
                out_dtype=None) -> int:
    """The query tile of a ragged call (`query_rows_per_step`) from its
    shapes: queries [B, S_q, heads, cols], the output's columns a head and
    its type (the queries' if None), the pools and what `_call_pools` took
    from them."""
    pages = by_pools["pages"]
    return query_rows_per_step(
        queries[0].shape[1],
        _query_row_vmem_bytes(queries, out_cols, pages * pools[0].shape[2],
                              out_dtype),
        _query_vmem_budget(pools, by_pools["kv_tile"], pages))


def _stacked(layer, *pools):
    """(layer id as int32[1], the pools with a leading layer axis).

    Every paged kernel reads a STACKED pool [L, NB, bs, ...]: the layer id
    is scalar-prefetched and leads each pool index map, so the engine's
    steps hand the whole pool over and no per-layer slice is materialised.
    A caller that holds one layer's pool (layer None) gets a unit axis."""
    if layer is None:
        return (jnp.zeros((1,), jnp.int32),
                tuple(None if p is None else p[None] for p in pools))
    return jnp.reshape(layer, (1,)).astype(jnp.int32), pools


def paged_attention_latent(q_lat: jnp.ndarray, q_pe: jnp.ndarray,
                           lat_pages: jnp.ndarray, pe_pages: jnp.ndarray,
                           page_table: jnp.ndarray, kv_lens: jnp.ndarray,
                           w_v: jnp.ndarray,
                           q_lens: Optional[jnp.ndarray] = None,
                           softmax_scale: Optional[float] = None,
                           lat_scales: Optional[jnp.ndarray] = None,
                           pe_scales: Optional[jnp.ndarray] = None,
                           mesh=None, layer=None) -> jnp.ndarray:
    """MLA latent-space ragged paged attention with absorbed q weights
    (ISSUE 17 tentpole) — the latent-family entry point.

    q_lat [B, nq, klat] (decode) or [B, S_q, nq, klat] with q_lens [B]
    (ragged multi-query): the ABSORBED query — q_nope (× YaRN mscale²
    when active) contracted through kv_up's k_nope columns, so block
    scores form directly in latent space. q_pe [..., nq, dpe]: the
    roped decoupled heads. lat_pages [NB, bs, klat] / pe_pages
    [NB, bs, dpe]: the compressed pool (NO per-head axis). w_v
    [klat, nq, dv]: kv_up's v columns. They are no operand of the kernel:
    the walk returns the normalised latent sum Σ p · latent
    [B(, S_q), nq, klat] in float32 and `_expand_values` takes it through
    w_v once a query row (ISSUE 39). lat_scales/pe_scales [NB, bs] fp32
    mark int8/fp8 pools (per-ROW scalar scales). softmax_scale is REQUIRED:
    the MLA scale 1/sqrt(dqk + dpe) is not derivable from the latent
    width. mesh: latent-COLUMN-shard over the tp axis (_tp_place_latent
    — MLA has no KV heads to split; it keeps a value path of its own);
    callers gate on tp_paged_eligible.
    layer: int32 scalar — the pools (and scale pools) are then STACKED
    with a leading layer axis and the kernel reads that layer's blocks.
    Returns [B(, S_q), nq, dv] in q_lat's dtype."""
    ragged = q_lens is not None
    if softmax_scale is None:
        raise ValueError(
            "paged_attention_latent requires softmax_scale: the MLA "
            "scale is 1/sqrt(qk_head_dim + qk_pos_emb_head_dim), which "
            "cannot be derived from the latent width")
    lid, (lat_pages, pe_pages, lat_scales, pe_scales) = _stacked(
        layer, lat_pages, pe_pages, lat_scales, pe_scales)
    if mesh is not None:
        return _tp_place_latent(q_lat, q_pe, lat_pages, pe_pages,
                                page_table, kv_lens, w_v, q_lens,
                                softmax_scale, lat_scales, pe_scales,
                                mesh, lid)
    if ragged:
        b, s_q, nq, klat = q_lat.shape
    else:
        b, nq, klat = q_lat.shape
        s_q = 1
    dpe = q_pe.shape[-1]
    bs = lat_pages.shape[2]
    mb = page_table.shape[1]
    pools, by_pools = _call_pools(
        [lat_pages, pe_pages], [lat_scales, pe_scales], mb, LATENT_KEY_TILE)
    queries, join = [q_lat, q_pe], None
    if ragged:
        s_q = _query_tile(queries, klat, pools, by_pools, jnp.float32)
        page_table, kv_lens, q_lens, queries, join = _query_tiled(
            s_q, page_table, kv_lens, q_lens, queries)
    spec = PagedSpec(ragged=ragged, s_q=s_q, block_size=bs,
                     num_blocks_seq=mb, hkv=nq, group=1,
                     scale=float(softmax_scale), latent=True, klat=klat,
                     dpe=dpe, **by_pools)
    summed = _walk_call(
        spec, _paged_name(ragged, spec.quant_dtype, "_latent"), lid,
        page_table, kv_lens, q_lens, queries, pools,
        jax.ShapeDtypeStruct(queries[0].shape, jnp.float32),
        (s_q * nq, klat))
    return _expand_values(join(summed) if ragged else summed,
                          w_v).astype(q_lat.dtype)


def _expand_values(summed, w_v):
    """A latent walk's normalised sums [..., nq, klat] float32 through
    kv_up's value columns w_v [klat, nq, dv] -> [..., nq, dv] float32, once
    a query row. The sums keep their float32: at the default precision the
    MXU would round them to bfloat16 first."""
    return jnp.einsum("...nk,knd->...nd", summed, w_v,
                      precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)


def _latent_block_scores(q, pages, page_table, kv_lens, lid, scales=None,
                         family="paged_decode"):
    """Phase 1 of the latent-column tp path: ALL block scores
    q · pages^T over the page table — q [B, rows, d] × layer lid[0] of the
    stacked pages [L, NB, bs, d] (scales [L, NB, bs])
    → [B, rows, MB*bs] fp32, NO softmax. Out-of-range blocks write 0 so
    the cross-shard psum of klat-column partials stays finite; the
    caller masks before its fp32 softmax. scales [NB, bs] fp32 mark a
    quantized pool (per-row scalar scales compose multiplicatively with
    column shards, so per-shard dequant partials sum exactly)."""
    b, rows, d = q.shape
    bs = pages.shape[2]
    mb = page_table.shape[1]
    quantized = scales is not None

    def kernel(lid_ref, *refs):
        table_ref, lens_ref, q_ref, kv_ref = refs[:4]
        rest = refs[4:]
        if quantized:
            sc_ref, o_ref = rest
        else:
            o_ref, = rest
        del lid_ref, table_ref
        b_ = pl.program_id(0)
        j = pl.program_id(1)
        kv_len = lens_ref[b_]

        @pl.when(j * bs < kv_len)
        def _compute():
            if quantized:
                kv = kv_ref[0].astype(jnp.float32) * sc_ref[0][:, None]
            else:
                kv = kv_ref[0]
            o_ref[0] = jnp.dot(q_ref[0].astype(kv.dtype), kv.T,
                               preferred_element_type=jnp.float32)

        @pl.when(j * bs >= kv_len)
        def _zero():
            o_ref[0] = jnp.zeros_like(o_ref)[0]

    kv_spec = pl.BlockSpec((None, 1, bs, d),
                           lambda b_, j, l, t, *_: (l[0], t[b_, j], 0, 0))
    q_spec = pl.BlockSpec((1, rows, d), lambda b_, j, *_: (b_, 0, 0))
    in_specs = [q_spec, kv_spec]
    operands = [q, pages]
    if quantized:
        in_specs.append(pl.BlockSpec(
            (None, 1, bs), lambda b_, j, l, t, *_: (l[0], t[b_, j], 0)))
        operands.append(scales)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, mb),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, rows, bs), lambda b_, j, *_: (b_, 0, j)),
    )
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, rows, mb * bs), jnp.float32),
        interpret=_interpret(),
        name=f"{family}_latent_scores",
    )(lid, page_table.astype(jnp.int32), kv_lens.astype(jnp.int32),
      *operands)


def _latent_block_wsum(p, pages, page_table, kv_lens, w_v, lid,
                       scales=None, family="paged_decode"):
    """Phase 2 of the latent-column tp path: probability-weighted value
    sum over the page table with the per-tile in-register re-expansion
    — p [B, rows, MB*bs] fp32 (masked softmax, zeros past each row's
    run) × layer lid[0] of the stacked pages [L, NB, bs, klat_local]
    through w_v [klat_local, nq, dv]
    → [B, rows, dv] fp32 partials (the caller psums over the klat
    shards)."""
    b, rows, _ = p.shape
    bs = pages.shape[2]
    mb = page_table.shape[1]
    nq, dv = w_v.shape[1], w_v.shape[2]
    s_q = rows // nq
    quantized = scales is not None
    mbs_ = mb

    def kernel(lid_ref, *refs):
        table_ref, lens_ref, p_ref, kv_ref = refs[:4]
        rest = refs[4:]
        if quantized:
            sc_ref, wv_ref, o_ref, acc = rest
        else:
            wv_ref, o_ref, acc = rest
        del lid_ref, table_ref
        b_ = pl.program_id(0)
        j = pl.program_id(1)

        @pl.when(j == 0)
        def _init():
            acc[:] = jnp.zeros_like(acc)

        kv_len = lens_ref[b_]

        @pl.when(j * bs < kv_len)
        def _compute():
            if quantized:
                lat = kv_ref[0].astype(jnp.float32) * sc_ref[0][:, None]
            else:
                lat = kv_ref[0]
            wv = wv_ref[...]
            v_t = jax.lax.dot_general(                    # [bs, nq, dv]
                lat, wv.astype(lat.dtype),
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            v3 = jnp.swapaxes(v_t, 0, 1)                  # [nq, bs, dv]
            p3 = jnp.transpose(p_ref[0].reshape(s_q, nq, bs), (1, 0, 2))
            pv = jax.lax.dot_general(                     # [nq, s_q, dv]
                p3.astype(v3.dtype), v3,
                (((2,), (1,)), ((0,), (0,))),
                preferred_element_type=jnp.float32)
            acc[:] += jnp.transpose(pv, (1, 0, 2)).reshape(rows, dv)

        @pl.when(j == mbs_ - 1)
        def _finalize():
            o_ref[0] = acc[:]

    kv_spec = pl.BlockSpec((None, 1, bs, pages.shape[-1]),
                           lambda b_, j, l, t, *_: (l[0], t[b_, j], 0, 0))
    p_spec = pl.BlockSpec((1, rows, bs), lambda b_, j, *_: (b_, 0, j))
    in_specs = [p_spec, kv_spec]
    operands = [p, pages]
    if quantized:
        in_specs.append(pl.BlockSpec(
            (None, 1, bs), lambda b_, j, l, t, *_: (l[0], t[b_, j], 0)))
        operands.append(scales)
    in_specs.append(pl.BlockSpec(w_v.shape, lambda b_, j, *_: (0, 0, 0)))
    operands.append(w_v)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, mb),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, rows, dv), lambda b_, j, *_: (b_, 0, 0)),
        scratch_shapes=[pltpu.VMEM((rows, dv), jnp.float32)],
    )
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, rows, dv), jnp.float32),
        interpret=_interpret(),
        name=f"{family}_latent_wsum",
    )(lid, page_table.astype(jnp.int32), kv_lens.astype(jnp.int32),
      *operands)


def _tp_place_latent(q_lat, q_pe, lat_pages, pe_pages, page_table,
                     kv_lens, w_v, q_lens, softmax_scale, lat_scales,
                     pe_scales, mesh, lid):
    """Latent-COLUMN sharded placement of the MLA kernel family: MLA
    has no KV heads to split, so the tp axis shards the klat dim of the
    latent pool, the absorbed query, and kv_up's v rows (q_pe / pe
    pages / per-row scales / table / lens stay replicated — the rope
    head and the scalar scales have no latent columns). The softmax
    couples every latent column, so the body runs TWO emitted kernels
    around a replicated fp32 softmax: block scores (nope partials
    psum'd over shards + replicated pe scores) → host mask/softmax →
    weighted value sum (dv partials psum'd). The pools arrive STACKED
    [L, NB, bs, ...] with the layer id lid int32[1] (replicated). The
    latent pool is read
    once per phase; the output is fully replicated (the psum), so the
    out-projection runs identically on every device and per-request
    streams stay engine-exact."""
    from jax.sharding import PartitionSpec as P

    from megatronapp_tpu.config.parallel_config import TP_AXIS
    from megatronapp_tpu.parallel.collectives import psum, shard_map_compat

    ragged = q_lens is not None
    family = _paged_name(ragged)
    dv = w_v.shape[-1]
    bs = lat_pages.shape[2]
    join = None
    if ragged:
        # The two kernels hold a row's whole query block, as the walk
        # does: a slot's queries go in as rows of one query tile
        # (`_query_tiled`; the mask below is l_ - qlens_ + row, a row's
        # own). Beside a tile VMEM holds a shard's rows of w_v twice (the
        # pipeline's buffers) and a page's values before and after they
        # are regrouped by head; 1 MiB is left over.
        tp = mesh.shape[TP_AXIS]
        nq = q_lat.shape[2]
        tile = query_rows_per_step(
            q_lat.shape[1],
            _latent_tp_row_vmem_bytes(nq, -(-q_lat.shape[3] // tp), bs, dv),
            VMEM_SCOPE - 2 * w_v.size // tp * w_v.dtype.itemsize
            - 2 * 4 * bs * nq * _padded(dv, 128) - (1 << 20))
        page_table, kv_lens, q_lens, (q_lat, q_pe), join = _query_tiled(
            tile, page_table, kv_lens, q_lens, [q_lat, q_pe])
        b, s_q, nq, klat = q_lat.shape
    else:
        b, nq, klat = q_lat.shape
        s_q = 1
    rows = s_q * nq
    mb = page_table.shape[1]
    quantized = lat_scales is not None
    out_dtype = q_lat.dtype

    q_sh = (P(None, None, None, TP_AXIS) if ragged
            else P(None, None, TP_AXIS))
    q_rep = (P(None, None, None, None) if ragged else P(None, None, None))
    pool_sh = P(None, None, None, TP_AXIS)
    pool_rep = P(None, None, None, None)
    rep3, rep2, rep1 = P(None, None, None), P(None, None), P(None)
    out_sh = (P(None, None, None, None) if ragged else P(None, None, None))

    in_specs = [q_sh, q_rep, pool_sh, pool_rep, rep2, rep1,
                P(TP_AXIS, None, None), rep1]
    operands = [q_lat, q_pe, lat_pages, pe_pages, page_table, kv_lens,
                w_v, lid]
    if ragged:
        in_specs.append(rep1)
        operands.append(q_lens)
    if quantized:
        in_specs += [rep3, rep3]
        operands += [lat_scales, pe_scales]

    def body(*args):
        it = iter(args)
        ql_, qp_, lat_, pe_, t_, l_, wv_, lid_ = (next(it)
                                                  for _ in range(8))
        qlens_ = next(it) if ragged else None
        ls_ = ps_ = None
        if quantized:
            ls_, ps_ = next(it), next(it)
        # fp32 flat rows with the softmax scale applied up front (the
        # shard partials must carry it identically).
        qlf = (ql_.astype(jnp.float32) * softmax_scale).reshape(
            b, rows, -1)
        qpf = (qp_.astype(jnp.float32) * softmax_scale).reshape(
            b, rows, -1)
        s_nope = _latent_block_scores(qlf, lat_, t_, l_, lid_, ls_,
                                      family)
        s_nope = psum(s_nope, TP_AXIS)
        # pe scores are replicated work (dpe is tiny) — identical on
        # every shard, no psum.
        s = s_nope + _latent_block_scores(qpf, pe_, t_, l_, lid_, ps_,
                                          family)
        pos = jnp.arange(mb * bs, dtype=jnp.int32)[None, None, :]
        if ragged:
            row_q = (jnp.arange(rows, dtype=jnp.int32)
                     // nq)[None, :, None]
            abs_q = (l_ - qlens_)[:, None, None] + row_q
        else:
            abs_q = (l_ - 1)[:, None, None]
        valid = (pos <= abs_q) & (pos < l_[:, None, None])
        s = jnp.where(valid, s, _NEG_INF)
        m = jnp.max(s, axis=-1, keepdims=True)
        pr = jnp.exp(s - jnp.maximum(m, _NEG_INF / 2))
        pr = jnp.where(valid, pr, 0.0)
        pr = pr / jnp.maximum(jnp.sum(pr, axis=-1, keepdims=True), 1e-20)
        out = _latent_block_wsum(pr, lat_, t_, l_, wv_, lid_, ls_,
                                 family)
        out = psum(out, TP_AXIS).astype(out_dtype)
        return (out.reshape(b, s_q, nq, dv) if ragged
                else out.reshape(b, nq, dv))

    # manual-ok: full-manual kernel placement; the only collectives are
    # the two psums over the klat shards. tp_paged_eligible callers
    # gate on no ambient manual axes.
    out = shard_map_compat(body, mesh, in_specs=tuple(in_specs),
                           out_specs=out_sh)(*operands)
    return join(out) if ragged else out


def paged_attention(q: jnp.ndarray, k_pages: jnp.ndarray,
                    v_pages: jnp.ndarray, page_table: jnp.ndarray,
                    kv_lens: jnp.ndarray,
                    q_lens: Optional[jnp.ndarray] = None,
                    softmax_scale: Optional[float] = None,
                    k_scales: Optional[jnp.ndarray] = None,
                    v_scales: Optional[jnp.ndarray] = None,
                    mesh=None, layer=None, window: int = 0) -> jnp.ndarray:
    """Ragged paged attention — the single generator entry point.

    window: > 0, a sliding-window layer's call (PagedSpec.window): a query
    sees the window - 1 keys before it and itself, the walk of a slot starts
    at the block that holds its first query's oldest key, and the table's
    entries before that block are never read (one device, bf16 pools).

    q [B, Hq, D] (decode) or [B, S_q, Hq, D] with q_lens [B] (ragged
    multi-query); k_pages/v_pages [NB, bs, Hkv, D]; page_table [B, MB]
    int32; kv_lens [B]. k_scales/v_scales [NB, bs, Hkv] fp32 mark int8
    pools (dequant rides the same page-table indirection, in-register).
    mesh: head-shard the emitted kernel over the tp axis of this mesh
    (full-manual shard_map — q on heads, pools + scale pools on Hkv,
    table/lens replicated); callers gate on tp_paged_eligible. layer:
    int32 scalar — the pools (and scale pools) are then STACKED
    [L, NB, bs, Hkv, D] and the kernel reads that layer's blocks (the
    engine's steps: the pool is a loop carry, never sliced). Returns q's
    shape."""
    ragged = q_lens is not None
    lid, (k_pages, v_pages, k_scales, v_scales) = _stacked(
        layer, k_pages, v_pages, k_scales, v_scales)
    if window and (mesh is not None or k_scales is not None):
        raise NotImplementedError(
            "a sliding-window walk runs on one device over bf16 pools: no "
            "mesh, no quantized pool")
    if mesh is not None:
        return _tp_place(q, k_pages, v_pages, page_table, kv_lens, q_lens,
                         softmax_scale, k_scales, v_scales, mesh, lid)
    if ragged:
        b, s_q, hq, d = q.shape
    else:
        b, hq, d = q.shape
        s_q = 1
    _, _, bs, hkv, _ = k_pages.shape
    mb = page_table.shape[1]
    if softmax_scale is None:
        softmax_scale = 1.0 / (d ** 0.5)
    pools, by_pools = _call_pools(
        [k_pages, v_pages], [k_scales, v_scales], mb)
    join = None
    if ragged:
        s_q = _query_tile([q], d, pools, by_pools)
        page_table, kv_lens, q_lens, (q,), join = _query_tiled(
            s_q, page_table, kv_lens, q_lens, [q])
    spec = PagedSpec(ragged=ragged, s_q=s_q, block_size=bs,
                     num_blocks_seq=mb, hkv=hkv, group=hq // hkv,
                     scale=float(softmax_scale), window=int(window),
                     **by_pools)
    out = _walk_call(spec, _paged_name(ragged, spec.quant_dtype,
                                       window=bool(window)), lid,
                     page_table, kv_lens, q_lens, [q], pools,
                     jax.ShapeDtypeStruct(q.shape, q.dtype),
                     (hkv, s_q * (hq // hkv), d))
    return join(out) if ragged else out


def _tp_place(q, k_pages, v_pages, page_table, kv_lens, q_lens,
              softmax_scale, k_scales, v_scales, mesh, lid):
    """Head-sharded placement of the emitted kernel: a FULL-MANUAL
    shard_map over the tp axis — q sharded on heads, the STACKED pools
    (and int8 scale pools) on Hkv, page table / lengths / q_lens / layer
    id replicated. Each
    shard owns matched GQA groups (contiguous slicing of both head dims
    preserves h // group), so the per-shard body is the UNMODIFIED
    emitted kernel; no collectives run inside. tp_paged_eligible callers
    gate on no ambient manual axes."""
    from jax.sharding import PartitionSpec as P

    from megatronapp_tpu.config.parallel_config import TP_AXIS
    from megatronapp_tpu.parallel.collectives import shard_map_compat

    ragged = q_lens is not None
    if softmax_scale is None:
        softmax_scale = 1.0 / (q.shape[-1] ** 0.5)
    head = (P(None, None, TP_AXIS, None) if ragged
            else P(None, TP_AXIS, None))
    pages = P(None, None, None, TP_AXIS, None)  # [L, NB, bs, Hkv, D]
    scales = P(None, None, None, TP_AXIS)       # [L, NB, bs, Hkv]
    rep2, rep1 = P(None, None), P(None)

    in_specs = [head, pages, pages, rep2, rep1, rep1]
    operands = [q, k_pages, v_pages, page_table, kv_lens, lid]
    if ragged:
        in_specs.append(rep1)
        operands.append(q_lens)
    if k_scales is not None:
        in_specs += [scales, scales]
        operands += [k_scales, v_scales]

    def body(*args):
        q_, k_, v_, t_, l_, lid_ = args[:6]
        rest = args[6:]
        ql_ = None
        if ragged:
            ql_, rest = rest[0], rest[1:]
        ks_ = vs_ = None
        if rest:
            ks_, vs_ = rest
        return paged_attention(q_, k_, v_, t_, l_, q_lens=ql_,
                               softmax_scale=softmax_scale,
                               k_scales=ks_, v_scales=vs_, layer=lid_[0])

    # manual-ok: full-manual kernel placement, no collectives in body;
    # tp_paged_eligible callers gate on no ambient manual axes.
    return shard_map_compat(body, mesh, in_specs=tuple(in_specs),
                            out_specs=head)(*operands)


# ---------------------------------------------------------------------------
# The pool writer: new rows go into the stacked pool in place
# ---------------------------------------------------------------------------


def paged_append(pool: jnp.ndarray, rows: jnp.ndarray, layer,
                 blocks: jnp.ndarray, offsets: jnp.ndarray,
                 mesh=None) -> jnp.ndarray:
    """Write `rows` into one layer of a stacked page pool, IN PLACE.

    pool [L, NB, bs, *row]; rows [N, *row]; layer int32 scalar; blocks /
    offsets [N] int32. Row i lands at pool[layer, blocks[i], offsets[i]];
    a row whose block id is NB or more is DROPPED, never clamped (an
    inactive slot's table may name a block another request owns now, and
    padding rows of a ragged chunk have no position). Returns the pool:
    the same buffer wherever the caller's own copy of it is dead (a
    donated jit argument, a loop carry), so a step touches the N rows it
    appends and not the pool.

    K/V rows ([Hkv, D]: whole tiled planes of the pool) go through ONE
    Pallas call whose output aliases the pool. Its grid runs over the
    VALID rows only (they are sorted first and their count bounds the
    grid, so a call with no valid row writes nothing), each step copying
    a row into the block the scalar-prefetched (layer, block, offset)
    name. Narrower rows (scale rows [Hkv], MLA latent rows [klat], latent
    scale rows []) are part of a tile, which no DMA addresses: they are
    written by one `dynamic_update_slice` a row, which XLA applies in
    place to a loop carry or a donated argument.

    mesh: the pool is sharded over the tp axis on its first row dim (Hkv)
    and the Pallas call is placed like the readers (`_tp_place`); the XLA
    rows need no placement (GSPMD partitions a dynamic_update_slice)."""
    nb = pool.shape[1]
    layer = jnp.asarray(layer, jnp.int32)
    blocks = blocks.astype(jnp.int32)
    offsets = offsets.astype(jnp.int32)
    row = rows.shape[1:]
    if len(row) < 2:
        tail = (0,) * len(row)

        def one(i, pool_):
            at = (layer, jnp.minimum(blocks[i], nb - 1), offsets[i]) + tail
            new = rows[i][None, None, None]
            old = jax.lax.dynamic_slice(pool_, at, new.shape)
            return jax.lax.dynamic_update_slice(
                pool_, jnp.where(blocks[i] < nb, new, old), at)

        return jax.lax.fori_loop(0, rows.shape[0], one, pool)
    if mesh is not None:
        from jax.sharding import PartitionSpec as P

        from megatronapp_tpu.config.parallel_config import TP_AXIS
        from megatronapp_tpu.parallel.collectives import shard_map_compat
        rest = (None,) * (len(row) - 1)
        pool_spec = P(None, None, None, TP_AXIS, *rest)
        # manual-ok: full-manual kernel placement, no collectives in body;
        # tp_paged_eligible callers gate on no ambient manual axes.
        return shard_map_compat(
            paged_append, mesh,
            in_specs=(pool_spec, P(None, TP_AXIS, *rest), P(), P(None),
                      P(None)),
            out_specs=pool_spec)(pool, rows, layer, blocks, offsets)

    valid = blocks < nb
    order = jnp.argsort(~valid, stable=True).astype(jnp.int32)
    zeros = (0,) * len(row)

    def kernel(lid_ref, blk_ref, off_ref, src_ref, rows_ref, pool_ref,
               out_ref):
        # where each row goes is all in the index maps; the pool is only
        # here to be aliased
        del lid_ref, blk_ref, off_ref, src_ref, pool_ref
        out_ref[...] = rows_ref[...]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(jnp.sum(valid, dtype=jnp.int32),),
        in_specs=[
            pl.BlockSpec((None,) + row,
                         lambda i, lid, blk, off, src: (src[i],) + zeros),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec(
            (None, None, None) + row,
            lambda i, lid, blk, off, src: (lid[0], blk[i], off[i]) + zeros),
    )
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(pool.shape, pool.dtype),
        input_output_aliases={5: 0},
        interpret=_interpret(),
        name="paged_append",
    )(layer.reshape(1), blocks[order], offsets[order], order, rows, pool)


def eva_summary(k_pool: jnp.ndarray, v_pool: jnp.ndarray, phi, mu, layer,
                src: jnp.ndarray, dst: jnp.ndarray, offsets: jnp.ndarray,
                scale: float):
    """EVA's chunk summariser (transformer/eva.py), IN PLACE: pool the rows
    of a page into one key and one value row of the same pools.

    k_pool / v_pool [L, NB, bs, Hkv, D] (a chunk is a page); phi, mu
    [Hkv, D]; layer int32 scalar; src / dst / offsets [N] int32. For each i
    the page (layer, src[i]) is read, alpha = softmax over its bs rows of
    scale * k . phi a head, and k~ = sum alpha k + mu, v~ = sum alpha v
    (float32 inside) land at row (layer, dst[i], offsets[i]). An i whose
    dst is NB or more is DROPPED: like `paged_append`, the valid ones are
    sorted first and their count bounds the grid, so a step that fills no
    chunk runs no grid step. Both pools are aliased to the outputs: a step
    touches the pages it pools and the rows it writes. No dst block may be
    a src block of the same call (the engine's are another region of a
    slot's table)."""
    nb, bs, hkv, d = k_pool.shape[1:]
    layer = jnp.asarray(layer, jnp.int32)
    valid = dst < nb
    order = jnp.argsort(~valid, stable=True).astype(jnp.int32)
    src = jnp.minimum(src.astype(jnp.int32), nb - 1)[order]
    dst = dst.astype(jnp.int32)[order]
    offsets = offsets.astype(jnp.int32)[order]

    def kernel(lid_ref, src_ref, dst_ref, off_ref, phi_ref, mu_ref, k_ref,
               v_ref, ko_ref, vo_ref):
        del lid_ref, src_ref, dst_ref, off_ref
        k = k_ref[...].astype(jnp.float32)                  # [bs, Hkv, D]
        v = v_ref[...].astype(jnp.float32)
        logit = jnp.sum(k * phi_ref[...].astype(jnp.float32)[None],
                        axis=-1, keepdims=True) * scale     # [bs, Hkv, 1]
        e = jnp.exp(logit - jnp.max(logit, axis=0, keepdims=True))
        alpha = e / jnp.sum(e, axis=0, keepdims=True)
        ko_ref[...] = (jnp.sum(alpha * k, axis=0)
                       + mu_ref[...].astype(jnp.float32)).astype(ko_ref.dtype)
        vo_ref[...] = jnp.sum(alpha * v, axis=0).astype(vo_ref.dtype)

    def whole(i, lid, src_, dst_, off):
        return (0, 0)

    def page(i, lid, src_, dst_, off):
        return (lid[0], src_[i], 0, 0, 0)

    def row(i, lid, src_, dst_, off):
        return (lid[0], dst_[i], off[i], 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(jnp.sum(valid, dtype=jnp.int32),),
        in_specs=[pl.BlockSpec((hkv, d), whole), pl.BlockSpec((hkv, d), whole),
                  pl.BlockSpec((None, None, bs, hkv, d), page),
                  pl.BlockSpec((None, None, bs, hkv, d), page)],
        out_specs=[pl.BlockSpec((None, None, None, hkv, d), row),
                   pl.BlockSpec((None, None, None, hkv, d), row)],
    )
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(k_pool.shape, k_pool.dtype),
                   jax.ShapeDtypeStruct(v_pool.shape, v_pool.dtype)],
        input_output_aliases={6: 0, 7: 1},
        interpret=_interpret(),
        name="eva_summary",
    )(layer.reshape(1), src, dst, offsets, phi, mu, k_pool, v_pool)


# ---------------------------------------------------------------------------
# Batched-LoRA delta kernels (ISSUE 19): one decode batch, many adapters
# ---------------------------------------------------------------------------
# The device half of inference/lora.py: a decode batch carries a per-row
# bank-slot id (0 = the NULL adapter), and every LoRA-targeted matmul
# adds delta[b] = (x[b] @ A_{id[b]}) @ B_{id[b]} to its base output.
# Two interchangeable per-row-exact implementations:
#
#   - lora_delta_reference  the jnp oracle AND the eager fallback:
#                           gather the per-row factors, two einsums in
#                           fp32;
#   - lora_segmented_delta  the emitted Pallas kernel: rows grouped into
#                           adapter SEGMENTS in-trace, the segment's
#                           adapter id scalar-prefetched like a page
#                           table so each grid step DMAs exactly one
#                           adapter's [din, rank]/[rank, dout] factors
#                           from the bank (vs the reference's [rows, …]
#                           gathered copies).
#
# Both compute row b's delta from row b's x and factors ONLY —
# never from batch composition — which is what makes a mixed-tenant
# batch token-exact vs serving each tenant serially.


# What one grid step of the segmented kernel may hold in VMEM (Mosaic's
# default scope is 16 MiB); a delta over it takes the eager fallback.
LORA_VMEM_BUDGET = 12 * 1024 * 1024


def lora_segment_info(row_adapter):
    """Group batch rows by adapter id, in-trace (no host sort, no
    dynamic shapes — O(B²) compares on a decode-batch-sized B).

    row_adapter [B] int32 bank slots → (seg_adapter [B], row_seg [B],
    nseg): segments are numbered by FIRST occurrence order;
    seg_adapter[s] is segment s's bank slot (0 for the unused tail
    s >= nseg, so padding grid steps DMA the NULL adapter's block);
    row_seg[b] is row b's segment."""
    ids = row_adapter.astype(jnp.int32)
    b = ids.shape[0]
    iota = jnp.arange(b, dtype=jnp.int32)
    first = jnp.argmax(ids[:, None] == ids[None, :], axis=1)  # [B]
    is_first = first == iota
    seg_of_first = jnp.cumsum(is_first.astype(jnp.int32)) - 1
    row_seg = seg_of_first[first]
    seg_adapter = jnp.zeros((b,), jnp.int32).at[row_seg].set(ids)
    nseg = jnp.sum(is_first.astype(jnp.int32))
    return seg_adapter, row_seg, nseg


def lora_delta_reference(x, a_bank, b_bank, row_adapter):
    """jnp oracle (and THE eager fallback for kernel-ineligible
    shapes): per-row gathered two-step product in fp32.

    x [B, din], a_bank [slots, din, rank], b_bank [slots, rank, dout],
    row_adapter [B] int32 → delta [B, dout] fp32 (callers cast when
    adding into the base matmul output)."""
    a = a_bank[row_adapter].astype(jnp.float32)       # [B, din, rank]
    b = b_bank[row_adapter].astype(jnp.float32)       # [B, rank, dout]
    t = jnp.einsum("bi,bir->br", x.astype(jnp.float32), a)
    return jnp.einsum("br,bro->bo", t, b)


def lora_kernel_ineligible_reason(din: int, dout: int, rank: int,
                                  rows: int) -> Optional[str]:
    """Why the segmented Pallas kernel may NOT serve this delta — None
    when eligible, else the FIRST failed predicate by name (the caller
    falls back to the eager gather, which is the oracle itself, so
    ineligible shapes lose speed, never correctness)."""
    if rank > min(din, dout):
        return (f"adapter rank {rank} exceeds min(din={din}, "
                f"dout={dout}) — a low-rank delta this fat is an eager "
                f"gather, not a segmented GEMM")
    # One grid step holds x [rows, din], one adapter's factors, the
    # rank-space intermediate and the fp32 accumulator + row_seg.
    need = 4 * (rows * din + din * rank + rank * dout
                + rows * rank + rows * dout + rows)
    if need > LORA_VMEM_BUDGET:
        return (f"segmented-LoRA kernel operands ({need} B at "
                f"rows={rows}, din={din}, dout={dout}, rank={rank}) "
                f"exceed the VMEM budget ({LORA_VMEM_BUDGET} B): the eager "
                f"fallback serves it")
    return None


def lora_segmented_delta(x, a_bank, b_bank, row_adapter):
    """The emitted segmented batched-LoRA GEMM.

    Grid = one step per row-SEGMENT (rows sharing an adapter), with the
    segment's bank slot scalar-prefetched (PrefetchScalarGridSpec —
    exactly how the paged kernels prefetch page tables) so each step's
    BlockSpec index map DMAs ONE adapter's A [din, rank] and
    B [rank, dout] blocks from the HBM bank. The step computes the full
    batch's delta through that adapter and accumulates only its own
    rows (mask by row_seg) — per-row results never depend on which
    OTHER rows share the batch. Unused tail segments (the grid is sized
    B, the worst case of B distinct adapters) index the NULL slot-0
    block and mask to nothing.

    x [B, din], banks [slots, din, rank]/[slots, rank, dout],
    row_adapter [B] int32 → delta [B, dout] fp32 — bit-for-bit the
    jnp oracle's dtype contract (fp32 accumulate, caller casts)."""
    b, din = x.shape
    rank = a_bank.shape[-1]
    dout = b_bank.shape[-1]
    seg_adapter, row_seg, _ = lora_segment_info(row_adapter)

    def kernel(seg_ref, rs_ref, x_ref, a_ref, b_ref, o_ref):
        s = pl.program_id(0)

        @pl.when(s == 0)
        def _init():
            o_ref[...] = jnp.zeros_like(o_ref)

        xv = x_ref[...].astype(jnp.float32)
        a = a_ref[0].astype(jnp.float32)              # [din, rank]
        bf = b_ref[0].astype(jnp.float32)             # [rank, dout]
        t = jax.lax.dot_general(xv, a, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
        d = jax.lax.dot_general(t, bf, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
        mine = (rs_ref[...] == s)[:, None]            # [B, 1]
        o_ref[...] += jnp.where(mine, d, 0.0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b,),
        in_specs=[
            pl.BlockSpec((b, din), lambda s, *_: (0, 0)),
            pl.BlockSpec((1, din, rank),
                         lambda s, seg, rs: (seg[s], 0, 0)),
            pl.BlockSpec((1, rank, dout),
                         lambda s, seg, rs: (seg[s], 0, 0)),
        ],
        out_specs=pl.BlockSpec((b, dout), lambda s, *_: (0, 0)),
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, dout), jnp.float32),
        interpret=_interpret(),
        name="lora_segmented_delta",
    )(seg_adapter, row_seg, x, a_bank, b_bank)


def lora_delta(x, a_bank, b_bank, row_adapter):
    """THE batched-LoRA delta entry point: the segmented kernel when
    eligible, else the eager gather fallback (= the oracle). Returns
    [B, dout] fp32."""
    b, din = x.shape
    rank = a_bank.shape[-1]
    dout = b_bank.shape[-1]
    if lora_kernel_ineligible_reason(din, dout, rank, b) is None:
        return lora_segmented_delta(x, a_bank, b_bank, row_adapter)
    return lora_delta_reference(x, a_bank, b_bank, row_adapter)


def _lora_rows_delta(x, bank_pair, row_adapter):
    """Delta for possibly-[B, S, din] x against one target's per-layer
    bank pair, broadcasting the per-SLOT adapter ids over S (the
    engine's batch dim is slots; every token row of a slot wears its
    slot's adapter). Returns x-shaped fp32 delta."""
    a_bank, b_bank = bank_pair
    if x.ndim == 2:
        return lora_delta(x, a_bank, b_bank, row_adapter)
    b, s, din = x.shape
    ids = jnp.repeat(row_adapter, s)
    flat = lora_delta(x.reshape(b * s, din), a_bank, b_bank, ids)
    return flat.reshape(b, s, -1)


def apply_lora_delta(y, x, lora, target):
    """Add ``target``'s adapter delta to base output y (computed from
    input x), when lora carries that target; no-op otherwise. The ONE
    call-site helper the forward passes use — delta in fp32,
    cast into y's dtype at the add (zero-B adapters add an exact 0.0
    and leave y's token stream bitwise unchanged)."""
    if lora is None or target not in lora["banks"]:
        return y
    d = _lora_rows_delta(x, lora["banks"][target], lora["row_adapter"])
    return y + d.astype(y.dtype)
