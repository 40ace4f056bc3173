"""Paged KV-cache block pool: allocator, prefix cache, preemption support.

vLLM-style block management for the continuous-batching engine
(inference/dynamic_engine.py): KV storage is a shared pool
shaped [L, num_blocks, block_size, Hkv, D] (MLA: the compressed latent
[L, num_blocks, block_size, kv_lora_rank] + shared roped key
[..., qk_pos_emb_head_dim] pair), and each slot owns an ordered page
table row [max_blocks_per_seq] int32. Capacity is admitted per block, so
a 6-token request costs one block, not an S_max row.

Prefix caching: full blocks are keyed by a rolling hash of the token
prefix they complete (hash chains over whole prefixes, so a hit
guarantees exact token equality up to the block boundary) and
refcounted. Blocks whose refcount drops to zero stay resident on an LRU
list and remain hittable until the allocator evicts them for fresh
demand. A request whose prompt fully hits still needs the last
position's logits, so its final block is **copy-on-write**: the shared
block's rows are copied into a private block and only the diverging row
is recomputed — shared blocks are never written.

All bookkeeping is host-side (numpy/python); the page DATA lives in jnp
arrays on `self.pages` and is only touched by jit-able scatter/gather
helpers (ops/pallas/paged_attention.py) plus the small copy-on-write
block copy here. The arrays are held ROW-MAJOR on their devices
(`pool_format`): the layout in which the Pallas kernels read and write
them, so the engine's steps update the one pool in place.

Quantized pools (ISSUE 10, ``kv_cache_dtype="int8"``): pages store int8
with a per-(row, kv-head) fp32 scale pool [L, NB, bs, Hkv] on
`self.scales` — rows quantize independently on insert
(quantize_kv_rows), so every page-table operation here (CoW, refcounts,
prefix hashing, transfer, rewind) is UNCHANGED: block identity and
sharing semantics never depend on the storage dtype. Capacity
accounting (`bytes_total`, `bytes_per_block`) reads the addressable
arrays, so it is dtype-aware by construction. MLA pools (ISSUE 17)
quantize the same way: the latent row [bs, klat] and roped-key row
[bs, dpe] have no kv-head axis, so their scale pools are per-row
SCALARS [L, NB, bs] — `quantize_kv_rows` over the trailing dim yields
exactly that layout, and every pool-shaped operation here is generic
over the per-pool trailing dims.

fp8 pools (ISSUE 13, ``kv_cache_dtype="fp8"``): same scale-pool layout
as int8 but the pages store e4m3 — quantize_kv_rows maps each row's
absmax to the e4m3 range bound (448) and saturate-casts, dropping the
integer rounding step; dequant stays the same cast-and-scale in-kernel
path. The storage dtypes, their CLI choices, and every validation
message derive from the one KV_CACHE_DTYPES registry below."""

from __future__ import annotations

import dataclasses
import hashlib
from collections import OrderedDict, deque
from typing import List, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from megatronapp_tpu.config.transformer_config import TransformerConfig
from megatronapp_tpu.utils import chaos
from megatronapp_tpu.utils import metrics as telemetry
from megatronapp_tpu.utils.platform import fresh_compiles


def cdiv(a: int, b: int) -> int:
    return -(-a // b)


def prefix_block_keys(tokens, block_size: int, limit: int) -> List[bytes]:
    """Rolling hash per FULL block of tokens[:limit]: key i commits to
    the whole prefix through block i, so a table hit is an exact prefix
    match. The ONE hashing implementation shared by the pool's prefix
    cache and the fleet router's affinity map (inference/fleet.py) — a
    hash mismatch between them would silently zero the affinity signal,
    so neither side rolls its own."""
    tokens = np.asarray(tokens, np.int32)
    keys: List[bytes] = []
    digest = b""
    for i in range(limit // block_size):
        digest = hashlib.sha1(
            digest + np.ascontiguousarray(
                tokens[i * block_size:(i + 1) * block_size],
                dtype=np.int32).tobytes()
        ).digest()
        keys.append(digest)
    return keys


@dataclasses.dataclass(frozen=True)
class KvDtypeSpec:
    """One KV-cache storage dtype (the SHARED registry entry): the pool
    check, the CLI choices/help, and the server-side validation all
    derive from KV_CACHE_DTYPES so adding a dtype cannot leave them
    disagreeing (ISSUE 13 satellite). Quantized entries take their
    page dtype and range bound from the KERNEL registry
    (ops/pallas/kernel_gen.QUANT_DTYPES — the same map quantize_kv_rows
    and the PagedSpec quant-dtype axis consume), so a new storage
    format lands there once and flows to the CLI/pool/kernels
    together."""
    name: str
    page_dtype: object          # jnp dtype of the page pools (None = compute)
    quantized: bool             # per-(row, kv-head) fp32 scale pool present
    qmax: Optional[float]       # symmetric quantization range bound
    help: str                   # one-line CLI help fragment


def _quantized_spec(name: str, help_text: str) -> KvDtypeSpec:
    from megatronapp_tpu.ops.pallas.kernel_gen import QUANT_DTYPES
    dtype, _tile, qmax = QUANT_DTYPES[name]
    return KvDtypeSpec(name, dtype, True, qmax, help_text)


KV_CACHE_DTYPES = {
    "bf16": KvDtypeSpec("bf16", None, False, None,
                        "compute-dtype pages (the baseline)"),
    "int8": _quantized_spec(
        "int8",
        "int8 pages + per-(row, kv-head) fp32 scales, rounded "
        "symmetric [-127, 127], dequantized in-kernel per DMA'd block"),
    "fp8": _quantized_spec(
        "fp8",
        "fp8 (e4m3) pages + per-(row, kv-head) fp32 scales — same "
        "bytes as int8 but saturating float rounding (no integer "
        "rounding step), dequantized in-kernel per DMA'd block"),
}


def kv_cache_dtype_help() -> str:
    """CLI help text for --kv-cache-dtype, derived from the registry."""
    return "; ".join(f"{n}: {s.help}" for n, s in KV_CACHE_DTYPES.items())


def validate_kv_cache_dtype(name: str, *, mla: bool = False) -> KvDtypeSpec:
    """Single source of truth for kv_cache_dtype validation: the pool
    constructor and the parse-time CLI check both raise THIS message
    (ValueError; entry points wrap in SystemExit)."""
    spec = KV_CACHE_DTYPES.get(name)
    if spec is None:
        raise ValueError(
            f"kv_cache_dtype must be one of "
            f"{sorted(KV_CACHE_DTYPES)}, got {name!r}")
    # mla is accepted (and kept in the signature) so call sites document
    # the layout they validate for; quantized MLA pools are supported
    # since ISSUE 17 (per-row scalar scales on the latent/pe pools).
    del mla
    return spec


# What a tenant of a slot's cache other than plain K/V rows cannot do yet.
# One row a kind of per-slot state: (whether a model has it, the sentence a
# refusal opens with: what the state is, why, and the ROADMAP id that holds
# the work, {capability it lacks: what asking for it would take}). A
# capability a row does not list works on such a model. The capabilities:
#   rewind    drop a slot's newest rows again (spec_method)
#   snapshot  copy a slot's cache out and in again (spill_host_mb,
#             export_request / import_request, the pool's export_slot /
#             import_slot)
#   handoff   fill a slot in one place and adopt it in another (an injected
#             pool, staging slots, adopt_request, the pool's transfer_slot)
#   adapters  per-row LoRA deltas in the paged steps (adapter_cache)
#   shard     a serving mesh (ctx)
#   quantize  int8/fp8 rows (kv_cache_dtype)
#   prefix    serve a prompt's head from another request's blocks; asked for
#             by default, so it is switched off and not refused
# A table of facts with one reader, check_tenants: a model PR adds a row.
_STATE_LACKS = {
    "rewind": "speculative decoding rewinds rejected tokens",
    "snapshot": "parking or moving a session",
    "handoff": "disaggregated prefill hands a sequence over by its page "
               "table",
    "adapters": "lora",
    "shard": "a serving mesh",
    "prefix": "a prefix hit would skip tokens whose state nobody kept",
}
_STATE_WHY = ("this model has {}, whose recurrent state lives in the paged "
              "engine's slots on one device and has no snapshot yet (state "
              "snapshots: ROADMAP M4)")
TENANT_LACKS = {
    "ssm": (lambda cfg: cfg.num_ssm_layers > 0,
            _STATE_WHY.format("recurrent mixers (state-space or Kimi delta "
                              "attention layers)"), _STATE_LACKS),
    "conv": (lambda cfg: cfg.num_conv_layers > 0,
             _STATE_WHY.format("gated short-convolution layers"),
             _STATE_LACKS),
    "eva": (lambda cfg: cfg.is_eva,
            "this model's attention is EVA, whose chunk summaries live in a "
            "second region of each slot's page table on one device, pooled "
            "from that slot's own cached rows, and have no snapshots yet "
            "(ROADMAP M4)",
            dict(_STATE_LACKS, quantize="a quantized pool",
                 prefix="a prefix hit would skip tokens whose summaries "
                        "nobody kept")),
    "window": (lambda cfg: cfg.num_window_layers > 0,
               "this model has sliding-window attention layers, whose planes "
               "of the paged cache give a slot's blocks back as they fall "
               "behind the window and hold the rows that are left on one "
               "device, with no snapshot yet (window planes: ROADMAP M1)",
               {"rewind": "speculative decoding rewinds rejected tokens, "
                          "past a block the window planes may have given "
                          "back",
                "snapshot": "parking or moving a session",
                "handoff": "disaggregated prefill hands a sequence over by "
                           "its page table, and the window planes have a "
                           "table of their own",
                "adapters": "lora",
                "shard": "a serving mesh",
                "quantize": "a quantized pool",
                "prefix": "a prefix hit would need the window planes' last "
                          "rows of the prefix, which are gone"}),
    # two planes a layer, or a held share of the experts
    "double": (lambda cfg: (cfg.moe_shortcut_double_layer
                            or cfg.moe_experts_held is not None),
               "this model runs double layers over two planes of the paged "
               "pools and holds a share of its experts on one device "
               "(ROADMAP M3, M6)",
               {"handoff": "disaggregated prefill fills a dense one-plane "
                           "cache",
                "adapters": "lora on latent attention and experts",
                "shard": "a serving mesh: the all-to-all between expert "
                         "shares and a latent pool sharded under two "
                         "sublayers",
                "quantize": "int8/fp8 latent pools under the scaled "
                            "latent"}),
}


def check_tenants(cfg: TransformerConfig, asked=None) -> frozenset:
    """The capabilities `cfg`'s cache lacks (TENANT_LACKS). `asked`:
    {capability: the argument or call that asks for it}; a ValueError
    names every one of them that a kind of state this model keeps lacks."""
    lacking = set()
    for has, why, lacks in TENANT_LACKS.values():
        if not has(cfg):
            continue
        refused = [f"{how} ({lacks[cap]})"
                   for cap, how in (asked or {}).items() if cap in lacks]
        if refused:
            raise ValueError(
                f"{why}: cannot serve it with " + "; ".join(refused))
        lacking.update(lacks)
    return frozenset(lacking)


@dataclasses.dataclass
class AdmitPlan:
    """Result of admitting a token sequence into a slot."""
    blocks: List[int]        # page-table row, sequence order
    cached_tokens: int       # leading tokens whose KV is already resident
    cow: bool                # last block was copy-on-write'd (full hit)


def pool_format(sharding, ndim: int):
    """How a pool array of `ndim` dims lies on the devices of `sharding`:
    row-major, the order in which every Pallas kernel addresses it.

    It has to be said: a TPU's default layout for an array whose minor
    dim is not a multiple of 128 lanes (head dim 80 or 64) makes ANOTHER
    axis minor-most to save the padding (the block axis, for a pool), and
    a step that hands such an array to a kernel relayouts all of it on
    the way in and again on the way out. Row-major pads D to 128 lanes on
    the device instead (1.6x the bytes at D 80); `bytes_total` counts the
    elements, as before. On a CPU this is the default layout. Row-major
    still leaves the chip its tile over the last two dims: a pool of ONE
    key/value head of 128 lies padded to two rows a key in HBM
    (`bf16[.., 1, 128]` as T(2,128)(2,1)), which is why its view
    [.., bs, 128] is a copy of the pool and `kernel_gen._heads_fold` takes
    two and four heads only."""
    from jax.experimental.layout import Format, Layout
    return Format(Layout(major_to_minor=tuple(range(ndim))), sharding)


def _in_pool_format(a, sharding=None):
    """`a` committed to `sharding` (default: where it is) in pool_format;
    `a` itself when it already is, as every step's output is."""
    import jax
    if sharding is None:
        sharding = a.sharding
    if not (a.committed and a.sharding == sharding):
        # manual-ok: host-side pool placement (may move the array; its
        # layout follows below), no manual region
        a = jax.device_put(a, sharding)
    if a.format.layout.major_to_minor != tuple(range(a.ndim)):
        with fresh_compiles():
            # manual-ok: host-side relayout of a pool array
            a = jax.device_put(a, pool_format(sharding, a.ndim))
    return a


def _new_pool(shape, dtype, fill):
    """A pool array of `fill`s on the default device, made there in
    pool_format (never in the default layout first: at a deployment's
    size the two do not fit side by side)."""
    import jax
    fmt = pool_format(jnp.zeros((), dtype).sharding, len(shape))
    with fresh_compiles():
        return jax.jit(lambda: jnp.full(shape, fill, dtype),
                       out_shardings=fmt)()


class PagedKVCache:
    """Block pool + page tables + refcounted prefix cache."""

    def __init__(self, cfg: TransformerConfig, max_batch: int,
                 max_seq_len: int, num_blocks: Optional[int] = None,
                 block_size: int = 16, enable_prefix_caching: bool = True,
                 extra_slots: int = 0, kv_cache_dtype: str = "bf16",
                 window_call_rows: Optional[int] = None):
        """window_call_rows: on a model with sliding-window layers, the most
        rows ONE call appends to a slot (the engine's prefill width; None:
        max_seq_len, so any call fits): the window planes are sized for
        every slot's window (`window_blocks_slot`) and one such call."""
        dtype_spec = validate_kv_cache_dtype(
            kv_cache_dtype, mla=cfg.multi_latent_attention)
        self.lacks = check_tenants(cfg, {cap: how for cap, how, on in (
            ("quantize", f"kv_cache_dtype {kv_cache_dtype!r}",
             dtype_spec.quantized),
            ("handoff", "staging slots (disaggregated prefill)",
             extra_slots)) if on})
        if "prefix" in self.lacks:
            enable_prefix_caching = False
        self.cfg = cfg
        self.kv_cache_dtype = kv_cache_dtype
        self.dtype_spec = dtype_spec
        self.quantized = dtype_spec.quantized
        self.max_batch = max_batch
        self.max_seq_len = max_seq_len
        self.block_size = block_size
        self.max_blocks_per_seq = cdiv(max_seq_len, block_size)
        # EVA attention (transformer/eva.py): a slot's table row is TWO
        # regions the kernels walk as one, the summary rows of its closed
        # windows (one a chunk, `_eva_spb` blocks a window) and behind them
        # the open window's exact rows (at most `_eva_wb` blocks); its last
        # `_eva_spb` columns, which no kernel walks, name the blocks the
        # open window's summaries are written to as its chunks fill. When a
        # window closes those blocks join the first region and the window's
        # exact blocks go back to the free list (`_eva_close`), so a slot
        # holds cdiv(R(T)) blocks and a few, never cdiv(T). `_eva_counts`
        # [slot] = blocks of (summaries, open window, pending summaries);
        # `_slot_blocks` lists them in that order.
        self.eva = cfg.is_eva
        if self.eva:
            from megatronapp_tpu.transformer.eva import (
                summary_blocks_per_window,
            )
            self._eva_spb = summary_blocks_per_window(cfg, block_size)
            self._eva_wb = cfg.eva_window_size // block_size
            self.max_blocks_per_seq = self._eva_wb + self._eva_spb * cdiv(
                max_seq_len, cfg.eva_window_size)
        # Default pool = dense capacity (max_batch full sequences); size
        # it down for the actual workload to realize the memory win.
        self.num_blocks = (num_blocks if num_blocks is not None
                           else max_batch * self.max_blocks_per_seq)
        self.enable_prefix_caching = enable_prefix_caching
        # extra_slots: staging page-table rows past the engine's decode
        # slots — the disaggregated prefill side (inference/disagg.py)
        # admits in-flight prefills there and hands finished ones to a
        # decode slot via transfer_slot (pure bookkeeping, no KV copy).
        self.num_slots = max_batch + extra_slots

        # A plane an attention sublayer: a hybrid stack's state-space
        # layers cache no token (their state is `self.state`, below), a
        # shortcut-connected double layer's two sublayers own planes
        # 2·layer and 2·layer + 1.
        l = cfg.kv_planes
        nb, bs = self.num_blocks, self.block_size
        # scales: per-(row, kv-head) fp32 quantization scales for int8
        # pools (None for bf16) — scattered/copied exactly like the data
        # pools (same leading [L, NB, bs] dims).
        self.scales = None
        dt = dtype_spec.page_dtype if self.quantized else cfg.compute_dtype
        if cfg.multi_latent_attention:
            # The latent/pe rows have no kv-head axis — the scales are
            # one SCALAR per (layer, block, row).
            shapes = ((l, nb, bs, cfg.kv_lora_rank),
                      (l, nb, bs, cfg.qk_pos_emb_head_dim))
            sshape = (l, nb, bs)
        else:
            shapes = ((l, nb, bs, cfg.num_query_groups, cfg.head_dim),) * 2
            sshape = (l, nb, bs, cfg.num_query_groups)
        self.pages = tuple(_new_pool(sh, dt, 0) for sh in shapes)
        if self.quantized:
            self.scales = tuple(_new_pool(sshape, jnp.float32, 1)
                                for _ in shapes)

        # The second tenant: the recurrent state of a hybrid stack's
        # recurrent mixers (state-space layers, transformer/ssm.py, or Kimi
        # delta attention, transformer/kda.py), which no page table
        # names. A slot owns row `slot` of every layer's plane, whatever
        # its sequence's length: h [N, E] in float32 (E minor: a whole
        # number of 128-lane vregs; a Mamba-2 or Kimi-delta-attention
        # layer's matrix state a head is that head's columns of it, 4 MiB a
        # slot a layer at [128, 8192])
        # and the convolution's last k-1 inputs (Mamba-1: E columns each;
        # Mamba-2: x, B and C, E + 2N; Kimi delta attention: q, k and v, 3E)
        # in the compute type, side by side in one row [(k-1) * C] (as
        # [L, slots, k-1, E] XLA pads 3 taps to 4 sublanes and relayouts
        # all of it on the way into a decode step and out again; as
        # [L, k-1, slots, E] it does the same in a prefill call). Nothing
        # here writes them: the engine's steps carry, donate and update
        # them in place like the pages, and a sequence's first prefill
        # call starts from zeros whatever the slot held, so admission,
        # release and preemption have nothing to do. They cannot be
        # shared, exported or rewound (yet): TENANT_LACKS holds what that
        # refuses.
        # A hybrid stack of gated short convolutions
        # (transformer/shortconv.py) has no h: its tenant is the one tail
        # pool [L_conv, slots, (k-1) * H], held and carried the same way.
        self.state = None
        if cfg.num_recurrent_layers:
            if cfg.num_conv_layers:
                self.state = (_new_pool(
                    (cfg.num_conv_layers, max_batch,
                     (cfg.shortconv_kernel - 1) * cfg.hidden_size),
                    cfg.compute_dtype, 0),)
            else:
                self.state = (
                    _new_pool((cfg.num_ssm_layers, max_batch,
                               cfg.ssm_state_dim, cfg.ssm_inner),
                              jnp.float32, 0),
                    _new_pool((cfg.num_ssm_layers, max_batch,
                               (cfg.ssm_conv_kernel - 1)
                               * cfg.ssm_conv_channels),
                              cfg.compute_dtype, 0))

        # The window planes: a sliding-window stack's window layers cache
        # their rows in pools of their OWN, [L_window, NB_window, bs, Hkv,
        # D], under a table and a free list of their own. A query there
        # sees its last cfg.sliding_window keys, so a block that lies wholly
        # behind the window of the slot's next query is given back
        # (`window_ensure`) and a slot holds window/bs + 2 blocks at most
        # between calls, whatever its length, plus a call's rows while one
        # runs. The table row keeps a sequence's block j in column j, like
        # the full planes': the columns of blocks given back go stale, and
        # the window walk never reads them (kernel_gen._window_first).
        # `_window_first[slot]`: the first block the slot still holds;
        # `_window_blocks[slot]`: those it holds, in order from there.
        self.window = cfg.sliding_window if cfg.num_window_layers else 0
        self.window_pages = None
        self.window_stats = {"blocks_taken": 0, "blocks_given_back": 0,
                             "peak_blocks_held": 0, "max_blocks_slot": 0}
        if self.window:
            call = (max_seq_len if window_call_rows is None
                    else min(window_call_rows, max_seq_len))
            self.num_window_blocks = min(
                self.num_slots * cdiv(max_seq_len, bs),
                self.num_slots * self.window_blocks_slot
                + cdiv(call, bs) + 1)
            wshape = (cfg.num_window_layers, self.num_window_blocks, bs,
                      cfg.num_query_groups, cfg.head_dim)
            self.window_pages = tuple(_new_pool(wshape, dt, 0)
                                      for _ in range(2))
            self.window_table = np.zeros(
                (self.num_slots, self.max_blocks_per_seq), np.int32)
            self._window_free: deque = deque(range(self.num_window_blocks))
            self._window_first = np.zeros((self.num_slots,), np.int64)
            self._window_blocks: List[List[int]] = [
                [] for _ in range(self.num_slots)]

        self.page_table = np.zeros((self.num_slots, self.max_blocks_per_seq),
                                   np.int32)
        self._free: deque = deque(range(nb))
        self._refcount = np.zeros((nb,), np.int32)
        self._table: dict = {}            # prefix hash -> block id
        self._hash_of: dict = {}          # block id -> prefix hash
        self._lru: OrderedDict = OrderedDict()  # rc==0 hashed blocks
        self._slot_blocks: List[List[int]] = [
            [] for _ in range(self.num_slots)]
        self._eva_counts = np.zeros((self.num_slots, 3), np.int64)
        self.eva_stats = {"windows_closed": 0, "blocks_freed": 0,
                          "max_blocks_slot": 0}
        self.stats = {"prefix_hit_tokens": 0, "prefill_tokens": 0,
                      "cow_copies": 0, "evictions": 0, "preemptions": 0,
                      "peak_blocks_in_use": 0, "handoff_transfers": 0,
                      "slot_exports": 0, "slot_imports": 0,
                      "prefix_block_exports": 0, "prefix_block_imports": 0}
        # Fleet-router hooks (inference/fleet.py): prefix_listener(keys)
        # fires with every batch of NEWLY registered prefix-block hashes
        # (the router's hash→replica affinity map is fed from these
        # events); flush_listener() fires when the prefix cache is
        # flushed (rolling reload — the router must drop this replica's
        # affinity entries, or it would keep steering sessions to it for
        # stale-weight "hits"). Both default to None (zero cost).
        self.prefix_listener = None
        self.flush_listener = None

    # ---- placement -------------------------------------------------------
    @property
    def pages(self) -> Optional[Tuple[jnp.ndarray, ...]]:
        """The data pools (K, V — MLA: latent, k_pe), each in pool_format.
        Whatever is assigned is put into it: an engine step hands its
        pools back as they are, an eager `.at[].set` of the writers below
        hands back the default layout. None frees them."""
        return self._pages

    @pages.setter
    def pages(self, new):
        self._pages = (None if new is None
                       else tuple(_in_pool_format(a) for a in new))

    @property
    def state(self) -> Optional[Tuple[jnp.ndarray, ...]]:
        """The second tenant's pools, held like `pages`: (ssm, conv) of a
        model with state-space layers, (conv,) of one with gated short
        convolutions, else None."""
        return self._state

    @state.setter
    def state(self, new):
        self._state = (None if new is None
                       else tuple(_in_pool_format(a) for a in new))

    @property
    def window_pages(self) -> Optional[Tuple[jnp.ndarray, ...]]:
        """The window planes' pools (K, V) of a model with sliding-window
        layers (else None), held like `pages`."""
        return self._window_pages

    @window_pages.setter
    def window_pages(self, new):
        self._window_pages = (None if new is None
                              else tuple(_in_pool_format(a) for a in new))

    @property
    def scales(self) -> Optional[Tuple[jnp.ndarray, ...]]:
        """The fp32 scale pools of a quantised pool (else None), held
        like `pages`."""
        return self._scales

    @scales.setter
    def scales(self, new):
        self._scales = (None if new is None
                        else tuple(_in_pool_format(a) for a in new))

    def place_pages(self, sharding, scales_sharding=None):
        """Commit the page pools to an explicit device placement (tp
        serving mesh: sharded on the Hkv dim — MLA: latent columns —
        so each device holds 1/tp of the pool; disaggregated serving:
        the decode sub-mesh). Quantized pools place their scale pools
        alongside (scales_sharding). `sharding` / `scales_sharding` may
        each be a single sharding applied to every pool, OR a sequence
        with one entry per pool (the MLA tp layout shards the latent
        pool but replicates the pe pool). Later jnp updates (CoW copy,
        the engine's steps) preserve the committed sharding by
        propagation, and the setters keep the layout."""
        def _per_pool(sh, n):
            if isinstance(sh, (list, tuple)):
                assert len(sh) == n, (len(sh), n)
                return tuple(sh)
            return (sh,) * n

        data_sh = _per_pool(sharding, len(self.pages))
        # manual-ok: host-side pool placement, no manual region
        self.pages = tuple(_in_pool_format(p, s)
                           for p, s in zip(self.pages, data_sh))
        if self.scales is not None:
            sc_sh = _per_pool(scales_sharding if scales_sharding is not None
                              else sharding, len(self.scales))
            self.scales = tuple(
                # manual-ok: host-side pool placement, no manual region
                _in_pool_format(s, sh)
                for s, sh in zip(self.scales, sc_sh))

    # ---- sizing ----------------------------------------------------------
    def _arrays(self):
        return self.pages + (self.scales or ())

    @property
    def bytes_total(self) -> int:
        """Resident cache bytes, dtype-aware: int8 data + fp32 scales for
        quantized pools, compute-dtype data otherwise, and the recurrent
        state of a model that has it — always read off the addressable
        arrays, never derived from the param dtype."""
        return self.num_blocks * self.bytes_per_block \
            + self.max_batch * self.state_bytes_per_slot \
            + self.window_bytes_total

    @property
    def window_bytes_total(self) -> int:
        """What the window planes take (0 without sliding-window layers)."""
        return sum(p.size * p.dtype.itemsize
                   for p in self.window_pages or ())

    @property
    def window_blocks_slot(self) -> int:
        """The most window-plane blocks a slot holds between calls: the
        window's rows lie in at most cdiv(window, bs) + 1 blocks, and the
        block the next row opens is taken before the one it closes behind
        the window is given back."""
        return cdiv(self.window, self.block_size) + 2

    def window_blocks_held(self) -> int:
        return self.num_window_blocks - len(self._window_free)

    def window_slot_blocks(self, slot: int) -> List[int]:
        """The window-plane blocks `slot` holds, from its first on."""
        return list(self._window_blocks[slot])

    def bytes_held(self) -> int:
        """Pool bytes the live slots' blocks take now, full planes and
        window planes together (prefix-cached blocks no slot holds are not
        counted)."""
        held = self.blocks_in_use() * self.bytes_per_block
        if self.window:
            held += (self.window_blocks_held() * self.window_bytes_total
                     // self.num_window_blocks)
        return held

    @property
    def bytes_per_block(self) -> int:
        """What one block of cached tokens takes (pages and scales)."""
        return sum(p.size * p.dtype.itemsize
                   for p in self._arrays()) // self.num_blocks

    @property
    def state_bytes_per_slot(self) -> int:
        """What one slot's recurrent state takes (0 without recurrent
        mixers)."""
        return sum(p.size * p.dtype.itemsize
                   for p in self.state or ()) // self.max_batch

    def blocks_in_use(self) -> int:
        """Blocks with live references (excludes free + evictable)."""
        return self.num_blocks - len(self._free) - len(self._lru)

    def available_blocks(self) -> int:
        return len(self._free) + len(self._lru)

    def free_blocks(self) -> int:
        return len(self._free)

    def evictable_blocks(self) -> int:
        return len(self._lru)

    def refcount(self, block: int) -> int:
        return int(self._refcount[block])

    def slot_blocks(self, slot: int) -> List[int]:
        return list(self._slot_blocks[slot])

    # ---- low-level block lifecycle --------------------------------------
    def _take_free(self) -> Optional[int]:
        if self._free:
            return self._free.popleft()
        if self._lru:
            # Chaos site fires BEFORE the eviction mutates anything, so
            # an injected fault leaves the allocator consistent and the
            # caller's rollback (admit/_rollback) owns the cleanup.
            chaos.fire("paged-evict")
            blk, _ = self._lru.popitem(last=False)   # least recently used
            key = self._hash_of.pop(blk, None)
            if key is not None and self._table.get(key) == blk:
                del self._table[key]
            self.stats["evictions"] += 1
            telemetry.inc("paged_evictions")
            return blk
        return None

    def _acquire_cached(self, blk: int):
        self._refcount[blk] += 1
        self._lru.pop(blk, None)

    def _release_block(self, blk: int):
        self._refcount[blk] -= 1
        assert self._refcount[blk] >= 0, f"block {blk} over-released"
        if self._refcount[blk] == 0:
            if blk in self._hash_of:
                self._lru[blk] = None    # evictable, still hittable
            else:
                self._free.append(blk)

    def _copy_block(self, src: int, dst: int):
        # Chaos site fires before the copy: pages/stats untouched, the
        # caller's rollback returns src's ref and dst to the pool.
        chaos.fire("paged-cow")
        self.pages = tuple(p.at[:, dst].set(p[:, src]) for p in self.pages)
        if self.scales is not None:
            # Rows quantize independently, so CoW copies scales verbatim
            # alongside the int8 rows — no re-quantization.
            self.scales = tuple(s.at[:, dst].set(s[:, src])
                                for s in self.scales)
        self.stats["cow_copies"] += 1
        telemetry.inc("paged_cow_copies")

    def _note_usage(self):
        self.stats["peak_blocks_in_use"] = max(
            self.stats["peak_blocks_in_use"], self.blocks_in_use())

    # ---- prefix hashing --------------------------------------------------
    def _block_keys(self, tokens: np.ndarray, limit: int) -> List[bytes]:
        """Rolling hash per FULL block of tokens[:limit] (delegates to
        the module-level prefix_block_keys — the implementation shared
        with the fleet router's affinity map)."""
        return prefix_block_keys(tokens, self.block_size, limit)

    # ---- engine-facing API ----------------------------------------------
    def admit(self, slot: int, tokens: np.ndarray) -> Optional[AdmitPlan]:
        """Install blocks covering `tokens` into `slot`'s page table,
        reusing cached prefix blocks. Returns None (state rolled back)
        when the pool cannot supply the fresh blocks."""
        assert not self._slot_blocks[slot], f"slot {slot} still holds blocks"
        p_len = len(tokens)
        if self.eva:
            # The prefill takes its blocks a call at a time (ensure_rows)
            # and gives windows back as they close; nothing else allocates
            # meanwhile, so room for its largest holding now is room
            # throughout.
            if self.blocks_for(p_len + 1) > self.available_blocks():
                return None
            self.page_table[slot, :] = 0
            self.stats["prefill_tokens"] += p_len
            telemetry.inc("paged_prefill_tokens", p_len)
            return AdmitPlan([], 0, False)
        need_total = cdiv(p_len, self.block_size)

        hits: List[int] = []
        if self.enable_prefix_caching:
            for key in self._block_keys(tokens, p_len):
                blk = self._table.get(key)
                if blk is None:
                    break
                hits.append(blk)
        cached = len(hits) * self.block_size
        cow = cached >= p_len        # full hit: recompute the last token
        if cow:
            cached = p_len - 1

        for blk in hits:
            self._acquire_cached(blk)
        fresh_needed = need_total - len(hits) + (1 if cow else 0)
        fresh: List[int] = []

        def _rollback():
            for b in fresh:
                self._refcount[b] = 0
                self._free.append(b)
            for b in hits:
                self._release_block(b)

        # Exception-safe allocation: _take_free (eviction) and
        # _copy_block (CoW) are fault-injection sites — a failure there
        # must return every acquired ref/block, not leak them (the
        # paged-evict / paged-cow drills audit() exactly this).
        try:
            for _ in range(fresh_needed):
                blk = self._take_free()
                if blk is None:
                    _rollback()
                    return None
                self._refcount[blk] = 1
                fresh.append(blk)
            if cow:
                src = hits[-1]
                dst = fresh[0]
                self._copy_block(src, dst)
        except Exception:
            _rollback()
            raise

        if cow:
            self._release_block(src)
            blocks = hits[:-1] + [dst] + fresh[1:]
        else:
            blocks = hits + fresh

        self._slot_blocks[slot] = blocks
        self.page_table[slot, :] = 0
        self.page_table[slot, :len(blocks)] = blocks
        self.stats["prefix_hit_tokens"] += cached
        self.stats["prefill_tokens"] += p_len - cached
        telemetry.inc("paged_prefix_hit_tokens", cached)
        telemetry.inc("paged_prefill_tokens", p_len - cached)
        self._note_usage()
        return AdmitPlan(blocks, cached, cow)

    def ensure_capacity(self, slot: int, position: int) -> bool:
        """Make sure `slot` owns the block covering `position` (decode
        appends grow one block at a time)."""
        if self.eva:
            return self.ensure_rows(slot, position, 1)
        idx = position // self.block_size
        owned = self._slot_blocks[slot]
        if idx < len(owned):
            return True
        assert idx == len(owned), (
            f"slot {slot} skipped a block: position {position} needs block "
            f"{idx}, owns {len(owned)}")
        blk = self._take_free()
        if blk is None:
            return False
        self._refcount[blk] = 1
        owned.append(blk)
        self.page_table[slot, idx] = blk
        self._note_usage()
        return True

    # ---- the window planes ------------------------------------------------
    def window_ensure(self, slot: int, position: int, count: int = 1
                      ) -> bool:
        """Before a call that appends rows [position, position + count) to
        `slot` through the window layers: give back every block that lies
        wholly behind the window of the call's FIRST query (it sees the
        keys position - window + 1 .. position), then take the blocks that
        hold the call's rows. A block given back is on the free list at
        once. False when the window planes run out (what was taken stays
        the slot's): their size counts every slot's window and one call, so
        that is a caller appending more than `window_call_rows`."""
        bs = self.block_size
        owned = self._window_blocks[slot]
        keep = max(position - (self.window - 1), 0) // bs
        first = int(self._window_first[slot])
        while owned and first < keep:
            self._window_free.append(owned.pop(0))
            first += 1
            self.window_stats["blocks_given_back"] += 1
        if not owned:
            first = keep        # a sequence's first call, or all went back
        self._window_first[slot] = first
        need = (position + count - 1) // bs + 1 - first
        while len(owned) < need:
            if not self._window_free:
                return False
            blk = self._window_free.popleft()
            self.window_table[slot, first + len(owned)] = blk
            owned.append(blk)
            self.window_stats["blocks_taken"] += 1
        st = self.window_stats
        st["max_blocks_slot"] = max(st["max_blocks_slot"], len(owned))
        st["peak_blocks_held"] = max(st["peak_blocks_held"],
                                     self.window_blocks_held())
        return True

    def window_trim(self, slot: int, length: int):
        """After a call: `slot` holds `length` rows, and the blocks behind
        the window of its next query (at position `length`) go back."""
        self.window_ensure(slot, length, 0)

    def window_release(self, slot: int):
        for blk in self._window_blocks[slot]:
            self._window_free.append(blk)
        self.window_stats["blocks_given_back"] += len(
            self._window_blocks[slot])
        self._window_blocks[slot] = []
        self._window_first[slot] = 0
        self.window_table[slot, :] = 0

    # ---- EVA: two regions of one table ------------------------------------
    def _eva_hold(self, position: int) -> int:
        """Blocks a slot holds once `position` has its capacity."""
        w, bs = self.cfg.eva_window_size, self.block_size
        at = position % w
        return (self._eva_spb * (position // w) + at // bs + 1
                + at // (bs * bs) + 1)

    def blocks_for(self, length: int) -> int:
        """The most blocks a sequence holds at once while it grows to
        `length` cached rows. EVA: the last window that fills on the way,
        or the end."""
        if not self.eva:
            return cdiv(length, self.block_size)
        last = max(length, 1) - 1
        w = self.cfg.eva_window_size
        return max(self._eva_hold(last),
                   self._eva_hold(last // w * w - 1) if last >= w else 0)

    def _eva_close(self, slot: int):
        """The open window is full and the next position opens another: its
        summaries, written as its chunks filled, join the table's first
        region, and its exact rows' blocks go back to the free list."""
        n_sum, n_win, n_pend = (int(n) for n in self._eva_counts[slot])
        assert n_win == self._eva_wb and n_pend == self._eva_spb, (
            f"slot {slot} closes a window it has not filled: "
            f"{n_win} window blocks, {n_pend} summary blocks")
        owned = self._slot_blocks[slot]
        for blk in owned[n_sum:n_sum + n_win]:
            self._release_block(blk)
        owned[:] = owned[:n_sum] + owned[n_sum + n_win:]
        self._eva_counts[slot] = (n_sum + n_pend, 0, 0)
        self.page_table[slot, :] = 0
        self.page_table[slot, :len(owned)] = owned
        self.eva_stats["windows_closed"] += 1
        self.eva_stats["blocks_freed"] += n_win

    def ensure_rows(self, slot: int, position: int, count: int) -> bool:
        """EVA: make sure `slot` owns the blocks for positions
        [position, position + count), which lie in one window: the exact
        rows' blocks in the open window's region and the blocks of the
        summaries their chunks will be pooled into. A position that opens a
        window closes the one before it first. False when the pool runs
        out (what was taken stays owned)."""
        w, bs = self.cfg.eva_window_size, self.block_size
        last = position + count - 1
        assert position // w == last // w, (
            f"positions {position}..{last} cross a window's edge")
        owned = self._slot_blocks[slot]
        counts = self._eva_counts[slot]
        if counts[0] < self._eva_spb * (position // w):
            self._eva_close(slot)
        assert counts[0] == self._eva_spb * (position // w), (
            f"slot {slot} skipped a window: position {position}, "
            f"{int(counts[0])} summary blocks")
        need = ((1, last % w // bs + 1),            # the open window
                (2, last % w // (bs * bs) + 1))     # its summaries
        for region, blocks in need:
            while counts[region] < blocks:
                blk = self._take_free()
                if blk is None:
                    return False
                self._refcount[blk] = 1
                at = int(counts[:region + 1].sum())
                owned.insert(at, blk)
                col = (at if region == 1 else
                       self.max_blocks_per_seq - self._eva_spb
                       + int(counts[2]))
                self.page_table[slot, col] = blk
                counts[region] += 1
        self.eva_stats["max_blocks_slot"] = max(
            self.eva_stats["max_blocks_slot"], len(owned))
        self._note_usage()
        return True

    def _refuse_lacking(self, cap: str, call: str):
        if cap in self.lacks:       # (a set lookup: rewind runs a slot a round)
            check_tenants(self.cfg, {cap: call})

    def extend_capacity(self, slot: int, position: int, span: int) -> int:
        """Best-effort growth for a multi-token (speculative) append:
        allocate blocks so `slot` covers positions
        [position, position + span), WITHOUT preempting anyone. Returns
        the span actually covered (>= 0); the caller shrinks its
        speculation to fit. Partially-granted blocks stay owned — a
        later rewind() or release() returns them."""
        granted = 0
        for p in range(position, position + span):
            if p >= self.max_seq_len:
                break
            if not self.ensure_capacity(slot, p):
                break
            granted += 1
        return granted

    def flush_prefix_cache(self):
        """Invalidate every cached prefix (rolling engine reload: blocks
        hold KV computed with the OLD weights — a post-swap request
        hitting them would decode new-weight logits over old-weight KV).
        Evictable blocks return to the free list; blocks still
        referenced by live slots merely lose their hash, so they free
        (not LRU-park) on release."""
        self._table.clear()
        self._hash_of.clear()
        for blk in self._lru:
            self._free.append(blk)
        self._lru.clear()
        if self.flush_listener is not None:
            # Structural invalidation (ISSUE 14 satellite): ANY flush —
            # however set_params was reached — drops the fleet router's
            # affinity entries for this replica, so the router cannot
            # keep steering sessions at stale-weight "hits".
            self.flush_listener()

    def transfer_slot(self, src: int, dst: int):
        """Move block ownership from slot `src` to slot `dst` (which
        must be empty): the prefill→decode KV handoff of the
        disaggregated engine. PURE bookkeeping — the page-table row and
        the block list move, refcounts and the page DATA are untouched,
        so adoption never copies KV (the no-dense-copy pin in
        tests/test_disagg.py)."""
        self._refuse_lacking("handoff", "transfer_slot")
        assert not self._slot_blocks[dst], (
            f"transfer_slot: destination slot {dst} still holds blocks")
        self._slot_blocks[dst] = self._slot_blocks[src]
        self._slot_blocks[src] = []
        self.page_table[dst, :] = self.page_table[src, :]
        self.page_table[src, :] = 0
        self.stats["handoff_transfers"] += 1

    def export_slot(self, slot: int, valid_len: int) -> dict:
        """READ-ONLY export of a slot's written KV rows for CROSS-POOL
        live session migration (inference/fleet.py — the PR-8/10 disagg
        handoff generalized; `transfer_slot` above stays the intra-pool
        fast path). Gathers the first `valid_len` rows of every pool
        tensor to host arrays IN THE STORED DTYPE: quantized pools ship
        their int8/fp8 rows + fp32 scales VERBATIM — no dequantize/
        re-quantize round trip, so an import on the destination is
        copy-exact and the migrated stream stays token-exact. Nothing
        here mutates the source pool: a migration that fails after the
        export (the "fleet-migrate" chaos site) leaves the source slot
        fully intact."""
        self._refuse_lacking("snapshot", "export_slot")
        import jax
        from megatronapp_tpu.ops.pallas.paged_attention import (
            gather_prefix_pages,
        )
        assert valid_len > 0, "export_slot: nothing written yet"
        nblocks = cdiv(valid_len, self.block_size)
        owned = self._slot_blocks[slot]
        assert nblocks <= len(owned), (
            f"export_slot: slot {slot} owns {len(owned)} blocks but "
            f"{valid_len} rows need {nblocks}")
        table_row = jnp.asarray(self.page_table[slot])

        def grab(pools):
            return tuple(
                np.asarray(jax.device_get(
                    gather_prefix_pages(p, table_row, nblocks)
                ))[:, :valid_len] for p in pools)

        rows = grab(self.pages)
        scales = grab(self.scales) if self.scales is not None else None
        nbytes = sum(r.nbytes for r in rows)
        if scales is not None:
            nbytes += sum(s.nbytes for s in scales)
        self.stats["slot_exports"] += 1
        telemetry.inc("fleet_kv_exported_bytes", nbytes)
        return {"kv_cache_dtype": self.kv_cache_dtype, "rows": rows,
                "scales": scales, "valid_len": valid_len,
                "nbytes": nbytes}

    def import_slot(self, slot: int, payload: dict) -> bool:
        """Install an `export_slot` payload into empty slot `slot`:
        allocate fresh blocks covering valid_len rows and scatter the
        exported rows (+ scales) into them verbatim. ALL-OR-NOTHING:
        returns False with every allocated block returned to the pool
        when capacity is short, and rolls the allocation back on any
        scatter fault — `audit()` passes either way. The storage dtype
        must match (rows are stored bytes, never converted): fleet
        replicas share one --kv-cache-dtype by construction."""
        self._refuse_lacking("snapshot", "import_slot")
        if payload["kv_cache_dtype"] != self.kv_cache_dtype:
            raise ValueError(
                f"cannot import {payload['kv_cache_dtype']!r} KV rows "
                f"into a {self.kv_cache_dtype!r} pool — migration ships "
                "the stored rows verbatim; every fleet replica must run "
                "the same --kv-cache-dtype")
        assert not self._slot_blocks[slot], (
            f"import_slot: destination slot {slot} still holds blocks")
        valid_len = payload["valid_len"]
        need = cdiv(valid_len, self.block_size)
        fresh: List[int] = []

        def _rollback():
            for b in fresh:
                self._refcount[b] = 0
                self._free.append(b)

        try:
            for _ in range(need):
                blk = self._take_free()
                if blk is None:
                    _rollback()
                    return False
                self._refcount[blk] = 1
                fresh.append(blk)
        except Exception:
            _rollback()
            raise
        self._slot_blocks[slot] = fresh
        self.page_table[slot, :] = 0
        self.page_table[slot, :need] = fresh
        from megatronapp_tpu.ops.pallas.paged_attention import (
            write_prompt_pages,
        )
        table_row = jnp.asarray(self.page_table[slot])
        try:
            self.pages = tuple(
                write_prompt_pages(p, jnp.asarray(r), table_row, 0,
                                   valid_len)
                for p, r in zip(self.pages, payload["rows"]))
            if self.scales is not None:
                self.scales = tuple(
                    write_prompt_pages(p, jnp.asarray(r), table_row, 0,
                                       valid_len)
                    for p, r in zip(self.scales, payload["scales"]))
        except Exception:
            # Partially-scattered rows are dead data in returned blocks
            # that the next writer overwrites — bookkeeping stays clean.
            self._slot_blocks[slot] = []
            self.page_table[slot, :] = 0
            _rollback()
            raise
        self.stats["slot_imports"] += 1
        telemetry.inc("fleet_kv_imported_bytes", payload["nbytes"])
        self._note_usage()
        return True

    def rewind(self, slot: int, valid_len: int):
        """Roll back a slot to `valid_len` written positions: release the
        tail blocks past ceil(valid_len / block_size) — the rejected-
        speculation path (and the cleanup for over-granted
        extend_capacity blocks). Only privately-owned tail blocks may be
        dropped; a refcounted/hashed block here would mean speculation
        wrote into a shared prefix block (never legal — CoW guarantees
        the writable tail is private), so that asserts rather than
        corrupting the prefix cache. Rewinding never splits a block:
        KV rows past valid_len inside the kept tail block are simply
        overwritten by the next append."""
        self._refuse_lacking("rewind", "rewind")
        keep = cdiv(max(valid_len, 1), self.block_size)
        owned = self._slot_blocks[slot]
        while len(owned) > keep:
            blk = owned.pop()
            assert self._refcount[blk] == 1 and blk not in self._hash_of, (
                f"rewind would drop shared/hashed block {blk} "
                f"(rc={int(self._refcount[blk])}) — speculative tail "
                "blocks must be private")
            self.page_table[slot, len(owned)] = 0
            self._release_block(blk)

    def audit(self):
        """Consistency check (tests): every block is exactly one of
        free / LRU-evictable / slot-referenced, and each block's
        refcount equals the number of slot page-table references to it.
        Raises AssertionError on double-free, leak, or refcount skew."""
        nb = self.num_blocks
        refs = np.zeros((nb,), np.int64)
        for blocks in self._slot_blocks:
            for blk in blocks:
                refs[blk] += 1
        assert np.array_equal(refs, self._refcount), (
            f"refcount skew: table={self._refcount.tolist()} "
            f"actual={refs.tolist()}")
        free = set(self._free)
        assert len(free) == len(self._free), (
            "duplicate block on the free list (double-free)")
        lru = set(self._lru)
        held = {b for b in range(nb) if refs[b] > 0}
        assert not (free & lru) and not (free & held) and not (lru & held), (
            "block in two states: "
            f"free∩lru={free & lru} free∩held={free & held} "
            f"lru∩held={lru & held}")
        assert len(free) + len(lru) + len(held) == nb, (
            f"leaked blocks: free={len(free)} lru={len(lru)} "
            f"held={len(held)} != {nb}")
        for blk in lru:
            assert blk in self._hash_of, f"unhashed block {blk} on LRU"
        for pool in self._arrays():
            assert pool.shape[:3] == (self.cfg.kv_planes, nb,
                                      self.block_size), (
                f"a pool of shape {pool.shape} for {self.cfg.kv_planes} "
                f"planes of {nb} blocks")
        if self.window:
            # The window planes' own allocator: every block free or held by
            # one slot; a slot's blocks in its table row from its first on;
            # taken - given back = held.
            wfree = list(self._window_free)
            wheld = [b for blocks in self._window_blocks for b in blocks]
            assert sorted(wfree + wheld) == list(
                range(self.num_window_blocks)), (
                f"window planes: free={len(wfree)} held={len(wheld)} of "
                f"{self.num_window_blocks}, or a block in two places")
            st = self.window_stats
            assert st["blocks_taken"] - st["blocks_given_back"] == len(
                wheld), (st, len(wheld))
            for slot, blocks in enumerate(self._window_blocks):
                first = int(self._window_first[slot])
                assert list(self.window_table[
                    slot, first:first + len(blocks)]) == blocks, (
                    f"slot {slot}: window table row differs from its "
                    "blocks")
        if self.eva:
            # Both regions and the pending summaries: the table's row is
            # the slot's blocks, region by region, and nothing else.
            spb, mb = self._eva_spb, self.max_blocks_per_seq
            for slot, blocks in enumerate(self._slot_blocks):
                n_sum, n_win, n_pend = (int(n)
                                        for n in self._eva_counts[slot])
                assert len(blocks) == n_sum + n_win + n_pend, (
                    f"slot {slot}: {len(blocks)} blocks, regions "
                    f"{(n_sum, n_win, n_pend)}")
                assert n_sum % spb == 0 and n_win <= self._eva_wb \
                    and n_pend <= cdiv(n_win, self.block_size), (
                    f"slot {slot}: regions {(n_sum, n_win, n_pend)}")
                row = np.zeros((mb,), np.int32)
                row[:n_sum + n_win] = blocks[:n_sum + n_win]
                row[mb - spb:mb - spb + n_pend] = blocks[n_sum + n_win:]
                assert np.array_equal(row, self.page_table[slot]), (
                    f"slot {slot}: table row differs from its regions")
        return True

    def register_prefix(self, slot: int, tokens: np.ndarray, valid_len: int):
        """Hash this slot's full blocks over tokens[:valid_len] so later
        same-prefix requests hit them (only rows actually written count —
        the engine passes valid_len excluding the pending last token)."""
        if not self.enable_prefix_caching:
            return
        owned = self._slot_blocks[slot]
        inserted: List[bytes] = []
        for i, key in enumerate(self._block_keys(tokens, valid_len)):
            if i >= len(owned):
                break
            blk = owned[i]
            if blk not in self._hash_of and key not in self._table:
                self._table[key] = blk
                self._hash_of[blk] = key
                inserted.append(key)
        if inserted and self.prefix_listener is not None:
            # Per-replica prefix-insert event: the fleet router's
            # affinity map learns which replica holds which prefix.
            self.prefix_listener(inserted)

    def release(self, slot: int, tokens: np.ndarray, valid_len: int,
                preempted: bool = False):
        """Return a slot's blocks to the pool. Full blocks get registered
        in the prefix cache first (so a preempted request can re-hit its
        own KV on resume, and finished prompts stay warm for followers),
        then every block is de-referenced — rc==0 hashed blocks park on
        the LRU list, unhashed ones go straight to the free list."""
        self.register_prefix(slot, tokens, valid_len)
        for blk in self._slot_blocks[slot]:
            self._release_block(blk)
        self._slot_blocks[slot] = []
        self._eva_counts[slot] = 0
        self.page_table[slot, :] = 0
        if self.window:
            self.window_release(slot)
        if preempted:
            self.stats["preemptions"] += 1
            telemetry.inc("paged_preemptions")

    # ---- per-block prefix export/import (fleet prefix store) -------------
    def has_prefix(self, key: bytes) -> bool:
        """Whether a prefix-block hash is currently hittable in this
        pool (the fleet router probes this before serving a store
        payload — a locally-present block never crosses the wire)."""
        return key in self._table

    def export_prefix_block(self, key: bytes) -> Optional[dict]:
        """READ-ONLY export of ONE cached prefix block's stored rows
        (+ scales) for the FLEET-GLOBAL PREFIX STORE (ISSUE 20): the
        block is shipped in export_slot discipline — verbatim stored
        bytes in the storage dtype, exact nbytes off the addressable
        arrays — so an import on any same-dtype pool is copy-exact.
        Returns None when the hash is no longer hittable (evicted or
        flushed between the insert event and the export). Nothing here
        mutates the pool."""
        import jax
        blk = self._table.get(key)
        if blk is None:
            return None
        rows = tuple(np.asarray(jax.device_get(p[:, blk]))
                     for p in self.pages)
        scales = (tuple(np.asarray(jax.device_get(s[:, blk]))
                        for s in self.scales)
                  if self.scales is not None else None)
        nbytes = sum(r.nbytes for r in rows)
        if scales is not None:
            nbytes += sum(s.nbytes for s in scales)
        self.stats["prefix_block_exports"] += 1
        return {"kv_cache_dtype": self.kv_cache_dtype, "rows": rows,
                "scales": scales, "block_size": self.block_size,
                "nbytes": nbytes}

    def import_prefix_block(self, key: bytes, payload: dict) -> bool:
        """Install an `export_prefix_block` payload as a HITTABLE prefix
        block: one fresh block is filled with the stored rows verbatim
        and registered under `key` with refcount 0 on the LRU list —
        exactly the state a locally-prefilled block reaches after its
        last owner releases, so a subsequent admit() hits it like any
        local prefix and the prefill starts past it (the
        prefill-chunks-avoided win). ALL-OR-NOTHING: returns True when
        the key is already present (idempotent), False when the pool
        cannot supply a block, and rolls the allocation back on any
        scatter fault — audit() passes either way."""
        if payload["kv_cache_dtype"] != self.kv_cache_dtype:
            raise ValueError(
                f"cannot import a {payload['kv_cache_dtype']!r} prefix "
                f"block into a {self.kv_cache_dtype!r} pool — the store "
                "ships stored rows verbatim; every fleet replica must "
                "run the same --kv-cache-dtype")
        if payload["block_size"] != self.block_size:
            raise ValueError(
                f"prefix-block size mismatch: payload block_size="
                f"{payload['block_size']} vs pool {self.block_size} — "
                "prefix hashes only align across equal block sizes")
        if not self.enable_prefix_caching:
            return False
        if key in self._table:
            return True
        blk = self._take_free()
        if blk is None:
            return False
        try:
            self.pages = tuple(p.at[:, blk].set(jnp.asarray(r))
                               for p, r in zip(self.pages,
                                               payload["rows"]))
            if self.scales is not None:
                self.scales = tuple(
                    s.at[:, blk].set(jnp.asarray(r))
                    for s, r in zip(self.scales, payload["scales"]))
        except Exception:
            # Partially-written rows are dead data in a returned block
            # the next writer overwrites — bookkeeping stays clean.
            self._free.append(blk)
            raise
        self._table[key] = blk
        self._hash_of[blk] = key
        self._lru[blk] = None       # rc==0, evictable, hittable
        self.stats["prefix_block_imports"] += 1
        telemetry.inc("fleet_prefix_blocks_imported")
        return True


class HostSpillTier:
    """Host-RAM spill tier for PARKED sessions (ISSUE 20): a strict
    byte-budgeted dict of `export_slot`-format payloads (numpy rows +
    scales — already host-resident, exact nbytes off the serialized
    arrays) keyed by request id. The tier never evicts: a parked
    session is LIVE state, so `put` past the budget is refused and the
    engine falls back to preemption (spill preferred, never forced).
    Insertion order is the engine's unpark order (FIFO — the
    least-recently-parked session resumes first)."""

    def __init__(self, budget_bytes: int):
        assert budget_bytes > 0, "spill tier needs a positive byte budget"
        self.budget_bytes = int(budget_bytes)
        self.bytes_used = 0
        self._entries: OrderedDict = OrderedDict()   # rid -> payload
        self.counters = {"parks": 0, "unparks": 0, "park_bytes": 0,
                         "unpark_bytes": 0, "rejects": 0,
                         "peak_bytes": 0, "peak_parked": 0}

    def __len__(self) -> int:
        return len(self._entries)

    def __contains__(self, rid) -> bool:
        return rid in self._entries

    def would_fit(self, nbytes: int) -> bool:
        return self.bytes_used + nbytes <= self.budget_bytes

    def put(self, rid, payload: dict) -> bool:
        """Park a payload. False (tier untouched, reject counted) when
        the exact serialized bytes would exceed the budget."""
        assert rid not in self._entries, f"request {rid} already parked"
        nbytes = payload["nbytes"]
        if not self.would_fit(nbytes):
            self.counters["rejects"] += 1
            return False
        self._entries[rid] = payload
        self.bytes_used += nbytes
        self.counters["parks"] += 1
        self.counters["park_bytes"] += nbytes
        self.counters["peak_bytes"] = max(self.counters["peak_bytes"],
                                          self.bytes_used)
        self.counters["peak_parked"] = max(self.counters["peak_parked"],
                                           len(self._entries))
        telemetry.inc("kv_spill_parks")
        telemetry.inc("kv_spill_park_bytes", nbytes)
        return True

    def get(self, rid) -> Optional[dict]:
        return self._entries.get(rid)

    def pop(self, rid, unpark: bool = True) -> Optional[dict]:
        """Remove a parked payload (unpark=False for aborts/expiry —
        only genuine resumes count as unparks)."""
        payload = self._entries.pop(rid, None)
        if payload is None:
            return None
        self.bytes_used -= payload["nbytes"]
        if unpark:
            self.counters["unparks"] += 1
            self.counters["unpark_bytes"] += payload["nbytes"]
            telemetry.inc("kv_spill_unparks")
        return payload

    def rids(self) -> List:
        """Parked request ids, oldest (next to unpark) first."""
        return list(self._entries)

    def stats(self) -> dict:
        return {"parked": len(self._entries),
                "budget_bytes": self.budget_bytes,
                "bytes_used": self.bytes_used, **self.counters}


class FleetPrefixStore:
    """Fleet-global prefix store (ISSUE 20): `export_prefix_block`
    payloads keyed by the SAME rolling `prefix_block_keys` hashes the
    pool's prefix cache and the routers' affinity maps use — so a store
    hit is an exact-prefix match by construction. Bounded by bytes with
    LRU eviction (a prefix block is derived state — unlike the spill
    tier it may always be dropped and re-prefilled), with per-fleet
    hit/byte counters. Both routers (inference/fleet.py in-process,
    inference/fleet_rpc.py cross-process via the prefix_put/prefix_get
    verbs) populate it from prefix-insert events and serve admissions
    from it."""

    def __init__(self, capacity_bytes: int):
        assert capacity_bytes > 0, "prefix store needs a positive capacity"
        self.capacity_bytes = int(capacity_bytes)
        self.bytes_used = 0
        self._entries: OrderedDict = OrderedDict()   # key -> payload
        self.counters = {"puts": 0, "put_bytes": 0, "hits": 0,
                         "hit_bytes": 0, "misses": 0, "evictions": 0,
                         "flushes": 0, "peak_bytes": 0}

    def __len__(self) -> int:
        return len(self._entries)

    def has(self, key: bytes) -> bool:
        return key in self._entries

    def put(self, key: bytes, payload: dict) -> bool:
        """Insert a block payload, evicting LRU entries to fit. A
        payload larger than the whole store is refused (never counted
        as resident)."""
        if key in self._entries:
            return True
        nbytes = payload["nbytes"]
        if nbytes > self.capacity_bytes:
            return False
        while self.bytes_used + nbytes > self.capacity_bytes:
            _, old = self._entries.popitem(last=False)
            self.bytes_used -= old["nbytes"]
            self.counters["evictions"] += 1
        self._entries[key] = payload
        self.bytes_used += nbytes
        self.counters["puts"] += 1
        self.counters["put_bytes"] += nbytes
        self.counters["peak_bytes"] = max(self.counters["peak_bytes"],
                                          self.bytes_used)
        telemetry.inc("fleet_prefix_store_put_bytes", nbytes)
        return True

    def get(self, key: bytes) -> Optional[dict]:
        payload = self._entries.get(key)
        if payload is None:
            self.counters["misses"] += 1
            return None
        self._entries.move_to_end(key)
        self.counters["hits"] += 1
        self.counters["hit_bytes"] += payload["nbytes"]
        telemetry.inc("fleet_prefix_store_hits")
        return payload

    def clear(self):
        """Drop everything (params reload / replica death: stored
        blocks hold KV from weights no longer guaranteed fleet-wide)."""
        if self._entries:
            self.counters["flushes"] += 1
        self._entries.clear()
        self.bytes_used = 0

    def stats(self) -> dict:
        return {"entries": len(self._entries),
                "capacity_bytes": self.capacity_bytes,
                "bytes_used": self.bytes_used, **self.counters}
