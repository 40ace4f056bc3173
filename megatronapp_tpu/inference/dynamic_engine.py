"""Dynamic inference engine: continuous batching over paged KV.

Parity with /root/reference/megatron/core/inference/engines/dynamic_engine.py
+ contexts/dynamic_context.py + scheduler.py: requests of different lengths
enter a waiting queue; the engine admits them into free cache slots
(prefill), decodes ONE token per step for every active slot, and retires
finished requests — new requests join mid-flight without draining the batch.

One cache: KV lives in a shared block pool
[L, num_blocks, block_size, Hkv, D] (MLA: the compressed latent + shared
roped key pair) with per-request page tables (inference/paged_cache.py —
vLLM-style): admission is by block availability rather than whole slots,
identical prompt prefixes are served from the refcounted prefix cache
instead of recomputed, exhaustion preempts the lowest-priority running
request back to the waiting queue (it resumes by re-prefilling
prompt+generated, usually re-hitting its own cached blocks), and decode
attends through the ragged paged-attention Pallas kernel
(ops/pallas/paged_attention.py). A compiled step updates the pool IN
PLACE: the pools are donated, ride the layer loop as a carry, are written
row by row (kernel_gen.paged_append) and read through the layer id, and
stay row-major on the device (paged_cache.pool_format), so the device
holds one pool and a step touches the rows it appends and the blocks it
attends. The parity oracle is the static engine (inference/engine.py).

The plain decode loop runs one round ahead: a step dispatches round n+1
before it fetches round n's tokens, so the host's part of a round (record,
retire, callbacks, the next sweep and admission) runs while the chip runs
(`_plain_round` and the comment above `_owed` say what that takes).

TPU-first: all shapes static; the decode step is ONE jit for all slots
(per-row rope positions, per-row lengths in the kernel), prefill runs in
calls of one width, and sampling is ONE batched on-device jit per step
(per-request streams stay reproducible via fold_in key chains —
PRNGKey(seed) ∘ request_id ∘ step — independent of batch composition).

Speculative decoding (ISSUE 4, inference/speculative.py): with
``spec_method`` set ("draft"/"mtp"/"ngram"), every
decode round proposes up to spec_k draft tokens per request, verifies
them in ONE batched multi-query forward (`_paged_multiquery_step`, the
unified prefill/decode primitive of arXiv 2604.15464), and exact
rejection sampling keeps greedy streams bit-identical to plain decode
and sampled streams distributed exactly like the target model. Rejected
tokens' KV is rolled back (PagedKVCache.rewind). The same multi-query
step prefills the uncached prompt tail in fixed-size chunks, so prefill
traces once per chunk shape instead of once per (bucket, cached-length)
pair.
"""

from __future__ import annotations

import contextlib
import dataclasses
import itertools
import logging
import math
import time
from collections import OrderedDict, deque
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from megatronapp_tpu.config.transformer_config import TransformerConfig
from megatronapp_tpu.inference.engine import (
    SamplingParams, mask_padded_vocab,
)
from megatronapp_tpu.inference.paged_cache import (
    HostSpillTier, PagedKVCache, cdiv, check_tenants, pool_format,
)
from megatronapp_tpu.models.gpt import gpt_embed, gpt_head, gpt_rope_tables
from megatronapp_tpu.trace import scope_map
from megatronapp_tpu.trace.request_trace import (
    PhaseStats, get_request_tracer,
)
from megatronapp_tpu.transformer.block import (
    layer_forward, layer_loop, layer_params,
)
from megatronapp_tpu.transformer.eva import table_rows
from megatronapp_tpu.transformer.moe import HELD_COUNTS, StackedLayer
from megatronapp_tpu.utils import chaos
from megatronapp_tpu.utils import metrics as telemetry
from megatronapp_tpu.utils.flops import tpu_roofs
from megatronapp_tpu.utils.platform import fresh_compiles

logger = logging.getLogger(__name__)


def _handed_over(host_array) -> jnp.ndarray:
    """A host array for an asynchronous step, as a COPY: the engine goes
    on writing `lengths`, `last_tokens` and the page table in place while
    the dispatched step has yet to read them, and on a CPU jnp.asarray may
    share the numpy buffer (ROADMAP S3). A step that reads them late (a
    hybrid stack's first attention layer comes after its state-space
    layers) then sees the next round's values every time."""
    return jnp.asarray(np.array(host_array))


class DeadlineExceeded(RuntimeError):
    """A request's deadline passed: rejected at admission, or aborted
    mid-flight by the engine/stepper (its pool blocks are reclaimed on
    the retire path like any finished request)."""


class StepStats(PhaseStats):
    """The engine's always-on step counters, ``stats_snapshot()["steps"]``
    (GET /stats). Every ``mta.engine.*`` span adds its wall time to its
    phase here; beside the phases: ``queue_wait`` (observed where a
    request leaves the queue; a preempted request's second wait counts
    again) and ``slowest``, the flight recorder: the longest pure decode
    rounds (steps that admitted nothing) since start, each with its step
    index, wall time, batch and the seconds every phase took inside it.
    The tokens the model ran are counted elsewhere already:
    ``pool.stats["prefill_tokens"]`` (the snapshot's ``pool``) and
    ``spec_stats["emitted_tokens"]``. ``rounds_ahead``: the plain decode
    rounds that were dispatched before their predecessor's tokens were read
    (over ``decode_round``'s count: the share of rounds the chip did not
    wait for the host); ``overrun_rows``: rows of such rounds whose request
    had ended meanwhile (on its ``eod_id``, or stopped from outside), whose
    token was dropped. ``admit_steps``: the steps that admitted a request,
    ``admitted``: the requests they admitted (a window's own are the
    ``mta.engine.prefill`` spans inside its ``mta.engine.step`` spans:
    perfbench/admission_spans.py); ``first_samples_ahead``: those of them
    whose first token was read after the next round's dispatch (ISSUE 53:
    the ``mta.engine.prefill.sample`` spans with ``ahead`` = 1), so that the
    chip did not stand while the host fetched it."""

    PHASES = ("step", "admit", "prefill", "prefill_call", "prefill.sample",
              "capacity", "decode_round", "decode.stage",
              "decode.stage.sample", "decode.stage.put",
              "decode.stage.dispatch", "decode.wait", "decode.record",
              "retire", "queue_wait")
    SLOWEST = 8

    def __init__(self):
        super().__init__(self.PHASES)
        self.slowest: List[dict] = []       # longest first
        self.rounds_ahead = 0
        self.overrun_rows = 0
        self.admit_steps = 0
        self.admitted = 0
        self.first_samples_ahead = 0

    def totals(self) -> List[float]:
        return [row[1] for row in self.phases.values()]

    def note_round(self, wall_s: float, batch: int, before: List[float]):
        """A pure decode round has ended; `before` is ``totals()`` as the
        step began. Kept if it is among the SLOWEST longest so far."""
        slowest = self.slowest
        if len(slowest) == self.SLOWEST and wall_s <= slowest[-1]["wall_s"]:
            return
        split = {p: row[1] - b for (p, row), b
                 in zip(self.phases.items(), before) if row[1] > b}
        record = {"step": int(self.phases["step"][0]), "wall_s": wall_s,
                  "batch": batch, "phases": split}
        # A new list, put in place at once: /stats reads from another
        # thread.
        self.slowest = sorted(slowest + [record],
                              key=lambda r: -r["wall_s"])[:self.SLOWEST]

    def snapshot(self) -> dict:
        return dict(super().snapshot(),
                    rounds_ahead=self.rounds_ahead,
                    overrun_rows=self.overrun_rows,
                    admit_steps=self.admit_steps,
                    admitted=self.admitted,
                    first_samples_ahead=self.first_samples_ahead,
                    slowest=[dict(r, phases=dict(r["phases"]))
                             for r in self.slowest])


def validate_admission(prompt_tokens, max_new_tokens: int,
                       max_seq_len: int, pool=None,
                       deadline_s=None) -> np.ndarray:
    """Shared admission validation (single source of truth for the
    plain engine AND the disaggregated coordinator — the two must
    accept/reject identically): deadline, non-empty prompt, sequence
    bound, and pool-capacity bound. Returns the normalized int32
    prompt."""
    import time as _time
    if deadline_s is not None and _time.monotonic() >= deadline_s:
        raise DeadlineExceeded(
            "request deadline already expired at admission")
    prompt = np.asarray(prompt_tokens, np.int32).reshape(-1)
    if len(prompt) == 0:
        raise ValueError(
            "empty prompt: prefill samples the first token from the "
            "last PROMPT position, so at least one token (e.g. BOS/"
            "eod) is required")
    if len(prompt) + max_new_tokens > max_seq_len:
        raise ValueError(
            f"prompt({len(prompt)}) + max_new({max_new_tokens}) exceeds "
            f"max_seq_len({max_seq_len})")
    if pool is not None:
        need = pool.blocks_for(len(prompt) + max_new_tokens)
        if need > pool.num_blocks:
            raise ValueError(
                f"request needs {need} blocks "
                f"(prompt {len(prompt)} + max_new {max_new_tokens} at "
                f"block_size {pool.block_size}) but the pool has "
                f"only {pool.num_blocks}")
    return prompt


@dataclasses.dataclass
class Request:
    """One generation request (reference inference_request.py analogue).

    priority: lower = more important; the engine preempts the
    highest (priority, request_id) running request when the block pool
    is exhausted.

    deadline_s: absolute time.monotonic() deadline; overdue requests are
    aborted by step()'s expiry sweep (event key "expired") and their
    cache/pool resources reclaimed.

    adapter_id/tenant: multi-tenant LoRA serving (inference/lora.py,
    ISSUE 19) — adapter_id names the tenant's low-rank adapter in the
    engine's AdapterCache registry (None = the base model); tenant is a
    free-form accounting label for per-tenant telemetry/SLO classes.
    Both ride the Request itself, so fleet migration carries them and a
    migrated stream stays token-exact under the same adapter."""
    request_id: int
    prompt: np.ndarray                  # [P] int32
    max_new_tokens: int
    sampling: SamplingParams
    eod_id: Optional[int] = None
    priority: int = 0
    deadline_s: Optional[float] = None
    adapter_id: Optional[str] = None
    tenant: Optional[str] = None
    # Filled by the engine:
    slot: int = -1
    generated: list = dataclasses.field(default_factory=list)
    finished: bool = False
    # Wall-clock admission time (time.monotonic()) — time-to-first-token
    # telemetry measures from here (first admission only; a preempted
    # request's resume is not a first token).
    admit_t: float = 0.0
    # When the request last ENTERED a queue (admission or re-queue after
    # preemption/rollback) — queue-wait telemetry measures from here, so
    # a resumed request's second wait doesn't include its first life.
    queued_t: float = 0.0
    # Speculative-decoding stats (spec_method engines):
    spec_proposed: int = 0
    spec_accepted: int = 0

    @property
    def tokens(self) -> np.ndarray:
        return np.concatenate([self.prompt,
                               np.asarray(self.generated, np.int32)])


def _moe_of(tree):
    """The "moe" params of a layer or of a stack of layers (None without):
    a shortcut-connected double layer keeps them in its first half, a
    hybrid stack among its feed-forward halves ("ffn")."""
    if not isinstance(tree, dict):
        return None
    return tree.get("first", tree.get("ffn", tree)).get("moe")


def _with_moe(tree, moe):
    """`tree` with `moe` where _moe_of found the old one."""
    if "first" in tree:
        return dict(tree, first=dict(tree["first"], moe=moe))
    if "ffn" in tree:
        return dict(tree, ffn=dict(tree["ffn"], moe=moe))
    return dict(tree, moe=moe)


def _moe_stacks(block, ctx=None):
    """(`block` without its experts' fc1/fc2 stacks, {name: stack}): what
    the paged layer loop reads through the layer id and not as a layer's
    slice (_scan_paged_layers). Nothing is taken out on a mesh, of a dense
    model, or of resident int8 pairs."""
    moe = _moe_of(block)
    if ctx is not None or moe is None:
        return block, {}
    stacks = {k: w for k, w in moe.items()
              if k in ("fc1_kernel", "fc2_kernel")
              and not isinstance(w, dict)}
    return _with_moe(block, {k: w for k, w in moe.items()
                             if k not in stacks}), stacks


def _scan_paged_layers(params, h, pages, scales, lora, cfg, layer,
                       ctx=None, rows=None):
    """The layer loop of both paged steps: h through every layer, each
    appending its new rows to the pools and attending through them.

    The stacked pools [L, NB, bs, ...] (K and V — MLA: latent and k_pe —
    and the scale pools of a quantised pool) ride the loop as a CARRY:
    each layer writes its rows into its own plane in place and reads that
    plane through the layer id (kernel_gen.paged_append /
    paged_attention(layer=)), so the buffer that enters the step is the
    buffer that leaves it, and with the callers' donation a step holds one
    pool. The per-layer inputs (xs) are the block params, the layer ids
    and lora's factor banks (a, b per target, leading L dim).
    layer(layer_p, hh, lid, kv_cache, kv_scales, lora_l) runs one layer and
    returns layer_forward's ((h, new_cache), aux).

    The experts' fc1/fc2 stacks [L_moe, E, K, N] are read through the layer
    id too: a grouped GEMM is a custom call, for which the loop's slice of
    an xs leaf is a copy of the layer's experts, so the stacks enter the
    loop whole and a layer gets moe.StackedLayer(stack, its index). Only
    the single-device trace does this, and only for plain arrays: resident
    int8 pairs dequantize a layer at a time, and on a mesh (ctx) the
    kernels stay per-layer operands.

    An MoE model's leading dense layers (params["lead_block"], layer ids
    0..k-1) run first, through the same body and into planes 0..k-1 of the
    same pools; the scanned stack takes ids k..L-1.

    A stack of several kinds of layer (cfg.stack_plan) is walked by
    block.layer_loop's scanned runs, and `pages` holds a pool a kind of
    state behind the KV pools (paged_cache.py), all carried and written in
    place alike: (k, v, ssm, conv), (k, v, conv) or (k, v, k_window,
    v_window). A layer's mixer says which it writes, in the plane that is
    its row of its stack: "mixers_attn" the KV pools (kv_plane), "mixers_swa"
    the window pools through their own table, "mixers_ssm" / "mixers_kda" /
    "mixers_conv" the state pools at `rows`, the slots of h's rows (None: row
    b is slot b); a feed-forward alone none. The experts' stacks are read
    through the layer's row of "ffn" as below, and the layers' counts are
    summed along the loop's carry.

    A shortcut-connected double layer (cfg.moe_shortcut_double_layer) is
    one step of the same scan: layer_forward runs its two attention
    sublayers into planes 2·lid and 2·lid + 1 of the pools [2L, NB, ...].

    Returns (h, moe, (k, v[, ssm, conv][, k_scales, v_scales])); moe is
    None for a dense model, else int32 [2]: the MoE layers' routing_counts
    summed ([6], moe.HELD_COUNTS, on a model that holds a share of its
    experts or has zero-compute ones)."""
    if cfg.hybrid_stack:
        if lora is not None or ctx is not None:
            raise ValueError("a hybrid state-space stack serves on one "
                             "device, without lora")
        block, stacks = _moe_stacks(params["block"])
        counts0 = None
        if cfg.is_moe:
            counts0 = jnp.zeros(
                (len(HELD_COUNTS) if cfg.moe_counts_load else 2,), jnp.int32)

        def run(carry, entry, at, lid):
            hh, pools, kvs, counts = carry
            layer_p = layer_params(block, entry, at)
            if stacks and "ffn" in entry:
                layer_p = dict(layer_p, moe=dict(layer_p["moe"], **{
                    name: StackedLayer(w, at["ffn"])
                    for name, w in stacks.items()}))
            mixer = entry[0]
            if mixer == "mixers_attn":
                (hh, new), aux = layer(layer_p, hh, lid, pools[:2], kvs,
                                       None, kv_plane=at[mixer])
                pools = tuple(new[:2]) + pools[2:]
                kvs = None if kvs is None else tuple(new[2:])
            elif mixer == "mixers_swa":
                # the window pools, through their own table and rotary
                # table (`layer` knows both)
                (hh, new), aux = layer(layer_p, hh, lid, pools[2:4], None,
                                       None, kv_plane=at[mixer], window=True)
                pools = pools[:2] + tuple(new[:2]) + pools[4:]
            elif mixer in ("mixers_ssm", "mixers_kda", "mixers_conv"):
                (hh, new), aux = layer(layer_p, hh, lid, None, None, None,
                                       ssm_state=pools[2:] + (at[mixer],),
                                       state_rows=rows)
                pools = pools[:2] + tuple(new)
            else:                   # a feed-forward alone owns no plane
                (hh, _), aux = layer(layer_p, hh, lid, None, None, None)
            if counts is not None and "ffn" in entry:
                counts = counts + aux
            return hh, pools, kvs, counts

        h, pages, scales, moe = layer_loop(
            cfg, (h, tuple(pages), None if scales is None else tuple(scales),
                  counts0), run)
        return h, moe, pages + (scales or ())
    lead = cfg.moe_first_k_dense
    block, stacks = _moe_stacks(params["block"], ctx)

    def body(carry, xs):
        hh, kv, kvs = carry
        layer_p, lid, banks = xs
        moe_p = _moe_of(layer_p)
        if stacks and moe_p is not None:
            layer_p = _with_moe(layer_p, dict(moe_p, **{
                k: StackedLayer(w, lid - lead) for k, w in stacks.items()}))
        ll = None
        if lora is not None:
            ll = {"row_adapter": lora["row_adapter"], "banks": banks}
        (hh, new), aux = layer(layer_p, hh, lid, kv, kvs, ll)
        return (hh, tuple(new[:2]),
                None if kvs is None else tuple(new[2:])), aux

    banks = None if lora is None else lora["banks"]
    carry = (h, tuple(pages), None if scales is None else tuple(scales))
    if lead:
        carry, _ = jax.lax.scan(
            body, carry, (params["lead_block"], jnp.arange(lead), banks))
    (h, pages, scales), moe = jax.lax.scan(
        body, carry, (block, jnp.arange(lead, cfg.num_layers), banks),
        unroll=cfg.scan_unroll)
    # The layers' counts add up, but for the last of a share's
    # (moe.HELD_COUNTS): Σ over MoE layers of the most rows one held expert
    # got is a sum of maxima already.
    moe = jnp.sum(moe, axis=0) if cfg.is_moe else None
    return h, moe, pages + (scales or ())


def _rope_rows(cfg: TransformerConfig, max_seq_len: int, positions):
    """((cos, sin) at `positions` [B, S] of the model's rotary table, None
    where it has none; the same of a sliding-window stack's window layers'
    table, None where the model has no such layers)."""
    def rows(window):
        cos, sin = gpt_rope_tables(cfg, max_seq_len, window=window)
        if cos is None:
            return None, None
        return (jnp.take(cos, positions, axis=0),           # [B, S, half]
                jnp.take(sin, positions, axis=0))
    return rows(False), (rows(True) if cfg.sliding_window else None)


def _split_tables(cfg: TransformerConfig, page_table):
    """(the full planes' table, the window planes'): a step on a
    sliding-window stack is handed the pair, any other the one table."""
    if cfg.sliding_window:
        return page_table
    return page_table, None


def _paged_decode_step(params, tokens, pages, page_table, lengths, active,
                       cfg: TransformerConfig, max_seq_len: int, ctx=None,
                       scales=None, lora=None):
    """One-token decode for every slot against the paged block pool.

    pages: ([L, NB, bs, Hkv, D], same) K/V pools (MLA: latent + k_pe
    pools; a hybrid stack: then its two state pools, and every slot's
    state advances a token; a sliding-window stack: then its window
    planes' K/V pools); page_table [B, max_blocks_per_seq] int32 (a
    sliding-window stack: the pair of it and the window planes' table);
    lengths [B] append
    positions; active [B] bool (inactive rows' writes are dropped and
    their outputs discarded). scales: ([L, NB, bs, Hkv] fp32, same) for
    an int8 pool — the step then quantizes the appended rows in-jit and
    returns the updated scale pools alongside.
    lora: batched adapter deltas (inference/lora.py) — {"row_adapter":
    [B] int32 bank slots, "banks": {target: (a [L, slots, din, r],
    b [L, slots, r, dout])}}; the banks are sliced per layer by the
    layer loop and each projection matmul grows a
    per-row low-rank delta (slot 0 = the all-zero null adapter, so the
    trace is identical whether or not any row has a real adapter).
    The layer loop (_scan_paged_layers) honors cfg.scan_unroll (PERF
    lever 3: unrolling removes the while-loop dispatch overhead and lets
    XLA fuse across layer boundaries). Returns (last_logits [B,V], moe,
    the pools (k, v[, k_scales, v_scales])): moe is None for a dense
    model, else int32 [2], the round's token-expert assignments and
    (layer, expert) pairs touched by active rows; the pools are the arrays
    that came in, each with B new rows a layer written in place."""
    h = gpt_embed(params, tokens, cfg, position_ids=lengths[:, None])
    (cos, sin), window_rope = _rope_rows(cfg, max_seq_len, lengths[:, None])
    page_table, window_table = _split_tables(cfg, page_table)

    # The ragged kernels mask by per-row kv length themselves (MLA
    # included since ISSUE 17 — the latent kernel attends through the
    # page table, no dense gather and no host-built mask).
    def layer(layer_p, hh, lid, kv, kvs, ll, window=False, **kind):
        if window:
            kind.update(window_rope=window_rope)
        return layer_forward(
            layer_p, hh, cfg, cos, sin, None, layer_id=lid,
            kv_cache=kv, cache_index=None,
            cache_positions=lengths,
            page_table=window_table if window else page_table,
            active=active, ctx=ctx, kv_scales=kvs, lora=ll, **kind)

    h, moe, new_pages = _scan_paged_layers(params, h, pages, scales, lora,
                                           cfg, layer, ctx)
    logits = gpt_head(params, h, cfg)[:, -1]
    return logits, moe, new_pages


def _paged_multiquery_step(params, tokens, pages, page_table, starts,
                           q_lens, active, cfg: TransformerConfig,
                           max_seq_len: int, ctx=None, scales=None,
                           lora=None, rows=None, last=None):
    """Ragged multi-token step against the paged pool — the UNIFIED
    prefill/decode primitive (speculative verify + chunked prefill).

    tokens [B, S]; starts [B] per-row append positions; q_lens [B] valid
    token counts in [1, S] (rows past a row's count are padding whose
    outputs are garbage); active [B] bool. Row b's token i lands at
    position starts[b] + i and attends the paged context plus the new
    tail causally. On a hybrid stack row b runs in slot rows[b]: its
    state-space layers scan the chunk from that slot's state (from zeros
    where starts[b] is 0: a sequence begins), stop at q_lens[b] and write
    the state back. Returns (logits [B, S, V], hidden [B, S, H] pre-head,
    the pools, written in place as in _paged_decode_step) — hidden feeds
    the MTP self-draft proposer. last [B] (a prefill call, which samples
    from one position a row): the head runs on row b's position last[b]
    alone, and logits and hidden are [B, 1, ...]; speculative verify reads
    every position and passes none."""
    b, s = tokens.shape
    positions = starts[:, None] + jnp.arange(s)[None, :]       # [B, S]
    positions = jnp.minimum(positions, max_seq_len - 1)
    h = gpt_embed(params, tokens, cfg, position_ids=positions)
    (cos, sin), window_rope = _rope_rows(cfg, max_seq_len, positions)
    page_table, window_table = _split_tables(cfg, page_table)

    # The multi-query ragged kernels mask themselves (MLA included since
    # ISSUE 17 — the latent kernel's scalar-prefetched q_lens carries
    # the causal tail mask).
    def layer(layer_p, hh, lid, kv, kvs, ll, window=False, **kind):
        if window:
            kind.update(window_rope=window_rope)
        return layer_forward(
            layer_p, hh, cfg, cos, sin, None, layer_id=lid,
            kv_cache=kv, cache_index=None,
            cache_positions=starts,
            page_table=window_table if window else page_table,
            active=active, chunk_counts=q_lens, ctx=ctx,
            kv_scales=kvs, lora=ll, **kind)

    h, _, new_pages = _scan_paged_layers(params, h, pages, scales, lora,
                                         cfg, layer, ctx, rows)
    if last is not None:
        h = jnp.take_along_axis(h, last[:, None, None], axis=1)
    logits = gpt_head(params, h, cfg)
    return logits, h, new_pages


class _PoolStep:
    """The jit of one paged step, `fn(params, tokens, pages, scales,
    ...)`, whose output is `n_lead` arrays and then the pools it was
    given.

    The pools are donated and pinned to `pool_format` (row-major, the
    kernels' order) on the way in and on the way out, so a step aliases
    them and never relayouts them. A jit's layout names its devices, and
    the same engine code meets its pools on a CPU (tests), on the chip,
    and as ShapeDtypeStructs on a DESCRIBED chip (tests/test_chip_compile
    and perfbench's compile rehearsal lower these steps) — so the jit is
    built for the sharding the pools arrive with, once, and kept. Pools
    that carry no sharding (tracers under make_jaxpr) get the plain jit.
    A call that compiles (the first with its tokens' shape) compiles
    afresh: utils/platform.fresh_compiles says why. `fn` must not close
    over arrays (nor over the engine, which holds the weights): the scope
    map registry keeps the step past its engine's life."""

    def __init__(self, fn, n_lead: int, kind: str, guard=None):
        self._fn = fn
        self._n_lead = n_lead
        self._jits = {}              # pools' shardings -> jit
        self._called = set()         # (pools' shardings, tokens' shape)
        # trace/scope_map.scope_maps() lowers this step again, afresh as
        # here, from the abstract arguments of each first call.
        self._scope_step = scope_map.register(
            self.lower, kind=kind, guard=guard)

    def _jit(self, args):
        pools = tuple(args[2]) + tuple(args[3] or ())
        shardings = tuple(
            None if isinstance(a, jax.core.Tracer)
            else getattr(a, "sharding", None) for a in pools)
        key = (shardings, len(args))
        if key not in self._jits:
            pinned = {}
            if None not in shardings:
                fmt = tuple(pool_format(sh, a.ndim)
                            for sh, a in zip(shardings, pools))
                n = len(args[2])
                pinned = dict(
                    in_shardings=(None, None, fmt[:n], fmt[n:] or None)
                    + (None,) * (len(args) - 4),
                    out_shardings=(None,) * self._n_lead + (fmt,))
            self._jits[key] = jax.jit(
                self._fn, donate_argnums=(2, 3), **pinned)
        return shardings, self._jits[key]

    def __call__(self, *args):
        shardings, jit = self._jit(args)
        call = (shardings, args[1].shape)
        if call in self._called:
            return jit(*args)
        if None not in shardings:       # real pools, not make_jaxpr's tracers
            self._scope_step.note(call, args)
        with fresh_compiles():
            out = jit(*args)
        self._called.add(call)
        return out

    def lower(self, *args):
        return self._jit(args)[1].lower(*args)


def _request_keys(seeds, rids, steps):
    """Per-row PRNG keys: PRNGKey(seed) ∘ fold_in(request_id) ∘
    fold_in(step). The previous additive scheme
    (seed + step*7919 + request_id) collided across requests/steps —
    e.g. (rid, step) and (rid + 7919, step - 1) shared a key."""
    def one(s, r, t):
        return jax.random.fold_in(
            jax.random.fold_in(jax.random.PRNGKey(s), r), t)
    return jax.vmap(one)(seeds, rids, steps)


def _warp_logits(logits, temps, top_ks, top_ps):
    """Per-row temperature → top-k → top-p filtering ([N, V] → [N, V],
    filtered entries at -1e30). Single source of truth for the sampling
    semantics: `_sample_batched` (plain decode) and the speculative
    rejection-sampling verifier (inference/speculative.py) both warp
    through here, so speculation preserves the target distribution wrt
    the EXACT sampler plain decode uses.

    The vocabulary is ordered only when a row asks for top-k or top-p
    (a `lax.cond` on the per-row parameters: a temperature alone filters
    nothing), and then once: what top-k masks is the tail of the sorted
    row, so the masked row in order is the sorted row masked."""
    v = logits.shape[-1]
    x = logits / jnp.maximum(temps[:, None], 1e-6)

    def ordered(x):
        sorted_desc = jnp.sort(x, axis=-1)[:, ::-1]
        k_idx = jnp.clip(top_ks - 1, 0, v - 1)
        kth = jnp.take_along_axis(sorted_desc, k_idx[:, None], axis=-1)
        use_k = top_ks[:, None] > 0
        x = jnp.where(use_k & (x < kth), -1e30, x)
        sorted_desc = jnp.where(use_k & (sorted_desc < kth), -1e30,
                                sorted_desc)
        probs = jax.nn.softmax(sorted_desc, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        cutoff_idx = jnp.sum(cum < top_ps[:, None], axis=-1)
        cutoff = jnp.take_along_axis(sorted_desc, cutoff_idx[:, None],
                                     axis=-1)
        return jnp.where((top_ps[:, None] > 0.0) & (x < cutoff), -1e30, x)

    return jax.lax.cond(jnp.any(_asks_order(top_ks, top_ps)),
                        ordered, lambda x: x, x)


def _asks_order(top_ks, top_ps):
    """Rows whose filter needs the vocabulary in order (numpy or jax:
    the sampler's conditional and the host's counters read the same
    expression)."""
    return (top_ks > 0) | (top_ps > 0.0)


def _sample_batched(logits, seeds, rids, steps, temps, top_ks, top_ps,
                    greedys, tail=None):
    """Batched on-device sampling, one jit for all slots (replaces the
    per-request device_get loop). Per-row params; rows mirror
    engine.sample_logits semantics exactly: temperature → top-k →
    top-p → categorical, greedy bypasses all. logits [B,V] → [B].
    The step does what its rows ask for, inside the one executable: all
    greedy, `argmax` alone; else keys, the warp and a categorical, with
    the vocabulary ordered only for a sampling row's top-k or top-p.
    tail: a few int32 counters of the step (an MoE model's routing
    counts), appended to the tokens so that they reach the host in the
    same small array."""
    greedy_toks = jnp.argmax(logits, axis=-1)

    def sample():
        keys = _request_keys(seeds, rids, steps)
        # A greedy row's warp is thrown away: it asks for no ordering.
        x = _warp_logits(logits, temps, jnp.where(greedys, 0, top_ks),
                         jnp.where(greedys, 0.0, top_ps))
        sampled = jax.vmap(jax.random.categorical)(keys, x)
        return jnp.where(greedys, greedy_toks, sampled)

    toks = jax.lax.cond(jnp.all(greedys), lambda: greedy_toks,
                        sample).astype(jnp.int32)
    return toks if tail is None else jnp.concatenate([toks, tail])


def _sample_round(logits, seeds, rids, steps, temps, top_ks, top_ps,
                  greedys, tail=None, host_tokens=None, from_host=None):
    """The engine's sampler step: `_sample_batched`, and beside it the
    NEXT round's token operand [B, 1], so that a round's tokens reach the
    round after it without a trip through the host: row b is the token
    just sampled, or host_tokens[b] where from_host[b] says the host knows
    better (a slot a prefill has filled since, a row that does not run).
    An admission's one-row call ([1, V] logits, `from_host` false in its
    slot alone) gets `host_tokens` back with the token just sampled in that
    slot's row: the first token reaches the next round on the device too."""
    toks = _sample_batched(logits, seeds, rids, steps, temps, top_ks,
                           top_ps, greedys)
    nxt = None
    if host_tokens is not None:
        nxt = jnp.where(from_host[:, None], host_tokens, toks[:, None])
    return (toks if tail is None else jnp.concatenate([toks, tail])), nxt


@dataclasses.dataclass
class _Round:
    """A plain decode round from its staging to the reading of its tokens.
    The engine keeps at most one that it has dispatched and not read
    (`DynamicInferenceEngine._round`)."""
    rows: Dict[int, "Request"]      # slot -> the request that runs in it
    attrs: dict                     # the span's, from the lengths before it
    ahead: int = 0                  # 1: dispatched before the one before
    #                                 it was read
    logits: Optional[jnp.ndarray] = None    # [B, V], on the device
    moe: Optional[jnp.ndarray] = None       # the step's routing counts
    toks: Optional[jnp.ndarray] = None      # the sampler's, once dispatched
    batch: int = dataclasses.field(init=False)      # rows at the dispatch

    def __post_init__(self):
        self.batch = len(self.rows)


def prefill_call_costs(cfg: TransformerConfig, params):
    """What a [1, width] prefill call costs, from the shapes alone: (bytes
    of weights it streams whatever its width, matmul flops a position).

    Every matrix of the model is read once a call: the layers' (each expert
    of an MoE layer too: tens of routed rows touch nearly all of them), the
    head, and the word embedding where the head is tied to it (an untied
    embedding is gathered by row). A position multiplies each matrix once,
    of an expert stack [L, E, K, N] its top-k."""
    stream = flops = 0.0
    tied = "output" not in params
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        name = jax.tree_util.keystr(path)
        if leaf.ndim < 2 or ("embedding" in name
                             and not (tied and "word" in name)):
            continue
        share = 1.0
        if (cfg.is_moe and leaf.ndim == 4
                and leaf.shape[1] == cfg.moe_experts_here[1]):
            # a position's top-k fall on the router's whole width, of
            # which this stack holds [1] experts
            share = cfg.moe_router_topk / cfg.moe_router_width
        stream += leaf.size * leaf.dtype.itemsize
        flops += 2.0 * leaf.size * share
    if cfg.ssm_heads:
        # A Mamba-2 layer's chunked scan is matrix products too, a position
        # (transformer/ssm.ssd_chunked): Q scores of N a group and Q x P a
        # head within the chunk, N x E into the state and N x E out of it.
        q, n, e = cfg.ssm_chunk_size, cfg.ssm_state_dim, cfg.ssm_inner
        flops += cfg.num_ssm_layers * 2.0 * (
            q * n * cfg.ssm_groups + q * e + 2 * n * e)
    if cfg.kda_heads:
        # A Kimi-delta-attention layer's chunked pass (transformer/kda.py),
        # a position: its row of A and of B (Q scores of K a head each), of
        # the solve and of B U (Q x V a head each), K x E into the chunk's
        # state and twice K x E out of the one that came in.
        q, n, e = cfg.ssm_chunk_size, cfg.ssm_state_dim, cfg.ssm_inner
        flops += cfg.num_kda_layers * 2.0 * (
            2 * q * n * cfg.kda_heads + 2 * q * e + 3 * n * e)
    return stream, flops


def choose_prefill_width(cfg: TransformerConfig, params, max_seq_len: int,
                         block_size: int,
                         device_kind: Optional[str] = None) -> int:
    """The width of the engine's prefill call where the caller gives none,
    from what the engine can see.

    A call reads every weight once whatever its width, so a position is
    free until the positions' matmuls take as long as the stream: (stream
    bytes / HBM rate) over (flops a position / peak), the device's ridge
    (240 flops a byte on a v5e) for a dense bf16 model, more where a
    position computes on a share of what the call streams (an MoE layer's
    top-k of all its experts); the nearest power of two. Wider, a call
    costs by the position.

    The result is at most max_seq_len and at least the pool's block; on an
    EVA model at most the window, which a power of two divides, as it holds
    whole chunks (the chunk is the block). A device whose roofs
    utils/flops.py does not know (a CPU, where the tests run) gets 32."""
    if device_kind is None:
        # where the weights are; abstract ones: JAX's default device
        leaf = next(iter(jax.tree.leaves(params)), None)
        devices = (leaf.devices() if isinstance(leaf, jax.Array)
                   else jax.devices())
        device_kind = next(iter(devices)).device_kind
    roofs = tpu_roofs(device_kind)
    if roofs is None:
        return min(32, max_seq_len)
    peak, rate = roofs
    stream, flops = prefill_call_costs(cfg, params)
    width = 2 ** round(math.log2((stream / rate) / (flops / peak)))
    if cfg.is_eva:
        width = min(width, 2 ** int(math.log2(cfg.eva_window_size)))
    return max(min(width, max_seq_len), block_size)


class DynamicInferenceEngine:
    """Continuous-batching engine (reference DynamicInferenceEngine).

    add_request() any time; step() decodes one token for every active
    request and admits waiting requests into free slots. Finished requests
    surface through the returned events and the optional token_callback.

    block_size/num_blocks size the block pool (see module docstring;
    num_blocks defaults to max_batch full sequences — pass less to run
    oversubscribed with preemption), and enable_prefix_caching turns
    shared-prefix block reuse on/off. `paged` is what is left of the dense
    slot cache's switch: True, and False is refused (perfbench/ passes
    True; ROADMAP D3 drops the argument with those three call sites).

    spec_method ("draft"/"mtp"/"ngram") turns on speculative
    decoding with up to spec_k drafts per round (see module docstring);
    "draft" additionally needs draft_params/draft_cfg (a small model
    sharing the target vocab, e.g. from models/presets.py). When the
    requested proposer is unavailable (no MTP heads, no draft model) the
    engine warns and falls back to plain decode.
    """

    def __init__(self, params, cfg: TransformerConfig, tokenizer=None,
                 max_batch: int = 4, max_seq_len: Optional[int] = None,
                 prefill_buckets: Tuple[int, ...] = (32, 128, 512),
                 paged: bool = True, block_size: int = 16,
                 num_blocks: Optional[int] = None,
                 enable_prefix_caching: bool = True,
                 spec_method: Optional[str] = None, spec_k: int = 4,
                 draft_params=None, draft_cfg=None,
                 prefill_chunk: Optional[int] = None, ctx=None, pool=None,
                 kv_cache_dtype: str = "bf16",
                 adapter_cache=None,
                 spill_host_mb: float = 0.0,
                 spill_watermark_blocks: int = 0):
        if not paged:
            raise ValueError("paged=False: the dense slot cache went in PR "
                             "44; this engine has the block pool alone")
        self.params = params
        self.cfg = cfg
        self.tokenizer = tokenizer
        self.max_batch = max_batch
        self.max_seq_len = max_seq_len or cfg.max_position_embeddings
        self.prefill_buckets = tuple(
            b for b in sorted(prefill_buckets) if b <= self.max_seq_len
        ) or (self.max_seq_len,)
        # The width of a prefill call ([1, prefill_chunk], one compiled
        # program): the caller's, or chosen from the shapes.
        if prefill_chunk is None:
            prefill_chunk = choose_prefill_width(
                cfg, params, self.max_seq_len, block_size)
        self.prefill_chunk = min(prefill_chunk, self.max_seq_len)
        # Rolling reload (DynamicBatchingDriver.request_reload): while
        # True, _admit leaves the waiting queue untouched so running
        # requests drain and the params swap lands on an empty batch.
        self.pause_admission = False

        # A model with state-space layers or gated short convolutions
        # (cfg.stack_plan) keeps, beside its attention layers'
        # pages, a recurrent state or a convolution's tail a slot
        # (PagedKVCache.state); one with EVA attention (cfg.eva_window_size)
        # one pooled row a chunk behind each closed window, in a second
        # region of the slot's table; a shortcut-connected double layer owns
        # two planes of the pools, and a layer that holds a share of the
        # experts runs without its exchange. What each of them cannot do
        # yet is paged_cache.TENANT_LACKS, and refuses here or at its call,
        # in words.
        self.has_state = cfg.num_recurrent_layers > 0
        self.state_kind = ("conv" if cfg.num_conv_layers
                           else "ssm" if self.has_state else None)
        self.eva = cfg.is_eva
        # A sliding-window stack (cfg.sliding_window): its window layers'
        # rows live in planes of their own, which give a slot's blocks back
        # as they fall behind the window (PagedKVCache.window_pages).
        self.has_window = cfg.num_window_layers > 0
        check_tenants(cfg, {cap: how for cap, how, on in (
            ("rewind", "spec_method", spec_method and spec_method != "none"),
            ("snapshot", "spill_host_mb", spill_host_mb),
            ("adapters", "adapter_cache", adapter_cache is not None),
            ("handoff", "an injected pool", pool is not None),
            ("shard", "ctx", ctx is not None),
            ("quantize", f"kv_cache_dtype {kv_cache_dtype!r}",
             kv_cache_dtype != "bf16")) if on})
        if self.eva:
            w, c = cfg.eva_window_size, cfg.eva_chunk_size
            if w % self.prefill_chunk or (self.prefill_chunk % c
                                          and c % self.prefill_chunk):
                raise ValueError(
                    f"prefill_chunk ({self.prefill_chunk}) must divide the "
                    f"EVA window ({w}) and hold whole chunk summaries "
                    f"({c}) or lie inside one: a prefill call never "
                    "straddles a window's edge")
        self.state_stats = {"resets": 0, "dropped": 0, "prefill_scans": 0}
        # Always-on counters of an EVA model's plain decode rounds
        # (stats_snapshot()["eva"]): the rows the paged kernel walked
        # (R(T) a slot, summed) against what full attention would have
        # (T + 1), and the chunk summaries written, a chunk counted once
        # whatever the layers, by decode rounds and prefill calls alike.
        self.eva_stats = {"decode_rounds": 0, "rows_walked": 0,
                          "rows_full_attention": 0,
                          "summary_rows_written": 0}
        # An injected pool (disagg) carries its own kv_cache_dtype; prefix
        # reuse is the pool's to switch off on a model that cannot share.
        self.pool = pool if pool is not None else PagedKVCache(
            cfg, max_batch, self.max_seq_len, num_blocks=num_blocks,
            block_size=block_size,
            enable_prefix_caching=enable_prefix_caching,
            kv_cache_dtype=kv_cache_dtype,
            window_call_rows=self.prefill_chunk)
        # Always-on counters of a sliding-window stack's plain decode rounds
        # (stats_snapshot()["window"]): the rows its window layers' walks
        # read (whole blocks from the one that holds a slot's oldest visible
        # key) against the rows a full-length walk would have, and the pool
        # bytes the running slots held against their tokens in flight.
        self.window_stats = {"decode_rounds": 0, "rows_walked": 0,
                             "rows_full_walk": 0, "bytes_held": 0,
                             "tokens_in_flight": 0}

        # TP serving mesh (ISSUE 9): with a MeshContext whose tp > 1 and
        # a tp-eligible paged config, params replicate over the mesh and
        # the pool pages shard on their Hkv dim — the one-jit-per-step
        # then runs the paged kernels head-sharded (per-shard KV pools,
        # replicated page tables; see ops/pallas/paged_attention.py).
        self.ctx = ctx
        self.tp_paged = False
        if ctx is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P
            # manual-ok: engine construction runs outside any manual
            # region — mesh-level placement of params/pool is GSPMD by
            # design here.
            self._params_sharding = NamedSharding(ctx.mesh, P())
            self.params = jax.device_put(params, self._params_sharding)  # manual-ok: see above
            from megatronapp_tpu.config.parallel_config import TP_AXIS
            from megatronapp_tpu.ops.pallas.paged_attention import (
                tp_paged_ineligible_reason,
            )
            reason = tp_paged_ineligible_reason(cfg, ctx)
            self.tp_paged = reason is None
            if not self.tp_paged and ctx.tp > 1:
                # Name the SPECIFIC failed predicate instead of a
                # generic ineligible-fallback line (ISSUE 11
                # satellite).
                logger.warning(
                    "paged kernels stay single-device on a tp=%d "
                    "mesh: %s", ctx.tp, reason)
            # Pages [L, NB, bs, Hkv, D]: shard Hkv when eligible so
            # each device holds 1/tp of the pool; otherwise just
            # commit them to this mesh (disagg decode sub-mesh). An
            # int8 pool's scale pools [L, NB, bs, Hkv] shard on the
            # same Hkv dim (their last). MLA pools are rank-4 with
            # no head axis — the latent pool [L, NB, bs, klat]
            # shards on its COLUMN dim (kernel_gen._tp_place_latent
            # contracts per-shard columns and psums the logits), the
            # tiny pe pool and the per-row scalar scale pools
            # replicate.
            if not self.tp_paged:
                pages_spec = scales_spec = P()
            elif cfg.multi_latent_attention:
                pages_spec = [P(None, None, None, TP_AXIS), P()]
                scales_spec = P()
            else:
                pages_spec = P(None, None, None, TP_AXIS, None)
                scales_spec = P(None, None, None, TP_AXIS)

            def _sh(spec):
                if isinstance(spec, list):
                    # manual-ok: constructor-time placement, no manual region
                    return [NamedSharding(ctx.mesh, s) for s in spec]
                return NamedSharding(ctx.mesh, spec)  # manual-ok: see above

            # manual-ok: constructor-time placement, no manual region
            self.pool.place_pages(
                _sh(pages_spec),    # manual-ok: see above
                _sh(scales_spec))   # manual-ok: see above
        else:
            self._params_sharding = None
        # Where a decode step's token operand lives: the sampler hands the
        # next round its tokens on the device, committed there like every
        # result of a step; the host's copy (_host_tokens) is committed to
        # the same place, or the two would be two compiled programs.
        self._tokens_sharding = self._params_sharding or (
            jax.sharding.SingleDeviceSharding(
                next(iter(self.pool.pages[0].devices()))))
        # Telemetry (ISSUE 12): per-request lifecycle spans go to the
        # singleton ring tracer (every call is one enabled check when
        # tracing is off); counters/histograms to utils/metrics.
        self._rt = get_request_tracer()
        self._last_round_t: Optional[float] = None
        # Private always-on decode-interval histogram (the disagg
        # coordinator keeps the same) — the PER-REPLICA SLO signal the
        # fleet router scores off (inference/fleet.py): the router's
        # own round timing would measure the whole serial fleet round,
        # not this replica's decode cadence. Live even when the global
        # metrics registry is off.
        from megatronapp_tpu.utils.metrics import Histogram
        self.interval_hist = Histogram(lo=1e-2, hi=1e6, growth=1.25)
        # Multi-tenant LoRA serving (inference/lora.py, ISSUE 19):
        # adapter_cache is an AdapterCache pinning each running slot's
        # low-rank factors resident in HBM banks. row_adapter maps each
        # engine slot to its adapter's BANK slot (0 = the permanent
        # all-zero null adapter, so the step trace is identical whether
        # or not any row carries a real adapter). Acquire/release rides
        # the slot lifecycle: _admit acquires, _free_slot releases — an
        # in-use adapter can never be evicted.
        self.adapters = adapter_cache
        self.row_adapter = np.zeros((max_batch,), np.int32)
        # Optional lora.TenantSLO: the serving driver composes each
        # submit's (priority, deadline) through it when set.
        self.tenant_slo = None
        # Per-tenant serving counters (bounded cardinality: at most
        # _TENANT_LABEL_CAP distinct tenants get their own label; the
        # rest fold into "_other" — same discipline as the fleet's
        # per-replica /metrics labels).
        self._tenant_stats: Dict[str, Dict[str, int]] = {}
        self.lengths = np.zeros((max_batch,), np.int32)
        self.last_tokens = np.zeros((max_batch, 1), np.int32)
        # The plain decode loop runs one round ahead (_plain_round): the
        # round that is dispatched and whose tokens are not read yet, if
        # any. `lengths`, `last_tokens` and every request's `generated`
        # know nothing of it until its tokens are read: between two steps
        # they say what they said when every round was read in its step.
        self._round: Optional[_Round] = None
        # An admitted request's first token that is sampled and not read
        # (ISSUE 53): slot -> (request, the [1] token on the device), in
        # the order of admission, and `last_tokens` with those tokens in
        # their rows, on the device: the token operand of a round that is
        # dispatched before they are read (_sample, _host_tokens). Both
        # live inside one step: every step reads what it admitted (one
        # that raises in a later admission leaves them to the next).
        self._first: Dict[int, Tuple[Request, jnp.ndarray]] = {}
        self._first_tokens: Optional[jnp.ndarray] = None
        self.slots: List[Optional[Request]] = [None] * max_batch
        self.waiting: deque = deque()
        self.requests: Dict[int, Request] = {}
        self._aborted: List[Request] = []   # aborted mid-admission
        self._ids = itertools.count()

        # Host-RAM KV spill tier (ISSUE 20): parked sessions hold their
        # written KV as export_slot payloads in host memory instead of
        # pool blocks — resume imports the bytes back (copy-exact, so
        # the stream continues token-exact) rather than re-prefilling
        # like a preemption. _parked maps rid -> payload in FIFO
        # (= unpark) order; _held rids stay parked until the client
        # asks for the next token (resume_request); _no_repark guards
        # one step's unparked sessions from bouncing straight back out.
        self.spill: Optional[HostSpillTier] = None
        self.spill_watermark = int(spill_watermark_blocks)
        if spill_host_mb:
            self.spill = HostSpillTier(int(spill_host_mb * (1 << 20)))
        elif spill_watermark_blocks:
            raise ValueError(
                "spill_watermark_blocks without a spill budget does "
                "nothing — set spill_host_mb / --kv-spill-host-mb too")
        self._parked: "OrderedDict[int, dict]" = OrderedDict()
        self._held: set = set()
        self._no_repark: set = set()

        # Speculative decoding (inference/speculative.py).
        self.spec_method: Optional[str] = None
        self.spec_k = int(spec_k)
        self.proposer = None
        self.spec_stats = {"rounds": 0, "proposed": 0, "accepted": 0,
                           "emitted_tokens": 0, "model_steps": 0}
        self.step_stats = StepStats()
        # Always-on routing counters of an MoE model's plain decode
        # rounds (stats_snapshot()["moe"]): token-expert assignments of the
        # running requests, and how many (layer, expert) pairs they
        # touched; each round could touch moe_layers x num_moe_experts.
        # On a model that holds a share of its experts or has zero-compute
        # ones (moe.HELD_COUNTS) the pairs are of the experts HELD here,
        # and the assignments split into assignments_zero (picks of a
        # zero-compute expert: compute a token did not cost),
        # assignments_here and assignments_absent (picks of experts held
        # elsewhere, left out), with here_max_rows = Σ over rounds and MoE
        # layers of the most rows one held expert got; elsewhere they read
        # 0, all, 0, 0. `tokens`: the rounds' running requests, counted on
        # the host (assignments = tokens x top-k x MoE layers).
        self.moe_stats = {"decode_rounds": 0, "tokens": 0, "assignments": 0,
                          "expert_pairs_touched": 0, "assignments_zero": 0,
                          "assignments_here": 0, "assignments_absent": 0,
                          "here_max_rows": 0}
        # Always-on counters of what the sampler was asked for
        # (stats_snapshot()["sampler"]), read off the rows it is handed:
        # plain decode rounds and prefills' first samples that took
        # argmax alone (greedy), drew a categorical (sampled), or ordered
        # the vocabulary for a top-k or top-p first (ordered).
        self.sampler_stats = {
            f"{site}_{kind}": 0 for site in ("rounds", "prefills")
            for kind in ("greedy", "sampled", "ordered")}
        # Always-on counters of the paged prefill calls
        # (stats_snapshot()["prefill"]): every call is `prefill_chunk` rows
        # wide, and `tokens` of calls x width were a prompt's.
        self.prefill_stats = {"calls": 0, "tokens": 0}
        # Always-on counters of the paged kernels' walk over plain decode
        # rounds (stats_snapshot()["paged"]): the blocks the running slots
        # hold against running slots x max_blocks_per_seq.
        self.walk_stats = {"decode_rounds": 0, "blocks_live": 0,
                           "blocks_table": 0}
        # Pre-head hidden state at each slot's last verified position —
        # feeds the MTP self-draft proposer.
        self._h_last = np.zeros((max_batch, cfg.hidden_size), np.float32)
        self._h_valid = np.zeros((max_batch,), bool)
        if spec_method and spec_method != "none":
            from megatronapp_tpu.inference.speculative import make_proposer
            self.proposer = make_proposer(spec_method, self,
                                          draft_params=draft_params,
                                          draft_cfg=draft_cfg)
            if self.proposer is not None:
                self.spec_method = spec_method

        # Trace counter for the unified multi-query step (chunked prefill
        # + speculative verify): increments ONLY when jax re-traces, so
        # tests can assert chunked prefill stops retracing per
        # (bucket, cached-length) pair. decode_traces mirrors it for the
        # plain decode step (the /stats jit-count satellite).
        # The jitted steps close over this dict, not over the engine (see
        # _PoolStep); `mq_traces` and `decode_traces` read it.
        self._trace_counts = {"mq": 0, "decode": 0}
        # Launch counts of the traced decode step, cached per jit build
        # (dispatch_stats(); computed lazily: one trace of the jaxpr).
        self._dispatch_stats = None
        self._build_jits()
        logger.info(self.startup_line())

    @property
    def mq_traces(self) -> int:
        return self._trace_counts["mq"]

    @property
    def decode_traces(self) -> int:
        return self._trace_counts["decode"]

    def _state_words(self) -> str:
        return ("gated short-convolution layers" if self.state_kind == "conv"
                else "Kimi-delta-attention layers" if self.cfg.kda_heads
                else "state-space layers")

    def startup_line(self) -> str:
        """What this engine runs, for the log and a server's banner."""
        line = (f"dynamic engine: paged=True, max_batch="
                f"{self.max_batch}, max_seq_len={self.max_seq_len}, "
                f"prefill_chunk={self.prefill_chunk}")
        cfg = self.cfg
        # a hybrid stack's feed-forwards where they are experts
        moe_words = (f", the first {cfg.moe_first_k_dense} with a dense "
                     f"feed-forward, the others with {cfg.num_moe_experts} "
                     f"experts, top-{cfg.moe_router_topk} by "
                     f"{cfg.moe_router_score} scores")
        if self.has_state:
            line += (f", state={cfg.num_recurrent_layers} "
                     f"{self._state_words()} x "
                     f"{self.pool.state_bytes_per_slot} B a slot, prefix "
                     "reuse off (a prefix hit would skip tokens whose "
                     "state nobody kept)")
            if len(cfg.stack_plan[0]) == 1:
                line += (f", layers={cfg.num_layers}, one sublayer each: "
                         f"{cfg.num_attention_layers} attention + "
                         f"{cfg.num_recurrent_layers} {self._state_words()}"
                         f" + {cfg.num_moe_layers} of {cfg.num_moe_experts} "
                         f"experts, top-{cfg.moe_router_topk} by "
                         f"{cfg.moe_router_score} scores")
            elif cfg.is_moe:
                line += (f", layers={cfg.num_layers}: "
                         f"{cfg.num_attention_layers} attention + "
                         f"{cfg.num_recurrent_layers} {self._state_words()}"
                         + moe_words)
        if self.has_window:
            pool = self.pool
            line += (f", planes={cfg.kv_planes} full ({cfg.num_attention_heads}"
                     f" query heads, {pool.num_blocks} blocks) + "
                     f"{cfg.num_window_layers} sliding-window "
                     f"({cfg.window_heads} query heads, window "
                     f"{cfg.sliding_window}, {pool.num_window_blocks} blocks:"
                     f" at most {pool.window_blocks_slot} a slot between "
                     "calls, blocks behind the window are given back); "
                     "refused on window planes: prefix reuse (off), "
                     "spec_method, spill/park, export/import/adopt, lora, an "
                     "injected pool or mesh, a quantized pool")
            if cfg.is_moe:
                line += moe_words
        if self.eva:
            line += (f", eva=window {self.cfg.eva_window_size} exact rows + "
                     f"one summary row every {self.cfg.eva_chunk_size} "
                     f"older ones, at most {self.pool.max_blocks_per_seq} "
                     "blocks a slot; refused on chunk summaries: prefix "
                     "reuse (off), spec_method, spill/park, export/import/"
                     "adopt, lora, an injected pool or mesh, a quantized "
                     "pool")
        if cfg.moe_shortcut_double_layer:
            line += (f", layers={cfg.num_layers} double layers x 2 attention "
                     f"sublayers = {cfg.kv_planes} planes of the pools, one "
                     "MoE a layer on a shortcut")
        if cfg.moe_picks_unheld:
            first, count = cfg.moe_experts_here
            line += (f", experts={count} held ({first}..{first + count - 1}) "
                     f"of {cfg.num_moe_experts} published + "
                     f"{cfg.moe_zero_experts} zero-compute, top-"
                     f"{cfg.moe_router_topk} of {cfg.moe_router_width}; "
                     "absent experts' terms are left out (no exchange)")
        if cfg.vocab_slice_of:
            line += (f", vocabulary={cfg.vocab_size} rows, a slice of "
                     f"{cfg.vocab_slice_of}")
        return line

    def _build_jits(self):
        cfg = self.cfg
        counts = self._trace_counts

        @contextlib.contextmanager
        def counts_kept():
            # A lowering for the scope map traces the step again; the
            # counters say what the ENGINE's calls traced.
            was = dict(counts)
            try:
                yield
            finally:
                counts.update(was)

        # A module of its own and one part as a whole: its instructions
        # name none.
        self._sample_b = scope_map.noted(
            jax.jit(_sample_round), kind="sampler",
            default_part="sampler")
        self._dispatch_stats = None
        msl = self.max_seq_len
        # ctx rides into the step only on a tp-paged mesh (it then
        # dispatches the head-sharded kernel placement inside
        # attention_forward); otherwise the trace stays identical to
        # the single-device engine.
        step_ctx = self.ctx if self.tp_paged else None
        # The pools (`pages`, and `scales`: the int8 pool's fp32
        # scale-pool pair, None for bf16 pools — an empty pytree, so
        # the same signature serves both dtypes; a model with
        # state-space layers: its state pools behind the two page
        # pools, self._pools()) are DONATED, and the
        # layer loop carries them and writes them in place: a step's
        # output pools are its input buffers, and the device holds
        # one pool (_PoolStep pins their layout too). `lora` follows
        # the None trick: None without an adapter cache, else
        # {"row_adapter", "banks"} (the banks are NOT donated — they
        # are the cache's resident HBM arrays and outlive the
        # step).
        def _decode_traced(p, t, pages, scales, tbl, l, a, lora):
            # Python side-effect: runs only while TRACING.
            counts["decode"] += 1
            return _paged_decode_step(p, t, pages, tbl, l, a, cfg,
                                      msl, ctx=step_ctx,
                                      scales=scales, lora=lora)

        self._decode = _PoolStep(_decode_traced, n_lead=2,
                                 kind="decode", guard=counts_kept)

        def _mq_traced(p, t, pages, scales, tbl, starts, qlens, act,
                       lora, rows=None, last=None):
            # Python side-effect: runs only while TRACING.
            counts["mq"] += 1
            return _paged_multiquery_step(p, t, pages, tbl, starts,
                                          qlens, act, cfg, msl,
                                          ctx=step_ctx, scales=scales,
                                          lora=lora, rows=rows,
                                          last=last)

        self._mq_step = _PoolStep(_mq_traced, n_lead=2,
                                  kind="prefill", guard=counts_kept)
        if self.spec_method:
            from megatronapp_tpu.inference.speculative import (
                build_verify_sampler,
            )
            self._verify_sample = build_verify_sampler(
                point_mass=self.proposer.point_mass)
            self.proposer.reset_compilation()

    def reset_compilation(self):
        """Re-trace on next call (after MegaScope hook toggles — see
        StaticInferenceEngine.reset_compilation)."""
        self._build_jits()

    def _pools(self):
        """A step's `pages` operand: the page pools and, behind them, the
        window planes' pools or the recurrent-state pools of a model that
        has them."""
        return (self.pool.pages + (self.pool.window_pages or ())
                + (self.pool.state or ()))

    def _tables(self, rows=slice(None)):
        """A step's table operand for slots `rows`: the full planes' table,
        and on a sliding-window stack the pair of it and the window
        planes'."""
        pool = self.pool
        if not self.has_window:
            return _handed_over(pool.page_table[rows])
        return (_handed_over(pool.page_table[rows]),
                _handed_over(pool.window_table[rows]))

    def _commit_pools(self, new):
        """Take a step's pools back: the donated buffers themselves,
        written in place — (k, v), then (k_window, v_window) for a
        sliding-window stack, (ssm, conv) for a model with
        state-space layers or (conv,) for one with gated short
        convolutions, then (k_scales, v_scales) for int8 pools, whose
        in-jit quantize writes the scale pools through the same layer
        loop."""
        self.pool.pages = tuple(new[:2])
        rest = tuple(new[2:])
        if self.has_window:
            self.pool.window_pages, rest = rest[:2], rest[2:]
        if self.has_state:
            n = len(self.pool.state)
            self.pool.state, rest = rest[:n], rest[n:]
        if self.pool.quantized:
            self.pool.scales = rest

    def _span(self, name: str, rid: Optional[int] = None,
              ring: Optional[str] = None, **attrs):
        """One phase of this engine's work: profiler annotation, this
        engine's step_stats, and the request ring under `ring`."""
        return self._rt.span(name, rid, stats=self.step_stats, ring=ring,
                             **attrs)

    # Bounded per-tenant label cardinality (/metrics + /stats): beyond
    # this many distinct tenants, new ones fold into "_other".
    _TENANT_LABEL_CAP = 32

    def _tenant_label(self, tenant: Optional[str]) -> Optional[str]:
        if tenant is None:
            return None
        if tenant in self._tenant_stats:
            return tenant
        if len(self._tenant_stats) >= self._TENANT_LABEL_CAP:
            return "_other"
        return tenant

    def _tenant_inc(self, tenant: Optional[str], key: str, n: int = 1):
        """Per-tenant serving counters, mirrored to labeled /metrics
        counters at bounded cardinality."""
        label = self._tenant_label(tenant)
        if label is None:
            return
        st = self._tenant_stats.setdefault(
            label, {"requests": 0, "tokens": 0, "finished": 0,
                    "expired": 0})
        st[key] = st.get(key, 0) + n
        telemetry.inc(telemetry.labeled(f"serving_tenant_{key}",
                                        tenant=label), n)

    def _lora_args(self, rows: Optional[np.ndarray] = None):
        """The step jits' `lora` operand: None without an adapter cache
        (an empty pytree — same jit signature), else the per-slot bank
        slots + the cache's resident factor banks. `rows` overrides the
        full per-slot map for single-row calls (chunked prefill)."""
        if self.adapters is None:
            return None
        if rows is None:
            rows = self.row_adapter
        return {"row_adapter": _handed_over(np.asarray(rows, np.int32)),
                "banks": self.adapters.banks}

    # ---- request lifecycle ------------------------------------------------
    def add_request(self, prompt_tokens, max_new_tokens: int,
                    sampling: Optional[SamplingParams] = None,
                    eod_id: Optional[int] = None,
                    priority: int = 0,
                    deadline_s: Optional[float] = None,
                    request_id: Optional[int] = None,
                    adapter_id: Optional[str] = None,
                    tenant: Optional[str] = None) -> int:
        prompt = validate_admission(prompt_tokens, max_new_tokens,
                                    self.max_seq_len, pool=self.pool,
                                    deadline_s=deadline_s)
        # Unknown adapters are a PERMANENT submit-time error (the
        # registry names what it knows) — transient all-slots-pinned
        # pressure is handled at admission instead.
        if adapter_id is not None:
            if self.adapters is None:
                raise ValueError(
                    "adapter_id requires an engine adapter cache — "
                    "construct with adapter_cache= / --lora-dir")
            if adapter_id not in self.adapters.registry:
                raise KeyError(
                    f"unknown adapter {adapter_id!r}; known: "
                    f"{sorted(self.adapters.registry.ids())}")
        now = time.monotonic()
        # An explicit request_id is the cross-process fleet's admission
        # shape (inference/fleet_rpc.py): the ROUTER owns the one rid
        # space spanning every replica worker, so the engine must accept
        # a caller-minted id — the sampler's fold_in chain keys off it,
        # which is what makes a stream's tokens placement-independent.
        if request_id is None:
            request_id = next(self._ids)
        elif request_id in self.requests:
            raise ValueError(f"request id {request_id} already admitted")
        req = Request(request_id, prompt, max_new_tokens,
                      sampling or SamplingParams(), eod_id=eod_id,
                      priority=priority, deadline_s=deadline_s,
                      adapter_id=adapter_id, tenant=tenant,
                      admit_t=now, queued_t=now)
        self.waiting.append(req)
        self.requests[req.request_id] = req
        telemetry.inc("serving_requests_admitted")
        self._tenant_inc(tenant, "requests")
        rt = self._rt
        if rt.enabled:
            rt.instant("admit", req.request_id,
                       prompt_tokens=len(prompt), priority=priority)
            rt.begin("request", req.request_id)
            rt.begin("queue-wait", req.request_id)
        return req.request_id

    def pop_request(self, request_id: int) -> Optional[Request]:
        """Remove and return a finished request (server-side consumers)."""
        return self.requests.pop(request_id, None)

    def abort_request(self, request_id: int) -> Optional[str]:
        """Cancel a request. Returns 'waiting' if it was dequeued before
        running (no finish event will fire), 'running' if it was marked
        to retire on the next step, or None if unknown/already done."""
        req = self.requests.get(request_id)
        if req is None:
            return None
        if self._leave_queue(req):
            req.finished = True
            self._rt.finish(request_id, "abort")
            return "waiting"
        if not req.finished:
            # Running — or mid-admission on the stepper thread (slot not
            # yet assigned): either way, marking finished retires it on
            # the next step, releasing its cache. A canceller's thread
            # fetches nothing: the stepper finds the mark when it reads
            # the round in flight, and drops that round's row (_read).
            req.finished = True
            self._rt.instant("abort", request_id)
            return "running"
        return None

    def _leave_queue(self, req: Request) -> bool:
        """Take `req` out of the waiting queue; False if it is not there
        (it runs, or admission took it meanwhile). A canceller calls this
        from its own thread while the stepper's admission pops from the
        same deque, and a deque that changes under a search raises rather
        than answers (a queue of 192 under 384 cancels did, in one drain of
        seven: PERF.md, PR 41): such a search is made again."""
        while True:
            try:
                self.waiting.remove(req)
                return True
            except ValueError:
                return False
            except (RuntimeError, IndexError):
                continue

    def expire_overdue(self, now: Optional[float] = None) -> List[int]:
        """Abort every request whose deadline passed (per-request SLO
        enforcement): waiting ones leave the queue immediately; running
        ones are marked finished, so the SAME step's retire pass
        releases their slot and pool blocks. Returns the expired request
        ids — step() reports them under events["expired"] so the server
        driver can hand each a clean deadline error frame."""
        import time as _time
        if now is None:
            now = _time.monotonic()
        expired: List[int] = []

        def overdue(r: Request) -> bool:
            return (r.deadline_s is not None and not r.finished
                    and now >= r.deadline_s)

        # Snapshot the waiting deque tolerantly: the sweep runs on the
        # stepper thread while submit() may append concurrently (deque
        # iteration raises RuntimeError on mutation). Expiry is
        # re-checked every step, so skipping one contended sweep is
        # harmless — turning the race into a step failure is not.
        for _ in range(4):
            try:
                overdue_waiting = [r for r in self.waiting if overdue(r)]
                break
            except RuntimeError:
                continue
        else:
            overdue_waiting = []
        for req in overdue_waiting:
            try:
                self.waiting.remove(req)
            except ValueError:
                # cancel()/abort_request on the driver thread removed it
                # between the snapshot and here (same race guard as
                # abort_request) — it is already being retired.
                continue
            req.finished = True
            self._aborted.append(req)    # finish event fires this step
            expired.append(req.request_id)
            self._tenant_inc(req.tenant, "expired")
            self._rt.finish(req.request_id, "expire")
        for req in self.slots:
            if req is not None and overdue(req):
                req.finished = True      # retired (blocks released) below
                expired.append(req.request_id)
                self._tenant_inc(req.tenant, "expired")
                # Spans close when the same step's retire pass reclaims
                # the slot (the one finish funnel).
                self._rt.instant("expire", req.request_id)
        for rid in list(self._parked):
            req = self._parked[rid]["req"]
            if overdue(req):
                # Parked sessions hold no slot — marking finished lets
                # the SAME step's _spill_policy sweep drop the spill
                # entry and fire the finished event.
                req.finished = True
                expired.append(req.request_id)
                self._tenant_inc(req.tenant, "expired")
                self._rt.instant("expire", req.request_id)
        if expired:
            telemetry.inc("serving_deadline_expired", len(expired))
        return expired

    def abort_all(self):
        """Drop ALL queued and running requests (server error recovery).

        Paged blocks are released through the pool so capacity is
        reclaimed and the slot bookkeeping stays consistent — clearing
        slots without releasing would trip PagedKVCache.admit's
        slot-still-holds-blocks assert on the next request. Best-effort
        if the failure left pool bookkeeping itself inconsistent."""
        # A crashed round never reached the point that refreshes
        # _last_round_t — without this reset the first post-recovery
        # round would observe the crash + backoff gap as a "token
        # interval" and poison the histogram's tail.
        self._last_round_t = None
        # The round in flight is not read (the device may be what failed):
        # its tokens are lost with the requests, and so is a first token
        # that was not read.
        self._round = None
        self._first.clear()
        self._first_tokens = None
        for req in list(self.waiting):
            self.requests.pop(req.request_id, None)
            self._rt.finish(req.request_id, "abort")
        self.waiting.clear()
        for slot, req in enumerate(self.slots):
            if req is None:
                continue
            try:
                self.pool.release(slot, np.asarray(req.tokens),
                                  int(self.lengths[slot]))
            except Exception:  # noqa: BLE001 — best-effort reclaim
                pass
            self._free_slot(slot)
            self.requests.pop(req.request_id, None)
            self._rt.finish(req.request_id, "abort")
        for rid in list(self._parked):
            req = self._parked[rid]["req"]
            self._drop_parked(rid)
            self.requests.pop(req.request_id, None)
            self._rt.finish(req.request_id, "abort")

    def _free_slot(self, slot: int):
        """Clear every per-slot engine resource (request ref, length,
        proposer state, MTP hidden) — the ONE place to extend when a new
        per-slot resource is added; pool blocks are released by the
        caller (release semantics differ per path). A slot's recurrent
        state needs nothing: the next sequence's first prefill call starts
        from zeros whatever the slot holds. The slot's row of the round in
        flight, if it has one, is an over-run from here on, and a first
        token of its request that is sampled and not read is forgotten:
        the request samples it again, the same, where it resumes."""
        self._drop_row(slot)
        self._forget_first(slot)
        self.slots[slot] = None
        self.lengths[slot] = 0
        self._h_valid[slot] = False
        if self.adapters is not None:
            # Unpin the slot's adapter (slot 0 = null adapter, a no-op);
            # rc==0 residents park in the cache's LRU, still hittable.
            self.adapters.release(int(self.row_adapter[slot]))
            self.row_adapter[slot] = 0
        if self.proposer is not None:
            self.proposer.on_release(slot)

    @property
    def has_work(self) -> bool:
        return (bool(self.waiting) or bool(self._parked)
                or any(r is not None for r in self.slots))

    def set_params(self, params):
        """Install new model params (rolling engine reload). Same pytree
        structure/shapes as the old ones, so every jit trace stays valid
        — the driver drains running requests first and swaps on an empty
        batch, then re-admits the waiting queue against the new
        weights. The prefix cache is flushed: its blocks hold KV from
        the OLD weights."""
        if self._params_sharding is not None:
            # manual-ok: host-side reload path, no manual region
            params = jax.device_put(params, self._params_sharding)
        self.params = params
        self.pool.flush_prefix_cache()

    def free_decode_slots(self) -> int:
        return sum(1 for r in self.slots if r is None)

    def drained_for_reload(self) -> bool:
        """True when a rolling params swap is safe: no occupied slots
        (waiting requests keep their position and run on new weights)."""
        return all(r is None for r in self.slots)

    def adopt_request(self, req: Request, src_slot: int, length: int
                      ) -> int:
        """Adopt a prefilled request from the disaggregated prefill side
        (inference/disagg.py): move its pool blocks from staging slot
        `src_slot` into a free decode slot via the pool's page-table
        transfer — NO KV copy — and resume decoding at `length` (the
        prompt KV rows written by prefill; the first generated token was
        already sampled prefill-side with the identical fold_in chain).
        Returns the decode slot."""
        check_tenants(self.cfg, {"handoff": "adopt_request"})
        slot = next(i for i in range(self.max_batch)
                    if self.slots[i] is None)
        if self.adapters is not None:
            self.row_adapter[slot] = self.adapters.acquire(req.adapter_id)
        self.pool.transfer_slot(src_slot, slot)
        req.slot = slot
        self.slots[slot] = req
        self.requests[req.request_id] = req
        self.lengths[slot] = length
        self.last_tokens[slot, 0] = req.generated[-1]
        if self.proposer is not None:
            self.proposer.on_admit(slot, req)
        rt = self._rt
        if rt.enabled:
            rt.instant("adopt", req.request_id, slot=slot, length=length)
            rt.begin("decode", req.request_id)
        return slot

    # ---- live session migration (ISSUE 14, inference/fleet.py) -----------
    def export_request(self, rid: int) -> Optional[dict]:
        """READ-ONLY snapshot of a RUNNING request's migratable state:
        the pool's exported KV rows (+ scales, verbatim bytes) plus the
        Request object itself — which carries the sampler fold_in chain
        position (request_id + len(generated)) and every admission
        field, so the destination continues the EXACT stream (greedy
        and sampled alike: the key chain PRNGKey(seed)∘rid∘step never
        references which replica computes the step). Returns None when
        the request is not currently decoding in a slot — waiting /
        mid-prefill requests own no resumable KV and migrate by simple
        requeue instead. Nothing is mutated here: the source rolls
        nothing back if the migration dies between export and import
        (the "fleet-migrate" chaos site)."""
        check_tenants(self.cfg, {"snapshot": "export_request"})
        req = self.requests.get(rid)
        if req is not None and not req.finished and rid in self._parked:
            # A PARKED session migrates too (a drained/reloading replica
            # must not strand its parked sessions): the spill payload IS
            # the export_slot snapshot, handed over as-is — read-only
            # here; release_exported drops the spill entry on commit.
            return dict(self._parked[rid])
        if (req is None or req.finished or req.slot < 0
                or self.slots[req.slot] is not req or not req.generated
                or self._first_owed(req)):
            return None
        # The session leaves as of its last token read, which is what
        # `lengths` counts; its row of the round in flight goes when the
        # slot does (release_exported).
        valid_len = int(self.lengths[req.slot])
        payload = self.pool.export_slot(req.slot, valid_len)
        payload["req"] = req
        return payload

    def import_request(self, payload: dict) -> bool:
        """Install a migrated session from an `export_request` payload:
        the pool scatters the exported rows into fresh blocks
        (copy-exact — see PagedKVCache.import_slot) and the request
        resumes decoding at its exact position. Returns False with the
        destination untouched when no decode slot is free or the pool
        cannot host the rows. The MTP proposer's pre-head hidden is not
        shipped (proposal-quality-only, same note as the disagg adopt
        path); ngram/draft proposers are unaffected."""
        check_tenants(self.cfg, {"snapshot": "import_request"})
        req: Request = payload["req"]
        slot = next((i for i in range(self.max_batch)
                     if self.slots[i] is None), None)
        if slot is None:
            return False
        aslot = 0
        if self.adapters is not None:
            from megatronapp_tpu.inference.lora import AdapterSlotsPinned
            try:
                # The adapter ID rides the Request in the payload — the
                # destination re-acquires from ITS registry/cache, so a
                # migrated stream decodes under the same factors
                # (token-exact; drilled in tests).
                aslot = self.adapters.acquire(req.adapter_id)
            except (AdapterSlotsPinned, KeyError):
                # Can't host the adapter here (pinned-full / not in this
                # replica's registry): refuse with nothing touched — the
                # router treats False like a full destination.
                return False
        if not self.pool.import_slot(slot, payload):
            if self.adapters is not None:
                self.adapters.release(aslot)
            return False
        self.row_adapter[slot] = aslot
        valid_len = payload["valid_len"]
        req.slot = slot
        self.slots[slot] = req
        self.requests[req.request_id] = req
        self.lengths[slot] = valid_len
        self.last_tokens[slot, 0] = req.generated[-1]
        # Followers on THIS replica hit the migrated prompt blocks like
        # any locally-prefilled ones.
        self.pool.register_prefix(slot, np.asarray(req.tokens), valid_len)
        if self.proposer is not None:
            self.proposer.on_admit(slot, req)
        self._rt.instant("migrate-in", req.request_id, slot=slot,
                         length=valid_len)
        return True

    def release_exported(self, rid: int):
        """Source-side completion of a migration: the destination has
        imported the KV copy, so this replica's slot releases. The
        prompt prefix registers first (release() does) — the KV stays
        weight-valid, so followers on THIS replica keep hitting it. The
        request itself now lives in the destination engine's books."""
        req = self.requests.pop(rid)
        if rid in self._parked:
            # A PARKED session migrated: its KV never re-entered this
            # pool (export handed the spill payload over verbatim), so
            # completion just drops the spill entry. Not an unpark —
            # the session resumes on the destination, not here.
            self._drop_parked(rid)
            self._rt.instant("migrate-out", rid, slot=-1)
            return
        # req.slot already points at the DESTINATION slot (import set
        # it) — find the source slot by identity.
        slot = next(i for i, r in enumerate(self.slots) if r is req)
        self.pool.release(slot, np.asarray(req.tokens),
                          int(self.lengths[slot]))
        self._free_slot(slot)
        self._rt.instant("migrate-out", rid, slot=slot)

    # ---- host-RAM KV spill tier (ISSUE 20) -------------------------------
    def _park(self, req: Request, hold: bool = False) -> bool:
        """Move a RUNNING request's written KV to the host spill tier
        and release its slot + pool blocks. The copy is the SAME
        export_slot payload a migration ships (verbatim stored rows +
        scales), so the resume path (_unpark → import_slot) restores
        the pool bytes exactly and the stream continues token-exact for
        every KV dtype — unlike preemption, which re-prefills. Returns
        False with NOTHING mutated when the session is not parkable or
        the tier's byte budget refuses the payload (the caller falls
        back to preemption). `hold` marks a client-requested park
        (tools/loadgen.py long-idle phases): the session stays parked
        until resume_request, excluded from the auto-unpark pass."""
        if (self.spill is None or req.finished or req.slot < 0
                or self.slots[req.slot] is not req or not req.generated
                or self._first_owed(req)):
            # (a first token sampled and not read: still mid-admission,
            # the cache holds a row more than `tokens[:-1]`)
            return False
        rid = req.request_id
        slot = req.slot
        valid_len = int(self.lengths[slot])
        payload = self.pool.export_slot(slot, valid_len)   # read-only
        if not self.spill.would_fit(payload["nbytes"]):
            self.spill.counters["rejects"] += 1
            return False
        # Chaos site "kv-spill" (park window): fires between the
        # read-only host copy above and the page-table release below —
        # nothing has mutated yet, so the rollback is "do nothing": the
        # session keeps decoding in its slot, audit() passes, and the
        # stream is unaffected (tests/test_resilience.py drill).
        chaos.fire("kv-spill")
        payload["req"] = req
        assert self.spill.put(rid, payload)     # would_fit checked above
        # Not preempted=True: full blocks stay prefix-cached while
        # evictable (same as a retirement) and the preemption counters
        # keep meaning "KV thrown away", which a park is not.
        self.pool.release(slot, np.asarray(req.tokens), valid_len)
        self._free_slot(slot)
        req.slot = -1
        self._parked[rid] = payload
        if hold:
            self._held.add(rid)
        rt = self._rt
        if rt.enabled:
            rt.end("decode", rid)
            rt.instant("park", rid, bytes=payload["nbytes"])
        return True

    def _unpark(self, rid: int) -> bool:
        """Re-enter a parked session through the pool (import_slot) so
        the next decode step continues its stream token-exact. Returns
        False with the session STILL PARKED (and the pool untouched)
        when no slot is free, the adapter bank is pinned full, or the
        pool cannot host the rows right now — the policy retries next
        step."""
        payload = self._parked.get(rid)
        if payload is None:
            return False
        req: Request = payload["req"]
        slot = next((i for i in range(self.max_batch)
                     if self.slots[i] is None), None)
        if slot is None:
            return False
        aslot = 0
        if self.adapters is not None:
            from megatronapp_tpu.inference.lora import AdapterSlotsPinned
            try:
                aslot = self.adapters.acquire(req.adapter_id)
            except AdapterSlotsPinned:
                return False
        if not self.pool.import_slot(slot, payload):
            if self.adapters is not None:
                self.adapters.release(aslot)
            return False
        try:
            # Chaos site "kv-spill" (unpark mirror): fires between the
            # pool import and the spill-entry release — the rollback
            # returns the imported blocks to the pool and the session
            # stays parked (its payload was never dropped), so audit()
            # passes and a later resume is still token-exact.
            chaos.fire("kv-spill")
        except Exception:
            self.pool.release(slot, np.asarray(req.tokens),
                              int(payload["valid_len"]))
            if self.adapters is not None:
                self.adapters.release(aslot)
            raise
        self.row_adapter[slot] = aslot
        valid_len = int(payload["valid_len"])
        req.slot = slot
        self.slots[slot] = req
        self.lengths[slot] = valid_len
        self.last_tokens[slot, 0] = req.generated[-1]
        # Followers hit the resumed prompt blocks like locally-prefilled
        # ones (mirror of import_request).
        self.pool.register_prefix(slot, np.asarray(req.tokens), valid_len)
        if self.proposer is not None:
            self.proposer.on_admit(slot, req)
        self.spill.pop(rid)                       # counts the unpark
        del self._parked[rid]
        self._held.discard(rid)
        self._no_repark.add(rid)   # no park/unpark thrash within a step
        rt = self._rt
        if rt.enabled:
            rt.instant("unpark", rid, slot=slot, length=valid_len)
            rt.begin("decode", rid)
        return True

    def _drop_parked(self, rid: int):
        """Remove a parked session's spill entry WITHOUT counting an
        unpark (aborts, expiry, migration-out): only genuine resumes
        count."""
        self._parked.pop(rid, None)
        self._held.discard(rid)
        if self.spill is not None:
            self.spill.pop(rid, unpark=False)

    def park_request(self, rid: int) -> bool:
        """Client-requested park of a long-idle session (held until
        resume_request). True when the session is parked (or already
        was)."""
        req = self.requests.get(rid)
        if req is None or req.finished:
            return False
        if rid in self._parked:
            self._held.add(rid)
            return True
        return self._park(req, hold=True)

    def resume_request(self, rid: int) -> bool:
        """Unpark-on-next-token: the client wants this session's next
        token, so clear its hold and try to re-enter the pool now (the
        step policy retries if capacity refuses). True when the session
        is known (parked or running)."""
        if rid not in self._parked:
            return rid in self.requests
        self._held.discard(rid)
        self._unpark(rid)     # best-effort now; _spill_policy retries
        return True

    def _park_for_pressure(self) -> bool:
        """Park the lowest-priority running session (same victim order
        as preemption: highest (priority, request_id) first). False when
        nobody is parkable — the caller falls back to preemption."""
        runners = sorted(
            (r for r in self.slots
             if r is not None and not r.finished and r.slot >= 0
             and r.request_id not in self._no_repark),
            key=lambda r: (r.priority, r.request_id))
        for victim in reversed(runners):
            if self._park(victim):
                return True
        return False

    def _spill_policy(self):
        """Per-step spill housekeeping, run after the expiry sweep and
        before admission: (1) drop parked sessions finished by
        abort/expiry so their finished events fire this step; (2)
        auto-unpark (FIFO = park order) the non-held parked sessions
        capacity allows — forced when the engine is otherwise idle so a
        parked session can never stall forever; (3) watermark parking:
        while available_blocks() sits below --kv-spill-watermark-blocks,
        park lowest-priority sessions to keep decode/admission
        headroom."""
        if self.spill is None:
            return
        for rid in list(self._parked):
            req = self._parked[rid]["req"]
            if req.finished:
                self._drop_parked(rid)
                self._aborted.append(req)    # finished event this step
        for rid in [r for r in self._parked if r not in self._held]:
            payload = self._parked[rid]
            need = cdiv(int(payload["valid_len"]) + 1,
                        self.pool.block_size)
            idle = (not self.waiting and
                    all(r is None for r in self.slots))
            if (not idle and self.pool.available_blocks() - need
                    < self.spill_watermark):
                break    # below-watermark unpark would thrash right back
            if not self._unpark(rid):
                break    # no slot / pool full; FIFO — don't skip ahead
        if self.spill_watermark > 0:
            while (self.pool.available_blocks() < self.spill_watermark
                   and self._park_for_pressure()):
                pass

    def _admit(self) -> List[Request]:
        if (self.pause_admission or not self.waiting
                or all(r is not None for r in self.slots)):
            return []
        with self._span("engine.admit"):
            return self._admit_waiting()

    def _admit_waiting(self) -> List[Request]:
        admitted = []
        for slot in range(self.max_batch):
            if self.slots[slot] is not None or not self.waiting:
                continue
            # Pop FIRST (re-appended on failure): a peek-then-pop window
            # would race a concurrent abort_request removing the head —
            # popleft would then silently drop the NEXT request.
            req = self.waiting.popleft()
            if req.finished:          # aborted while queued (racy path)
                self._aborted.append(req)
                continue
            # Admission by block availability: if the pool cannot
            # host this prompt now, keep FIFO order and wait for
            # retirements/preemptions to free blocks.
            plan = self.pool.admit(slot, req.tokens)
            if plan is None and self.spill is not None:
                # Pressure path, spill preferred over waiting: park
                # idle-priority sessions (KV kept byte-exact in host
                # RAM) until the prompt fits — this is what lifts
                # concurrent sessions-at-budget past the HBM block
                # count.
                while plan is None and self._park_for_pressure():
                    plan = self.pool.admit(slot, req.tokens)
            if plan is None:
                self.waiting.appendleft(req)
                break
            if self.adapters is not None:
                from megatronapp_tpu.inference.lora import (
                    AdapterSlotsPinned)
                try:
                    aslot = self.adapters.acquire(req.adapter_id)
                except AdapterSlotsPinned:
                    # Every adapter bank slot is pinned by running
                    # requests — a transient capacity condition exactly
                    # like pool-full admit: keep FIFO order and wait for
                    # a retirement to unpin one.
                    self.pool.release(slot, np.asarray(req.tokens), 0)
                    self.waiting.appendleft(req)
                    break
                except Exception:
                    # Load fault (the "lora-load" chaos drill): the
                    # cache mutated nothing — release the admitted
                    # blocks, requeue at the head, re-raise for the
                    # stepper watchdog. The retry costs one step.
                    self.pool.release(slot, np.asarray(req.tokens), 0)
                    req.queued_t = time.monotonic()
                    self.waiting.appendleft(req)
                    raise
                self.row_adapter[slot] = aslot
            req.slot = slot
            self.slots[slot] = req
            rid = req.request_id
            self._rt.end("queue-wait", rid)
            # One measurement feeds /stats and /metrics alike.
            waited = time.monotonic() - req.queued_t
            self.step_stats.add("queue_wait", waited)
            telemetry.observe("serving_queue_wait_ms", waited * 1e3)
            p_len = len(req.tokens)
            try:
                with self._span("engine.prefill", rid, ring="prefill",
                                prompt_tokens=p_len,
                                cached_tokens=plan.cached_tokens):
                    self._prefill_into_slot(req, plan)
            except Exception:
                # The "kv-quant-write" chaos drill fires between quantize
                # and page-table commit in the chunk-scatter path.
                # Re-raised for the stepper watchdog's accounting.
                self._unadmit(req)
                raise
            admitted.append(req)
        return admitted

    def _unadmit(self, req: Request):
        """Exception-safe rollback of an admission whose prefill, or the
        fetch of whose first token, raised: return every admitted block
        (valid_len=0 — partially-written rows are stale data the retry
        overwrites, never registered prefixes), clear the slot (its row of
        the round ahead goes with it, _free_slot), and requeue the request
        at the head so a transient fault costs one step."""
        slot = req.slot
        self.pool.release(slot, np.asarray(req.tokens), 0)
        self._free_slot(slot)
        req.slot = -1
        req.queued_t = time.monotonic()
        self.waiting.appendleft(req)
        self._rt.begin("queue-wait", req.request_id)

    def _prefill_into_slot(self, req: Request, plan):
        # req.tokens (prompt + any pre-preemption generated tokens): a
        # resumed request re-prefills its full history and samples the
        # NEXT token, exactly like a fresh admission.
        tokens = req.tokens
        p_len = len(tokens)
        # Chunked prefill through the unified multi-query step: ONE
        # trace per chunk shape instead of one per
        # (bucket, cached-length) pair, and prefix-cache hits are
        # attended directly through the page table (no dense gather;
        # MLA rides the same path since ISSUE 17 — the latent kernel
        # handles the ragged chunk, and quantized latent rows
        # quantize inside the same _mq_step jit).
        logits_last = self._paged_prefill_chunked(req, tokens, p_len, plan)
        self.lengths[req.slot] = p_len
        # First generated token comes from the last PROMPT position. It
        # is sampled on the device and stays there: the step dispatches
        # the next round behind the prompt's calls, that token its row's
        # operand, before it fetches it (_plain_round, _read_first).
        logits_last = mask_padded_vocab(logits_last, self.cfg)
        self._first[req.slot] = (req, self._sample(logits_last[None], req))
        if self.spec_method:
            # A speculative round proposes from the host's tokens: the
            # host stands here until the device has run every call of the
            # prompt.
            self._take_first(req.slot)
            self.proposer.on_admit(req.slot, req)

    def _paged_prefill_chunked(self, req: Request, tokens, p_len: int,
                               plan) -> jnp.ndarray:
        """Prefill the uncached prompt tail in fixed-size chunks against
        the page table (the ROADMAP chunked-prefill follow-up): each
        chunk is one `_mq_step` call at shape [1, prefill_chunk], so the
        compiler sees ONE program for every (prompt length, cached
        length) combination. Returns the last prompt position's logits
        [V] and records the pre-head hidden for the MTP proposer."""
        slot = req.slot
        pool = self.pool
        cached = plan.cached_tokens
        c = self.prefill_chunk
        table_row = self._tables(slice(slot, slot + 1))          # [1, MB]
        # The sequence's state starts from zeros inside its first call
        # (start 0: prefix reuse is off on such a model), in slot `slot`.
        rows = None
        if self.has_state:
            assert cached == 0, cached
            rows = jnp.asarray([slot], jnp.int32)
            self.state_stats["resets"] += 1
        pos, count = cached, 0
        logits = hid = None
        # (what each Mamba-2 layer of a call scans, chunk by chunk)
        call_attrs = {"ssd_chunks": cdiv(
            c, min(self.cfg.ssm_chunk_size, c))} if self.cfg.ssm_heads else {}
        while pos < p_len:
            count = min(c, p_len - pos)
            if self.eva:
                # A call stays inside one window (prefill_chunk divides
                # it); its blocks, and the closing of the window before,
                # are the pool's (admission saw to the room).
                w = self.cfg.eva_window_size
                count = min(count, w - pos % w)
                if cached or not pool.ensure_rows(slot, pos, count):
                    raise RuntimeError(
                        f"EVA prefill of slot {slot} at position {pos}: "
                        f"{cached} cached tokens, or the pool ran out of "
                        "the blocks its admission had counted")
                table_row = self._tables(slice(slot, slot + 1))
                call_attrs = {"summaries": (
                    (pos + count) // self.cfg.eva_chunk_size
                    - pos // self.cfg.eva_chunk_size)}
                self.eva_stats["summary_rows_written"] += (
                    call_attrs["summaries"])
            if self.has_window:
                # The window planes' blocks for this call's rows; those
                # wholly behind its first query's window go back first.
                if cached or not pool.window_ensure(slot, pos, count):
                    raise RuntimeError(
                        f"prefill of slot {slot} at position {pos}: "
                        f"{cached} cached tokens, or the window planes ran "
                        f"out of blocks ({pool.num_window_blocks} for "
                        f"{self.max_batch} slots and one call of {c})")
                table_row = self._tables(slice(slot, slot + 1))
                call_attrs = {"window_blocks": len(
                    pool.window_slot_blocks(slot))}
            chunk = np.zeros((1, c), np.int32)
            chunk[0, :count] = tokens[pos:pos + count]
            if pool.quantized:
                # Chaos site "kv-quant-write": fires between staging the
                # chunk and committing its quantized rows + scales to
                # the pool — the admit caller (_admit) rolls the slot's
                # blocks back and requeues the request, so a transient
                # fault costs one step and audit() stays clean (the
                # tests/test_resilience.py drill).
                chaos.fire("kv-quant-write")
            with self._span("engine.prefill_call", tokens=count, width=c,
                            **call_attrs):
                # The head runs on the call's last real position: the one
                # the first sample reads, of the prompt's last call.
                logits, hid, new = self._mq_step(
                    self.params, jnp.asarray(chunk), self._pools(),
                    self.pool.scales,
                    table_row, jnp.asarray([pos], jnp.int32),
                    jnp.asarray([count], jnp.int32), jnp.ones((1,), bool),
                    self._lora_args(rows=self.row_adapter[slot:slot + 1]),
                    rows, jnp.asarray([count - 1], jnp.int32))
                self._commit_pools(new)
                self.state_stats["prefill_scans"] += (
                    self.cfg.num_recurrent_layers)
            self.prefill_stats["calls"] += 1
            self.prefill_stats["tokens"] += count
            pos += count
        if self.has_window:
            pool.window_trim(slot, p_len)
        # Register the prompt's full blocks so concurrent same-prefix
        # requests hit them immediately.
        pool.register_prefix(slot, np.asarray(tokens), p_len)
        if self.proposer is not None and self.proposer.needs_hidden:
            self._h_last[slot] = np.asarray(
                jax.device_get(hid[0, 0]), np.float32)
            self._h_valid[slot] = True
        return logits[0, 0]

    def _sample(self, logits, req: Request) -> jnp.ndarray:
        """Single-row sampling (prefill), dispatched and not fetched: the
        [1] token stays on the device, and the same call puts it into its
        slot's row of the next round's token operand (`_first_tokens`:
        the program the one-row sampler always was, with one `where` more,
        so an admission under a round in flight compiles nothing). Same
        fold_in key chain as the batched decode sampler, so a request's
        sample stream is reproducible and independent of batch
        composition."""
        s = req.sampling
        self._count_sample("prefills", s.greedy, s.top_k, s.top_p)
        tok, self._first_tokens = self._sample_b(
            logits,
            jnp.asarray([s.seed], jnp.int32),
            jnp.asarray([req.request_id], jnp.int32),
            jnp.asarray([len(req.generated)], jnp.int32),
            jnp.asarray([s.temperature], jnp.float32),
            jnp.asarray([s.top_k], jnp.int32),
            jnp.asarray([s.top_p], jnp.float32),
            jnp.asarray([s.greedy], bool), None, self._host_tokens(),
            jnp.asarray(np.arange(self.max_batch) != req.slot))
        return tok

    def _count_sample(self, site: str, greedys, top_ks, top_ps):
        """One call of the sampler into sampler_stats, by the predicates
        `_sample_batched` reduces from the same rows (or one row's
        scalars)."""
        sampling = np.logical_not(greedys)
        if not sampling.any():
            kind = "greedy"
        elif (sampling & _asks_order(top_ks, top_ps)).any():
            kind = "ordered"
        else:
            kind = "sampled"
        self.sampler_stats[f"{site}_{kind}"] += 1

    def _sampling_rows(self, held=None) -> Dict[str, np.ndarray]:
        """Per-slot sampling parameters + key-chain inputs for every
        non-finished slot (inactive rows are greedy, which asks the
        sampler for nothing; their outputs are ignored). Single source
        for the plain sampler, the speculative verifier, and the draft
        proposer — one place to thread a future sampling field through.
        held: a round's {slot: request} where that is not who holds the
        slots now (a round read a step after its dispatch). `steps` is
        the request's tokens so far (a first token that is sampled and not
        read counts): the round that is read is the oldest unread one, so
        the token it samples is the next of the chain."""
        b = self.max_batch
        rows = {"seeds": np.zeros(b, np.int32),
                "rids": np.zeros(b, np.int32),
                "steps": np.zeros(b, np.int32),
                "temps": np.ones(b, np.float32),
                "top_ks": np.zeros(b, np.int32),
                "top_ps": np.zeros(b, np.float32),
                "greedys": np.ones(b, bool)}
        for i, r in (enumerate(self.slots) if held is None
                     else held.items()):
            if r is None or r.finished:
                continue
            s = r.sampling
            rows["seeds"][i], rows["rids"][i] = s.seed, r.request_id
            rows["steps"][i] = len(r.generated) + self._first_owed(r)
            rows["temps"][i], rows["top_ks"][i] = s.temperature, s.top_k
            rows["top_ps"][i], rows["greedys"][i] = s.top_p, s.greedy
        return rows

    def _host_tokens(self) -> jnp.ndarray:
        """The host's `last_tokens` as a step's operand: a copy
        (_handed_over says why), committed where the sampler's own token
        operand is (_tokens_sharding); with the first tokens that are
        sampled and not read in their rows, where there are any (_sample
        put them there on the device)."""
        if self._first_tokens is not None:
            return self._first_tokens
        # manual-ok: a host array staged for a step, no manual region
        return jax.device_put(np.array(self.last_tokens),
                              self._tokens_sharding)

    def _first_owed(self, req: Request) -> bool:
        """Whether `req`'s first token (of this admission) is sampled and
        not read."""
        return self._first.get(req.slot, (None,))[0] is req

    def _forget_first(self, slot: int):
        """`slot`'s first token is read, or will not be."""
        self._first.pop(slot, None)
        if not self._first:
            self._first_tokens = None

    def _take_first(self, slot: int, ahead: int = 0) -> Tuple[int, int]:
        """Fetch and record the first token of the request admitted into
        `slot` -> (request id, token). ahead: 1 where the next round was
        dispatched since, that token its row's operand; the chip then runs
        that round while the host stands here, else it stands too."""
        req, tok = self._first[slot]
        rid = req.request_id
        with self._span("engine.prefill.sample", rid, ahead=ahead):
            tok = int(jax.device_get(tok)[0])
        self._forget_first(slot)
        self.step_stats.first_samples_ahead += ahead
        first_life = not req.generated   # vs resumed after preempt
        self._record_token(req, tok)
        if first_life:
            # TTFT is a first-token metric: a preempted request's
            # resume prefill emits its Nth token, not its first —
            # re-observing would inflate the percentiles the fleet
            # router scores replicas by.
            telemetry.observe("serving_ttft_ms",
                              (time.monotonic() - req.admit_t) * 1e3)
        self._rt.begin("decode", rid)
        if req.finished:
            self._drop_row(slot)
        return rid, tok

    def _read_first(self, out: List[Tuple[int, int]], ahead: int = 0):
        """Fetch and record every first token that is sampled and not
        read, in the order of admission, (request id, token) onto `out`. A
        fetch that raises rolls back the admissions not read yet, as a
        failed prefill is (_unadmit), the last first so that the queue
        keeps its order."""
        try:
            while self._first:
                out.append(self._take_first(next(iter(self._first)), ahead))
        except Exception:
            for req, _ in reversed(list(self._first.values())):
                self._unadmit(req)
            raise

    def _sample_round(self, rnd: _Round,
                      then: Optional[Dict[int, Request]] = None):
        """Dispatch the batched on-device sampler on `rnd`'s logits: ONE
        call for every slot, the step's counters (`moe`) riding behind
        the tokens. Nothing is fetched: `rnd.toks` stays on the device
        (_sample_all reads it), and the returned [B, 1] operand hands the
        tokens to the round that runs `then` {slot: request}: a row that
        goes on from `rnd` takes the token just sampled, any other the
        host's `last_tokens` (a slot a prefill has filled since, a row
        that does not run)."""
        r = self._sampling_rows(rnd.rows)
        self._count_sample("rounds", r["greedys"], r["top_ks"], r["top_ps"])
        then = then or {}
        from_host = np.array([s not in then or rnd.rows.get(s) is not then[s]
                              for s in range(self.max_batch)])
        rnd.toks, tokens = self._sample_b(
            rnd.logits, jnp.asarray(r["seeds"]), jnp.asarray(r["rids"]),
            jnp.asarray(r["steps"]), jnp.asarray(r["temps"]),
            jnp.asarray(r["top_ks"]), jnp.asarray(r["top_ps"]),
            jnp.asarray(r["greedys"]), rnd.moe, self._host_tokens(),
            jnp.asarray(from_host))
        return tokens

    def _sample_all(self, rnd: _Round) -> np.ndarray:
        """`rnd`'s sampled tokens (and the counters behind them) on the
        host: ONE device round-trip per decode round. The sampler was
        dispatched with the next round's stage where that round runs
        ahead, else it is now."""
        if rnd.toks is None:
            self._sample_round(rnd)
        return np.asarray(jax.device_get(rnd.toks))

    def _record_token(self, req: Request, tok: int):
        req.generated.append(tok)
        self.last_tokens[req.slot, 0] = tok
        self._tenant_inc(req.tenant, "tokens")
        if (tok == req.eod_id or
                len(req.generated) >= req.max_new_tokens):
            req.finished = True

    # ---- pool pressure handling ------------------------------------------
    def _preempt(self, req: Request, out: List[Request]):
        """Push a running request back to the waiting queue, releasing its
        blocks (full blocks stay prefix-cached while evictable, so the
        resume prefill usually re-hits its own KV). A recurrent state is
        dropped with the slot: the request is recomputed from its
        tokens."""
        slot = req.slot
        self.state_stats["dropped"] += int(self.has_state)
        self.pool.release(slot, np.asarray(req.tokens),
                          int(self.lengths[slot]), preempted=True)
        self._free_slot(slot)
        req.slot = -1
        req.queued_t = time.monotonic()
        self.waiting.appendleft(req)
        out.append(req)
        rt = self._rt
        if rt.enabled:
            rt.end("decode", req.request_id)
            rt.instant("preempt", req.request_id)
            rt.begin("queue-wait", req.request_id)

    def _decode_capacity(self, req: Request,
                         after: Optional[_Round] = None) -> bool:
        """The blocks that cover `req`'s append position, in the full
        planes and, on a sliding-window stack, in the window planes, whose
        blocks behind the round's window go back first. after: the unread
        round before the one to cover, which appends a row of its own."""
        at = int(self.lengths[req.slot]) + self._owed(req, after)
        return self.pool.ensure_capacity(req.slot, at) and (
            not self.has_window or self.pool.window_ensure(req.slot, at))

    def _ensure_decode_capacity(self) -> List[Request]:
        """Before a decode step, every active slot needs the block that
        covers its append position. Exhaustion preempts the
        lowest-priority running request (highest (priority, request_id));
        the needy request preempts ITSELF when it is the lowest."""
        preempted: List[Request] = []
        runners = sorted(
            (r for r in self.slots if r is not None
             and self._goes_on(r, None)),
            key=lambda r: (r.priority, r.request_id))
        for req in runners:
            if req.slot < 0:
                continue                 # preempted earlier this step
            while not self._decode_capacity(req):
                victim = next(r for r in reversed(runners)
                              if r.slot >= 0)
                if (victim is not req and self.spill is not None
                        and victim.request_id not in self._no_repark
                        and self._park(victim)):
                    # Spill preferred over preemption: the victim's KV
                    # moved to host RAM byte-exact instead of being
                    # thrown away — its resume costs an import, not a
                    # re-prefill. Falls through to preemption when the
                    # tier's budget refuses the payload.
                    continue
                self._preempt(victim, preempted)
                if victim is req:
                    break
        return preempted

    def _retire(self) -> List[Request]:
        done = []
        for slot, req in enumerate(self.slots):
            if req is not None and req.finished:
                done.append(req)
                # The cache holds tokens[:-1] (the final sampled
                # token's KV was never written) — register/release
                # only the written rows.
                self.pool.release(slot, np.asarray(req.tokens),
                                  int(self.lengths[slot]))
                self._free_slot(slot)
                telemetry.inc("serving_requests_retired")
                self._tenant_inc(req.tenant, "finished")
                self._rt.finish(req.request_id, "retire",
                                generated=len(req.generated))
        return done

    # ---- main loop --------------------------------------------------------
    def step(self) -> Dict[str, List]:
        """Admit → decode (one token, or a speculate+verify round) for
        all active slots → retire. A plain round's tokens are read a step
        after its dispatch (the loop runs one round ahead, _plain_round):
        a step still delivers one round's tokens, in order.

        Returns {"admitted": [ids], "tokens": [(id, tok)], "finished":
        [ids], "preempted": [ids], "expired": [ids]} for this step
        (expired ⊆ finished: deadline-overdue requests aborted by this
        step's expiry sweep)."""
        stats = self.step_stats
        before = stats.totals()
        with self._span("engine.step") as whole:
            events, batch = self._step()
        admitted = len(events["admitted"])
        if admitted:
            stats.admit_steps += 1
            stats.admitted += admitted
        elif batch:
            stats.note_round(whole.seconds, batch, before)
        return events

    def _step(self) -> Tuple[Dict[str, List], int]:
        """step() inside its span; also returns the decode round's batch
        (0 when no round ran)."""
        expired = self.expire_overdue()
        if self.spill is not None:
            self._no_repark.clear()
            self._spill_policy()
        admitted = self._admit()
        # (first tokens read at their admission: a speculative engine's;
        # the others join when they are read, _read_first)
        events = {"admitted": [r.request_id for r in admitted],
                  "tokens": [(r.request_id, r.generated[-1])
                             for r in admitted if not self._first_owed(r)],
                  "finished": [], "preempted": [], "expired": expired}

        # With a round in flight the step to cover is the one after it,
        # and that is asked for without preempting (_plain_round): a
        # victim is chosen only when no round is in flight, as it always
        # was.
        with self._span("engine.capacity"):
            preempted = ([] if self._round is not None
                         else self._ensure_decode_capacity())
        events["preempted"] = [r.request_id for r in preempted]

        # (a request whose unread first token is its last by count runs in
        # no round)
        active = [r for r in self.slots
                  if r is not None and self._goes_on(r, None)]
        batch = self._round.batch if self._round else len(active)
        if batch:
            # Token-interval telemetry: back-to-back decode rounds only
            # (an idle gap is not a token interval — same rule as the
            # disagg coordinator's SLO accounting).
            t_round = time.monotonic()
            if self._last_round_t is not None:
                iv_ms = (t_round - self._last_round_t) * 1e3
                telemetry.observe("decode_interval_ms", iv_ms)
                self.interval_hist.observe(iv_ms)
            if self.spec_method:
                self._spec_round(active, events)
            else:
                self._plain_round(active, events)
            self._last_round_t = time.monotonic()
        else:
            self._last_round_t = None
        # No round ran, or none that read them (a speculative one).
        self._read_first(events["tokens"])

        with self._span("engine.retire"):
            retired = self._retire()
        events["finished"] = [r.request_id for r in retired]
        events["finished"] += [r.request_id for r in self._aborted]
        self._aborted = []
        return events, batch

    # ---- the plain decode loop, one round ahead ---------------------------
    # A step reads ONE round's tokens. Before it blocks on them it stages
    # and dispatches the round after (_plain_round), so the fetch, the
    # record, the retire, the driver's callbacks and the next step's sweep,
    # admission and capacity pass all run while the chip runs that round.
    # Nothing in a round's inputs needs the host to have seen the round
    # before: the tokens go from the sampler to the next step on the device
    # (_sample_round), a row of the unread round appends at its slot's
    # length + 1 (_owed), and who ends by count is known a round ahead
    # (_goes_on). What is learned late is a stop on `eod_id`, or from outside
    # (abort_request, expire_overdue): the round ahead then has a row too
    # many, whose token is dropped (_drop_row). `lengths`, `last_tokens`
    # and `generated` move when a round is READ, so between two steps they
    # are what a loop that never ran ahead would hold, and whatever takes
    # a request out of its slot there (_retire, _preempt, _park,
    # release_exported: all through _free_slot) releases, registers or
    # exports as of the last token read, fetches nothing, and drops the
    # slot's row of the round in flight: the request leaves as of that
    # token, and the dropped one is sampled again, the same, where it
    # resumes (the key chain counts tokens, not rounds). The row such a
    # round wrote lies behind the length released, in a block the slot
    # still owned; a recurrent state it advanced dies with the slot. The
    # device runs what it is sent in order, so blocks that go back to the
    # pool under a round in flight are written by nothing before that
    # round has read them; an admission's calls queue behind it likewise.
    # Speculative rounds propose from the host's tokens and stay as they
    # were (_spec_round).
    # An admission is no exception (ISSUE 53): the prompt's calls and the
    # one-row sampler are dispatched and nothing is fetched (`_first`, the
    # pending first tokens; every request a step admits, so their calls
    # queue back to back), the round after the one in flight takes the
    # admitted row's token on the device (_host_tokens: the one-row sampler
    # wrote it into `last_tokens`' copy there), and only then does the step
    # fetch it, under `prefill.sample` with `ahead` = 1, beside the read of
    # the round in flight (_read_first; the first token goes out before any
    # later one of its request). To `_goes_on` and to the sampler's `steps`
    # a pending first token is a token owed, like a row of the unread round
    # (`_first_owed`; `_owed` itself is about positions, and the prompt's
    # rows are in `lengths` since the prefill). A first token that ends its
    # request late makes an over-run of the row staged with it; a fetch
    # that raises rolls the admission back (_unadmit); whatever takes the
    # request out of its slot before the fetch forgets the token
    # (_free_slot), and parking or exporting refuses such a request.
    # A step that dispatches no round (nothing goes on, or the pool does
    # not cover the next rows) reads first tokens with `ahead` = 0, as a
    # speculative engine does at the admission itself.
    @staticmethod
    def _owed(req: Request, rnd: Optional[_Round]) -> bool:
        """Whether `rnd`, a round that is dispatched and not read (None:
        there is none), owes `req` a token."""
        return rnd is not None and rnd.rows.get(req.slot) is req

    def _goes_on(self, req: Request, rnd: Optional[_Round]) -> bool:
        """Whether `req` runs in the round after `rnd`: it has not ended,
        and the token `rnd` owes it, or its first one where that is not
        read yet, is not its last by count."""
        return (not req.finished and len(req.generated)
                + self._owed(req, rnd) + self._first_owed(req)
                < req.max_new_tokens)

    def _lengths_after(self, after: Optional[_Round]) -> np.ndarray:
        """The slots' lengths as the step after `after` sees them (a copy):
        a row the unread round appends counts."""
        lengths = np.array(self.lengths)
        if after is not None:
            lengths[list(after.rows)] += 1
        return lengths

    def _drop_row(self, slot: int):
        """`slot`'s row of the round in flight, if it has one, is an
        over-run: its request ended or left the slot before the round's
        token for it was read, and the token is dropped. A round left
        with no row is not read at all."""
        rnd = self._round
        if rnd is not None and rnd.rows.pop(slot, None) is not None:
            self.step_stats.overrun_rows += 1
            if not rnd.rows:
                self._round = None

    def _new_round(self, rows: Dict[int, Request],
                   after: Optional[_Round] = None) -> _Round:
        """A round over `rows`, yet to be dispatched, with the
        `decode_round` span's attributes, from the lengths its step will
        see (_lengths_after), and the walk's always-on counters with
        them."""
        lens = self._lengths_after(after)[list(rows)]
        attrs = {"kv_tokens": int(lens.sum())}
        # the blocks this round's paged kernel walks (it reads the
        # row the round appends too), of those the table could name
        bs = self.pool.block_size
        walked = lens
        if self.eva:
            # kv_rows: what the kernel walks, R(T) a slot, the rows of
            # its closed windows' summaries (summary_rows) among them.
            cfg, st = self.cfg, self.eva_stats
            walked = table_rows(cfg, lens)
            attrs["kv_rows"] = int((walked + 1).sum())
            attrs["summary_rows"] = int(
                (lens // cfg.eva_window_size).sum()
                * (cfg.eva_window_size // cfg.eva_chunk_size))
            st["decode_rounds"] += 1
            st["rows_walked"] += attrs["kv_rows"]
            st["rows_full_attention"] += int((lens + 1).sum())
            # summaries: the chunks this round fills, and so pools
            attrs["summaries"] = int(
                ((lens + 1) % cfg.eva_chunk_size == 0).sum())
            st["summary_rows_written"] += attrs["summaries"]
        if self.has_window:
            # window_blocks / window_rows: what the window layers' walks
            # read a plane, whole blocks from the one that holds a slot's
            # oldest visible key; bytes_held: the pool bytes of the running
            # slots' blocks, both kinds of plane.
            first = np.maximum(lens + 1 - self.cfg.sliding_window, 0) // bs
            attrs["window_blocks"] = int((lens // bs + 1 - first).sum())
            attrs["window_rows"] = int((lens + 1 - first * bs).sum())
            attrs["bytes_held"] = self.pool.bytes_held()
            st = self.window_stats
            st["decode_rounds"] += 1
            st["rows_walked"] += attrs["window_rows"]
            st["rows_full_walk"] += int((lens + 1).sum())
            st["bytes_held"] += attrs["bytes_held"]
            st["tokens_in_flight"] += int((lens + 1).sum())
        attrs["kv_blocks"] = int((walked // bs + 1).sum())
        self.walk_stats["decode_rounds"] += 1
        self.walk_stats["blocks_live"] += attrs["kv_blocks"]
        self.walk_stats["blocks_table"] += (
            len(rows) * self.pool.page_table.shape[1])
        return _Round(rows, attrs, ahead=int(after is not None))

    def _plain_round(self, active: List[Request], events: Dict):
        """One round's tokens for the step (non-speculative): the round in
        flight's, or that of `active` where none is; and before they are
        read, the dispatch of the round after for those of `active` who
        go on, where the pool covers their next rows as it stands. Where
        it does not, nothing runs ahead: this round is read, and the next
        step starts afresh with no round in flight, preempting or parking
        as the loop that never ran ahead would, for the same victim at
        the same token. The first tokens of the requests this step
        admitted are read behind those dispatches, before the round's."""
        cur, self._round = self._round, None
        if cur is None:
            cur = self._new_round({r.slot: r for r in active})
        self.step_stats.rounds_ahead += cur.ahead
        with self._span("engine.decode_round", ring="decode-step",
                        batch=cur.batch, ahead=cur.ahead, **cur.attrs):
            sent = cur.logits is None
            if sent:
                self._dispatch(cur)
            with self._span("engine.capacity"):
                then = sorted((r for r in active if self._goes_on(r, cur)),
                              key=lambda r: (r.priority, r.request_id))
                covered = all(self._decode_capacity(r, cur) for r in then)
            if then and covered:
                self._round = self._new_round({r.slot: r for r in then},
                                              after=cur)
                self._dispatch(self._round, after=cur)
                sent = True
            try:
                self._read_first(events["tokens"], ahead=int(sent))
                self._read(cur, events["tokens"])
            except Exception:
                # Its tokens never came: the round ahead ran on them and
                # goes too. The host's record is as of the round before,
                # which is where the next step starts.
                self._round = None
                raise

    def _plain_round_inner(self, active: List[Request], events: Dict):
        """A round dispatched and read at once, inside its caller's span:
        what a speculative round falls back to when nothing was
        proposed. It runs on the host's tokens: first tokens not read yet
        (none on a speculative engine) are read before it."""
        self._read_first(events["tokens"])
        rnd = _Round({r.slot: r for r in active if not r.finished}, {})
        if rnd.rows:
            self._dispatch(rnd)
            self._read(rnd, events["tokens"])

    def _dispatch(self, rnd: _Round, after: Optional[_Round] = None):
        """Stage and dispatch `rnd`. Its tokens are the host's
        `last_tokens`, or come on the device from `after`, the unread
        round before it, whose sampler is dispatched here; a row that
        `after` appends lies a position further."""
        with self._span("engine.decode.stage"):
            if after is not None:
                with self._span("engine.decode.stage.sample"):
                    tokens = self._sample_round(after, rnd.rows)
            with self._span("engine.decode.stage.put"):
                if after is None:
                    tokens = self._host_tokens()
                active_np = np.zeros((self.max_batch,), bool)
                active_np[list(rnd.rows)] = True
                tables = self._tables(slice(0, self.max_batch))
                lengths = jnp.asarray(self._lengths_after(after))
                active = jnp.asarray(active_np)
                lora = self._lora_args()
            with self._span("engine.decode.stage.dispatch"):
                logits, rnd.moe, new = self._decode(
                    self.params, tokens, self._pools(), self.pool.scales,
                    tables, lengths, active, lora)
                self._commit_pools(new)
                rnd.logits = mask_padded_vocab(logits, self.cfg)

    def _read(self, rnd: _Round, out: List[Tuple[int, int]]):
        """Fetch `rnd`'s tokens and record them, (request id, token) onto
        `out`; a row read has written its slot's kv at lengths[slot]. A
        row whose request was stopped since the dispatch is an over-run,
        and a request that ends here makes one of its row of the round
        ahead."""
        with self._span("engine.decode.wait"):
            toks = self._sample_all(rnd)
        with self._span("engine.decode.record"):
            if rnd.moe is not None:
                # what the step counted, over-runs among its rows
                st, tail = self.moe_stats, toks[self.max_batch:]
                st["decode_rounds"] += 1
                st["tokens"] += rnd.batch
                counts = dict(zip(HELD_COUNTS, (int(n) for n in tail)))
                counts.setdefault("assignments_here", counts["assignments"])
                for name, n in counts.items():
                    st[name] += n
            self.spec_stats["model_steps"] += 1
            before = len(out)
            for slot, req in rnd.rows.items():
                if req.finished:
                    self.step_stats.overrun_rows += 1
                    continue
                tok = int(toks[slot])
                self.lengths[slot] += 1
                self._record_token(req, tok)
                out.append((req.request_id, tok))
                if req.finished:
                    self._drop_row(slot)
            self.spec_stats["emitted_tokens"] += len(out) - before
            telemetry.inc("serving_tokens_emitted", len(out) - before)

    def _spec_round(self, active: List[Request], events: Dict):
        """One speculate+verify round: propose up to spec_k drafts per
        slot, verify all of them in ONE batched multi-query forward, and
        accept by exact rejection sampling (greedy: bit-identical argmax
        chain; sampled: target distribution preserved). Rejected tokens'
        KV is rolled back via PagedKVCache.rewind."""
        b, k = self.max_batch, self.spec_k

        # Opportunistic capacity for the speculative tail: span-1 is
        # already guaranteed by _ensure_decode_capacity; under pressure
        # speculation SHRINKS instead of preempting.
        k_caps = np.zeros((b,), np.int32)
        for req in active:
            slot = req.slot
            length = int(self.lengths[slot])
            want = min(k, req.max_new_tokens - len(req.generated) - 1,
                       self.max_seq_len - 1 - length)
            if want > 0:
                k_caps[slot] = self.pool.extend_capacity(
                    slot, length + 1, want)

        try:
            with self._span("engine.decode_round", ring="spec-round",
                            batch=len(active)):
                self._spec_round_inner(active, events, k_caps)
        except Exception:
            # Leave the pool consistent on ANY mid-round failure (the
            # "spec-verify" chaos drill): every surviving slot rewinds
            # to its last VERIFIED length (+1 for this step's guaranteed
            # append block) — written-but-unaccepted draft KV becomes
            # stale rows that the retried round overwrites, and the
            # over-granted speculative tail blocks go back to the pool.
            # Slots already advanced by this round keep their accepted
            # tokens (their rewind is a no-op). audit() passes either
            # way.
            for req in active:
                if req.slot >= 0:
                    self.pool.rewind(req.slot,
                                     int(self.lengths[req.slot]) + 1)
            raise

    def _spec_round_inner(self, active: List[Request], events: Dict,
                          k_caps: np.ndarray):
        b, k = self.max_batch, self.spec_k
        drafts, counts, q_probs = self.proposer.propose(k_caps)
        if not counts.any():
            # Nothing proposed anywhere (e.g. n-gram on non-repetitive
            # text): the (K+1)-wide verify would pay ~K+1× decode cost
            # to emit one token per row — take the plain 1-token step
            # instead (streams are identical by construction). Drop the
            # over-granted spec blocks first, keeping the one covering
            # this step's append position.
            for req in active:
                self.pool.rewind(req.slot,
                                 int(self.lengths[req.slot]) + 1)
            self._plain_round_inner(active, events)
            return

        with self._span("engine.decode.stage"):
            with self._span("engine.decode.stage.sample"):
                # The verifier's operands; its dispatch waits for the
                # step's logits (decode.wait).
                rows = self._sampling_rows()
            with self._span("engine.decode.stage.put"):
                q_lens = np.ones((b,), np.int32)
                tokens = np.zeros((b, k + 1), np.int32)
                active_np = np.zeros((b,), bool)
                for req in active:
                    slot = req.slot
                    active_np[slot] = True
                    tokens[slot, 0] = self.last_tokens[slot, 0]
                    n = int(counts[slot])
                    tokens[slot, 1:1 + n] = drafts[slot, :n]
                    q_lens[slot] = 1 + n
                operands = (
                    jnp.asarray(tokens), self.pool.pages, self.pool.scales,
                    jnp.asarray(self.pool.page_table[:self.max_batch]),
                    jnp.asarray(self.lengths), jnp.asarray(q_lens),
                    jnp.asarray(active_np), self._lora_args())
            with self._span("engine.decode.stage.dispatch"):
                logits, hidden, new = self._mq_step(self.params, *operands)
                self._commit_pools(new)
                logits = mask_padded_vocab(logits, self.cfg)
                # Chaos site "spec-verify": fires at the WORST point — the
                # multi-query step already wrote every draft token's KV,
                # nothing is accepted yet — so the drill proves
                # _spec_round's rollback (rewind to the last verified
                # length) keeps the pool auditable and the stream exact.
                chaos.fire("spec-verify")
        with self._span("engine.decode.wait"):
            accepts, out_toks = self._verify_sample(
                logits, jnp.asarray(drafts), jnp.asarray(q_lens), q_probs,
                jnp.asarray(rows["seeds"]), jnp.asarray(rows["rids"]),
                jnp.asarray(rows["steps"]), jnp.asarray(rows["temps"]),
                jnp.asarray(rows["top_ks"]), jnp.asarray(rows["top_ps"]),
                jnp.asarray(rows["greedys"]))
            accepts = np.asarray(jax.device_get(accepts))
            out_toks = np.asarray(jax.device_get(out_toks))
            h_sel = None
            if self.proposer.needs_hidden:
                h_sel = np.asarray(jax.device_get(jnp.take_along_axis(
                    hidden, jnp.asarray(accepts)[:, None, None],
                    axis=1)[:, 0]), np.float32)
        with self._span("engine.decode.record"):
            self.spec_stats["rounds"] += 1
            self.spec_stats["model_steps"] += 1
            for req in active:
                slot = req.slot
                n = int(counts[slot])
                a = min(int(accepts[slot]), n)
                emitted = [int(t) for t in drafts[slot, :a]]
                emitted.append(int(out_toks[slot]))
                len_before = int(self.lengths[slot])
                m = 0
                for tok in emitted:
                    self._record_token(req, tok)
                    events["tokens"].append((req.request_id, tok))
                    m += 1
                    if req.finished:
                        break   # eod/budget: drop the rest of the window
                # Valid KV = [last_token, accepted drafts] — rewind the
                # written-but-rejected tail (and over-granted blocks).
                self.lengths[slot] = len_before + m
                self.pool.rewind(slot, len_before + m)
                if h_sel is not None:
                    self._h_last[slot] = h_sel[slot]
                    self._h_valid[slot] = True
                req.spec_proposed += n
                req.spec_accepted += a
                self.spec_stats["proposed"] += n
                self.spec_stats["accepted"] += a
                self.spec_stats["emitted_tokens"] += m
                # Acceptance histogram (ISSUE 12): accepted drafts per
                # verify round, per request row — /metrics percentiles
                # show the acceptance DISTRIBUTION, not just the mean.
                telemetry.observe("spec_accepted_per_round", a,
                                  lo=0.5, hi=64, growth=1.5)
                telemetry.inc("spec_proposed_tokens", n)
                telemetry.inc("spec_accepted_tokens", a)
                telemetry.inc("serving_tokens_emitted", m)
                self.proposer.on_verified(slot, a)

    def run_to_completion(self,
                          token_callback: Optional[Callable] = None
                          ) -> Dict[int, np.ndarray]:
        """Drive step() until every request finishes; returns
        {request_id: full token array}."""
        results: Dict[int, np.ndarray] = {}
        finished_reqs: Dict[int, Request] = {}
        while self.has_work:
            ev = self.step()
            if token_callback is not None:
                for rid, tok in ev["tokens"]:
                    token_callback(rid, tok)
            for rid in ev["finished"]:
                finished_reqs[rid] = self.requests[rid]
        for rid, req in finished_reqs.items():
            results[rid] = req.tokens
            self.requests.pop(rid, None)
        return results

    # ---- observability ----------------------------------------------------
    def dispatch_stats(self, force: bool = False) -> Dict:
        """Launch counts of the traced decode step at the engine's
        shapes (utils/dispatch.launch_stats): `kernels` is its
        pallas_calls a step, scan bodies times their length;
        `expert_stack_slices` its equations that cut one layer's expert
        kernel out of the stack (0 when the grouped GEMMs read the stack
        in place, and on a dense model); `scatters` its scatter equations
        (0 where a kernel appends the pages: the dropless experts move rows
        by gathers alone). One trace of the jaxpr, cached per jit build;
        nothing is compiled."""
        if self._dispatch_stats is not None and not force:
            return self._dispatch_stats
        from megatronapp_tpu.utils.dispatch import launch_stats
        spec = lambda a: jax.ShapeDtypeStruct(  # noqa: E731
            a.shape, a.dtype)
        p_spec = jax.tree.map(spec, self.params)
        pages_spec = jax.tree.map(spec, self._pools())
        scales_spec = jax.tree.map(spec, self.pool.scales)
        mb = self.pool.page_table.shape[1]
        table = jax.ShapeDtypeStruct((self.max_batch, mb), jnp.int32)
        args = (p_spec,
                jax.ShapeDtypeStruct((self.max_batch, 1), jnp.int32),
                pages_spec, scales_spec,
                (table, table) if self.has_window else table,
                jax.ShapeDtypeStruct((self.max_batch,), jnp.int32),
                jax.ShapeDtypeStruct((self.max_batch,), jnp.bool_),
                jax.tree.map(spec, self._lora_args()))
        try:
            moe = _moe_of(self.params["block"]) or {}
            kernels = [moe[k]["qint8"] if isinstance(moe[k], dict)
                       else moe[k]
                       for k in ("fc1_kernel", "fc2_kernel") if k in moe]
            stats = launch_stats(
                self._decode, *args,
                slice_shapes=[w.shape[1:] for w in kernels])
        except Exception as e:  # noqa: BLE001 — observability must not
            # take the serving loop down with it.
            logger.warning("decode dispatch accounting failed: %s", e)
            stats = {"error": str(e)}
        self._dispatch_stats = stats
        return stats

    def stats_snapshot(self, include_dispatch: bool = False) -> Dict:
        """JSON-ready serving stats (the server's GET /stats payload):
        pool occupancy, prefix-cache hit rate, speculative acceptance,
        active batch size — serving is observable without log scraping.
        "paged" holds the walk's counters over plain decode rounds:
        blocks_live of blocks_table is the share of the page table's
        width that held rows.
        "state" is a dict on a model with state-space layers or gated
        short convolutions (False otherwise): `kind` ("ssm" or "conv"),
        `layers` and `slots` of recurrent state at `bytes_per_slot`,
        `resets` (sequences started from zeros at admission), `dropped`
        (states thrown away by preemption), `prefill_scans` (chunk scans
        run: prefill calls x such layers); of kind "ssm" also which
        `mixer` ("mamba1", "mamba2", "kda"), its `heads` (0: a vector state a
        channel), `state_dim` and the convolution's `conv_channels`.
        "sampler" counts what the sampler was asked for, by
        plain decode rounds and by prefills' first samples: `*_greedy`
        (argmax alone), `*_sampled` (a categorical, the vocabulary not
        ordered), `*_ordered` (a sort ran for some row's top-k or top-p).
        "prefill": the `width` of a prefill call (`prefill_chunk`: given,
        or chosen from the shapes), the `calls` made, the prompt `tokens`
        they ran, and `fill_share` = tokens / (calls x width), the share of
        the calls' rows that were prompt and not padding.
        "eva" is a dict on a model with EVA attention (False otherwise):
        `layers`, `window`, `chunk`; `windows_closed` and the `blocks_freed`
        by them; `summary_rows_written` (chunks pooled, a chunk counted
        once whatever the layers); over plain decode rounds `rows_walked`
        (R(T) a slot: what the paged kernel read) against
        `rows_full_attention` (T + 1); `max_blocks_slot`, the most blocks
        one slot has held.
        "window" is a dict on a sliding-window stack (False otherwise):
        `window`, `planes_full` and `planes_window`; the window planes'
        `num_blocks`, `blocks_taken`, `blocks_given_back` and `blocks_held`
        (taken - given back), `blocks_slot_bound` (what a slot holds at most
        between calls), `max_blocks_slot` and `peak_blocks_held`; over plain
        decode rounds `rows_walked` (what a window layer's walk read, whole
        blocks from the one that holds a slot's oldest visible key) against
        `rows_full_walk` (T + 1 a slot), and `bytes_held` (pool bytes of the
        running slots' blocks, both kinds of plane, summed over rounds)
        against `tokens_in_flight` (T + 1 a slot, summed alike).
        "moe" is a dict on an MoE model, summed over plain decode rounds:
        `decode_rounds`, `tokens` (their running requests), `assignments`
        (tokens x top-k x MoE layers), `expert_pairs_touched` of
        `expert_pairs_possible` (MoE layers x `experts_here` x rounds); on
        a model that counts its held experts' load (cfg.moe_counts_load: a
        share of the experts, zero-compute ones, or a router's selection
        bias) the assignments split into `assignments_zero`,
        `assignments_here` and `assignments_absent`, and `here_max_rows`
        sums the most rows one held expert got a layer and round
        (elsewhere 0, all, 0, 0).

        include_dispatch=True adds the traced decode step's launch
        counts (dispatch_stats; the first call traces the step once and
        compiles nothing — /stats opts in, /healthz stays free of it)."""
        pool = self.pool
        st = dict(pool.stats)
        seen = st["prefix_hit_tokens"] + st["prefill_tokens"]
        rows = self.prefill_stats["calls"] * self.prefill_chunk
        # Byte accounting reads the ADDRESSABLE pool arrays (int8
        # data + fp32 scales for quantized pools), never a dtype
        # assumption — /stats and /healthz stay honest when the pool
        # dtype differs from the param dtype. resident_bytes counts
        # blocks whose data is live (in use + LRU-parked, still
        # hittable); pool_bytes_total is the full allocation.
        bpb = pool.bytes_per_block
        resident_blocks = pool.num_blocks - pool.free_blocks()
        out = {
            "engine": "dynamic",
            "paged": dict(self.walk_stats),
            "max_batch": self.max_batch,
            "active": sum(1 for r in self.slots if r is not None),
            "waiting": len(self.waiting),
            "multiquery_traces": self.mq_traces,
            "decode_traces": self.decode_traces,
            "steps": self.step_stats.snapshot(),
            "sampler": dict(self.sampler_stats),
            "prefill": dict(
                self.prefill_stats, width=self.prefill_chunk,
                fill_share=(round(self.prefill_stats["tokens"] / rows, 4)
                            if rows else 0.0)),
            "state": False,
            "eva": False,
            "window": False,
            "pool": {
                "num_blocks": pool.num_blocks,
                "block_size": pool.block_size,
                "kv_cache_dtype": pool.kv_cache_dtype,
                "bytes_per_block": bpb,
                "pool_bytes_total": pool.bytes_total,
                "resident_bytes": resident_blocks * bpb,
                "blocks_in_use": pool.blocks_in_use(),
                "blocks_free": pool.free_blocks(),
                "blocks_evictable": pool.evictable_blocks(),
                "prefix_hit_rate": (
                    round(st["prefix_hit_tokens"] / seen, 4) if seen
                    else 0.0),
                **st,
            },
        }
        if self.eva:
            out["eva"] = dict(
                self.eva_stats, **self.pool.eva_stats,
                layers=self.cfg.num_layers,
                window=self.cfg.eva_window_size,
                chunk=self.cfg.eva_chunk_size)
        if self.has_window:
            ws = pool.window_stats
            out["window"] = dict(
                self.window_stats, **ws,
                window=self.cfg.sliding_window,
                planes_full=self.cfg.kv_planes,
                planes_window=self.cfg.num_window_layers,
                num_blocks=pool.num_window_blocks,
                blocks_slot_bound=pool.window_blocks_slot,
                blocks_held=pool.window_blocks_held(),
                bytes_total=pool.window_bytes_total)
        if self.has_state:
            out["state"] = dict(
                self.state_stats, kind=self.state_kind,
                layers=self.cfg.num_recurrent_layers, slots=self.max_batch,
                bytes_per_slot=self.pool.state_bytes_per_slot)
            if self.state_kind == "ssm":
                out["state"].update(
                    mixer=("kda" if self.cfg.kda_heads else
                           "mamba2" if self.cfg.ssm_heads else "mamba1"),
                    heads=self.cfg.ssm_heads or self.cfg.kda_heads,
                    state_dim=self.cfg.ssm_state_dim,
                    conv_channels=self.cfg.ssm_conv_channels)
        if self.cfg.is_moe:
            here = self.cfg.moe_experts_here[1]
            per_round = self.cfg.num_moe_layers * here
            out["moe"] = dict(
                self.moe_stats, experts_here=here, expert_pairs_possible=(
                    per_round * self.moe_stats["decode_rounds"]))
        if include_dispatch:
            out["decode_dispatch"] = self.dispatch_stats()
        if self.spill is not None:
            out["spill"] = {"watermark_blocks": self.spill_watermark,
                            "held": len(self._held),
                            **self.spill.stats()}
        if self.adapters is not None:
            out["lora"] = self.adapters.stats_snapshot()
        if self._tenant_stats:
            # Per-tenant serving counters (bounded cardinality, see
            # _tenant_inc). slo_attainment = finished / closed requests
            # (deadline expiries are the misses).
            tenants = {}
            for t, st in self._tenant_stats.items():
                closed = st["finished"] + st["expired"]
                tenants[t] = dict(
                    st, slo_attainment=(round(st["finished"] / closed, 4)
                                        if closed else 1.0))
            out["tenants"] = tenants
        if self.spec_method:
            ss = dict(self.spec_stats)
            out["speculative"] = {
                "method": self.spec_method,
                "k": self.spec_k,
                "acceptance_rate": (
                    round(ss["accepted"] / ss["proposed"], 4)
                    if ss["proposed"] else 0.0),
                "tokens_per_step": (
                    round(ss["emitted_tokens"] / ss["model_steps"], 4)
                    if ss["model_steps"] else 0.0),
                **ss,
            }
        return out

    def generate_text(self, prompts, max_new_tokens: int,
                      sampling: Optional[SamplingParams] = None,
                      token_callback: Optional[Callable] = None):
        """String-level API (drop-in for StaticInferenceEngine
        .generate_text — lets the REST/WS server run on the dynamic
        engine)."""
        assert self.tokenizer is not None, "tokenizer required"
        eod = getattr(self.tokenizer, "eod", None)
        rids = []
        for prompt in prompts:
            ids = np.asarray(self.tokenizer.tokenize(prompt), np.int32)
            rids.append(self.add_request(ids, max_new_tokens, sampling,
                                         eod_id=eod))
        cb = None
        if token_callback is not None:
            def cb(rid, tok):
                token_callback(rid, np.asarray([tok]), None)
        results = self.run_to_completion(token_callback=cb)
        texts = []
        for prompt, rid in zip(prompts, rids):
            n_prompt = len(self.tokenizer.tokenize(prompt))
            new_ids = results[rid][n_prompt:].tolist()
            if eod is not None and eod in new_ids:
                new_ids = new_ids[: new_ids.index(eod)]
            texts.append(self.tokenizer.detokenize(new_ids))
        return texts
