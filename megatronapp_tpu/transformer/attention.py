"""Self-attention sublayer (GQA, RoPE, optional QK-layernorm).

Parity with /root/reference/megatron/core/transformer/attention.py:88
(Attention / SelfAttention :845). The reference splits weights across TP
ranks explicitly via ColumnParallelLinear/RowParallelLinear; here the kernels
carry logical axes ('heads'/'kv_heads' → tp) and XLA partitions the matmuls.

Param leaf layout (per layer, unstacked):
  q_kernel   [H, n_heads*D]        logical ('embed', 'qkv')
  kv_kernel  [H, 2*n_kv*D]         logical ('embed', 'qkv')
  q_bias     [n_heads*D]           logical ('qkv',)
  kv_bias    [2*n_kv*D]            logical ('qkv',)
  out_kernel [n_heads*D, H]        logical ('qkv', 'embed')
  out_bias   [H]                   logical ('embed',)
  (optional) q_ln_scale, k_ln_scale [D]
  (EVA, cfg.eva_window_size) eva_phi, eva_mu [n_kv, D]: transformer/eva.py
  (cfg.attention_output_gate) gate_kernel [H, n_heads]   ('embed', 'heads')
  (  + attention_gate_elementwise)        [H, n_heads*D] ('embed', 'qkv')

n_heads is cfg.num_attention_heads, or in a sliding-window layer of a stack
that says so cfg.window_heads: the forward reads it off q_kernel.

The paged branch on one device keeps its q/kv projection a flat
[tokens, H] x [H, heads*D] product (a barrier in front of the reshape to
heads): folded into the dot, the reshape makes XLA:TPU want the kernel
heads-major, and a layer's kernels, which arrive as lax.scan's slice of the
[L, H, heads*D] stacks, are then cut out and written again every layer of
every step (12% and 19% of two serving windows, PERF.md, PR 51).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from megatronapp_tpu.config.transformer_config import (
    AttnMaskType, TransformerConfig,
)
from megatronapp_tpu.ops.attention import dot_product_attention
from megatronapp_tpu.ops.normalization import rms_norm
from megatronapp_tpu.ops.per_rank import dense
from megatronapp_tpu.ops.pallas import flash_attention as fa
from megatronapp_tpu.ops import rotary
from megatronapp_tpu.scope.hooks import scope_capture
from megatronapp_tpu.transformer import eva

_announced: set = set()


def _backend() -> str:
    """The backend `choose_attention` is told (tests/test_chip_compile.py
    compiles for a described chip from a CPU process and says "tpu")."""
    return jax.default_backend()


def _whole_sequence_choice(cfg: TransformerConfig, ctx, b: int, s: int,
                           nq: int, dtype, segments: bool,
                           window: int) -> fa.AttentionChoice:
    """`choose_attention` for a layer's whole-sequence call of `b` rows and
    `nq` query heads, told the batch and heads ON ONE DEVICE: the [B,H,S,S]
    scores of the dense path shard only over dp/ep/tp; pp/cp devices each
    hold a full copy."""
    if ctx is not None and ctx.num_devices > 1:
        b = -(-b // (ctx.dp * ctx.ep))
        nq = -(-nq // ctx.tp)
    return fa.choose_attention(
        impl=cfg.attention_impl, batch=b, seq=s, heads=nq,
        head_dim=cfg.head_dim, dtype=dtype, segments=segments,
        backend=_backend(), window=window,
        block_q=cfg.flash_block_q, block_kv=cfg.flash_block_kv)


def flash_tile_counts(cfg: TransformerConfig, segment_ids, ctx=None) -> dict:
    """What the flash kernels compute of one packed forward pass through a
    hybrid stack's causal attention layers: `flash_tiles`, the tile pairs
    of their grids' causal or band part over the batch and the layers, and
    `flash_tiles_computed`, those the documents' table lets the kernels
    compute (flash_attention.segment_tile_counts, from the same table the
    kernels are handed). Empty where no layer takes the kernels."""
    b, s = segment_ids.shape
    tiles, computed = 0, jnp.int32(0)
    for layers, heads, window in (
            (cfg.num_attention_layers, cfg.num_attention_heads, 0),
            (cfg.num_window_layers, cfg.window_heads, cfg.sliding_window)):
        if not layers:
            continue
        choice = _whole_sequence_choice(cfg, ctx, b, s, heads,
                                        cfg.compute_dtype, True, window)
        if choice.impl != "pallas":
            continue
        layer_tiles, layer_computed = fa.segment_tile_counts(
            segment_ids, choice.block_q, choice.block_kv, window=window)
        tiles += layers * layer_tiles
        computed += layers * layer_computed
    if not tiles:
        return {}
    return {"flash_tiles": jnp.int32(tiles), "flash_tiles_computed": computed}


def _announce(site: str, impl: str, interpreted=None) -> None:
    """Print, once per distinct choice in this process, which attention
    implementation a call site traced — and, for a Pallas kernel, whether
    it is compiled for the chip or interpreted (chip_smoke.py reads it)."""
    if interpreted is not None:
        impl += " (interpreted)" if interpreted else " (compiled)"
    line = f"attention: {site} -> {impl}"
    if line not in _announced:
        _announced.add(line)
        print(line, flush=True)


def init_attention_params(rng, cfg: TransformerConfig, out_std: float,
                          heads: Optional[int] = None):
    """`heads`: this layer's query heads where they are not
    cfg.num_attention_heads (a sliding-window layer's, cfg.window_heads);
    attention_forward reads the count off q_kernel."""
    h = cfg.hidden_size
    d = cfg.head_dim
    nq, nkv = heads or cfg.num_attention_heads, cfg.num_query_groups
    keys = jax.random.split(rng, 3)
    std = cfg.init_method_std
    p = {
        "q_kernel": jax.random.normal(keys[0], (h, nq * d), cfg.params_dtype) * std,
        "kv_kernel": jax.random.normal(keys[1], (h, 2 * nkv * d), cfg.params_dtype) * std,
        "out_kernel": jax.random.normal(keys[2], (nq * d, h), cfg.params_dtype) * out_std,
    }
    ax = {
        "q_kernel": ("embed", "qkv"),
        "kv_kernel": ("embed", "qkv"),
        "out_kernel": ("qkv", "embed"),
    }
    if cfg.add_qkv_bias:
        p["q_bias"] = jnp.zeros((nq * d,), cfg.params_dtype)
        p["kv_bias"] = jnp.zeros((2 * nkv * d,), cfg.params_dtype)
        ax["q_bias"] = ("qkv",)
        ax["kv_bias"] = ("qkv",)
    if cfg.add_bias_linear:
        p["out_bias"] = jnp.zeros((h,), cfg.params_dtype)
        ax["out_bias"] = ("embed",)
    if cfg.qk_layernorm:
        p["q_ln_scale"] = jnp.ones((d,), cfg.params_dtype)
        p["k_ln_scale"] = jnp.ones((d,), cfg.params_dtype)
        ax["q_ln_scale"] = ("head_dim",)
        ax["k_ln_scale"] = ("head_dim",)
    if cfg.is_eva:
        eva_p, eva_ax = eva.init_eva_params(jax.random.fold_in(rng, 3), cfg)
        p.update(eva_p)
        ax.update(eva_ax)
    if cfg.attention_output_gate:
        # one gate a head, or (attention_gate_elementwise) one an element
        wide = nq * d if cfg.attention_gate_elementwise else nq
        p["gate_kernel"] = jax.random.normal(
            jax.random.fold_in(rng, 4), (h, wide), cfg.params_dtype) * std
        ax["gate_kernel"] = ("embed", "qkv" if cfg.attention_gate_elementwise
                             else "heads")
    return p, ax


def _replicate_heads(attn_out: jnp.ndarray, ctx) -> jnp.ndarray:
    """Gather a head-sharded paged-attention output back to replicated
    before the out-projection. Keeping the out-proj matmul replicated
    (instead of a partial-contraction + all-reduce) costs one small
    [B, S, Hq, D] all-gather per layer but makes the summation order —
    and therefore the sampled greedy stream — bit-identical to the
    single-device engine."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    # manual-ok: tp serving path only — callers gate on tp_paged, which
    # requires no ambient manual axes (GSPMD constraint is legal here).
    return jax.lax.with_sharding_constraint(
        attn_out, NamedSharding(ctx.mesh, P()))  # manual-ok: see above


def attention_forward(
    p, x: jnp.ndarray, cfg: TransformerConfig,
    rope_cos: Optional[jnp.ndarray] = None,
    rope_sin: Optional[jnp.ndarray] = None,
    attention_mask: Optional[jnp.ndarray] = None,
    kv_cache=None, cache_index=None, cache_positions=None,
    layer_id=None, ctx=None, zigzag: bool = False,
    segment_ids: Optional[jnp.ndarray] = None,
    page_table: Optional[jnp.ndarray] = None,
    active: Optional[jnp.ndarray] = None,
    chunk_counts: Optional[jnp.ndarray] = None,
    tp_sharded: bool = False,
    kv_scales=None,
    fp8=None,
    lora=None,
    kv_plane=None,
    window: int = 0,
) -> jnp.ndarray:
    """x: [B, S, H] → [B, S, H]. Returns (out, new_kv_cache).

    window: > 0 makes this a sliding-window layer: the query at position t
    sees the keys t - window + 1 .. t. Whole sequences run the flash
    kernels' band (`flash_window_*`) or XLA's dense attention under a band
    mask, as `choose_attention` says (the cp rings have no window term:
    ROADMAP); a paged step's kernels walk from the block that
    holds position len - window and mask the rows behind it
    (kernel_gen.paged_attention(window=)), and kv_cache / page_table are
    then the WINDOW pools and their table (inference/paged_cache.py).

    page_table: [B, max_blocks_per_seq] int32 — marks kv_cache as PAGED
    block-pool storage, the whole STACKED pool
    [L, num_blocks, block_size, Hkv, D] (inference/paged_cache.py) with
    layer_id (required then) naming this layer's plane: each row appends
    its token at its own (block, offset) of that plane, in place, and
    attends through the ragged paged-attention kernel, which masks by
    per-row kv length (no caller mask needed). kv_plane names the plane
    where it is not the layer id: a hybrid stack's pools hold planes for
    its attention layers only.
    active: [B] bool — inactive rows' writes are dropped (their page
    tables may reference blocks re-allocated to other requests).
    chunk_counts: [B] int32 — multi-token paged append (speculative
    verify / chunked prefill): row b's first chunk_counts[b] positions
    are real tokens starting at cache_positions[b]; attention runs
    through the multi-query ragged kernel (causal within the new tail,
    full attention to the paged context). Rows past a row's count are
    padding whose outputs are garbage (callers discard them).
    kv_scales: (k_scales, v_scales) fp32 [L, NB, bs, Hkv] — marks the
    paged pools as int8 (PagedKVCache kv_cache_dtype="int8"): new rows
    quantize per (row, head) in this jit before the scatter, the ragged
    kernels dequantize each DMA'd block in-register, and new_cache grows
    to (k, v, k_scales, v_scales). Paged paths only.

    zigzag: the CALLER laid the sequence out in zigzag cp order (model-side
    permutation, models/gpt.py) — required before the zigzag ring kernel may
    be dispatched; models that don't permute keep the contiguous ring.

    segment_ids: [B, S] packed-sequence map; the flash kernel masks
    in-block (O(S) memory), the reference impl builds the dense
    block-diagonal mask, and the cp impls thread segments through their
    collectives.

    tp_sharded: the caller (the pp pipeline stage body) runs inside an
    ambient FULL-MANUAL region with the residual stream tp-sharded along
    the sequence: x is this shard's [B, S/tp, H] chunk. QKV then runs as
    one fused ring all-gather-matmul over per-shard HEAD slices (q, k and
    v sliced separately so each shard owns matched GQA groups), attention
    runs on the full sequence with nq/tp local heads, and the out-proj
    ring reduce-scatters back to the local seq chunk
    (parallel/overlap.py *_manual; tp_stage_eligible gates callers)."""
    b, s, h = x.shape
    d = cfg.head_dim
    nq, nkv = cfg.num_attention_heads, cfg.num_query_groups
    x = x.astype(cfg.compute_dtype)

    # MegaScope 'weight' perturbation site (reference
    # tensor_parallel/layers.py:944-951 applies it to every parallel
    # linear's weights).
    from megatronapp_tpu.scope.disturbance import get_disturbance
    from megatronapp_tpu.parallel.overlap import (
        all_gather_matmul, matmul_reduce_scatter, tp_overlap_eligible,
    )
    _dist = get_disturbance()
    # Latency-hiding tp path (--tp-comm-overlap, parallel/overlap.py):
    # QKV column-parallel via ring all-gather-matmul, out-proj row-parallel
    # via matmul-reduce-scatter. The flat projection dims (not head counts)
    # must shard evenly over tp — the ring reproduces the global layout, so
    # GQA head counts indivisible by tp still work when nq*d / 2*nkv*d do.
    # (kv_cache = decode: S∈{1,prefill} matmuls are tiny and latency-bound,
    # the ring would be pure overhead — keep GSPMD there.)
    overlap = (kv_cache is None and not tp_sharded
               and tp_overlap_eligible(cfg, ctx, nq * d, 2 * nkv * d,
                                       batch=b))
    # fp8 (ISSUE 13): this layer's delayed-scaling state for the
    # qkv/out-proj ring sites — only legal when the rings actually run
    # (the amax history would silently rot otherwise).
    if fp8 is not None and not overlap:
        raise ValueError(
            "fp8 state passed but the tp-overlap rings are not "
            "eligible here (tp_overlap_eligible is False / decode "
            "path) — check fp8_ineligible_reason at wiring time")
    fp8_margin = int(getattr(cfg, "fp8_margin", 0))
    # Batched-LoRA serving (inference/lora.py): per-row adapter deltas
    # compose with the plain projection matmuls only — the tp-overlap
    # rings and the tp-sharded stage body slice weights per shard and
    # would need the delta ring-decomposed too.
    if lora is not None and (overlap or tp_sharded):
        raise ValueError(
            "lora deltas are not composable with the tp-overlap rings "
            "or the tp-sharded stage body — serving paths only")
    # Serving-resident int8 weights (inference/quantization.py
    # residentize_params): resolve_param dequantizes at matmul entry —
    # int8 stays in HBM, XLA fuses the per-channel scale multiply.
    from megatronapp_tpu.inference.quantization import resolve_param
    q_kernel = _dist.apply("weight", resolve_param(p["q_kernel"]),
                           layer_id)
    kv_kernel = _dist.apply("weight", resolve_param(p["kv_kernel"]),
                            layer_id)
    # this layer's own query heads (a sliding-window layer may have more
    # than cfg.num_attention_heads, over the same key/value heads)
    nq = q_kernel.shape[-1] // d
    gated = "gate_kernel" in p
    if (window or gated) and (
            tp_sharded or overlap or lora is not None or cfg.is_eva
            or (ctx is not None and ctx.cp > 1)
            or (kv_cache is not None and page_table is None)
            or kv_scales is not None):
        raise NotImplementedError(
            "a sliding-window layer or a gated attention output runs whole "
            "sequences on one tp shard, or paged bf16 pools on "
            "one device: no tp-sharded stage body, tp-overlap rings, cp, "
            "lora, EVA, dense (unpaged) cache or quantized pool")
    if tp_sharded:
        # Ambient-manual tp-sharded stage body: see docstring. Local head
        # counts; s stays the LOCAL seq chunk length, sf the full length.
        if (kv_cache is not None or attention_mask is not None
                or segment_ids is not None or zigzag or cfg.is_eva
                or cfg.attention_multiplier is not None):
            raise NotImplementedError(
                "tp-sharded stage body supports the plain training path "
                "only (no kv cache / explicit mask / packing / zigzag) — "
                "tp_stage_eligible callers gate these off")
        from jax import lax
        from megatronapp_tpu.config.parallel_config import TP_AXIS
        from megatronapp_tpu.parallel.overlap import (
            all_gather_matmul_manual, matmul_reduce_scatter_manual,
        )
        tp = ctx.tp
        me = lax.axis_index(TP_AXIS)
        nql, nkvl = nq // tp, nkv // tp
        dt = cfg.compute_dtype
        qw = lax.dynamic_slice_in_dim(q_kernel.astype(dt),
                                      me * nql * d, nql * d, axis=1)
        kw = lax.dynamic_slice_in_dim(kv_kernel.astype(dt),
                                      me * nkvl * d, nkvl * d, axis=1)
        vw = lax.dynamic_slice_in_dim(kv_kernel.astype(dt),
                                      nkv * d + me * nkvl * d, nkvl * d,
                                      axis=1)
        ov = bool(getattr(cfg, "tp_comm_overlap", False))
        q, k, v = all_gather_matmul_manual(x, (qw, kw, vw), tp, ov)
        if "q_bias" in p:
            qb = p["q_bias"].astype(dt)
            kvb = p["kv_bias"].astype(dt)
            q = q + lax.dynamic_slice_in_dim(qb, me * nql * d, nql * d)
            k = k + lax.dynamic_slice_in_dim(kvb, me * nkvl * d, nkvl * d)
            v = v + lax.dynamic_slice_in_dim(
                kvb, nkv * d + me * nkvl * d, nkvl * d)
        sf = s * tp
        q = q.reshape(b, sf, nql, d)
        k = k.reshape(b, sf, nkvl, d)
        v = v.reshape(b, sf, nkvl, d)
        q = scope_capture("qkv_q", q, layer_id)
        k = scope_capture("qkv_k", k, layer_id)
        v = scope_capture("qkv_v", v, layer_id)
        if cfg.qk_layernorm:
            q = rms_norm(q, p["q_ln_scale"], cfg.layernorm_epsilon)
            k = rms_norm(k, p["k_ln_scale"], cfg.layernorm_epsilon)
        if rope_cos is not None:
            # Post-ring tables: q/k carry the full sequence (cp == 1) or
            # this cp rank's full LOCAL chunk (cp > 1 — the caller
            # sliced the tables to the chunk, models/gpt.py stage_fn).
            q = rotary.apply_rope(q, rope_cos, rope_sin)
            k = rotary.apply_rope(k, rope_cos, rope_sin)
        if ctx.cp > 1:
            # pp x cp x tp composition (ISSUE 15): after the tp ring
            # gather the sequence is still the cp-LOCAL chunk — run the
            # contiguous cp ring attention per tp head shard instead of
            # treating the chunk as the whole sequence.
            # tp_stage_eligible restricts this path to dense
            # contiguous-p2p layouts (no zigzag — the caller skipped the
            # permutation).
            from megatronapp_tpu.ops.context_parallel import (
                context_attention,
            )
            # manual-ok: context_attention detects the ambient manual cp
            # axis and runs its ring body directly (no nested shard_map)
            attn_out = context_attention(
                q, k, v, ctx.shard_map_mesh, "p2p",
                causal=cfg.attn_mask_type == AttnMaskType.causal,
                overlap_ring=getattr(cfg, "cp_comm_overlap", True))
        else:
            attn_out = dot_product_attention(
                q, k, v, mask_type=cfg.attn_mask_type,
                attention_mask=None, softmax_scale=None,
                softmax_in_fp32=cfg.attention_softmax_in_fp32,
                layer_id=layer_id)
        attn_out = scope_capture("context", attn_out, layer_id)
        out_kernel = _dist.apply("weight", resolve_param(p["out_kernel"]),
                                 layer_id).astype(dt)
        ow = lax.dynamic_slice_in_dim(out_kernel, me * nql * d, nql * d,
                                      axis=0)
        out = matmul_reduce_scatter_manual(
            attn_out.reshape(b, sf, nql * d), ow, tp, ov)
        if "out_bias" in p:
            out = out + p["out_bias"].astype(dt)
        return out, None
    if overlap:
        # Fused call: one ring all-gather of x feeds both column-parallel
        # projections (two calls would move x around the ring twice).
        # manual-ok: overlap gated by tp_overlap_eligible (False inside
        # ambient manual regions; the pipeline takes tp_sharded above)
        q, kv = all_gather_matmul(
            x, (q_kernel.astype(cfg.compute_dtype),
                kv_kernel.astype(cfg.compute_dtype)), ctx.shard_map_mesh,
            fp8=None if fp8 is None else fp8["qkv"],
            fp8_margin=fp8_margin)
    else:
        q = dense(x, q_kernel.astype(cfg.compute_dtype))
        kv = dense(x, kv_kernel.astype(cfg.compute_dtype))
    if lora is not None:
        from megatronapp_tpu.ops.pallas.kernel_gen import apply_lora_delta
        q = apply_lora_delta(q, x, lora, "q_kernel")
        kv = apply_lora_delta(kv, x, lora, "kv_kernel")
    if "q_bias" in p:
        q = q + p["q_bias"].astype(cfg.compute_dtype)
        kv = kv + p["kv_bias"].astype(cfg.compute_dtype)
    if kv_cache is not None and page_table is not None and ctx is None:
        # A paged step on one device: see the module docstring. The values
        # are the same; the dot's fusion then reads the stack in place.
        q, kv = jax.lax.optimization_barrier((q, kv))
    q = q.reshape(b, s, nq, d)
    k, v = jnp.split(kv.reshape(b, s, 2 * nkv, d), 2, axis=2)
    if cfg.attention_multiplier is not None:
        # Every implementation below scales the scores by 1 / sqrt(d): the
        # model's own factor goes onto the query, less that.
        q = q * (cfg.attention_multiplier * d ** 0.5)

    # MegaScope QKV capture site (reference attention.py:979-981).
    q = scope_capture("qkv_q", q, layer_id)
    k = scope_capture("qkv_k", k, layer_id)
    v = scope_capture("qkv_v", v, layer_id)

    if cfg.qk_layernorm:
        q = rms_norm(q, p["q_ln_scale"], cfg.layernorm_epsilon)
        k = rms_norm(k, p["k_ln_scale"], cfg.layernorm_epsilon)

    q_offset = 0
    if rope_cos is not None:
        q = rotary.apply_rope(q, rope_cos, rope_sin)
        k = rotary.apply_rope(k, rope_cos, rope_sin)

    new_cache = None
    new_scales = None
    paged_out = None
    mask_type = cfg.attn_mask_type
    if kv_cache is not None:
        ck, cv = kv_cache
        if page_table is not None:
            # Paged continuous-batching decode, and (s > 1 or chunk_counts)
            # the multi-token append of speculative verify / chunked
            # prefill. kv_cache holds the STACKED block pools
            # [L, NB, bs, Hkv, D] and layer_id names this layer's plane:
            # the new rows are written into it in place and the ragged
            # kernel reads it through the layer id, so the pool passes
            # through a step as one buffer (kernel_gen.paged_append).
            #
            # TP serving mesh (ISSUE 9): head-shard the paged kernels
            # over ctx's tp axis — the pool is sharded on Hkv (1/tp of
            # the KV bytes and attention FLOPs per device) and writer
            # and reader are placed with a full-manual shard_map,
            # exactly like the flash wrapper above. The output is
            # constrained back to REPLICATED before the out-projection
            # so every device runs the identical dense matmul —
            # per-request greedy streams stay bit-identical to the
            # single-device engine (the tp2 parity pin in
            # tests/test_disagg.py).
            from megatronapp_tpu.ops.pallas import kernel_gen
            from megatronapp_tpu.ops.pallas.paged_attention import (
                append_kv, tp_paged_eligible,
            )
            from megatronapp_tpu.parallel.collectives import (
                current_manual_axes,
            )
            plane = layer_id if kv_plane is None else kv_plane
            if plane is None:
                raise ValueError(
                    "paged attention reads the stacked pool through "
                    "layer_id — pass this layer's index")
            tp_paged = (tp_paged_eligible(cfg, ctx)
                        and not current_manual_axes())
            # manual-ok: tp_paged requires no ambient manual axes
            mesh = ctx.shard_map_mesh if tp_paged else None
            if active is None:
                active = jnp.ones((b,), bool)
            ragged = s > 1 or chunk_counts is not None
            counts = None
            if ragged:
                counts = (chunk_counts if chunk_counts is not None
                          else jnp.full((b,), s, jnp.int32))
            true_positions = cache_positions
            if cfg.is_eva:
                # The slot's table is summaries, then the open window:
                # rows land at, and the kernels walk to, a position's row
                # of THAT table (transformer/eva.py; the engine and the
                # pool refuse a quantized pool and a mesh on such a model).
                cache_positions = eva.table_rows(cfg, true_positions)
            (ck, cv), new_scales = append_kv(
                kv_cache, kv_scales, (k, v) if ragged else (k[:, 0], v[:, 0]),
                page_table, cache_positions, active, plane, counts,
                mesh)
            if cfg.is_eva:
                ck, cv = eva.write_summaries(
                    p, cfg, (ck, cv), page_table, true_positions, counts,
                    active, plane, width=s)
            sc_kw = ({} if new_scales is None else
                     {"k_scales": new_scales[0], "v_scales": new_scales[1]})
            win = f", sliding window {window}" if window else ""
            if ragged:
                paged_out = kernel_gen.paged_attention(
                    q, ck, cv, page_table, cache_positions + counts,
                    q_lens=counts, mesh=mesh, layer=plane, window=window,
                    **sc_kw)
                _announce("paged multi-query" + win,
                          "pallas ragged paged kernel",
                          kernel_gen._interpret())
            else:
                paged_out = kernel_gen.paged_attention(
                    q[:, 0], ck, cv, page_table, cache_positions + 1,
                    mesh=mesh, layer=plane, window=window,
                    **sc_kw)[:, None]
                _announce("paged decode" + win, "pallas paged kernel",
                          kernel_gen._interpret())
            if tp_paged:
                paged_out = _replicate_heads(paged_out, ctx)
        elif cfg.is_eva:
            raise ValueError(
                "EVA attention keeps chunk summaries in a paged cache's "
                "table: a dense cache has no place for them (serve with "
                "the paged engine; gpt_forward runs whole sequences)")
        elif cache_positions is not None:
            # Continuous-batching decode (dynamic_context.py analogue):
            # each row appends at ITS OWN position; causality MUST come
            # from the caller's per-row attention_mask — fail fast if it
            # is missing rather than silently attending to stale/future
            # cache slots (round-2 advisor finding).
            if attention_mask is None:
                raise ValueError(
                    "per-row decode (cache_positions) requires an "
                    "explicit per-row attention_mask; see "
                    "inference/dynamic_engine.py's attend mask")
            ck = ck.at[jnp.arange(b), cache_positions].set(k[:, 0])
            cv = cv.at[jnp.arange(b), cache_positions].set(v[:, 0])
            mask_type = AttnMaskType.bidirectional
        else:
            # Static decode: append k,v at cache_index (static_context.py).
            ck = jax.lax.dynamic_update_slice_in_dim(ck, k, cache_index,
                                                     axis=1)
            cv = jax.lax.dynamic_update_slice_in_dim(cv, v, cache_index,
                                                     axis=1)
            q_offset = cache_index
        k, v = ck, cv
        # Quantized paged paths return the scale pools alongside: the
        # engine's layer loop carries all four pools.
        new_cache = ((ck, cv) if new_scales is None
                     else (ck, cv) + new_scales)

    # Note: the reference's apply_query_key_layer_scaling is numerically
    # neutral (it divides QK by layer_number for fp16 range safety and
    # multiplies it back inside the fused softmax). We always softmax in
    # fp32, so no scaling is needed — the flag is accepted for config parity
    # and intentionally has no effect on the math.
    if paged_out is not None:
        attn_out = paged_out
    elif cfg.is_eva:
        # Whole sequences from position 0, by XLA ops: the flash kernels
        # and the cp rings have no window term.
        if (attention_mask is not None or segment_ids is not None
                or tp_sharded or (ctx is not None and ctx.cp > 1)):
            raise ValueError(
                "EVA attention has no window term in the flash kernels or "
                "the context-parallel rings: whole causal sequences only "
                "(no explicit mask, packed segments, cp or tp-sharded "
                "stage body)")
        _announce("self-attention", "xla eva (windows and chunk summaries)")
        attn_out = eva.eva_attention(q, k, v, p["eva_phi"], p["eva_mu"], cfg)
    elif ctx is not None and ctx.cp > 1 and kv_cache is None:
        # Context-parallel attention over the cp axis (seq sharded).
        from megatronapp_tpu.ops.context_parallel import (
            context_attention, zigzag_active,
        )
        if attention_mask is not None:
            raise NotImplementedError(
                "explicit attention_mask is not supported under context "
                "parallelism yet (only causal/bidirectional); run with "
                "context_parallel=1 or drop the mask")
        comm = ("p2p_zigzag" if zigzag and zigzag_active(cfg, ctx)
                else cfg.cp_comm_type)
        # manual-ok: context_attention detects the ambient manual cp axis
        # and runs its ring bodies directly there (no nested shard_map)
        attn_out = context_attention(
            q, k, v, ctx.shard_map_mesh, comm,
            causal=cfg.attn_mask_type == AttnMaskType.causal,
            segment_ids=segment_ids,
            a2a_size=cfg.hierarchical_cp_a2a_size,
            overlap_ring=getattr(cfg, "cp_comm_overlap", True))
    else:
        from megatronapp_tpu.parallel.collectives import current_manual_axes

        multi_device = ctx is not None and ctx.num_devices > 1
        choice = _whole_sequence_choice(cfg, ctx, b, s, nq, q.dtype,
                                        segment_ids is not None, window)
        impl = choice.impl
        # GSPMD cannot partition a pallas_call (it would replicate full
        # attention on every device), so the kernel must be placed
        # explicitly: on a multi-device mesh we shard_map it manually over
        # (dp, ep, tp) — attention is embarrassingly parallel over
        # batch/heads. Inside an existing manual region (the pp/cp pipeline
        # body) nesting shard_maps is unsupported in this JAX build, so fall
        # back to the reference impl there.
        in_manual = bool(current_manual_axes())
        use_flash = (
            impl == "pallas" and attention_mask is None
            and kv_cache is None and not in_manual
            and cfg.attn_mask_type in (AttnMaskType.causal,
                                       AttnMaskType.bidirectional)
            and not (window
                     and cfg.attn_mask_type != AttnMaskType.causal))
        if use_flash and multi_device:
            dp_ep = ctx.dp * ctx.ep
            use_flash = (b % dp_ep == 0 and nq % ctx.tp == 0
                         and nkv % ctx.tp == 0)
        if use_flash:
            _announce("self-attention",
                      f"pallas flash kernel, {choice.block_q}x"
                      f"{choice.block_kv}, {choice.why}", fa._interpret())
            causal = cfg.attn_mask_type == AttnMaskType.causal
            tiles = dict(block_q=choice.block_q, block_kv=choice.block_kv)
            if multi_device:
                from jax.sharding import PartitionSpec as P
                from megatronapp_tpu.config.parallel_config import (
                    DP_AXIS, EP_AXIS, TP_AXIS,
                )
                from megatronapp_tpu.parallel.collectives import (
                    shard_map_compat,
                )
                # Full-manual region (shard_map_compat): the kernel is
                # purely local over (dp, ep, tp) shards; pp/cp ride
                # replicated (eligibility requires cp == 1 here).
                spec = P((DP_AXIS, EP_AXIS), None, TP_AXIS, None)
                seg_spec = P((DP_AXIS, EP_AXIS), None)
                if segment_ids is None:
                    # manual-ok: use_flash requires `not in_manual` above
                    flash = jax.jit(shard_map_compat(
                        lambda q_, k_, v_: fa.flash_attention(
                            q_, k_, v_, causal=causal, **tiles,
                            window=window,
                            head_fold=getattr(cfg, "flash_head_fold",
                                              False)),
                        ctx.shard_map_mesh,
                        in_specs=(spec, spec, spec),
                        out_specs=spec))
                    attn_out = flash(q, k, v)
                else:
                    # manual-ok: use_flash requires `not in_manual` above
                    flash = jax.jit(shard_map_compat(
                        lambda q_, k_, v_, s_: fa.flash_attention(
                            q_, k_, v_, causal=causal, **tiles,
                            segment_ids=s_, window=window),
                        ctx.shard_map_mesh,
                        in_specs=(spec, spec, spec, seg_spec),
                        out_specs=spec))
                    attn_out = flash(q, k, v, segment_ids)
            else:
                attn_out = fa.flash_attention(
                    q, k, v, causal=causal, **tiles,
                    segment_ids=segment_ids, window=window,
                    head_fold=getattr(cfg, "flash_head_fold", False))
        else:
            if impl != "pallas":
                _announce("self-attention", f"xla dense ({choice.why})")
            else:
                _announce("self-attention", "xla dense, pallas asked for "
                          "but " + ("unavailable inside a manual region"
                                    if in_manual else
                                    "the kernel does not take this mask/"
                                    "cache/head split"))
            if segment_ids is not None:
                seg_mask = (segment_ids[:, None, :, None]
                            == segment_ids[:, None, None, :])
                attention_mask = (seg_mask if attention_mask is None
                                  else attention_mask & seg_mask)
            if window:
                # the sliding window's band, beside the causal mask: the
                # query at t sees keys t - window + 1 .. t (positions in a
                # packed row run on across segments, which the segment mask
                # cuts: a band over the row's index is the band within)
                at = jnp.arange(s)
                band = (at[:, None] - at[None, :] < window)[None, None]
                attention_mask = (band if attention_mask is None
                                  else attention_mask & band)
            attn_out = dot_product_attention(
                q, k, v, mask_type=mask_type,
                attention_mask=attention_mask, softmax_scale=None,
                softmax_in_fp32=cfg.attention_softmax_in_fp32,
                q_offset=q_offset, layer_id=layer_id)
    attn_out = scope_capture("context", attn_out, layer_id)
    if gated:
        # one sigmoid gate a head (gate_kernel [H, heads]) or an element
        # ([H, heads x D]), from the layer's normed input
        gate = jax.nn.sigmoid(dense(
            x, resolve_param(p["gate_kernel"]).astype(cfg.compute_dtype)
        ).astype(jnp.float32))
        gate = (gate[..., None] if gate.shape[-1] == nq
                else gate.reshape(attn_out.shape))
        attn_out = (attn_out * gate).astype(attn_out.dtype)

    out_kernel = _dist.apply("weight", resolve_param(p["out_kernel"]),
                             layer_id)
    out_kernel = out_kernel.astype(cfg.compute_dtype)
    if overlap:
        # manual-ok: same tp_overlap_eligible gate as the QKV ring above
        out = matmul_reduce_scatter(
            attn_out.reshape(b, s, nq * d), out_kernel,
            ctx.shard_map_mesh,
            fp8=None if fp8 is None else fp8["out"],
            fp8_margin=fp8_margin)
    else:
        out = dense(attn_out.reshape(b, s, nq * d), out_kernel)
        if lora is not None:
            from megatronapp_tpu.ops.pallas.kernel_gen import (
                apply_lora_delta)
            out = apply_lora_delta(out, attn_out.reshape(b, s, nq * d),
                                   lora, "out_kernel")
    if "out_bias" in p:
        out = out + p["out_bias"].astype(cfg.compute_dtype)
    return (out, new_cache) if kv_cache is not None else (out, None)
