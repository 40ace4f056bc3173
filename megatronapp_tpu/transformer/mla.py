"""Multi-latent attention (MLA, DeepSeek-style).

Parity with /root/reference/megatron/core/transformer/
multi_latent_attention.py:44 (MLASelfAttention) and MLATransformerConfig
(transformer_config.py:1072): queries (optionally) and keys/values project
through low-rank latents; position information flows only through small
decoupled rope heads (qk_pos_emb_head_dim) — the KV cache compresses to the
latent + shared rope key.

Shapes (per layer):
  q path:   x[H] → (q_lora_rank → ln →)? nq*(dqk + dpe)
  kv path:  x[H] → kv_lora_rank + dpe   (latent ‖ shared k_pe)
            latent → ln → nq*(dqk + dv) (k_nope ‖ v)
  attn:     q = [q_nope ‖ rope(q_pe)], k = [k_nope ‖ rope(k_pe)] with the
            shared k_pe broadcast across heads; softmax scale
            1/sqrt(dqk + dpe); out: nq*dv → H.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from megatronapp_tpu.config.transformer_config import TransformerConfig
from megatronapp_tpu.ops import rotary
from megatronapp_tpu.ops.attention import dot_product_attention
from megatronapp_tpu.ops.normalization import rms_norm


def init_mla_params(rng, cfg: TransformerConfig, out_std: float):
    h = cfg.hidden_size
    nq = cfg.num_attention_heads
    dqk, dpe, dv = cfg.qk_head_dim, cfg.qk_pos_emb_head_dim, cfg.v_head_dim
    klat = cfg.kv_lora_rank
    keys = jax.random.split(rng, 6)
    std = cfg.init_method_std
    p = {}
    ax = {}
    if cfg.q_lora_rank:
        p["q_down"] = jax.random.normal(
            keys[0], (h, cfg.q_lora_rank), cfg.params_dtype) * std
        p["q_ln_scale"] = jnp.ones((cfg.q_lora_rank,), cfg.params_dtype)
        p["q_up"] = jax.random.normal(
            keys[1], (cfg.q_lora_rank, nq * (dqk + dpe)),
            cfg.params_dtype) * std
        ax["q_down"] = ("embed", None)
        ax["q_ln_scale"] = (None,)
        ax["q_up"] = (None, "qkv")
    else:
        p["q_proj"] = jax.random.normal(
            keys[0], (h, nq * (dqk + dpe)), cfg.params_dtype) * std
        ax["q_proj"] = ("embed", "qkv")
    # Compressed KV latent + shared rope key (one dpe-wide head).
    p["kv_down"] = jax.random.normal(
        keys[2], (h, klat + dpe), cfg.params_dtype) * std
    p["kv_ln_scale"] = jnp.ones((klat,), cfg.params_dtype)
    p["kv_up"] = jax.random.normal(
        keys[3], (klat, nq * (dqk + dv)), cfg.params_dtype) * std
    p["out_kernel"] = jax.random.normal(
        keys[4], (nq * dv, h), cfg.params_dtype) * out_std
    ax.update({
        "kv_down": ("embed", None), "kv_ln_scale": (None,),
        "kv_up": (None, "qkv"), "out_kernel": ("qkv", "embed"),
    })
    return p, ax


def latent_scales(cfg: TransformerConfig):
    """(s_q, s_kv): what cfg.mla_scale_q_lora / mla_scale_kv_lora multiply
    the expanded query and the normed latent by, sqrt(hidden_size / rank);
    None where the model has no such correction."""
    return (
        (cfg.hidden_size / cfg.q_lora_rank) ** 0.5
        if cfg.mla_scale_q_lora else None,
        (cfg.hidden_size / cfg.kv_lora_rank) ** 0.5
        if cfg.mla_scale_kv_lora else None)


def mla_forward(p, x: jnp.ndarray, cfg: TransformerConfig,
                rope_cos=None, rope_sin=None,
                attention_mask: Optional[jnp.ndarray] = None,
                layer_id=None, ctx=None, kv_cache=None, cache_index=None,
                cache_positions=None, page_table=None, active=None,
                chunk_counts=None, tp_sharded: bool = False,
                kv_scales=None, kv_plane=None):
    """kv_cache: optional (latent_cache [B, Smax, kv_lora_rank],
    kpe_cache [B, Smax, dpe]) — the COMPRESSED decode cache (the latent +
    shared roped key; reference MLA's defining cache shape). Returns
    (out, new_cache) when caching, else out.

    cache_positions: optional [B] int32 per-row write positions for
    continuous-batching decode (dynamic_context.py analogue) — each row
    appends its latent/k_pe at ITS OWN position; causality must then come
    from the caller's per-row attention_mask.

    Dense-cache decode recomputes k_nope/v from the cached latent via
    kv_up each step (the storage-optimal variant). The PAGED path
    (page_table is not None) instead absorbs kv_up's k_nope columns into
    the query and attends IN LATENT SPACE through the generated ragged
    paged kernel (ops/pallas/kernel_gen.paged_attention_latent,
    ISSUE 17): scores are q_lat·latentᵀ + q_pe·k_peᵀ over the page
    table, values are summed as latent rows and go through kv_up's v
    columns once a query row (ISSUE 39) — no dense gather and no
    per-step kv_up over the whole history.

    kv_scales: optional (lat_scales, pe_scales) per-row scalar fp32
    scale pools [L, NB, bs] marking a QUANTIZED latent/pe pool (paged path
    only); new rows quantize on insert (quantize_kv_rows) and new_cache
    then carries four pools.

    kv_plane: the plane of the stacked paged pools this sublayer owns where
    that is not its layer id (a double layer's two attention sublayers own
    planes 2·layer and 2·layer + 1).

    The query latent (q_down → norm → q_up) and the two scale corrections
    (latent_scales) run on every path: the paged one absorbs the scaled
    query into the latent as it absorbs an unscaled one, and caches the
    latent scaled.

    tp_sharded: ambient-manual tp-sharded stage body (see
    transformer/attention.py docstring) — training path only."""
    from megatronapp_tpu.scope.disturbance import get_disturbance
    from megatronapp_tpu.scope.hooks import scope_capture
    if tp_sharded:
        if kv_cache is not None or attention_mask is not None:
            raise NotImplementedError(
                "tp-sharded MLA supports the plain training path only")
        return _mla_forward_tp_sharded(p, x, cfg, rope_cos, rope_sin,
                                       layer_id, ctx)
    _dist = get_disturbance()

    b, s, h = x.shape
    nq = cfg.num_attention_heads
    dqk, dpe, dv = cfg.qk_head_dim, cfg.qk_pos_emb_head_dim, cfg.v_head_dim
    klat = cfg.kv_lora_rank
    dt = cfg.compute_dtype
    x = x.astype(dt)

    if "q_proj" in p:
        q = x @ _dist.apply("weight", p["q_proj"], layer_id).astype(dt)
    else:
        q_lat = x @ p["q_down"].astype(dt)
        q_lat = rms_norm(q_lat, p["q_ln_scale"], cfg.layernorm_epsilon)
        q = q_lat @ p["q_up"].astype(dt)
    s_q, s_kv = latent_scales(cfg)
    if s_q is not None:
        q = q * s_q
    q = q.reshape(b, s, nq, dqk + dpe)
    q_nope, q_pe = q[..., :dqk], q[..., dqk:]

    kv = x @ _dist.apply("weight", p["kv_down"],
                         layer_id).astype(dt)  # [B,S,klat+dpe]
    latent, k_pe = kv[..., :klat], kv[..., klat:]
    latent = rms_norm(latent, p["kv_ln_scale"], cfg.layernorm_epsilon)
    if s_kv is not None:
        latent = latent * s_kv

    if rope_cos is not None:
        q_pe = rotary.apply_rope(q_pe, rope_cos, rope_sin)
        k_pe = rotary.apply_rope(k_pe[:, :, None, :], rope_cos,
                                 rope_sin)[:, :, 0]

    from megatronapp_tpu.config.transformer_config import AttnMaskType
    new_cache = None
    s_kv = s
    mask_type = cfg.attn_mask_type
    q_offset = 0
    if kv_cache is not None:
        if ctx is not None and ctx.cp > 1:
            raise NotImplementedError(
                "MLA decode with a KV cache under context parallelism is "
                "not supported (each shard would attend only local KV)")
        c_lat, c_pe = kv_cache
        if page_table is not None:
            # Paged continuous-batching decode (ISSUE 17): kv_cache is
            # the shared latent/k_pe block pool, STACKED ([L, num_blocks,
            # block_size, klat/dpe], inference/paged_cache.py), of which
            # layer_id names this layer's plane. Each row appends at its
            # own (block, offset), in place; attention then runs IN LATENT
            # SPACE through the generated ragged paged kernel — q
            # absorbed through kv_up's k_nope columns, values summed as
            # latent rows and taken through kv_up's v columns once a
            # query row, after the walk — so the history is never
            # gathered dense nor re-expanded through kv_up per step.
            from megatronapp_tpu.config.transformer_config import (
                PositionEmbeddingKind,
            )
            from megatronapp_tpu.inference.quantization import (
                resolve_param,
            )
            from megatronapp_tpu.ops.pallas.kernel_gen import (
                paged_attention_latent,
            )
            from megatronapp_tpu.ops.pallas.paged_attention import (
                append_kv, tp_paged_eligible,
            )
            from megatronapp_tpu.scope import hooks as scope_hooks
            if layer_id is None:
                raise ValueError(
                    "paged attention reads the stacked pool through "
                    "layer_id — pass this layer's index")
            plane = layer_id if kv_plane is None else kv_plane
            if active is None:
                active = jnp.ones((b,), bool)
            # Multi-token paged append (speculative verify / chunked
            # prefill): ragged per-row chunk starting at cache_positions;
            # the kernel's scalar-prefetched q_lens carries the causal
            # tail mask. Quantized latent/pe pools: per-row SCALAR scales
            # (the rows have no kv-head axis) quantized on insert and
            # written through the same page table (append_kv).
            ragged = s > 1 or chunk_counts is not None
            counts = None
            if ragged:
                counts = (chunk_counts if chunk_counts is not None
                          else jnp.full((b,), s, jnp.int32))
            (c_lat, c_pe), new_scales = append_kv(
                kv_cache, kv_scales,
                (latent, k_pe) if ragged else (latent[:, 0], k_pe[:, 0]),
                page_table, cache_positions, active, plane, counts)
            sc_kw = ({} if new_scales is None else
                     {"lat_scales": new_scales[0],
                      "pe_scales": new_scales[1]})
            kv_lens = cache_positions + (counts if ragged else 1)
            new_cache = ((c_lat, c_pe) if new_scales is None
                         else (c_lat, c_pe) + new_scales)

            # YaRN: the rope tables already carry mscale, so the pe
            # logits get mscale² for free; the cached latent is
            # UNSCALED, so the absorbed query must carry the whole m²
            # the dense path splits as (q_nope·m)·(k_nope·m).
            m = 1.0
            if cfg.position_embedding == PositionEmbeddingKind.yarn:
                m = rotary.yarn_mscale(cfg.rope_scaling_factor,
                                       cfg.yarn_mscale_coeff)
            q_full = jnp.concatenate(
                [q_nope * m if m != 1.0 else q_nope, q_pe], axis=-1)
            q_full = scope_capture("qkv_q", q_full, layer_id)
            q_nope_y, q_pe = q_full[..., :dqk], q_full[..., dqk:]

            kvu = p["kv_up"].astype(dt).reshape(klat, nq, dqk + dv)
            wk, w_v = kvu[..., :dqk], kvu[..., dqk:]
            rows = q_nope_y.reshape(b * s, nq, dqk)
            if m != 1.0:
                rows = rows * m                    # second m factor
            q_abs = jnp.einsum("bnd,knd->bnk", rows, wk)
            q_abs = q_abs.reshape(b, s, nq, klat)

            scale = 1.0 / float((dqk + dpe) ** 0.5)
            tp_paged = False
            if ctx is not None:
                from megatronapp_tpu.parallel.collectives import (
                    current_manual_axes,
                )
                tp_paged = (tp_paged_eligible(cfg, ctx)
                            and not current_manual_axes())
            mesh = ctx.shard_map_mesh if tp_paged else None
            if ragged:
                attn = paged_attention_latent(
                    q_abs, q_pe, c_lat, c_pe, page_table, kv_lens, w_v,
                    q_lens=counts, softmax_scale=scale, mesh=mesh,
                    layer=plane, **sc_kw)
            else:
                attn = paged_attention_latent(
                    q_abs[:, 0], q_pe[:, 0], c_lat, c_pe, page_table,
                    kv_lens, w_v, softmax_scale=scale, mesh=mesh,
                    layer=plane, **sc_kw)[:, None]
            if tp_paged:
                from jax.sharding import NamedSharding, PartitionSpec
                # manual-ok: replicate the kernel output so the
                # out-projection runs identically on every device (the
                # latent shard_map already emits replicated output; the
                # constraint pins it for GSPMD).
                attn = jax.lax.with_sharding_constraint(
                    attn, NamedSharding(ctx.mesh, PartitionSpec()))  # manual-ok: see above

            if (scope_hooks.is_enabled("qkv_k")
                    or scope_hooks.is_enabled("qkv_v")):
                # MegaScope parity (debug-only, gated off the hot path):
                # reconstitute the dense k/v views the pre-kernel path
                # captured — gather the history and expand through
                # kv_up, exactly the work the kernel path avoids.
                from megatronapp_tpu.ops.pallas.paged_attention import (
                    gather_pages_batched,
                )
                g_lat = gather_pages_batched(c_lat[plane], page_table)
                g_pe = gather_pages_batched(c_pe[plane], page_table)
                if new_scales is not None:
                    g_ls = gather_pages_batched(new_scales[0][plane],
                                                page_table)
                    g_ps = gather_pages_batched(new_scales[1][plane],
                                                page_table)
                    g_lat = g_lat.astype(jnp.float32) * g_ls[..., None]
                    g_pe = g_pe.astype(jnp.float32) * g_ps[..., None]
                g_lat, g_pe = g_lat.astype(dt), g_pe.astype(dt)
                s_g = g_lat.shape[1]
                kvu_g = (g_lat @ p["kv_up"].astype(dt)).reshape(
                    b, s_g, nq, dqk + dv)
                k_nope_g, v_g = kvu_g[..., :dqk], kvu_g[..., dqk:]
                if m != 1.0:
                    k_nope_g = k_nope_g * m
                k_full_g = jnp.concatenate(
                    [k_nope_g, jnp.broadcast_to(g_pe[:, :, None, :],
                                                (b, s_g, nq, dpe))],
                    axis=-1)
                scope_capture("qkv_k", k_full_g, layer_id)
                scope_capture("qkv_v", v_g, layer_id)

            attn = scope_capture("context", attn, layer_id)
            out = attn.reshape(b, s, nq * dv) @ _dist.apply(
                "weight", resolve_param(p["out_kernel"]),
                layer_id).astype(dt)
            return out, new_cache
        elif cache_positions is not None:
            # Continuous-batching decode: per-row append positions.
            # Causality MUST come from the caller's per-row mask — the
            # scalar-offset causal mask cannot express per-row history
            # lengths, so an absent mask would silently attend to stale/
            # future cache slots (round-2 advisor finding).
            if attention_mask is None:
                raise ValueError(
                    "per-row decode (cache_positions) requires an "
                    "explicit per-row attention_mask; see "
                    "inference/dynamic_engine.py's attend mask")
            c_lat = c_lat.at[jnp.arange(b), cache_positions].set(
                latent[:, 0].astype(c_lat.dtype))
            c_pe = c_pe.at[jnp.arange(b), cache_positions].set(
                k_pe[:, 0].astype(c_pe.dtype))
            mask_type = AttnMaskType.bidirectional
        else:
            # Append the normed latent + roped shared key at cache_index;
            # the whole cached history reconstitutes k_nope/v below.
            c_lat = jax.lax.dynamic_update_slice_in_dim(
                c_lat, latent.astype(c_lat.dtype), cache_index, axis=1)
            c_pe = jax.lax.dynamic_update_slice_in_dim(
                c_pe, k_pe.astype(c_pe.dtype), cache_index, axis=1)
            q_offset = cache_index
        new_cache = (c_lat, c_pe)
        latent, k_pe = c_lat.astype(dt), c_pe.astype(dt)
        s_kv = latent.shape[1]

    kv_up = (latent @ p["kv_up"].astype(dt)).reshape(b, s_kv, nq, dqk + dv)
    k_nope, v = kv_up[..., :dqk], kv_up[..., dqk:]
    k_pe = jnp.broadcast_to(k_pe[:, :, None, :], (b, s_kv, nq, dpe))

    # YaRN: the rope tables already carry mscale (models/gpt.py), which
    # gives the pe logits the reference's mscale² factor; the nope logits
    # need the same factor explicitly (reference multi_latent_attention.py
    # :83-84 applies mscale²/sqrt(d) to ALL logits).
    from megatronapp_tpu.config.transformer_config import (
        PositionEmbeddingKind,
    )
    if cfg.position_embedding == PositionEmbeddingKind.yarn:
        m = rotary.yarn_mscale(cfg.rope_scaling_factor,
                               cfg.yarn_mscale_coeff)
        q_nope = q_nope * m
        k_nope = k_nope * m

    q_full = jnp.concatenate([q_nope, q_pe], axis=-1)
    k_full = jnp.concatenate([k_nope, k_pe], axis=-1)
    q_full = scope_capture("qkv_q", q_full, layer_id)
    k_full = scope_capture("qkv_k", k_full, layer_id)
    v = scope_capture("qkv_v", v, layer_id)
    scale = 1.0 / jnp.sqrt(jnp.float32(dqk + dpe))
    if ctx is not None and ctx.cp > 1 and kv_cache is None:
        # Context parallelism over the concatenated nope+rope heads
        # (values have a different head dim — the cp impls handle
        # d_v != d_qk). Contiguous modes only: MLA is excluded from the
        # zigzag layout (zigzag_active).
        from megatronapp_tpu.config.transformer_config import AttnMaskType
        from megatronapp_tpu.ops.context_parallel import context_attention
        if attention_mask is not None:
            raise NotImplementedError(
                "MLA + explicit attention mask under cp is unsupported")
        # manual-ok: context_attention detects the ambient manual cp axis
        out = context_attention(
            q_full, k_full, v, ctx.shard_map_mesh, cfg.cp_comm_type,
            causal=cfg.attn_mask_type == AttnMaskType.causal,
            softmax_scale=float(1.0 / (dqk + dpe) ** 0.5),
            a2a_size=cfg.hierarchical_cp_a2a_size,
            overlap_ring=getattr(cfg, "cp_comm_overlap", True))
    else:
        out = dot_product_attention(
            q_full, k_full, v, mask_type=mask_type,
            attention_mask=attention_mask, softmax_scale=scale,
            softmax_in_fp32=cfg.attention_softmax_in_fp32,
            q_offset=q_offset)
    out = scope_capture("context", out, layer_id)
    from megatronapp_tpu.inference.quantization import resolve_param
    out = out.reshape(b, s, nq * dv) @ _dist.apply(
        "weight", resolve_param(p["out_kernel"]), layer_id).astype(dt)
    return (out, new_cache) if kv_cache is not None else out


def _mla_forward_tp_sharded(p, x, cfg: TransformerConfig, rope_cos,
                            rope_sin, layer_id, ctx):
    """MLA with a tp-sharded residual stream inside the ambient full-manual
    pipeline stage body (training path, no cache).

    x: [B, S/tp, H] local seq chunk. The low-rank DOWN projections (q_down,
    kv_down) have small replicated-output widths: each shard computes them
    on its LOCAL rows only (FLOPs still cut tp×; wgrads are per-seq-chunk
    partials the enclosing transpose psums). The UP projections carry the
    head structure: q_up / kv_up run as ring all-gather-matmuls over
    per-shard head slices, producing full-sequence activations with nq/tp
    local heads. The tiny shared rope key k_pe is gathered explicitly
    (collectives.all_gather_seq) and roped with full tables; the out-proj
    ring reduce-scatters back to the local chunk."""
    from jax import lax
    from megatronapp_tpu.config.parallel_config import TP_AXIS
    from megatronapp_tpu.parallel.collectives import all_gather_seq
    from megatronapp_tpu.parallel.overlap import (
        all_gather_matmul_manual, matmul_reduce_scatter_manual,
    )
    from megatronapp_tpu.scope.disturbance import get_disturbance
    from megatronapp_tpu.scope.hooks import scope_capture
    from megatronapp_tpu.config.transformer_config import (
        PositionEmbeddingKind,
    )
    _dist = get_disturbance()

    b, s, h = x.shape
    nq = cfg.num_attention_heads
    dqk, dpe, dv = cfg.qk_head_dim, cfg.qk_pos_emb_head_dim, cfg.v_head_dim
    klat = cfg.kv_lora_rank
    dt = cfg.compute_dtype
    tp = ctx.tp
    me = lax.axis_index(TP_AXIS)
    ov = bool(getattr(cfg, "tp_comm_overlap", False))
    nql = nq // tp
    x = x.astype(dt)
    sf = s * tp

    dq = dqk + dpe
    if "q_proj" in p:
        qw = lax.dynamic_slice_in_dim(
            _dist.apply("weight", p["q_proj"], layer_id).astype(dt),
            me * nql * dq, nql * dq, axis=1)
        q = all_gather_matmul_manual(x, qw, tp, ov)      # [B, Sf, nql*dq]
    else:
        q_lat = x @ p["q_down"].astype(dt)               # local rows
        q_lat = rms_norm(q_lat, p["q_ln_scale"], cfg.layernorm_epsilon)
        quw = lax.dynamic_slice_in_dim(p["q_up"].astype(dt),
                                       me * nql * dq, nql * dq, axis=1)
        q = all_gather_matmul_manual(q_lat, quw, tp, ov)
    s_q, s_kv = latent_scales(cfg)
    if s_q is not None:
        q = q * s_q
    q = q.reshape(b, sf, nql, dq)
    q_nope, q_pe = q[..., :dqk], q[..., dqk:]

    kv = x @ _dist.apply("weight", p["kv_down"],
                         layer_id).astype(dt)            # [B, S/tp, klat+dpe]
    latent, k_pe = kv[..., :klat], kv[..., klat:]
    latent = rms_norm(latent, p["kv_ln_scale"], cfg.layernorm_epsilon)
    if s_kv is not None:
        latent = latent * s_kv

    # kv_up rides a ring all-gather of the latent seq chunks; the shared
    # rope key gathers explicitly (dpe-wide — negligible traffic).
    kuw = lax.dynamic_slice_in_dim(p["kv_up"].astype(dt),
                                   me * nql * (dqk + dv),
                                   nql * (dqk + dv), axis=1)
    kv_up = all_gather_matmul_manual(latent, kuw, tp, ov)
    kv_up = kv_up.reshape(b, sf, nql, dqk + dv)
    k_nope, v = kv_up[..., :dqk], kv_up[..., dqk:]
    k_pe = all_gather_seq(k_pe, TP_AXIS, axis=1)         # [B, Sf, dpe]

    if rope_cos is not None:
        q_pe = rotary.apply_rope(q_pe, rope_cos, rope_sin)
        k_pe = rotary.apply_rope(k_pe[:, :, None, :], rope_cos,
                                 rope_sin)[:, :, 0]
    k_pe = jnp.broadcast_to(k_pe[:, :, None, :], (b, sf, nql, dpe))

    if cfg.position_embedding == PositionEmbeddingKind.yarn:
        m = rotary.yarn_mscale(cfg.rope_scaling_factor,
                               cfg.yarn_mscale_coeff)
        q_nope = q_nope * m
        k_nope = k_nope * m

    q_full = jnp.concatenate([q_nope, q_pe], axis=-1)
    k_full = jnp.concatenate([k_nope, k_pe], axis=-1)
    q_full = scope_capture("qkv_q", q_full, layer_id)
    k_full = scope_capture("qkv_k", k_full, layer_id)
    v = scope_capture("qkv_v", v, layer_id)
    scale = 1.0 / jnp.sqrt(jnp.float32(dqk + dpe))
    out = dot_product_attention(
        q_full, k_full, v, mask_type=cfg.attn_mask_type,
        attention_mask=None, softmax_scale=scale,
        softmax_in_fp32=cfg.attention_softmax_in_fp32)
    out = scope_capture("context", out, layer_id)
    ow = lax.dynamic_slice_in_dim(
        _dist.apply("weight", p["out_kernel"], layer_id).astype(dt),
        me * nql * dv, nql * dv, axis=0)
    return matmul_reduce_scatter_manual(out.reshape(b, sf, nql * dv), ow,
                                        tp, ov)
