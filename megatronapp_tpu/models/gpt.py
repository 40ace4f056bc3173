"""GPT model (decoder-only LM).

Parity with /root/reference/megatron/core/models/gpt/gpt_model.py:32
(GPTModel: LanguageModelEmbedding → TransformerBlock → output layer with
optionally tied word embeddings, vocab-parallel logits + CE). TPU-first:
functional params pytree, scan-over-layers block, logical-axis shardings.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from megatronapp_tpu.config.transformer_config import (
    NormKind, PositionEmbeddingKind, TransformerConfig,
)
from megatronapp_tpu.ops import per_rank, rotary
from megatronapp_tpu.ops.cross_entropy import cross_entropy_loss
from megatronapp_tpu.ops.normalization import apply_norm
from megatronapp_tpu.transformer.block import block_forward, init_block_params
from megatronapp_tpu.scope.hooks import scope_capture


def init_gpt_params(rng, cfg: TransformerConfig, pp: int = 1, vpp: int = 1):
    """Returns (params, logical_axes) pytrees.

    pp > 1: block params are stored in the pipeline layout
    [pp, vpp, L/(pp*vpp), ...] (sharded over the pp mesh axis) with the
    interleaved chunk→stage assignment — see parallel/pipeline.py.
    """
    k_emb, k_pos, k_block, k_out = jax.random.split(rng, 4)
    std = cfg.init_method_std
    p = {
        "embedding": {
            "word": jax.random.normal(
                k_emb, (cfg.vocab_size, cfg.hidden_size), cfg.params_dtype) * std,
        },
        "final_ln_scale": jnp.full(
            (cfg.hidden_size,), 0.0 if cfg.norm_unit_offset else 1.0,
            cfg.params_dtype),
    }
    ax = {
        "embedding": {"word": ("vocab", "embed")},
        "final_ln_scale": ("embed",),
    }
    if cfg.position_embedding == PositionEmbeddingKind.learned_absolute:
        p["embedding"]["pos"] = jax.random.normal(
            k_pos, (cfg.max_position_embeddings, cfg.hidden_size),
            cfg.params_dtype) * std
        ax["embedding"]["pos"] = ("pos", "embed")
    if cfg.normalization == NormKind.layernorm:
        p["final_ln_bias"] = jnp.zeros((cfg.hidden_size,), cfg.params_dtype)
        ax["final_ln_bias"] = ("embed",)
    # A hybrid stack keeps its leading dense layers' halves inside its own
    # block ("ffn_lead", transformer/block.py): no stack beside it.
    hybrid = cfg.hybrid_stack
    lead = 0 if hybrid else cfg.moe_first_k_dense
    if hybrid and cfg.is_moe and pp > 1:
        raise ValueError(
            "a hybrid stack with MoE feed-forwards is not pipelined yet "
            "(its leading dense layers and two kinds of layer a stage "
            "unit) — run with pp == 1")
    if cfg.moe_shortcut_double_layer and pp > 1:
        raise ValueError(
            "the shortcut-connected double layer is not pipelined yet (two "
            "attention sublayers and a shortcut a stage unit) — run with "
            "pp == 1")
    p["block"], ax["block"] = init_block_params(
        k_block, cfg, num_layers=cfg.num_layers - lead)
    if lead:
        # The leading dense layers (ids 0..lead-1) of an MoE model: a
        # stack of their own, run as a prologue before the scanned MoE
        # stack (gpt_forward, dynamic_engine._scan_paged_layers).
        if pp > 1:
            raise ValueError(
                "moe_first_k_dense: the leading dense layers are not "
                "pipelined yet — run with pp == 1")
        p["lead_block"], ax["lead_block"] = init_block_params(
            jax.random.fold_in(k_block, lead), cfg, num_layers=lead,
            force_dense=True)
    if pp > 1:
        from megatronapp_tpu.parallel.pipeline import (
            reshape_params_for_pipeline,
        )
        # moe_layer_freq > 1 pipelines in GROUP units: the group-scan
        # layout {moe: [G,...], dense: [G, freq-1, ...]} reshapes its
        # leading G axis exactly like the uniform L axis (each pipeline
        # "layer" is one {1 moe + freq-1 dense} group).
        units = (cfg.num_layers // cfg.moe_layer_freq
                 if cfg.is_moe and cfg.moe_layer_freq > 1
                 else cfg.num_layers)
        if units % (pp * vpp) != 0:
            raise ValueError(
                f"{units} pipeline units (layers/groups) not divisible by "
                f"pp*vpp={pp * vpp}")
        p["block"] = reshape_params_for_pipeline(p["block"], pp, vpp)
        from megatronapp_tpu.parallel.sharding import is_logical_axes
        ax["block"] = jax.tree.map(
            lambda axes: ("pp_stage", "vpp_chunk", "stage_layers") + axes[1:],
            ax["block"], is_leaf=is_logical_axes)
    if cfg.untie_embeddings_and_output_weights:
        # num_pred_heads > 1: head j's vocab_size columns follow head j-1's.
        p["output"] = jax.random.normal(
            k_out, (cfg.hidden_size, cfg.vocab_size * cfg.num_pred_heads),
            cfg.params_dtype) * std
        ax["output"] = ("embed", "vocab")
    if cfg.mtp_num_layers:
        # MTP depth modules are NOT part of the pipelined stack: like the
        # embedding/head they run compiler-sharded on the last-stage
        # output (the reference places MTP on the last pp stage —
        # multi_token_prediction.py; here "outside the pipeline" is the
        # same placement expressed SPMD-style).
        from megatronapp_tpu.transformer.mtp import init_mtp_params
        p["mtp"], ax["mtp"] = init_mtp_params(k_out, cfg)
    return p, ax


def gpt_embed(p, tokens: jnp.ndarray, cfg: TransformerConfig,
              position_offset: int = 0, dtype=None,
              position_ids: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """tokens [B,S] → embeddings [B,S,H] (vocab axis tp-sharded: XLA handles
    the sharded gather; reference VocabParallelEmbedding layers.py:172).

    position_ids: optional explicit positions ([B,S] or [1,S]) — packed
    sequences reset positions per segment for learned-absolute embeddings
    too (reference resets the position_ids fed to the embedding)."""
    # The scope names this part in the compiled program's op_names
    # (trace/scope_map.py joins them to a device trace).
    with jax.named_scope("embedding"):
        h = per_rank.take(p["embedding"]["word"], tokens)
        if "pos" in p["embedding"]:
            if position_ids is None:
                position_ids = jnp.arange(tokens.shape[1])[None, :]
            pos = position_ids + position_offset
            h = h + jnp.take(p["embedding"]["pos"], pos, axis=0)
        if cfg.embedding_multiplier != 1.0:
            h = h * cfg.embedding_multiplier
        return h.astype(dtype or cfg.compute_dtype)


def rope_params(cfg: TransformerConfig, window: bool = False):
    """(inv_freq, mscale) for the configured rope variant, or (None, 1.0).

    Single source of truth for the variant selection so the per-token
    packed-sequence tables inherit YaRN's NTK-by-parts interpolation and
    mscale exactly like the standard tables. `window`: the table of a
    sliding-window stack's window layers, plain RoPE at sliding_rotary_base
    over sliding_rotary_percent of a head (the full layers' table where the
    model names no second one)."""
    # MLA applies rope only on the decoupled position heads.
    rope_dim = (cfg.qk_pos_emb_head_dim if cfg.multi_latent_attention
                else cfg.head_dim)
    if window and cfg.sliding_rotary_base is not None:
        return rotary.rope_frequencies(rope_dim, cfg.sliding_rotary_base,
                                       cfg.sliding_rotary_percent), 1.0
    if cfg.position_embedding == PositionEmbeddingKind.rope:
        return rotary.rope_frequencies(rope_dim, cfg.rotary_base,
                                       cfg.rotary_percent), 1.0
    if cfg.position_embedding == PositionEmbeddingKind.yarn:
        inv_freq = rotary.yarn_frequencies(
            rope_dim, cfg.rotary_base,
            scaling_factor=cfg.rope_scaling_factor,
            original_max_position=cfg.yarn_original_max_position,
            beta_fast=cfg.yarn_beta_fast, beta_slow=cfg.yarn_beta_slow,
            rotary_percent=cfg.rotary_percent)
        m = (cfg.yarn_attention_factor
             if cfg.yarn_attention_factor is not None
             else rotary.yarn_mscale(cfg.rope_scaling_factor,
                                     cfg.yarn_mscale_coeff))
        return inv_freq, m
    return None, 1.0


def gpt_rope_tables(cfg: TransformerConfig, seq_len: int,
                    position_offset: int = 0,
                    positions: Optional[jnp.ndarray] = None,
                    window: bool = False):
    """Rope cos/sin tables for arange positions, or explicit per-token
    `positions` (packed sequences); `window`: a sliding-window stack's
    window layers' (rope_params)."""
    inv_freq, m = rope_params(cfg, window)
    if inv_freq is None:
        return None, None
    if positions is None:
        positions = jnp.arange(seq_len)
    cos, sin = rotary.rope_cos_sin(positions + position_offset, inv_freq)
    if m != 1.0:
        cos, sin = cos * m, sin * m
    return cos, sin


def packed_attention_mask(segment_ids: jnp.ndarray) -> jnp.ndarray:
    """Block-diagonal mask for packed sequences: token i may attend j only
    within the same segment (causality comes from the standard causal mask
    on top). Parity with the reference packed/THD formats
    (core/packed_seq_params.py + --reset-attention-mask /
    --reset-position-ids semantics; positions reset per segment in
    packed_position_ids). Utility for mask-based consumers; the model
    path no longer densifies — the segment-aware flash kernel masks
    in-block and the cp impls thread segments through their collectives
    (transformer/attention.py).

    segment_ids [B,S] → bool mask [B,1,S,S] (True = may attend)."""
    same = segment_ids[:, None, :, None] == segment_ids[:, None, None, :]
    return same


def packed_position_ids(segment_ids: jnp.ndarray) -> jnp.ndarray:
    """Per-segment position ids: positions restart at 0 at each segment
    boundary (reference --reset-position-ids). [B,S] → [B,S] int32."""
    b, s = segment_ids.shape
    idx = jnp.arange(s)[None, :]
    is_start = jnp.concatenate(
        [jnp.ones((b, 1), bool),
         segment_ids[:, 1:] != segment_ids[:, :-1]], axis=1)
    seg_start = jax.lax.cummax(jnp.where(is_start, idx, 0), axis=1)
    return (idx - seg_start).astype(jnp.int32)


def gpt_forward(p, tokens: jnp.ndarray, cfg: TransformerConfig,
                attention_mask: Optional[jnp.ndarray] = None,
                position_offset: int = 0, ctx=None,
                segment_ids: Optional[jnp.ndarray] = None,
                zigzag_keep: bool = False, return_hidden: bool = False,
                fp8=None, moe_counts: bool = False):
    """tokens [B,S] → (logits [B,S,V] fp32, moe_aux_loss) —
    (+ pre-head hidden states and rope tables when return_hidden, for the
    MTP depth modules; + the layers' summed routing counts when moe_counts:
    block_forward).

    segment_ids: optional [B,S] packing map — attention is restricted to
    within-segment (packed sequences).

    Under causal ring context parallelism the sequence is transparently
    permuted into the load-balanced zigzag layout (ops/context_parallel.py
    zigzag_indices) and logits are unpermuted on return; `zigzag_keep=True`
    skips the unpermute (gpt_loss permutes the targets instead — cheaper
    than moving [B,S,V] logits across cp shards)."""
    from megatronapp_tpu.ops.context_parallel import (
        zigzag_active, zigzag_indices, zigzag_inverse_indices,
    )

    b, s = tokens.shape
    packed_pos = None
    if segment_ids is not None:
        # Positions restart per segment (reference --reset-position-ids) —
        # for BOTH the learned-absolute embedding and rope tables. The
        # segment mask itself is applied inside attention (flash in-block
        # masking / cp collectives), NOT as a dense [B,S,S] mask.
        packed_pos = packed_position_ids(segment_ids)
    positions = packed_pos
    zz = (zigzag_active(cfg, ctx) and segment_ids is None
          and attention_mask is None)
    if zz:
        idx = jnp.asarray(zigzag_indices(s, ctx.cp))
        tokens = jnp.take(tokens, idx, axis=1)
        positions = idx[None, :]
    h = gpt_embed(p, tokens, cfg, position_offset, position_ids=positions)
    cos, sin = gpt_rope_tables(cfg, s, position_offset,
                               positions=(positions[0] if zz else positions))
    window_rope = None
    if cfg.sliding_window:
        window_rope = gpt_rope_tables(cfg, s, position_offset,
                                      positions=positions, window=True)
    if "lead_block" in p:
        h, _ = block_forward(p["lead_block"], h, cfg, cos, sin,
                             attention_mask, ctx=ctx, zigzag=zz,
                             segment_ids=segment_ids)
    h, aux, *counts = block_forward(
        p["block"], h, cfg, cos, sin, attention_mask,
        layer_offset=(cfg.moe_first_k_dense if "lead_block" in p else 0),
        ctx=ctx, zigzag=zz, segment_ids=segment_ids,
        fp8=None if fp8 is None else fp8["block"],
        window_rope=window_rope, moe_counts=moe_counts)
    logits = gpt_head(p, h, cfg)
    if zz and not zigzag_keep:
        logits = jnp.take(logits, jnp.asarray(zigzag_inverse_indices(
            s, ctx.cp)), axis=1)
    if return_hidden:
        return logits, aux, h, (cos, sin)
    return (logits, aux, *counts)


def gpt_loss(p, tokens: jnp.ndarray, targets: jnp.ndarray,
             loss_mask: Optional[jnp.ndarray], cfg: TransformerConfig,
             ctx=None, segment_ids: Optional[jnp.ndarray] = None,
             fp8=None):
    """Training loss (CE + MoE aux). Mirrors pretrain_gpt.py loss_func
    (/root/reference/pretrain_gpt.py:159)."""
    from megatronapp_tpu.ops.context_parallel import (
        zigzag_active, zigzag_indices,
    )
    more = {}
    if cfg.num_pred_heads > 1:
        raise NotImplementedError(
            "a head of num_pred_heads x vocab_size columns has no training "
            "loss here yet: such a model is served, not trained")
    if cfg.mtp_num_layers:
        if segment_ids is not None:
            raise NotImplementedError(
                "multi token prediction + sequence packing is not "
                "supported (reference multi_token_prediction.py assert)")
        from megatronapp_tpu.transformer.mtp import mtp_loss as _mtp_loss
        logits, aux, hid, (cos, sin) = gpt_forward(
            p, tokens, cfg, ctx=ctx, zigzag_keep=True, return_hidden=True)
        if zigzag_active(cfg, ctx):
            # The depth modules' future-token rolls need contiguous
            # order: un-permute the main-stack output and run MTP with
            # plain rope tables — its attention then takes the contiguous
            # (non-zigzag) ring, which is correct under cp.
            from megatronapp_tpu.ops.context_parallel import (
                zigzag_inverse_indices,
            )
            inv = jnp.asarray(zigzag_inverse_indices(tokens.shape[1],
                                                     ctx.cp))
            hid = jnp.take(hid, inv, axis=1)
            cos, sin = gpt_rope_tables(cfg, tokens.shape[1])
        mtp_scaled, mtp_mean, mtp_layer_aux = _mtp_loss(
            p["mtp"], hid, lambda t: gpt_embed(p, t, cfg),
            lambda hh: gpt_head(p, hh, cfg), tokens, targets, loss_mask,
            cfg, cos, sin, ctx=ctx)
        # Keep 'moe_aux_loss' pure: the depth layers' router losses join
        # it (unscaled, like main-stack layers); the scaled MTP CE is
        # carried separately into the total.
        aux = aux + mtp_layer_aux
        more["mtp_loss"] = mtp_mean
        more["_mtp_scaled"] = mtp_scaled
    else:
        # A hybrid stack that counts its held experts' load (a share of an
        # expert-parallel job) counts it in training too.
        counting = cfg.moe_counts_load and cfg.hybrid_stack
        logits, aux, *counts = gpt_forward(
            p, tokens, cfg, ctx=ctx, segment_ids=segment_ids,
            zigzag_keep=True, fp8=fp8, moe_counts=counting)
        if counting:
            from megatronapp_tpu.transformer.moe import TRAIN_COUNTS
            moe_layers = cfg.num_moe_layers
            # "sums": a step's totals over micro-batches, not their mean
            # (training/train_step.py)
            more["sums"] = {
                **dict(zip(TRAIN_COUNTS, counts[0])),
                "experts_here": jnp.int32(
                    cfg.moe_experts_here[1] * moe_layers),
                "moe_layer_passes": jnp.int32(moe_layers),
                "router_loss": aux}
            if segment_ids is not None:
                # and the tiles its flash kernels computed of the packed
                # rows' causal triangles and bands
                from megatronapp_tpu.transformer.attention import (
                    flash_tile_counts,
                )
                more["sums"].update(flash_tile_counts(cfg, segment_ids, ctx))
    if zigzag_active(cfg, ctx) and segment_ids is None:
        # Logits are in zigzag order — permute targets/mask to match (the
        # masked-mean CE is permutation-invariant).
        idx = jnp.asarray(zigzag_indices(tokens.shape[1], ctx.cp))
        targets = jnp.take(targets, idx, axis=1)
        if loss_mask is not None:
            loss_mask = jnp.take(loss_mask, idx, axis=1)
    with jax.named_scope("head"):       # in training the head has the loss
        loss, _ = cross_entropy_loss(logits, targets, loss_mask)
    mtp_scaled_term = more.pop("_mtp_scaled",
                                      jnp.zeros((), jnp.float32))
    return loss + aux + mtp_scaled_term, {"lm_loss": loss,
                                          "moe_aux_loss": aux,
                                          **more}


def gpt_head(p, h: jnp.ndarray, cfg: TransformerConfig) -> jnp.ndarray:
    """Final norm + vocab projection. h [..., S, H] → logits fp32."""
    with jax.named_scope("head"):
        h = apply_norm(cfg.normalization, h, p["final_ln_scale"],
                       p.get("final_ln_bias"), cfg.layernorm_epsilon,
                       cfg.norm_unit_offset)
        out_kernel = (p["output"] if "output" in p
                      else jnp.swapaxes(p["embedding"]["word"], -1, -2))
        logits = per_rank.dense(h.astype(cfg.compute_dtype),
                                out_kernel.astype(cfg.compute_dtype))
        logits = scope_capture("result", logits).astype(jnp.float32)
        if cfg.logits_scaling != 1.0:
            logits = logits / cfg.logits_scaling
        return logits


def gpt_pipeline_loss(p, tokens_mb, targets_mb, loss_mask_mb,
                      cfg: TransformerConfig, ctx, vpp: int = 1,
                      order_policy: str = "dfc", segment_ids_mb=None,
                      schedule: str = "1f1b"):
    """Pipelined training loss over microbatched inputs [M, mb, S].

    Embedding and LM head run outside the pipeline body (compiler-sharded
    over dp/tp); the layer stack runs inside spmd_pipeline over the pp axis.
    The reference runs its schedules imperatively per rank
    (schedules.py:1918 1F1B); here the schedule is an instruction program
    executed by the jitted region (parallel/schedule.py) — `schedule`
    picks 1f1b/vpp or the zero-bubble B/W split (--pp-schedule).

    segment_ids_mb: optional [M, mb, S] packed map — segments and the
    per-token rope tables ride the pipeline as per-microbatch aux inputs
    (spmd_pipeline aux_mb).
    """
    from megatronapp_tpu.parallel.pipeline import spmd_pipeline

    from megatronapp_tpu.ops.context_parallel import (
        zigzag_active, zigzag_indices,
    )

    m, mb, s = tokens_mb.shape
    if "lead_block" in p:
        raise ValueError("moe_first_k_dense: the leading dense layers are "
                         "not pipelined yet")
    if segment_ids_mb is not None:
        if schedule == "zero-bubble":
            raise NotImplementedError(
                "--pp-schedule zero-bubble does not compose with packed "
                "sequences (per-microbatch aux inputs) yet — run the "
                "1f1b schedule there")
        return _gpt_pipeline_loss_packed(
            p, tokens_mb, targets_mb, loss_mask_mb, segment_ids_mb, cfg,
            ctx, vpp, order_policy)
    # tp-sharded stage body (parallel/overlap.py tp_stage_eligible —
    # decided BEFORE the zigzag layout: when both apply, the tp FLOPs
    # cut takes the contiguous cp ring over the zigzag load balance).
    from megatronapp_tpu.parallel.overlap import tp_stage_ineligible_reason
    _tp_reason = tp_stage_ineligible_reason(cfg, ctx, s)
    positions = None
    if zigzag_active(cfg, ctx) and _tp_reason is None:
        # pp x cp x tp composition (ISSUE 15): the seq-over-(cp, tp)
        # sharded stage body runs the CONTIGUOUS cp ring — the zigzag
        # permutation does not compose with the tp seq-sharding. The
        # tp-side FLOPs cut (tp x) dominates the zigzag load-balance
        # win; --no-tp-sharded-stage restores the zigzag layout.
        import logging
        logging.getLogger(__name__).info(
            "pp x cp x tp composition: tp-sharded stage bodies take the "
            "contiguous cp ring (zigzag layout does not compose with "
            "seq-over-tp sharding; --no-tp-sharded-stage restores "
            "zigzag)")
    elif zigzag_active(cfg, ctx):
        # Zigzag cp layout (see gpt_forward): permute the sequence so each
        # cp rank's contiguous block holds chunks (i, 2cp-1-i); rope tables
        # follow the permuted positions, and the in-pipeline cp-rank slicing
        # of cos/sin then picks each rank's zigzag positions. Targets are
        # permuted identically below, so the loss is unchanged.
        idx = jnp.asarray(zigzag_indices(s, ctx.cp))
        # jnp.take along the cp-SHARDED seq axis of the dp-sharded batch
        # arrays makes this build's SPMD partitioner emit an invalid
        # dynamic-slice (hlo verifier: "Slice dim size > dynamic slice
        # dimension" when mb is dp-sharded and seq cp-sharded). The
        # arrays are tiny ([M, mb, S] ints/mask), so replicate them for
        # the permutation — the embed/pipeline constraints re-shard
        # immediately downstream.
        rep = jax.sharding.NamedSharding(ctx.mesh,
                                         jax.sharding.PartitionSpec())
        tokens_mb, targets_mb, loss_mask_mb = (
            jnp.take(jax.lax.with_sharding_constraint(x, rep), idx, axis=2)
            for x in (tokens_mb, targets_mb, loss_mask_mb))
        positions = idx
    # fp32 across the shard_map boundary (spmd_pipeline casts to the compute
    # dtype at microbatch injection — see pipeline.py body notes).
    h = gpt_embed(p, tokens_mb.reshape(m * mb, s), cfg, dtype=jnp.float32,
                  position_ids=None if positions is None
                  else positions[None, :])
    h = h.reshape(m, mb, s, -1)
    cos, sin = gpt_rope_tables(cfg, s, positions=positions)

    # Pipeline offsets count scan units; with the moe group-scan each unit
    # is moe_layer_freq layers (layer ids feed scope captures/disturbance).
    unit_layers = (cfg.moe_layer_freq
                   if cfg.is_moe and cfg.moe_layer_freq > 1 else 1)

    # tp-sharded stage body (parallel/overlap.py tp_stage_eligible): the
    # manual pipeline region shards activations over tp along the seq dim
    # (jointly with cp under the pp x cp x tp composition) and the stage
    # body runs the ring-overlapped projections — tp× fewer stage FLOPs
    # instead of the tp-replicated redundant compute.
    tp_shard = positions is None and _tp_reason is None
    if (not tp_shard and ctx is not None and ctx.tp > 1 and ctx.pp > 1):
        # Trace-time log (fires once per compiled shape) naming the
        # SPECIFIC failed predicate instead of a generic ineligible
        # fallback (ISSUE 11 satellite).
        import logging
        logging.getLogger(__name__).info(
            "pipeline stage body runs tp-REPLICATED: %s",
            _tp_reason if positions is None
            else "inference path (positions given)")

    def stage_fn(chunk_params, x, layer_offset):
        layer_offset = layer_offset * unit_layers
        cos_l, sin_l = cos, sin
        from megatronapp_tpu.config.parallel_config import CP_AXIS
        from megatronapp_tpu.parallel.collectives import current_manual_axes
        if CP_AXIS in current_manual_axes() and cos is not None:
            # Inside the pipeline body the cp axis is manual: x carries
            # the local sequence block — slice the rope tables to this
            # cp rank's chunk. Under tp_shard the stream is additionally
            # tp-sharded ([.., S/(cp*tp), H]) and attention re-gathers
            # only the cp-LOCAL chunk through its tp rings, so the right
            # tables cover x.shape[1] * tp rows. With cp == 1 both
            # spellings slice the whole table at offset 0 (no-op). (In
            # the pp==1 fallback stage_fn runs outside any manual region
            # and x carries the full sequence — no slicing.)
            s_loc = x.shape[1] * (ctx.tp if tp_shard else 1)
            start = jax.lax.axis_index(CP_AXIS) * s_loc
            cos_l = jax.lax.dynamic_slice_in_dim(cos, start, s_loc)
            sin_l = jax.lax.dynamic_slice_in_dim(sin, start, s_loc)
        return block_forward(chunk_params, x, cfg, cos_l, sin_l, None,
                             layer_offset=layer_offset, ctx=ctx,
                             zigzag=positions is not None,
                             tp_sharded=tp_shard)

    out_mb, aux = spmd_pipeline(
        stage_fn, p["block"], h, ctx, num_microbatches=m, vpp=vpp,
        compute_dtype=cfg.compute_dtype, order_policy=order_policy,
        tp_shard=tp_shard, schedule=schedule)
    # Aux losses are summed over the M microbatches inside the pipeline;
    # normalize to per-microbatch scale to match the non-pipelined path.
    aux = aux / m

    mtp_metrics = {}
    mtp_scaled_term = jnp.zeros((), jnp.float32)
    if cfg.mtp_num_layers:
        # MTP runs on the last-stage output, outside the pp body, like the
        # head (reference last-stage placement, multi_token_prediction.py).
        if positions is not None:
            raise NotImplementedError(
                "multi token prediction + zigzag context parallelism is "
                "not supported (future-token rolls assume contiguous "
                "sequence order)")
        from megatronapp_tpu.transformer.mtp import mtp_loss as _mtp_loss
        mtp_scaled_term, mtp_mean, mtp_layer_aux = _mtp_loss(
            p["mtp"], out_mb.reshape(m * mb, s, -1),
            lambda t: gpt_embed(p, t, cfg),
            lambda hh: gpt_head(p, hh, cfg),
            tokens_mb.reshape(m * mb, s), targets_mb.reshape(m * mb, s),
            loss_mask_mb.reshape(m * mb, s), cfg, cos, sin, ctx=ctx)
        aux = aux + mtp_layer_aux
        mtp_metrics["mtp_loss"] = mtp_mean

    logits = gpt_head(p, out_mb, cfg)
    with jax.named_scope("head"):
        loss, _ = cross_entropy_loss(logits, targets_mb, loss_mask_mb)
    return loss + aux + mtp_scaled_term, {"lm_loss": loss,
                                          "moe_aux_loss": aux,
                                          **mtp_metrics}


def _gpt_pipeline_loss_packed(p, tokens_mb, targets_mb, loss_mask_mb,
                              segment_ids_mb, cfg: TransformerConfig, ctx,
                              vpp: int, order_policy: str):
    """Packed-sequence pipelined loss: per-token positions/rope tables and
    segment ids flow as spmd_pipeline aux inputs; attention applies the
    segment mask inside the pipeline body (reference packed/THD under pp)."""
    from megatronapp_tpu.parallel.pipeline import spmd_pipeline

    if cfg.mtp_num_layers:
        raise NotImplementedError(
            "multi token prediction + sequence packing is not "
            "supported (reference multi_token_prediction.py assert)")
    m, mb, s = tokens_mb.shape
    flat_segs = segment_ids_mb.reshape(m * mb, s)
    packed_pos = packed_position_ids(flat_segs)                # [M*mb, S]
    h = gpt_embed(p, tokens_mb.reshape(m * mb, s), cfg, dtype=jnp.float32,
                  position_ids=packed_pos)
    h = h.reshape(m, mb, s, -1)

    inv_freq, msc = rope_params(cfg)
    aux = {"segs": segment_ids_mb}
    if inv_freq is not None:
        cos, sin = rotary.rope_cos_sin(packed_pos.reshape(m, mb, s),
                                       inv_freq)              # [M,mb,S,half]
        if msc != 1.0:
            cos, sin = cos * msc, sin * msc
        aux["cos"], aux["sin"] = cos, sin

    def stage_fn(chunk_params, x, layer_offset, aux_m):
        return block_forward(chunk_params, x, cfg, aux_m.get("cos"),
                             aux_m.get("sin"), None,
                             layer_offset=layer_offset, ctx=ctx,
                             segment_ids=aux_m["segs"])

    out_mb, aux_loss = spmd_pipeline(
        stage_fn, p["block"], h, ctx, num_microbatches=m, vpp=vpp,
        compute_dtype=cfg.compute_dtype, order_policy=order_policy,
        aux_mb=aux)
    aux_loss = aux_loss / m

    logits = gpt_head(p, out_mb, cfg)
    with jax.named_scope("head"):
        loss, _ = cross_entropy_loss(logits, targets_mb, loss_mask_mb)
    return loss + aux_loss, {"lm_loss": loss, "moe_aux_loss": aux_loss}
