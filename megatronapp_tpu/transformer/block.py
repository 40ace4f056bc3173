"""Transformer layer + block (scan-over-layers).

Parity with /root/reference/megatron/core/transformer/transformer_layer.py:237
(TransformerLayer) and transformer_block.py:220 (TransformerBlock). The
reference builds a Python list of layer modules and loops; here per-layer
params are *stacked* along a leading 'layers' axis and the block runs
``jax.lax.scan`` over them — one compiled layer body regardless of depth
(TPU-first: fast compiles, natural fit for pipeline chunking and remat).

Pre-LN residual structure (reference: input_layernorm → attn → +residual →
pre_mlp_layernorm → mlp → +residual).
"""

from __future__ import annotations

import collections
import contextlib
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

from megatronapp_tpu.config.transformer_config import (
    NormKind, TransformerConfig,
)
from megatronapp_tpu.ops.normalization import apply_norm
from megatronapp_tpu.ops.per_rank import RANK_DENSE_OUT
from megatronapp_tpu.transformer.attention import (
    attention_forward, init_attention_params,
)
from megatronapp_tpu.transformer.mlp import init_mlp_params, mlp_forward
from megatronapp_tpu.transformer.moe import (
    EXPERT_GEMM_OUT, init_moe_params, moe_forward,
)
from megatronapp_tpu.scope.hooks import scope_capture


def _norm_scale(cfg: TransformerConfig):
    """A norm's scale leaf at initialisation: 1, or with norm_unit_offset
    the g = 0 of a scale 1 + g."""
    return jnp.full((cfg.hidden_size,), 0.0 if cfg.norm_unit_offset else 1.0,
                    cfg.params_dtype)


def _init_depth(cfg: TransformerConfig) -> int:
    """The depth the scaled init divides by (cfg.scaled_init_layers)."""
    return cfg.scaled_init_layers or cfg.num_layers


def _init_mixer_half(rng, cfg: TransformerConfig, out_std,
                     stack: str = "mixers_attn"):
    """A layer's first half: its norm and its mixer, the one whose layers
    cfg.stack_plan stacks under `stack`: attention (or MLA), under
    "mixers_swa" a sliding-window attention layer of cfg.window_heads query
    heads, under "mixers_conv" a gated short convolution, under "mixers_ssm"
    a selective-state-space mixer, under "mixers_kda" Kimi delta
    attention."""
    if stack == "mixers_swa":
        name = "attention"
        mix_p, mix_ax = init_attention_params(rng, cfg, out_std,
                                              heads=cfg.window_heads)
    elif stack == "mixers_conv":
        from megatronapp_tpu.transformer.shortconv import (
            init_shortconv_params,
        )
        name = "conv"
        mix_p, mix_ax = init_shortconv_params(rng, cfg, out_std)
    elif stack == "mixers_ssm":
        from megatronapp_tpu.transformer.ssm import init_ssm_params, ssm_dims
        name = "ssm"
        mix_p, mix_ax = init_ssm_params(rng, cfg, ssm_dims(cfg), out_std)
    elif stack == "mixers_kda":
        from megatronapp_tpu.transformer.kda import init_kda_params
        name = "kda"
        mix_p, mix_ax = init_kda_params(rng, cfg, out_std)
    elif cfg.multi_latent_attention:
        from megatronapp_tpu.transformer.mla import init_mla_params
        name = "attention"
        mix_p, mix_ax = init_mla_params(rng, cfg, out_std)
    else:
        name = "attention"
        mix_p, mix_ax = init_attention_params(rng, cfg, out_std)
    p = {"ln1_scale": _norm_scale(cfg), name: mix_p}
    ax = {"ln1_scale": ("embed",), name: mix_ax}
    if cfg.normalization == NormKind.layernorm:
        p["ln1_bias"] = jnp.zeros((cfg.hidden_size,), cfg.params_dtype)
        ax["ln1_bias"] = ("embed",)
    return p, ax


def _init_ffn_half(rng, cfg: TransformerConfig, out_std,
                   force_dense: bool = False):
    """A layer's second half: its norm and its feed-forward."""
    p = {"ln2_scale": _norm_scale(cfg)}
    ax = {"ln2_scale": ("embed",)}
    if cfg.normalization == NormKind.layernorm:
        p["ln2_bias"] = jnp.zeros((cfg.hidden_size,), cfg.params_dtype)
        ax["ln2_bias"] = ("embed",)
    if cfg.is_moe and not force_dense:
        p["moe"], ax["moe"] = init_moe_params(rng, cfg, out_std)
    else:
        p["mlp"], ax["mlp"] = init_mlp_params(rng, cfg, out_std)
    return p, ax


def init_layer_params(rng, cfg: TransformerConfig, force_dense: bool = False):
    """One layer's params + logical axes (unstacked).

    A shortcut-connected double layer (cfg.moe_shortcut_double_layer) is
    {"first", "second"}: two ordinary halves-of-a-layer pairs (norm,
    attention, norm, dense FFN), the first of which also holds the "moe"
    that reads its second norm's output (layer_forward)."""
    if cfg.moe_shortcut_double_layer:
        # five residual-out projections a layer, four of them in sequence
        out_std = cfg.init_method_std / jnp.sqrt(4.0 * _init_depth(cfg))
        halves = {}
        for name, key in zip(("first", "second"), jax.random.split(rng)):
            k_attn, k_mlp, k_moe = jax.random.split(key, 3)
            p, ax = _init_mixer_half(k_attn, cfg, out_std)
            ffn_p, ffn_ax = _init_ffn_half(k_mlp, cfg, out_std,
                                           force_dense=True)
            p, ax = {**p, **ffn_p}, {**ax, **ffn_ax}
            if name == "first":
                p["moe"], ax["moe"] = init_moe_params(k_moe, cfg, out_std)
            halves[name] = (p, ax)
        return ({k: v[0] for k, v in halves.items()},
                {k: v[1] for k, v in halves.items()})
    # Scaled init for residual-out projections: std/sqrt(2*num_layers)
    # (reference scaled_init_method_normal, training/utils).
    out_std = cfg.init_method_std / jnp.sqrt(2.0 * _init_depth(cfg))
    k_attn, k_mlp = jax.random.split(rng)
    p, ax = _init_mixer_half(k_attn, cfg, out_std)
    ffn_p, ffn_ax = _init_ffn_half(k_mlp, cfg, out_std, force_dense)
    return {**p, **ffn_p}, {**ax, **ffn_ax}


def layer_forward(p, x: jnp.ndarray, cfg: TransformerConfig,
                  rope_cos=None, rope_sin=None, attention_mask=None,
                  layer_id=None, kv_cache=None, cache_index=None,
                  cache_positions=None, ctx=None,
                  zigzag: bool = False, segment_ids=None,
                  page_table=None, active=None, chunk_counts=None,
                  tp_sharded: bool = False, kv_scales=None,
                  fp8=None, lora=None, kv_plane=None, ssm_state=None,
                  state_rows=None, window_rope=None, moe_counts: bool = False):
    """One transformer layer. x: [B,S,H] → ((out, new_cache), aux_losses).

    moe_counts: a training step's MoE layer hands back (aux loss, its
    routing counts, moe.TRAIN_COUNTS) in place of the aux loss.

    window_rope: (cos, sin) of a sliding-window stack's window layers
    (models/gpt.py gpt_rope_tables(window=True)). Not None marks THIS layer
    as one of them: it attends through cfg.sliding_window keys, rotates by
    that table, and in a paged step kv_cache, page_table and kv_plane are
    the window pools', their table's and its plane of them.

    A layer whose params hold "ssm" in place of "attention" runs the
    selective-state-space mixer (transformer/ssm.py) as its first half; one
    that holds "kda" Kimi delta attention (transformer/kda.py), on the same
    two pools.
    In a paged serving step such a layer has no kv_cache: ssm_state =
    (ssm pool, conv pool, this layer's plane of them), state_rows maps x's
    rows to slots, and new_cache is the two state pools. A layer that
    holds "conv" runs the gated short convolution (transformer/
    shortconv.py) the same way; its ssm_state is (tail pool, plane) and
    new_cache the one pool. kv_plane: the
    plane of the paged KV pools an attention layer owns where that is not
    its layer id (a hybrid stack's pools hold its attention layers only).

    page_table/active: paged-KV decode (inference/paged_cache.py) —
    kv_cache is then the whole STACKED block pool [L, NB, bs, ...], of
    which layer_id names this layer's plane: each batch row appends at
    its own page-table position of it, in place (see attention.py /
    mla.py).
    kv_scales: stacked fp32 scale pools marking a quantized paged pool
    (see attention.py; MLA: per-row scalar scales on the latent/pe
    pools, see mla.py); new_cache then carries four pools.

    tp_sharded: ambient-manual tp-sharded stage body (pp pipeline) — x is
    the local [B, S/tp, H] seq chunk; norms/residuals run on it directly
    (elementwise over seq) and the sublayers take their ring paths.

    fp8: this layer's delayed-scaling amax state (training/fp8.py,
    ISSUE 13) — {"attention": {"qkv", "out"}, "mlp": {"fc1", "fc2"}}
    sub-dicts threaded into the tp-overlap ring GEMMs; the updated
    histories travel out through their cotangents.

    lora: batched per-row adapter deltas (inference/lora.py, ISSUE 19) —
    {"row_adapter": [B] int32 bank slots, "banks": {target: (a, b)}}
    with THIS layer's factor banks a [slots, din, r] / b [slots, r, dout]
    per RESIDENT_KERNELS target. Serving paths only: each projection
    matmul grows a ``base(x) + B_i A_i x`` delta
    (kernel_gen.apply_lora_delta); slot 0 is the all-zero null
    adapter.

    A shortcut-connected double layer (p holds "first" and "second",
    cfg.moe_shortcut_double_layer) runs

        x1 = x  + A1(norm1(x));   h = norm2(x1);   m = MoE(h)
        x2 = x1 + F1(h);   x3 = x2 + A2(norm3(x2));   x4 = x3 + F2(norm4(x3))
        out = x4 + m

    as two calls of this function on its halves: a half that holds both
    "mlp" and "moe" adds the dense FFN to the stream and hands the MoE's
    output back beside aux, as (aux, m), for the caller to add at the end.
    In a paged step its attention sublayers own planes 2·layer_id and
    2·layer_id + 1 of the pools."""
    if "second" in p:
        refused = [what for what, on in (
            ("a tp-sharded stage body", tp_sharded), ("fp8", fp8 is not None),
            ("lora", lora is not None), ("zigzag cp", zigzag),
            ("a quantized pool (kv_scales)", kv_scales is not None),
            ("a dense (unpaged) KV cache",
             kv_cache is not None and page_table is None),
            ("context parallelism", ctx is not None and ctx.cp > 1),
            ) if on]
        if refused:
            raise NotImplementedError(
                "the shortcut-connected double layer runs whole sequences "
                "or paged bf16 pools on one device: cannot run with "
                + "; ".join(refused))
        both = dict(
            rope_cos=rope_cos, rope_sin=rope_sin,
            attention_mask=attention_mask, layer_id=layer_id,
            cache_index=cache_index, cache_positions=cache_positions,
            ctx=ctx, segment_ids=segment_ids, page_table=page_table,
            active=active, chunk_counts=chunk_counts)
        plane = None if kv_cache is None else 2 * layer_id
        (x, cache), (aux, m) = layer_forward(
            p["first"], x, cfg, kv_cache=kv_cache, kv_plane=plane, **both)
        (x, cache), _ = layer_forward(
            p["second"], x, cfg, kv_cache=cache,
            kv_plane=None if plane is None else plane + 1, **both)
        return (x + m.astype(x.dtype), cache), aux
    def mixer_half(x):
        """x + Mixer(norm(x)), and the cache the mixer wrote."""
        residual = x
        h = apply_norm(cfg.normalization, x, p["ln1_scale"], p.get("ln1_bias"),
                       cfg.layernorm_epsilon, cfg.norm_unit_offset)
        # The scopes put a layer's parts into the compiled program's op_names
        # (HLO text, the profiler's own viewer). The events of a TPU trace as
        # jax.profiler.ProfileData gives them carry an instruction's name and
        # no op_name: trace/scope_map.py reads the scopes from the compiled
        # text and a reader joins the two by instruction name (PERF.md, PR 36).
        def attend():
            mask = attention_mask
            cos, sin = ((rope_cos, rope_sin) if window_rope is None
                        else window_rope)
            # "window" is no part of its own (trace/scope_map.PARTS): a window
            # layer's operations stay in `attention`, and carry the sub-part.
            with jax.named_scope("attention"), (
                    jax.named_scope("window") if window_rope is not None
                    else contextlib.nullcontext()):
                if cfg.multi_latent_attention:
                    if lora is not None:
                        raise ValueError(
                            "lora serving targets the GQA projection "
                            "kernels — MLA has no q_kernel/kv_kernel "
                            "(lora.AdapterCache rejects MLA configs at "
                            "construction)")
                    from megatronapp_tpu.transformer.mla import mla_forward
                    if segment_ids is not None:
                        # MLA routes through the reference attention impl —
                        # packed segments densify into the mask here.
                        seg_mask = (segment_ids[:, None, :, None]
                                    == segment_ids[:, None, None, :])
                        mask = seg_mask if mask is None else mask & seg_mask
                    if kv_cache is not None:
                        attn_out, new_cache = mla_forward(
                            p["attention"], h, cfg, rope_cos, rope_sin, mask,
                            layer_id=layer_id, ctx=ctx, kv_cache=kv_cache,
                            cache_index=cache_index,
                            cache_positions=cache_positions,
                            page_table=page_table, active=active,
                            chunk_counts=chunk_counts, kv_scales=kv_scales,
                            kv_plane=kv_plane)
                    else:
                        attn_out = mla_forward(
                            p["attention"], h, cfg, rope_cos, rope_sin, mask,
                            layer_id=layer_id, ctx=ctx, tp_sharded=tp_sharded)
                        new_cache = None
                else:
                    attn_out, new_cache = attention_forward(
                        p["attention"], h, cfg, cos, sin, mask,
                        window=(cfg.sliding_window if window_rope is not None
                                else 0),
                        kv_cache=kv_cache, cache_index=cache_index,
                        cache_positions=cache_positions, layer_id=layer_id,
                        ctx=ctx, zigzag=zigzag, segment_ids=segment_ids,
                        page_table=page_table, active=active,
                        chunk_counts=chunk_counts, tp_sharded=tp_sharded,
                        kv_scales=kv_scales,
                        fp8=None if fp8 is None else fp8["attention"],
                        lora=lora, kv_plane=kv_plane)
            return attn_out, new_cache

        if "ssm" in p:
            if segment_ids is not None or tp_sharded or lora is not None:
                raise NotImplementedError(
                    "a state-space layer runs whole sequences on one tp "
                    "shard: no packed segments (the state would cross "
                    "them), tp-sharded stage body or lora")
            from megatronapp_tpu.transformer.ssm import (
                ssm_dims, ssm_forward, ssm_paged_forward,
            )
            with jax.named_scope("ssm"):
                if ssm_state is not None:
                    attn_out, new_cache = ssm_paged_forward(
                        p["ssm"], h, cfg, ssm_state, rows=state_rows,
                        starts=cache_positions, counts=chunk_counts,
                        active=active)
                else:
                    attn_out, _ = ssm_forward(p["ssm"], h, cfg, ssm_dims(cfg))
                    new_cache = None
        elif "kda" in p:
            if segment_ids is not None or tp_sharded or lora is not None:
                raise NotImplementedError(
                    "a Kimi delta attention layer runs whole sequences on "
                    "one tp shard: no packed segments (the state would "
                    "cross them), tp-sharded stage body or lora")
            from megatronapp_tpu.transformer.kda import (
                kda_forward, kda_paged_forward,
            )
            # the recurrent mixers' part (trace/scope_map.PARTS)
            with jax.named_scope("ssm"):
                if ssm_state is not None:
                    attn_out, new_cache = kda_paged_forward(
                        p["kda"], h, cfg, ssm_state, rows=state_rows,
                        starts=cache_positions, counts=chunk_counts,
                        active=active)
                else:
                    attn_out, _ = kda_forward(p["kda"], h, cfg)
                    new_cache = None
        elif "conv" in p:
            if tp_sharded or lora is not None:
                raise NotImplementedError(
                    "a gated short convolution runs on one tp shard, without "
                    "lora: a tap reads the positions before its own")
            from megatronapp_tpu.transformer.shortconv import (
                shortconv_forward, shortconv_paged_forward,
            )
            with jax.named_scope("conv"):
                if ssm_state is not None:
                    attn_out, new_cache = shortconv_paged_forward(
                        p["conv"], h, cfg, ssm_state, rows=state_rows,
                        starts=cache_positions, counts=chunk_counts,
                        active=active)
                else:
                    attn_out, _ = shortconv_forward(p["conv"], h, cfg,
                                                    segment_ids=segment_ids)
                    new_cache = None
        else:
            attn_out, new_cache = attend()
        # Tag for the 'selective_attn' remat policy (a no-op otherwise).
        attn_out = checkpoint_name(attn_out, "attn_out")
        if cfg.residual_multiplier != 1.0:
            attn_out = attn_out * cfg.residual_multiplier
        x = residual + attn_out.astype(residual.dtype)
        return x, new_cache

    def ffn_half(x):
        """x + FFN(norm(x)), and what the feed-forward hands back."""
        residual = x
        h = apply_norm(cfg.normalization, x, p["ln2_scale"], p.get("ln2_bias"),
                       cfg.layernorm_epsilon, cfg.norm_unit_offset)
        aux = None
        if "moe" in p:
            if fp8 is not None:
                raise ValueError("fp8 does not support MoE layers "
                                 "(fp8_ineligible_reason gates this off)")
            if lora is not None:
                raise ValueError("lora serving targets the dense fc1/fc2 "
                                 "kernels — MoE layers are unsupported")
            count_rows = None
            if page_table is not None:
                # A paged serving step: its real tokens are the active rows'
                # first chunk_counts positions; moe_forward counts their
                # routing in place of the aux loss.
                b, s = x.shape[:2]
                count_rows = jnp.ones((b, s), bool)
                if active is not None:
                    count_rows &= active[:, None]
                if chunk_counts is not None:
                    count_rows &= (jnp.arange(s)[None, :]
                                   < chunk_counts[:, None])
            with jax.named_scope("moe"):
                mlp_out, aux = moe_forward(p["moe"], h, cfg, layer_id=layer_id,
                                           ctx=ctx, tp_sharded=tp_sharded,
                                           count_rows=count_rows,
                                           train_counts=moe_counts)
        if "mlp" in p:
            if "moe" in p:
                # The shortcut: the MoE's output skips the rest of the layer.
                aux = (aux, mlp_out)
            with jax.named_scope("mlp"):
                mlp_out = mlp_forward(p["mlp"], h, cfg, layer_id=layer_id,
                                      ctx=ctx, tp_sharded=tp_sharded,
                                      fp8=None if fp8 is None else fp8["mlp"],
                                      lora=lora)
        if cfg.residual_multiplier != 1.0:
            mlp_out = mlp_out * cfg.residual_multiplier
        x = residual + mlp_out.astype(residual.dtype)
        return x, aux

    # A layer may be ONE sublayer (cfg.stack_plan): it holds one half, and
    # runs that.
    new_cache = aux = None
    if "ln1_scale" in p:
        x, new_cache = mixer_half(x)
    if "ln2_scale" in p:
        x, aux = ffn_half(x)
    # MegaScope 'system' perturbation + capture site between layers
    # (transformer_block.py:542-544).
    from megatronapp_tpu.scope.disturbance import get_disturbance
    x = get_disturbance().apply("system", x, layer_id)
    x = scope_capture("between_layers", x, layer_id)
    return (x, new_cache), aux


_SAVE_MATMULS = jax.checkpoint_policies.save_from_both_policies(
    jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
    jax.checkpoint_policies.save_only_these_names(RANK_DENSE_OUT,
                                                  EXPERT_GEMM_OUT))


def _remat_wrap(fn, policy: str):
    if policy == "full":
        return jax.checkpoint(fn, policy=jax.checkpoint_policies.nothing_saveable)
    if policy == "selective":
        # Save matmul outputs, recompute the rest (attention softmax etc.) —
        # semantics of the reference --recompute-activations selective mode.
        # A product batched over data-parallel ranks (ops/per_rank.py) and
        # an MoE layer's grouped products (`lax.ragged_dot`, which the dot
        # policy does not know) are such outputs by name.
        return jax.checkpoint(fn, policy=_SAVE_MATMULS)
    if policy == "selective_attn":
        # Selective + the tagged attention outputs: skips the flash-kernel
        # forward recompute in the backward pass for one [B,S,H] bf16
        # residual per layer (~6 MB/layer at GPT-2 125M shapes) — trades a
        # little HBM for the kernel re-execution.
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.save_from_both_policies(
                _SAVE_MATMULS,
                jax.checkpoint_policies.save_only_these_names("attn_out")))
    return fn


from megatronapp_tpu.parallel.sharding import is_logical_axes as _is_axes


def _stack_layers(per_layer, extra_axis: str = "layers"):
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs),
                           *[p for p, _ in per_layer])
    ax = jax.tree.map(lambda axes: (extra_axis,) + axes, per_layer[0][1],
                      is_leaf=_is_axes)
    return stacked, ax


def _vmapped_layers(keys, init):
    """init(key) -> (params, axes) for every key, as ONE vmapped program:
    the same numbers as a Python loop of per-layer initialisers and a
    stack, without a copy of the initialiser a layer in the program (87 s
    of a 3B model's first compile, PERF.md, PR 32)."""
    axes = []

    def one(key):
        p, ax = init(key)
        axes.append(ax)
        return p

    params = jax.vmap(one)(keys)
    return params, jax.tree.map(lambda a: ("layers",) + a, axes[0],
                                is_leaf=_is_axes)


# How the halves under each key of cfg.stack_plan start: init(rng, cfg=,
# out_std=) -> (params, axes).
HALF_INITS = {
    **{stack: functools.partial(_init_mixer_half, stack=stack)
       for stack in ("mixers_attn", "mixers_swa", "mixers_conv",
                     "mixers_ssm", "mixers_kda")},
    "ffn": _init_ffn_half,
    "ffn_lead": functools.partial(_init_ffn_half, force_dense=True),
    "ffn_dense": functools.partial(_init_ffn_half, force_dense=True),
}


def init_hybrid_block_params(rng, cfg: TransformerConfig):
    """A stack of several kinds of layer (cfg.stack_plan): the halves that
    the plan names alike are stacked under that name, in layer order
    ("mixers_attn" [num_attention_layers, ...], "ffn" a row each layer that
    holds one, ...; layer_loop walks them). A mixer half is {"ln1_scale",
    its mixer}, a feed-forward half {"ln2_scale", "moe" / "mlp"}.

    Layer i draws from split(rng, L)[i]: split once more into (mixer,
    feed-forward) where a layer holds two halves, as it is where it is one
    sublayer. The residual-out projections start at std / sqrt(halves x
    depth): a layer adds to the stream once a half (single sublayers: HF
    `nemotron_h` rescale_prenorm_residual)."""
    plan = cfg.stack_plan
    halves = len(plan[0])
    out_std = cfg.init_method_std / jnp.sqrt(float(halves) * _init_depth(cfg))
    keys = jax.random.split(rng, cfg.num_layers)
    if halves > 1:
        keys = jax.vmap(jax.random.split)(keys)         # [L, (mixer, ffn)]
    done = {}
    for stack in sorted({stack for layer in plan for stack in layer}):
        rows = np.asarray([i for i, layer in enumerate(plan)
                           if stack in layer])
        half = plan[rows[0]].index(stack)       # which of a layer's keys
        done[stack] = _vmapped_layers(
            keys[rows] if halves == 1 else keys[rows, half],
            functools.partial(HALF_INITS[stack], cfg=cfg, out_std=out_std))
    return ({k: v[0] for k, v in done.items()},
            {k: v[1] for k, v in done.items()})


def tandem_runs(layers):
    """[(unit, repeats)] that spell `layers` (a string of letters, a tuple
    of cfg.stack_plan's entries): from the left, the repeated unit that
    covers the most of them (the shortest such), or where nothing repeats
    the next one alone, once. "MEMEM*EMEMEM*" is ("ME", 2), "M", "*",
    ("EM", 3), "*"."""
    runs, at, n = [], 0, len(layers)
    while at < n:
        unit, reps = 1, 1
        for u in range(1, (n - at) // 2 + 1):
            r = 1
            while layers[at + r * u:at + (r + 1) * u] == layers[at:at + u]:
                r += 1
            if r > 1 and u * r > unit * reps:
                unit, reps = u, r
        runs.append((layers[at:at + unit], reps))
        at += unit * reps
    return runs


def layer_loop(cfg: TransformerConfig, carry, run, scan_runs: bool = True):
    """Walk a stack of several kinds of layer (cfg.stack_plan) in layer
    order as SCANNED runs of its repeating units (tandem_runs; a unit's own
    repeats are scanned inside it), the layers between them one after the
    other: nothing is unrolled a layer, no stack is copied, and a layer
    reads the stacks of its own halves alone.

    run(carry, layer, rows, layer_id) -> carry runs one layer: `layer`
    (static) is its entry of the plan, rows[key] its row of the stack under
    each key it names (the number of earlier layers that name it: an
    attention layer's plane of the KV pools, a state-space layer's of the
    state pools), layer_id its index in the model; Python ints outside a
    scan, int32 scalars inside one.

    scan_runs: False keeps the scan over an outermost unit of several
    layers and writes every other repeat out: the runs inside the unit and
    a run of one kind wherever it lies. block_forward, which is
    differentiated, does. Measured on the sliding-window MoE training cell
    (PERF.md, PR 48): its step compiled for a described v5e holds 10.49 GiB
    with its run of three written out, 14.00 with the run scanned and 13.69
    scanned with the run's rows handed to the scan as its xs in place of the
    whole stacks (under a gradient a scan keeps its own cotangent
    accumulator for every stack its body reads and the residuals of all its
    turns); on the chip the written-out step trains 24,441 tokens a second
    and the scanned one 23,380. The serving steps keep nothing for a
    backward pass and scan every run of two or more: a launch a run.

    An MoE model's leading dense layers (the entries that hold "ffn_lead":
    another body) are never folded into a run: they run first, one after
    the other, and the units are found from the first layer behind them."""
    plan = cfg.stack_plan

    def held(layers):
        return collections.Counter(k for layer in layers for k in layer)

    def walk(carry, layers, seen, lid, outermost=False):
        # seen: {key: layers that name it before `layers`}; lid: layers
        # before them
        for unit, reps in tandem_runs(layers):
            per = held(unit)

            def after(j, per=per, seen=seen):
                """`seen` behind j turns of the unit."""
                return {**seen, **{k: seen.get(k, 0) + j * n
                                   for k, n in per.items()}}

            def turn(c, j, unit=unit, after=after, lid=lid):
                return walk(c, unit, after(j), lid + j * len(unit))

            if reps == 1:           # one layer
                layer, = unit
                carry = run(carry, layer,
                            {k: seen.get(k, 0) for k in layer}, lid)
            elif scan_runs or (outermost and len(unit) > 1):
                carry = jax.lax.scan(
                    lambda c, j, turn=turn: (turn(c, j), None), carry,
                    jnp.arange(reps, dtype=jnp.int32))[0]
            else:
                for j in range(reps):
                    carry = turn(carry, j)
            seen, lid = after(reps), lid + reps * len(unit)
        return carry

    lead = sum("ffn_lead" in layer for layer in plan)
    for at in range(lead):
        carry = walk(carry, plan[at:at + 1], held(plan[:at]), at)
    return walk(carry, plan[lead:], held(plan[:lead]), lead, outermost=True)


def layer_params(stacks, layer, rows):
    """One layer's params out of cfg.stack_plan's stacks: row rows[key] of
    the stack under each key its entry `layer` names, the halves merged
    (layer_forward runs the halves a layer holds). The feed-forward's rows
    are cut first: the order in which the stacks enter a scanned run as its
    operands, which the steps' pinned texts hold."""
    layer_p = {}
    for key in reversed(layer):
        layer_p.update(jax.tree.map(
            lambda a, row=rows[key]: jax.lax.dynamic_index_in_dim(
                a, row, 0, keepdims=False), stacks[key]))
    return layer_p


def init_block_params(rng, cfg: TransformerConfig, num_layers: int = None,
                      force_dense: bool = False):
    """Stacked layer params for lax.scan.

    force_dense: `num_layers` dense-MLP layers of an MoE model (its leading
    dense layers, cfg.moe_first_k_dense), stacked like a uniform block.

    Uniform case: every leaf gains a leading [L] 'layers' axis.
    moe_layer_freq > 1 (reference transformer_config moe_layer_freq int
    pattern — layer i is MoE iff i % freq == 0): layers are grouped into
    L/freq scan units of {1 MoE layer + (freq-1) dense layers}, stacked as
    {'moe': [G,...], 'dense': [G, freq-1, ...]} so the scan body stays
    uniform (TPU-first: one compiled group body).
    """
    n = num_layers or cfg.num_layers
    if getattr(cfg, "hetero_block_specs", None):
        from megatronapp_tpu.transformer.heterogeneous import (
            init_hetero_block_params,
        )
        return init_hetero_block_params(rng, cfg)
    if cfg.hybrid_stack:
        return init_hybrid_block_params(rng, cfg)
    freq = cfg.moe_layer_freq if cfg.is_moe else 1
    if cfg.moe_shortcut_double_layer:
        return _vmapped_layers(
            jax.random.split(rng, n),
            functools.partial(init_layer_params, cfg=cfg))
    if freq == 1 or force_dense:
        keys = jax.random.split(rng, n)
        return _stack_layers([init_layer_params(k, cfg, force_dense)
                              for k in keys])

    if n % freq != 0:
        raise ValueError(f"num_layers={n} not divisible by "
                         f"moe_layer_freq={freq}")
    groups = n // freq
    keys = jax.random.split(rng, n)
    moe_layers, dense_groups = [], []
    for g in range(groups):
        moe_layers.append(init_layer_params(keys[g * freq], cfg))
        dense = [init_layer_params(keys[g * freq + 1 + j], cfg,
                                   force_dense=True)
                 for j in range(freq - 1)]
        dense_groups.append(_stack_layers(dense, extra_axis="stage_layers"))
    moe_p, moe_ax = _stack_layers(moe_layers)
    dense_p, dense_ax = _stack_layers(dense_groups, extra_axis="layers")
    return ({"moe": moe_p, "dense": dense_p},
            {"moe": moe_ax, "dense": dense_ax})


def block_forward(stacked_p, x: jnp.ndarray, cfg: TransformerConfig,
                  rope_cos=None, rope_sin=None, attention_mask=None,
                  layer_offset: int = 0, ctx=None, zigzag: bool = False,
                  segment_ids=None, tp_sharded: bool = False, fp8=None,
                  window_rope=None, moe_counts: bool = False):
    """Run all stacked layers via lax.scan. Returns (x, moe_aux_sum).

    window_rope: the window layers' (cos, sin) of a sliding-window stack.

    moe_counts: return (x, moe_aux_sum, counts) with the layers' routing
    counts summed (int32, moe.TRAIN_COUNTS' order): a hybrid stack whose
    layers count their held experts' load (cfg.moe_counts_load) alone.

    tp_sharded: thread the ambient-manual tp-sharded stage-body path
    through every layer (pp pipeline; see layer_forward).

    fp8: layer-stacked delayed-scaling amax state (training/fp8.py,
    leaves [L, n_tensors, H]) — rides the SAME layer scan as the
    stacked params, so each layer's ring GEMMs see their own history
    slice and the scan's xs-cotangent stacks the updated histories
    back to [L, ...] for the train step."""
    if fp8 is not None and (getattr(cfg, "hetero_block_specs", None)
                            or (isinstance(stacked_p, dict)
                                and "dense" in stacked_p)):
        raise ValueError("fp8 does not support heterogeneous / "
                         "MoE-interleaved layer stacks "
                         "(fp8_ineligible_reason gates this off)")
    if getattr(cfg, "hetero_block_specs", None):
        if segment_ids is not None or zigzag:
            raise NotImplementedError(
                "heterogeneous per-layer configs do not compose with "
                "packed sequences or zigzag CP yet")
        from megatronapp_tpu.transformer.heterogeneous import (
            hetero_block_forward,
        )
        return hetero_block_forward(
            stacked_p, x, cfg, rope_cos, rope_sin, attention_mask,
            layer_offset=layer_offset, ctx=ctx)
    if cfg.hybrid_stack:
        if fp8 is not None or tp_sharded or zigzag:
            raise NotImplementedError(
                "a hybrid state-space stack trains on the plain path: no "
                "fp8, tp-sharded stage body or zigzag cp")
        if cfg.is_moe and ctx is not None and max(
                ctx.tp, ctx.ep, ctx.cp, ctx.pp) > 1:
            raise NotImplementedError(
                "a hybrid stack with MoE feed-forwards runs whole "
                "sequences on one device or data-parallel: its experts' "
                "ep all-to-all, and tp, cp and pp layouts of its layer "
                "loop, are not written yet (ROADMAP M4)")

        from megatronapp_tpu.transformer.moe import TRAIN_COUNTS

        def one_layer(stacks, carry, rows, lid, layer):
            # The layer's rows are cut out of the stacks INSIDE the
            # recomputed body: cut outside it they are its inputs, and the
            # scans would keep every layer's row for the backward pass, a
            # second copy of the block's parameters.
            # The router's loss and the routing counts ride the scans'
            # carry beside the stream, in both passes and under
            # recomputation (the counts are integers: no cotangent).
            h, aux_sum, counts = carry
            (h2, _), aux = layer_forward(
                layer_params(stacks, layer, rows), h, cfg,
                rope_cos, rope_sin, attention_mask,
                layer_id=lid + layer_offset, ctx=ctx,
                segment_ids=segment_ids,
                window_rope=window_rope if "mixers_swa" in layer else None,
                moe_counts=moe_counts)
            if aux is not None:         # a dense feed-forward has none
                if moe_counts:
                    aux, layer_counts = aux
                    counts = counts + layer_counts
                aux_sum = aux_sum + aux
            return h2, aux_sum, counts

        # a body a kind of layer: which one a layer is, is static
        bodies = {layer: _remat_wrap(
            functools.partial(one_layer, layer=layer), cfg.remat_policy)
            for layer in set(cfg.stack_plan)}
        x, aux, counts = layer_loop(
            cfg, (x, jnp.zeros((), jnp.float32),
                  jnp.zeros((len(TRAIN_COUNTS),), jnp.int32)),
            lambda c, layer, rows, lid: bodies[layer](
                stacked_p, c, rows, lid), scan_runs=False)
        return (x, aux, counts) if moe_counts else (x, aux)
    if moe_counts:
        raise NotImplementedError(
            "routing counts ride the hybrid layer loop's carry alone")
    hetero = isinstance(stacked_p, dict) and "dense" in stacked_p

    def run_layer(layer_p, h, lid, fp8_l=None):
        (h2, _), aux = layer_forward(
            layer_p, h, cfg, rope_cos, rope_sin, attention_mask,
            layer_id=lid, ctx=ctx, zigzag=zigzag,
            segment_ids=segment_ids, tp_sharded=tp_sharded, fp8=fp8_l)
        return h2, (aux if aux is not None
                    else jnp.zeros((), jnp.float32))

    if not hetero:
        def body(carry, layer_in):
            h, lid = carry
            if fp8 is not None:
                layer_p, fp8_l = layer_in
            else:
                layer_p, fp8_l = layer_in, None
            h2, aux = run_layer(layer_p, h, lid, fp8_l)
            return (h2, lid + 1), aux

        body = _remat_wrap(body, cfg.remat_policy)
        xs = stacked_p if fp8 is None else (stacked_p, fp8)
        (x, _), aux = jax.lax.scan(
            body, (x, jnp.int32(layer_offset)), xs,
            unroll=cfg.scan_unroll)
        return x, jnp.sum(aux)

    freq = cfg.moe_layer_freq

    def group_body(carry, group_p):
        h, lid = carry
        h, aux_moe = run_layer(group_p["moe"], h, lid)

        def dense_body(inner, layer_p):
            hh, l = inner
            hh, a = run_layer(layer_p, hh, l)
            return (hh, l + 1), a

        (h, _), aux_dense = jax.lax.scan(
            dense_body, (h, lid + 1), group_p["dense"],
            unroll=cfg.scan_unroll)
        return (h, lid + freq), aux_moe + jnp.sum(aux_dense)

    group_body = _remat_wrap(group_body, cfg.remat_policy)
    (x, _), aux = jax.lax.scan(
        group_body, (x, jnp.int32(layer_offset)), stacked_p,
        unroll=cfg.scan_unroll)
    return x, jnp.sum(aux)
