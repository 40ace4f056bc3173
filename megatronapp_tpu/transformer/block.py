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


def _init_mixer_half(rng, cfg: TransformerConfig, out_std, ssm: bool = False):
    """A layer's first half: its norm and its mixer (attention, MLA, or with
    `ssm` a hybrid stack's other kind: a selective-state-space mixer, with
    cfg.shortconv_kernel a gated short convolution, with cfg.sliding_window
    a sliding-window attention layer of cfg.window_heads query heads)."""
    if ssm and cfg.sliding_window:
        name = "attention"
        mix_p, mix_ax = init_attention_params(rng, cfg, out_std,
                                              heads=cfg.window_heads)
    elif ssm and cfg.shortconv_kernel:
        from megatronapp_tpu.transformer.shortconv import (
            init_shortconv_params,
        )
        name = "conv"
        mix_p, mix_ax = init_shortconv_params(rng, cfg, out_std)
    elif ssm:
        from megatronapp_tpu.transformer.ssm import init_ssm_params, ssm_dims
        name = "ssm"
        mix_p, mix_ax = init_ssm_params(rng, cfg, ssm_dims(cfg), out_std)
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
    selective-state-space mixer (transformer/ssm.py) as its first half.
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

    # A layer of a pattern stack (cfg.layer_pattern) is ONE sublayer: it
    # holds one half, and runs that.
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


def init_hybrid_block_params(rng, cfg: TransformerConfig):
    """A hybrid stack (cfg.attn_layer_period): the state-space layers'
    first halves stacked [num_ssm_layers, ...] under "mixers_ssm" (gated
    short convolutions: [num_conv_layers, ...] under "mixers_conv"), the
    sliding-window attention layers: [num_window_layers, ...] under
    "mixers_swa"), the
    attention layers' [num_attention_layers, ...] under "mixers_attn", and
    the layers' feed-forward halves under "ffn", each in layer order
    (hybrid_layer_loop walks them): every layer's [num_layers, ...], or in
    an MoE model with cfg.moe_first_k_dense the MoE layers'
    [num_layers - k, ...], the k leading layers' dense halves being
    "ffn_lead" [k, ...]."""
    if cfg.layer_pattern is not None:
        return _init_pattern_block_params(rng, cfg)
    out_std = cfg.init_method_std / jnp.sqrt(2.0 * _init_depth(cfg))
    keys = jax.vmap(jax.random.split)(
        jax.random.split(rng, cfg.num_layers))        # [L, (mixer, ffn)]
    attends = np.asarray([cfg.layer_is_attention(i)
                          for i in range(cfg.num_layers)])
    lead = cfg.moe_first_k_dense

    kinds = {
        "mixers_swa" if cfg.sliding_window else
        "mixers_conv" if cfg.shortconv_kernel else "mixers_ssm": (
            keys[~attends, 0], functools.partial(
                _init_mixer_half, cfg=cfg, out_std=out_std, ssm=True)),
        "mixers_attn": (keys[attends, 0], functools.partial(
            _init_mixer_half, cfg=cfg, out_std=out_std, ssm=False)),
        "ffn": (keys[lead:, 1], functools.partial(
            _init_ffn_half, cfg=cfg, out_std=out_std)),
        "ffn_lead": (keys[:lead, 1], functools.partial(
            _init_ffn_half, cfg=cfg, out_std=out_std, force_dense=True)),
    }
    done = {k: _vmapped_layers(*v) for k, v in kinds.items() if len(v[0])}
    return ({k: v[0] for k, v in done.items()},
            {k: v[1] for k, v in done.items()})


# Where each kind of a pattern stack's layers is stacked, in layer order
# (cfg.layer_pattern's letters): the keys a period's stack uses for the same
# halves, so that what reads a hybrid block by key reads this one.
PATTERN_STACKS = {"M": "mixers_ssm", "*": "mixers_attn", "E": "ffn",
                  "-": "ffn_dense"}


def _init_pattern_block_params(rng, cfg: TransformerConfig):
    """A pattern stack (cfg.layer_pattern): every layer is ONE sublayer
    behind its norm, and the layers of a kind are stacked in layer order
    under PATTERN_STACKS[kind]: "M" and "*" hold {"ln1_scale", mixer}, "E"
    and "-" {"ln2_scale", "moe" / "mlp"}. A layer adds to the stream once,
    so its residual-out projection starts at std / sqrt(depth) (HF
    `nemotron_h` rescale_prenorm_residual)."""
    out_std = cfg.init_method_std / jnp.sqrt(1.0 * _init_depth(cfg))
    keys = jax.random.split(rng, cfg.num_layers)
    letters = np.asarray(list(cfg.layer_pattern))
    inits = {
        "M": functools.partial(_init_mixer_half, cfg=cfg, out_std=out_std,
                               ssm=True),
        "*": functools.partial(_init_mixer_half, cfg=cfg, out_std=out_std),
        "E": functools.partial(_init_ffn_half, cfg=cfg, out_std=out_std),
        "-": functools.partial(_init_ffn_half, cfg=cfg, out_std=out_std,
                               force_dense=True),
    }
    done = {PATTERN_STACKS[kind]: _vmapped_layers(keys[letters == kind], init)
            for kind, init in inits.items() if kind in cfg.layer_pattern}
    return ({k: v[0] for k, v in done.items()},
            {k: v[1] for k, v in done.items()})


def tandem_runs(pattern: str):
    """[(unit, repeats)] that spell `pattern`: from the left, the repeated
    unit that covers the most letters (the shortest such), or where nothing
    repeats the next letter alone, once. "MEMEM*EMEMEM*" is ("ME", 2), "M",
    "*", ("EM", 3), "*"."""
    runs, at, n = [], 0, len(pattern)
    while at < n:
        unit, reps = 1, 1
        for u in range(1, (n - at) // 2 + 1):
            r = 1
            while pattern.startswith(pattern[at:at + u], at + r * u):
                r += 1
            if r > 1 and u * r > unit * reps:
                unit, reps = u, r
        runs.append((pattern[at:at + unit], reps))
        at += unit * reps
    return runs


def pattern_layer_loop(cfg: TransformerConfig, carry, run,
                       scan_runs: bool = True):
    """Walk a pattern stack (cfg.layer_pattern) in layer order as SCANNED
    runs of its repeating units (tandem_runs; a unit's own repeats are
    scanned inside it), the letters between them one after the other:
    nothing is unrolled a layer, no stack is copied, and a layer reads the
    stack of its own kind alone.

    run(carry, kind, k, layer_id) -> carry runs one layer: `kind` (static)
    is its letter, k its index among the layers of its kind (its row of
    PATTERN_STACKS[kind]; an attention layer's plane of the KV pools, a
    state-space layer's of the state pools), layer_id its index in the
    model; both Python ints outside a scan, int32 scalars inside one.

    scan_runs: False writes the runs out (block_forward, which is
    differentiated: hybrid_layer_loop says what a scanned run costs
    there)."""
    def walk(carry, pattern, seen, lid):
        # seen: {kind: layers of it before `pattern`}; lid: layers before it
        for unit, reps in tandem_runs(pattern):
            per = {kind: unit.count(kind) for kind in set(unit)}

            def after(j, per=per, seen=seen):
                """`seen` behind j turns of the unit."""
                return {**seen, **{k: seen.get(k, 0) + j * n
                                   for k, n in per.items()}}

            def turn(c, j, unit=unit, after=after, lid=lid):
                return walk(c, unit, after(j), lid + j * len(unit))

            if reps == 1:           # one letter
                carry = run(carry, unit, seen.get(unit, 0), lid)
            elif scan_runs:
                carry = jax.lax.scan(
                    lambda c, j, turn=turn: (turn(c, j), None), carry,
                    jnp.arange(reps, dtype=jnp.int32))[0]
            else:
                for j in range(reps):
                    carry = turn(carry, j)
            seen, lid = after(reps), lid + reps * len(unit)
        return carry

    return walk(carry, cfg.layer_pattern, {}, 0)


def pattern_layer_params(stacked_p, kind: str, k):
    """One layer's params out of a pattern stack: row k of its kind's."""
    return jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, k, 0, keepdims=False),
        stacked_p[PATTERN_STACKS[kind]])


def hybrid_layer_loop(cfg: TransformerConfig, carry, run,
                      scan_runs: bool = True):
    """Walk a hybrid stack in layer order as SCANNED runs, not unrolled
    and without carrying both kinds' weights through every layer: an outer
    scan over whole periods of (scan `offset` state-space layers, the
    period's one attention layer, scan the rest), then the same over a
    last partial period.

    run(carry, attends, k, layer_id) → carry runs one layer: `attends`
    (static) says which kind, k is its index among the layers of its kind
    (its row of "mixers_attn"/"mixers_ssm"/"mixers_conv", and for an
    attention layer its plane of the KV pools), layer_id its index in the
    model (its row of "ffn"); both are int32 scalars, traced inside the
    scans.

    scan_runs: False writes a period's runs out layer by layer and leaves
    the scan over periods the only one. block_forward, which is
    differentiated, does. Measured on the sliding-window MoE training cell
    (PERF.md, PR 48): its step compiled for a described v5e holds 10.49 GiB
    with its run of three written out, 14.00 with the run scanned and 13.69
    scanned with the run's rows handed to the scan as its xs in place of the
    whole stacks (under a gradient a scan keeps its own cotangent
    accumulator for every stack its body reads and the residuals of all its
    turns); on the chip the written-out step trains 24,441 tokens a second
    and the scanned one 23,380. The serving steps keep nothing for a
    backward pass and scan every run of two or more: a launch a run.

    An MoE model's cfg.moe_first_k_dense leading layers (dense
    feed-forwards: another body) run first, one after the other, with k
    and layer_id Python ints and `lead=True`; the periods are then counted
    from the first layer behind them."""
    period = cfg.attn_layer_period
    lead = cfg.moe_first_k_dense
    # the kinds' layers among the leading ones, and where the attention
    # layer lies in a period counted from the first layer behind them
    lead_attn = sum(cfg.layer_is_attention(i) for i in range(lead))
    lead_rec = lead - lead_attn
    offset = (cfg.attn_layer_offset - lead) % period
    for i in range(lead):
        attends = cfg.layer_is_attention(i)
        k = sum(cfg.layer_is_attention(j) == attends for j in range(i))
        carry = run(carry, attends, k, i, lead=True)

    def ssm_run(carry, k0, lid0, count):
        if count <= 0:
            return carry
        if count == 1:
            return run(carry, False, k0, lid0)
        if not scan_runs:
            for j in range(count):
                carry = run(carry, False, k0 + j, lid0 + j)
            return carry
        return jax.lax.scan(
            lambda c, j: (run(c, False, k0 + j, lid0 + j), None), carry,
            jnp.arange(count, dtype=jnp.int32))[0]

    def part_period(carry, p, count):
        """The first `count` (static) layers of period p."""
        lid0, k0, ka = p * period, p * (period - 1), p
        if lead:
            lid0, k0, ka = lid0 + lead, k0 + lead_rec, ka + lead_attn
        carry = ssm_run(carry, k0, lid0, min(count, offset))
        if count > offset:
            carry = run(carry, True, ka, lid0 + offset)
            carry = ssm_run(carry, k0 + offset, lid0 + offset + 1,
                            count - offset - 1)
        return carry

    whole, rest = divmod(cfg.num_layers - lead, period)
    if whole == 1:
        carry = part_period(carry, jnp.int32(0), period)
    elif whole:
        carry = jax.lax.scan(
            lambda c, p: (part_period(c, p, period), None), carry,
            jnp.arange(whole, dtype=jnp.int32))[0]
    if rest:
        carry = part_period(carry, jnp.int32(whole), rest)
    return carry


def hybrid_layer_params(stacked_p, attends: bool, k, layer_id,
                        lead: bool = False):
    """One layer's params out of a hybrid stack: row k of its kind's first
    halves and its feed-forward half: row layer_id of "ffn_lead" for a
    leading dense layer (`lead`), else of "ffn", which in a stack with
    leading dense layers starts behind them."""
    def row(stack, i):
        return jax.tree.map(
            lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False),
            stack)
    kind = "mixers_attn" if attends else next(
        k for k in ("mixers_ssm", "mixers_conv", "mixers_swa")
        if k in stacked_p)
    if lead:
        ffn = row(stacked_p["ffn_lead"], layer_id)
    elif "ffn_lead" in stacked_p:
        ffn = row(stacked_p["ffn"], layer_id - jax.tree.leaves(
            stacked_p["ffn_lead"])[0].shape[0])
    else:
        ffn = row(stacked_p["ffn"], layer_id)
    return {**row(stacked_p[kind], k), **ffn}


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

        def one_layer(stacks, carry, k, lid, attends, lead):
            # The layer's rows are cut out of the stacks INSIDE the
            # recomputed body: cut outside it they are its inputs, and the
            # scans would keep every layer's row for the backward pass, a
            # second copy of the block's parameters.
            # The router's loss and the routing counts ride the scans'
            # carry beside the stream, in both passes and under
            # recomputation (the counts are integers: no cotangent).
            h, aux_sum, counts = carry
            windowed = bool(cfg.sliding_window) and not attends
            # a pattern stack's `attends` is the layer's letter
            layer_p = (pattern_layer_params(stacks, attends, k)
                       if cfg.layer_pattern is not None else
                       hybrid_layer_params(stacks, attends, k, lid, lead))
            (h2, _), aux = layer_forward(
                layer_p, h, cfg,
                rope_cos, rope_sin, attention_mask,
                layer_id=lid + layer_offset, ctx=ctx,
                segment_ids=segment_ids,
                window_rope=window_rope if windowed else None,
                moe_counts=moe_counts)
            if aux is not None:         # a leading dense layer has none
                if moe_counts:
                    aux, layer_counts = aux
                    counts = counts + layer_counts
                aux_sum = aux_sum + aux
            return h2, aux_sum, counts

        # a body a kind of layer: which one a layer is, is static
        pattern = cfg.layer_pattern is not None
        bodies = {(a, ld): _remat_wrap(
            functools.partial(one_layer, attends=a, lead=ld),
            cfg.remat_policy)
            for a in (set(cfg.layer_pattern) if pattern else (False, True))
            for ld in (False, True)}
        x, aux, counts = (pattern_layer_loop if pattern
                          else hybrid_layer_loop)(
            cfg, (x, jnp.zeros((), jnp.float32),
                  jnp.zeros((len(TRAIN_COUNTS),), jnp.int32)),
            lambda c, attends, k, lid, lead=False: bodies[attends, lead](
                stacked_p, c, k, lid), scan_runs=False)
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
