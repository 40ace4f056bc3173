"""MLP sublayer (dense FFN, gated variants).

Parity with /root/reference/megatron/core/transformer/mlp.py:32 (MLP with
ColumnParallelLinear fc1 → activation → RowParallelLinear fc2). TP falls out
of the 'mlp' logical axis; gated activations fuse gate+value into one fc1
matmul exactly like the reference's ``gated_linear_unit`` path.

Param leaf layout:
  fc1_kernel [H, F] or [H, 2F] (gated)   logical ('embed','mlp')
  fc1_bias   [F] / [2F]                  logical ('mlp',)
  fc2_kernel [F, H]                      logical ('mlp','embed')
  fc2_bias   [H]                         logical ('embed',)
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from megatronapp_tpu.config.transformer_config import TransformerConfig
from megatronapp_tpu.ops.activations import apply_activation, is_gated
from megatronapp_tpu.ops.per_rank import dense
from megatronapp_tpu.scope.hooks import scope_capture


def init_mlp_params(rng, cfg: TransformerConfig, out_std: float,
                    ffn_hidden: int = None):
    h = cfg.hidden_size
    f = ffn_hidden or cfg.ffn_hidden_size
    k1, k2 = jax.random.split(rng)
    std = cfg.init_method_std
    fc1_out = 2 * f if is_gated(cfg.activation) else f
    p = {
        "fc1_kernel": jax.random.normal(k1, (h, fc1_out), cfg.params_dtype) * std,
        "fc2_kernel": jax.random.normal(k2, (f, h), cfg.params_dtype) * out_std,
    }
    ax = {"fc1_kernel": ("embed", "mlp"), "fc2_kernel": ("mlp", "embed")}
    if cfg.add_bias_linear:
        p["fc1_bias"] = jnp.zeros((fc1_out,), cfg.params_dtype)
        p["fc2_bias"] = jnp.zeros((h,), cfg.params_dtype)
        ax["fc1_bias"] = ("mlp",)
        ax["fc2_bias"] = ("embed",)
    return p, ax


def mlp_forward(p, x: jnp.ndarray, cfg: TransformerConfig, layer_id=None,
                ctx=None, tp_sharded: bool = False, fp8=None, lora=None):
    """fp8: this layer's delayed-scaling state for the fc1/fc2 ring
    sites ({"fc1": {hist, sat}, "fc2": ...} — training/fp8.py). Only
    legal when the tp-overlap rings actually run (fp8_ineligible_reason
    gates callers); raising here instead of silently ignoring keeps the
    amax history from rotting."""
    from megatronapp_tpu.scope.disturbance import get_disturbance
    from megatronapp_tpu.parallel.overlap import (
        all_gather_matmul, matmul_reduce_scatter, tp_overlap_eligible,
    )
    if tp_sharded:
        if lora is not None:
            raise ValueError(
                "lora deltas are not composable with the tp-sharded "
                "stage body — serving paths only")
        if fp8 is not None:
            raise ValueError(
                "fp8 is not supported on the tp-sharded pipeline stage "
                "body (ambient-manual rings keep bf16) — "
                "fp8_ineligible_reason gates this off")
        # Ambient-manual tp-sharded stage body (pp pipeline): x is this
        # shard's [b, S/tp, H] seq chunk; fc1 runs as a ring all-gather-
        # matmul on a local column slice, fc2 as a matmul-reduce-scatter
        # on the matching row slice (parallel/overlap.py *_manual).
        return _mlp_forward_tp_sharded(p, x, cfg, layer_id, ctx)
    _dist = get_disturbance()
    # Serving-resident int8 weights dequantize at matmul entry
    # (inference/quantization.py resolve_param — a no-op on plain
    # arrays).
    from megatronapp_tpu.inference.quantization import resolve_param
    fc1_res = resolve_param(p["fc1_kernel"])
    fc2_res = resolve_param(p["fc2_kernel"])
    # Latency-hiding tp path (--tp-comm-overlap): fc1 column-parallel via
    # ring all-gather-matmul, fc2 row-parallel via matmul-reduce-scatter.
    # One eligibility decision covers the pair (both weight dims must
    # shard evenly) so the intermediate layout stays consistent.
    overlap = tp_overlap_eligible(cfg, ctx, fc1_res.shape[1],
                                  fc2_res.shape[0],
                                  batch=x.shape[0])
    if fp8 is not None and not overlap:
        raise ValueError(
            "fp8 state passed but the tp-overlap rings are not "
            "eligible here (tp_overlap_eligible is False) — the fp8 "
            "GEMMs live inside the ring bodies; check "
            "fp8_ineligible_reason at wiring time")
    margin = int(getattr(cfg, "fp8_margin", 0))
    # Batched-LoRA serving (inference/lora.py): per-row deltas compose
    # with the plain matmuls only, not the ring-decomposed overlap path.
    if lora is not None and overlap:
        raise ValueError(
            "lora deltas are not composable with the tp-overlap rings "
            "— serving paths only")
    x = x.astype(cfg.compute_dtype)
    fc1_kernel = _dist.apply("weight", fc1_res, layer_id)
    fc1_kernel = fc1_kernel.astype(cfg.compute_dtype)
    if overlap:
        # manual-ok: overlap gated by tp_overlap_eligible (False inside
        # ambient manual regions; the pipeline takes the tp_sharded path)
        y = all_gather_matmul(x, fc1_kernel, ctx.shard_map_mesh,
                              fp8=None if fp8 is None else fp8["fc1"],
                              fp8_margin=margin)
    else:
        y = dense(x, fc1_kernel)
        if lora is not None:
            from megatronapp_tpu.ops.pallas.kernel_gen import (
                apply_lora_delta)
            y = apply_lora_delta(y, x, lora, "fc1_kernel")
    if "fc1_bias" in p:
        y = y + p["fc1_bias"].astype(cfg.compute_dtype)
    y = scope_capture("mlp1", y, layer_id)
    # MegaScope 'calculation' perturbation site (reference mlp.py).
    from megatronapp_tpu.scope.disturbance import get_disturbance
    y = get_disturbance().apply("calculation", y, layer_id)
    if is_gated(cfg.activation):
        gate, val = jnp.split(y, 2, axis=-1)
        y = apply_activation(cfg.activation, val, gate)
    else:
        y = apply_activation(cfg.activation, y)
    fc2_kernel = _dist.apply("weight", fc2_res, layer_id)
    fc2_kernel = fc2_kernel.astype(cfg.compute_dtype)
    if overlap:
        # manual-ok: same tp_overlap_eligible gate as fc1 above
        out = matmul_reduce_scatter(
            y, fc2_kernel, ctx.shard_map_mesh,
            fp8=None if fp8 is None else fp8["fc2"], fp8_margin=margin)
    else:
        out = dense(y, fc2_kernel)
        if lora is not None:
            from megatronapp_tpu.ops.pallas.kernel_gen import (
                apply_lora_delta)
            out = apply_lora_delta(out, y, lora, "fc2_kernel")
    if "fc2_bias" in p:
        out = out + p["fc2_bias"].astype(cfg.compute_dtype)
    out = scope_capture("mlp2", out, layer_id)
    return out


def _mlp_forward_tp_sharded(p, x: jnp.ndarray, cfg: TransformerConfig,
                            layer_id, ctx):
    """MLP with a tp-SHARDED residual stream inside an ambient full-manual
    region (the pp pipeline stage body).

    Weights enter replicated (pipeline in_specs mention only pp) and each
    tp shard slices its column/row block locally — the slice transpose
    scatters the local wgrad into a zero full-size cotangent, which the
    enclosing shard_map's transpose psums across tp into the full grad
    (pipeline.py grad-axes bookkeeping). Gated activations shard the gate
    and value halves SEPARATELY so each shard owns matching (gate, value)
    column pairs — a contiguous slice of the packed [gate | value] fc1
    would hand shard 0 only gate columns."""
    from jax import lax
    from megatronapp_tpu.config.parallel_config import TP_AXIS
    from megatronapp_tpu.parallel.overlap import (
        all_gather_matmul_manual, matmul_reduce_scatter_manual,
    )
    from megatronapp_tpu.scope.disturbance import get_disturbance
    _dist = get_disturbance()
    tp = ctx.tp
    me = lax.axis_index(TP_AXIS)
    overlap = bool(getattr(cfg, "tp_comm_overlap", False))
    dt = cfg.compute_dtype
    x = x.astype(dt)
    fc1_kernel = _dist.apply("weight", p["fc1_kernel"], layer_id).astype(dt)
    gated = is_gated(cfg.activation)
    f = p["fc2_kernel"].shape[0]
    fl = f // tp

    def colslice(w, start):
        return lax.dynamic_slice_in_dim(w, start, fl, axis=1)

    if gated:
        wg = colslice(fc1_kernel, me * fl)
        wv = colslice(fc1_kernel, f + me * fl)
        yg, yv = all_gather_matmul_manual(x, (wg, wv), tp, overlap)
        if "fc1_bias" in p:
            b1 = p["fc1_bias"].astype(dt)
            yg = yg + lax.dynamic_slice_in_dim(b1, me * fl, fl)
            yv = yv + lax.dynamic_slice_in_dim(b1, f + me * fl, fl)
        # Repack this shard's halves into the baseline's [gate | value]
        # layout so 'mlp1' captures both halves and 'calculation' draws
        # ONE disturbance per (site, layer), like every other path.
        y = jnp.concatenate([yg, yv], axis=-1)
        y = scope_capture("mlp1", y, layer_id)
        y = _dist.apply("calculation", y, layer_id)
        yg, yv = jnp.split(y, 2, axis=-1)
        y = apply_activation(cfg.activation, yv, yg)
    else:
        w1 = colslice(fc1_kernel, me * fl)
        y = all_gather_matmul_manual(x, w1, tp, overlap)
        if "fc1_bias" in p:
            y = y + lax.dynamic_slice_in_dim(p["fc1_bias"].astype(dt),
                                             me * fl, fl)
        y = scope_capture("mlp1", y, layer_id)
        y = _dist.apply("calculation", y, layer_id)
        y = apply_activation(cfg.activation, y)

    fc2_kernel = _dist.apply("weight", p["fc2_kernel"], layer_id).astype(dt)
    w2 = lax.dynamic_slice_in_dim(fc2_kernel, me * fl, fl, axis=0)
    out = matmul_reduce_scatter_manual(y, w2, tp, overlap)
    if "fc2_bias" in p:
        out = out + p["fc2_bias"].astype(dt)
    out = scope_capture("mlp2", out, layer_id)
    return out
