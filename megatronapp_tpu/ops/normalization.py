"""LayerNorm / RMSNorm.

Parity with the reference's fused norms (/root/reference/megatron/core/fusions/
fused_layer_norm.py — Apex-backed) — on TPU, XLA fuses the reduction+scale
chain natively, so a plain jnp implementation compiles to a fused kernel; no
hand-written Pallas needed for the norm itself.
Computation runs in fp32 regardless of input dtype (parity with Apex fused LN).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from megatronapp_tpu.config.transformer_config import NormKind


def layer_norm(x, scale, bias, eps: float = 1e-5):
    dtype = x.dtype
    x = x.astype(jnp.float32)
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    out = (x - mean) * jax.lax.rsqrt(var + eps)
    out = out * scale.astype(jnp.float32)
    if bias is not None:
        out = out + bias.astype(jnp.float32)
    return out.astype(dtype)


def rms_norm(x, scale, eps: float = 1e-5):
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    out = x * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)
    return out.astype(dtype)


def apply_norm(kind: NormKind, x, scale, bias=None, eps: float = 1e-5,
               unit_offset: bool = False):
    """unit_offset: the stored leaf is g and the scale 1 + g
    (cfg.norm_unit_offset), added in float32."""
    if unit_offset:
        scale = 1.0 + scale.astype(jnp.float32)
    if kind == NormKind.rmsnorm:
        return rms_norm(x, scale, eps)
    return layer_norm(x, scale, bias, eps)
