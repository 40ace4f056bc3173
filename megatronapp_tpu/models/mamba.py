"""Mamba (selective state-space) model: a pure-M stack (and the unrolled
'M*' hybrids of pretrain_mamba.py) around the mixer of transformer/ssm.py.
The scanned hybrid that trains and serves through models/gpt.py and the
paged engine is TransformerConfig.attn_layer_period.

Parity with /root/reference/megatron/core/ssm/ (MambaMixer/MambaBlock,
1.6k LoC; hybrid mamba/attention layer allocation in mamba_hybrid_layer_
allocation.py). The reference leans on Triton kernels for the selective
scan; TPU-first this is a ``lax.associative_scan`` — the first-order
recurrence h_t = a_t h_{t-1} + b_t is associative, so XLA lowers it to a
log-depth parallel scan that maps well onto the VPU, no custom kernel
needed.

Mixer structure (Mamba-1): in_proj → (x, z); causal depthwise conv1d;
silu; data-dependent Δ, B, C; selective scan over diagonal A; gate by
silu(z); out_proj.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from megatronapp_tpu.config.transformer_config import (
    NormKind, TransformerConfig,
)
from megatronapp_tpu.ops.cross_entropy import cross_entropy_loss
from megatronapp_tpu.ops.normalization import apply_norm, rms_norm
from megatronapp_tpu.parallel.sharding import is_logical_axes
from megatronapp_tpu.transformer.block import (
    _remat_wrap, init_layer_params, layer_forward,
)
from megatronapp_tpu.transformer.ssm import (
    SsmDims, init_ssm_params, selective_scan, ssm_forward,
)


@dataclasses.dataclass
class MambaConfig:
    """SSM hyperparameters (reference MambaMixer defaults)."""
    state_dim: int = 16        # N
    conv_kernel: int = 4
    expand: int = 2            # E = expand * hidden
    dt_rank: Optional[int] = None  # defaults to ceil(hidden/16)
    # 'M' = mamba layer, '*' = attention layer (reference hybrid allocation
    # string, e.g. 'MMM*MMM*' — ssm/mamba_hybrid_layer_allocation.py).
    hybrid_pattern: Optional[str] = None


def _dims(mcfg: MambaConfig) -> SsmDims:
    return SsmDims(mcfg.state_dim, mcfg.conv_kernel, mcfg.expand,
                   mcfg.dt_rank)


def init_mamba_mixer_params(rng, cfg: TransformerConfig, mcfg: MambaConfig):
    return init_ssm_params(rng, cfg, _dims(mcfg))


def _selective_scan(u, dt, A, B, C, D, return_h: bool = False):
    """u,dt [B,S,E]; A [E,N]; B,C [B,S,N]; D [E] → y [B,S,E] (and, with
    return_h, the final state [B,N,E]): transformer/ssm.selective_scan
    from a zero state."""
    y, h_last = selective_scan(u, dt, A.T, B, C, D)
    return (y, h_last) if return_h else y


def mamba_mixer_forward(p, x, cfg: TransformerConfig, mcfg: MambaConfig,
                        return_state: bool = False):
    """x [B,S,H] → [B,S,H] (+ (conv_tail [B,k-1,E], h_last [B,N,E]) when
    return_state — the decode cache seeded by prefill)."""
    out, state = ssm_forward(p, x, cfg, _dims(mcfg))
    return (out, state) if return_state else out


def mamba_mixer_step(p, conv_buf, ssm_h, x, cfg: TransformerConfig,
                     mcfg: MambaConfig):
    """One-token recurrent mixer step, plain jnp.

    conv_buf [B,k-1,E] (pre-conv inputs), ssm_h [B,N,E], x [B,H] →
    (y [B,H], (conv_buf', ssm_h')).
    """
    out, state = ssm_forward(p, x[:, None], cfg, _dims(mcfg),
                             state=(conv_buf, ssm_h))
    return out[:, 0], state


def init_mamba_params(rng, cfg: TransformerConfig, mcfg: MambaConfig):
    """Stacked mamba layers (+ optional interleaved attention via
    hybrid_pattern) + embedding + head."""
    pattern = mcfg.hybrid_pattern or "M" * cfg.num_layers
    if len(pattern) != cfg.num_layers:
        raise ValueError("hybrid_pattern length must equal num_layers")
    k_emb, k_layers, k_out = jax.random.split(rng, 3)
    std = cfg.init_method_std
    h = cfg.hidden_size
    p = {
        "embedding": {"word": jax.random.normal(
            k_emb, (cfg.vocab_size, h), cfg.params_dtype) * std},
        "final_ln_scale": jnp.ones((h,), cfg.params_dtype),
    }
    ax = {
        "embedding": {"word": ("vocab", "embed")},
        "final_ln_scale": ("embed",),
    }
    keys = jax.random.split(k_layers, cfg.num_layers)
    layers_p, layers_ax = [], None
    for i, kind in enumerate(pattern):
        if kind == "M":
            mp, max_ = init_mamba_mixer_params(keys[i], cfg, mcfg)
            lp = {"ln_scale": jnp.ones((h,), cfg.params_dtype),
                  "mixer": mp}
            lax_ = {"ln_scale": ("embed",), "mixer": max_}
        elif kind == "*":
            lp, lax_ = init_layer_params(keys[i], cfg)
        else:
            raise ValueError(f"unknown layer kind {kind!r}")
        layers_p.append((kind, lp, lax_))
    # Hybrid stacks are heterogeneous → store as a list (unrolled loop);
    # a pure-M stack is stacked for lax.scan.
    if set(pattern) == {"M"}:
        p["layers"] = jax.tree.map(lambda *xs: jnp.stack(xs),
                                   *[lp for _, lp, _ in layers_p])
        ax["layers"] = jax.tree.map(lambda axes: ("layers",) + axes,
                                    layers_p[0][2], is_leaf=is_logical_axes)
    else:
        p["layers"] = [lp for _, lp, _ in layers_p]
        ax["layers"] = [lax_ for _, _, lax_ in layers_p]
    return p, ax


def mamba_forward(p, tokens, cfg: TransformerConfig, mcfg: MambaConfig,
                  ctx=None):
    pattern = mcfg.hybrid_pattern or "M" * cfg.num_layers
    h = jnp.take(p["embedding"]["word"], tokens, axis=0).astype(
        cfg.compute_dtype)

    if set(pattern) == {"M"}:
        def body(carry, layer_p):
            x = carry
            y = rms_norm(x, layer_p["ln_scale"], cfg.layernorm_epsilon)
            x = x + mamba_mixer_forward(layer_p["mixer"], y, cfg,
                                        mcfg).astype(x.dtype)
            return x, None

        body = _remat_wrap(body, cfg.remat_policy)
        h, _ = jax.lax.scan(body, h, p["layers"])
    else:
        from megatronapp_tpu.models.gpt import gpt_rope_tables
        cos, sin = gpt_rope_tables(cfg, tokens.shape[1])
        for kind, layer_p in zip(pattern, p["layers"]):
            if kind == "M":
                y = rms_norm(h, layer_p["ln_scale"], cfg.layernorm_epsilon)
                h = h + mamba_mixer_forward(layer_p["mixer"], y, cfg,
                                            mcfg).astype(h.dtype)
            else:
                (h, _), _ = layer_forward(layer_p, h, cfg, cos, sin,
                                          ctx=ctx)

    h = rms_norm(h, p["final_ln_scale"], cfg.layernorm_epsilon)
    dt = cfg.compute_dtype
    logits = h.astype(dt) @ p["embedding"]["word"].T.astype(dt)
    return logits.astype(jnp.float32)


def mamba_loss(p, tokens, targets, loss_mask, cfg: TransformerConfig,
               mcfg: MambaConfig, ctx=None):
    """pretrain_mamba.py loss parity."""
    logits = mamba_forward(p, tokens, cfg, mcfg, ctx=ctx)
    loss, _ = cross_entropy_loss(logits, targets, loss_mask)
    return loss, {"lm_loss": loss}


# ---------------------------------------------------------------------------
# Recurrent generation (reference: core/inference mamba support +
# tools mamba text-generation server). Pure-M stacks carry stacked
# (conv_tail, ssm_h) states through a scan; hybrid stacks additionally
# carry a per-'*'-layer attention KV cache (reference hybrid allocation
# serves through the same inference context as attention models).

def mamba_prefill(p, tokens, cfg: TransformerConfig, mcfg: MambaConfig,
                  max_len: Optional[int] = None):
    """Parallel-scan prefill: logits for the prompt AND the per-layer
    decode caches. Pure-M stacks: states stacked [L, ...]. Hybrid stacks:
    a per-layer list of ('M' conv tail + SSM state) or ('*' K/V cache of
    length ``max_len``, which must cover prompt + generated tokens)."""
    pattern = mcfg.hybrid_pattern or "M" * cfg.num_layers
    h = jnp.take(p["embedding"]["word"], tokens, axis=0).astype(
        cfg.compute_dtype)

    if set(pattern) == {"M"}:
        def body(x, layer_p):
            y = rms_norm(x, layer_p["ln_scale"], cfg.layernorm_epsilon)
            out, state = mamba_mixer_forward(layer_p["mixer"], y, cfg, mcfg,
                                             return_state=True)
            return x + out.astype(x.dtype), state

        h, states = jax.lax.scan(body, h, p["layers"])
    else:
        from megatronapp_tpu.models.gpt import gpt_rope_tables
        b, s = tokens.shape
        max_len = max_len or s
        cos_full, sin_full = gpt_rope_tables(cfg, max_len)
        cos = None if cos_full is None else cos_full[:s]
        sin = None if sin_full is None else sin_full[:s]
        states = []
        for kind, layer_p in zip(pattern, p["layers"]):
            if kind == "M":
                y = rms_norm(h, layer_p["ln_scale"], cfg.layernorm_epsilon)
                out, state = mamba_mixer_forward(layer_p["mixer"], y, cfg,
                                                 mcfg, return_state=True)
                h = h + out.astype(h.dtype)
            else:
                kv = (jnp.zeros((b, max_len, cfg.num_query_groups,
                                 cfg.head_dim), cfg.compute_dtype),
                      jnp.zeros((b, max_len, cfg.num_query_groups,
                                 cfg.head_dim), cfg.compute_dtype))
                (h, state), _ = layer_forward(
                    layer_p, h, cfg, cos, sin, kv_cache=kv, cache_index=0)
            states.append(state)
    h = rms_norm(h, p["final_ln_scale"], cfg.layernorm_epsilon)
    dt = cfg.compute_dtype
    logits = h.astype(dt) @ p["embedding"]["word"].T.astype(dt)
    return logits.astype(jnp.float32), states


def mamba_decode_step(p, states, token, cfg: TransformerConfig,
                      mcfg: MambaConfig, cache_index=None):
    """token [B] + per-layer states → (logits [B,V], new states).

    ``cache_index`` (scalar int32, the absolute position of ``token``) is
    required for hybrid stacks — attention layers write their KV cache and
    select rope angles at that position; pure-M stacks ignore it."""
    pattern = mcfg.hybrid_pattern or "M" * cfg.num_layers
    x = jnp.take(p["embedding"]["word"], token, axis=0).astype(
        cfg.compute_dtype)

    if set(pattern) == {"M"}:
        def body(carry, inp):
            x = carry
            layer_p, (conv_buf, ssm_h) = inp
            y = rms_norm(x, layer_p["ln_scale"], cfg.layernorm_epsilon)
            out, new_state = mamba_mixer_step(layer_p["mixer"], conv_buf,
                                              ssm_h, y, cfg, mcfg)
            return x + out.astype(x.dtype), new_state

        x, new_states = jax.lax.scan(body, x, (p["layers"], states))
    else:
        from megatronapp_tpu.models.gpt import gpt_rope_tables
        if cache_index is None:
            raise ValueError("hybrid mamba decode requires cache_index")
        max_len = next(s[0].shape[1] for kind, s in zip(pattern, states)
                       if kind == "*")
        cos_full, sin_full = gpt_rope_tables(cfg, max_len)
        cos = None if cos_full is None else jax.lax.dynamic_slice_in_dim(
            cos_full, cache_index, 1)
        sin = None if sin_full is None else jax.lax.dynamic_slice_in_dim(
            sin_full, cache_index, 1)
        h = x[:, None]  # [B,1,H]
        new_states = []
        for kind, layer_p, state in zip(pattern, p["layers"], states):
            if kind == "M":
                y = rms_norm(h[:, 0], layer_p["ln_scale"],
                             cfg.layernorm_epsilon)
                out, new_state = mamba_mixer_step(
                    layer_p["mixer"], state[0], state[1], y, cfg, mcfg)
                h = h + out[:, None].astype(h.dtype)
            else:
                (h, new_state), _ = layer_forward(
                    layer_p, h, cfg, cos, sin, kv_cache=state,
                    cache_index=cache_index)
            new_states.append(new_state)
        x = h[:, 0]
    x = rms_norm(x, p["final_ln_scale"], cfg.layernorm_epsilon)
    dt = cfg.compute_dtype
    logits = x.astype(dt) @ p["embedding"]["word"].T.astype(dt)
    return logits.astype(jnp.float32), new_states


def mamba_generate(p, prompt_tokens, cfg: TransformerConfig,
                   mcfg: MambaConfig, *, max_new_tokens: int = 32,
                   greedy: bool = True, temperature: float = 1.0,
                   seed: int = 0, token_callback=None):
    """Convenience one-shot generation: parallel prefill then jitted
    recurrent decode (state donated). prompt_tokens [B,S] →
    [B, S+max_new]. For serving (sampling params, eod stop, compile
    caching) use inference.engine.MambaInferenceEngine."""
    import numpy as np

    from megatronapp_tpu.inference.engine import mask_padded_vocab

    prompt_len = prompt_tokens.shape[1]
    max_len = prompt_len + max_new_tokens
    prefill = jax.jit(
        lambda p, t: mamba_prefill(p, t, cfg, mcfg, max_len=max_len))
    step = jax.jit(
        lambda p, s, t, i: mamba_decode_step(p, s, t, cfg, mcfg,
                                             cache_index=i),
        donate_argnums=(1,))

    logits, states = prefill(p, prompt_tokens)
    out = [np.asarray(prompt_tokens)]
    rng = jax.random.PRNGKey(seed)
    next_logits = mask_padded_vocab(logits[:, -1], cfg)
    for i in range(max_new_tokens):
        if greedy:
            token = jnp.argmax(next_logits, axis=-1)
        else:
            rng, k = jax.random.split(rng)
            token = jax.random.categorical(
                k, next_logits / max(temperature, 1e-6), axis=-1)
        token = token.astype(prompt_tokens.dtype)
        out.append(np.asarray(token)[:, None])
        if token_callback is not None:
            token_callback(np.asarray(token))
        next_logits, states = step(p, states, token,
                                   jnp.int32(prompt_len + i))
        next_logits = mask_padded_vocab(next_logits, cfg)
    return np.concatenate(out, axis=1)
