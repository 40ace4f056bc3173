"""Optimizer construction (optax).

Parity with /root/reference/megatron/core/optimizer/__init__.py:431
(get_megatron_optimizer) + optimizer.py (Float16Optimizer etc.) +
optimizer_param_scheduler.py (warmup + cosine/linear decay) + clip_grads.py.

TPU-native notes: fp16 loss-scaling machinery is unnecessary (bf16 training
is the norm on TPU — master params fp32, compute bf16, no dynamic scaler);
ZeRO-1 state sharding is obtained by sharding optimizer-state pytrees with
the same logical rules as params plus dp over the 'embed' axis (reference
distrib_optimizer.py:80 semantics) — see training/train.py.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import optax

from megatronapp_tpu.config.training_config import OptimizerConfig


def lr_schedule(cfg: OptimizerConfig, train_iters: int) -> optax.Schedule:
    decay_iters = cfg.lr_decay_iters or train_iters
    warmup = cfg.lr_warmup_iters

    def sched(step):
        step = jnp.asarray(step, jnp.float32)
        warm = cfg.lr * step / jnp.maximum(warmup, 1)
        frac = jnp.clip((step - warmup) / jnp.maximum(decay_iters - warmup, 1),
                        0.0, 1.0)
        if cfg.lr_decay_style == "cosine":
            decay = cfg.min_lr + 0.5 * (cfg.lr - cfg.min_lr) * (
                1.0 + jnp.cos(jnp.pi * frac))
        elif cfg.lr_decay_style == "linear":
            decay = cfg.lr + (cfg.min_lr - cfg.lr) * frac
        else:
            decay = jnp.asarray(cfg.lr)
        return jnp.where(step < warmup, warm, decay)

    return sched


# Leaf-name suffixes/names exempt from weight decay: biases, norm scales,
# and Mamba's per-channel state params.  Matching by NAME, not ndim: block
# params are stacked with leading layers/stage axes (init_block_params), so
# semantically-1-D leaves (ln scales, biases) can have ndim > 1.
_NO_DECAY_SUFFIXES = ("_bias", "_scale")
_NO_DECAY_NAMES = frozenset({"A_log", "D"})


def leaf_name(path) -> str:
    """The name of a parameter leaf: the last dict key of its tree path."""
    import jax.tree_util as jtu
    return next((k.key for k in reversed(path)
                 if isinstance(k, jtu.DictKey)), "")


def _weight_decay_mask(params):
    """No decay for biases and norm params — reference get_param_groups
    (optimizer/__init__.py) no_weight_decay_cond default."""
    import jax.tree_util as jtu

    def decay(path, p):
        name = leaf_name(path)
        if name.endswith(_NO_DECAY_SUFFIXES) or name in _NO_DECAY_NAMES:
            return False
        return p.ndim > 1

    return jtu.tree_map_with_path(decay, params)


def get_optimizer(cfg: OptimizerConfig, train_iters: int,
                  schedule: Optional[optax.Schedule] = None,
                  distributed: bool = False):
    """distributed=True returns the ZeRO-1 DistributedOptimizer wrapper
    (training/distributed_optimizer.py): same optax-transform arithmetic,
    dict-shaped state whose m/v/master leaves setup_train_state shards
    over dp, mixed-precision state dtypes from cfg. The plain chain below
    is the replicated baseline (and what non-ZeRO paths — FBD, tools,
    model families — keep using)."""
    if distributed:
        from megatronapp_tpu.training.distributed_optimizer import (
            DistributedOptimizer,
        )
        return DistributedOptimizer(cfg, train_iters, schedule=schedule)
    # The mixed-precision state knobs only exist on the ZeRO-1 layout;
    # the plain chain stores fp32 unconditionally. Refuse rather than
    # silently train with a different precision than the config claims
    # (the CLI validates the same constraint at parse time — this guard
    # covers programmatic OptimizerConfig construction).
    low = [n for n, v in (("exp_avg_dtype", cfg.exp_avg_dtype),
                          ("exp_avg_sq_dtype", cfg.exp_avg_sq_dtype),
                          ("main_params_dtype", cfg.main_params_dtype))
           if str(v).lower() not in ("fp32", "float32")]
    if low:
        raise ValueError(
            f"OptimizerConfig {', '.join(low)} != fp32 requires the "
            "ZeRO-1 distributed-optimizer wrapper, but this code path "
            "builds the replicated optax chain (plain DP, FSDP, FBD, or "
            "a direct get_optimizer(distributed=False) call), which "
            "stores fp32 state only — use fp32 state dtypes here")
    sched = schedule or lr_schedule(cfg, train_iters)
    chain = []
    if cfg.clip_grad:
        chain.append(optax.clip_by_global_norm(cfg.clip_grad))
    if cfg.optimizer == "adam":
        chain.append(optax.scale_by_adam(
            b1=cfg.adam_beta1, b2=cfg.adam_beta2, eps=cfg.adam_eps))
        if cfg.weight_decay:
            chain.append(optax.add_decayed_weights(
                cfg.weight_decay, mask=_weight_decay_mask))
    elif cfg.optimizer == "sgd":
        chain.append(optax.trace(decay=cfg.sgd_momentum))
    else:
        raise ValueError(f"unknown optimizer {cfg.optimizer}")
    chain.append(optax.scale_by_learning_rate(sched))
    return optax.chain(*chain)


def global_grad_norm(grads) -> jnp.ndarray:
    return optax.global_norm(grads)
