"""``"model": "gpt_dense"``: the dense GPT of ``models/gpt.py`` as GPT-2 and
GPT-3 publish it (learned absolute positions, qkv bias, one key/value head
per query head, tanh-GELU MLP, tied head, vocabulary padded to a multiple of
128), with its plain reference and its count of operations.

What a model module gives the cell runners, found through a configuration
file's ``model`` key (``manifest.load_module("models", ...)``):

- ``model_config(config, params_dtype, **extra)``: the program's own model
  configuration for the file's sizes;
- ``init_params(model_cfg, seed, device=None)``: parameters on the device
  from the seed, in one jitted program;
- ``reference_logits`` / ``reference_loss``: the plain float32 reference
  (``perfbench/reference.py``) that decides ``correct``;
- ``flops_per_token(config, seq_len)``: the yardstick of ``mfu``;
- ``kv_bytes_per_token(config, dtype)``: what one cached token takes, by
  which a serving runner holds the engine's pool to the type the
  configuration states;
- ``REHEARSAL``: the sizes a CPU rehearsal puts in the file's place.

A model this one does not cover (grouped-query or latent attention, rotary
positions, experts) comes as a module of its own beside it, with its
reference, in the PR that adds its configuration.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from perfbench.flops import flops_per_token  # noqa: F401 (part of the API)
from perfbench.reference import logits as reference_logits  # noqa: F401
from perfbench.reference import masked_loss as reference_loss  # noqa: F401

DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}

REHEARSAL = {"num_layers": 2, "hidden_size": 64, "num_attention_heads": 4,
             "head_dim": 16, "ffn_hidden_size": 256,
             "max_position_embeddings": 128, "vocab_size": 500,
             "padded_vocab_size": 512}


def kv_bytes_per_token(config: dict, dtype: str) -> int:
    """Keys and values of every head of every layer, in `dtype`."""
    return (2 * config["num_layers"] * config["num_attention_heads"]
            * config["head_dim"] * jnp.dtype(DTYPES[dtype]).itemsize)


def model_config(config: dict, params_dtype: str, **extra):
    """The program's TransformerConfig for a configuration file. Everything
    not named here stays at the program's default."""
    from megatronapp_tpu.config.transformer_config import (
        PositionEmbeddingKind, TransformerConfig,
    )
    if config["position_embedding"] != "learned_absolute" \
            or not config["tie_word_embeddings"]:
        raise SystemExit("perfbench: models/gpt_dense.py builds learned "
                         "positions and a tied head only")
    return TransformerConfig(
        num_layers=config["num_layers"],
        hidden_size=config["hidden_size"],
        num_attention_heads=config["num_attention_heads"],
        kv_channels=config["head_dim"],
        ffn_hidden_size=config["ffn_hidden_size"],
        vocab_size=config["padded_vocab_size"],
        true_vocab_size=config["vocab_size"],
        max_position_embeddings=config["max_position_embeddings"],
        position_embedding=PositionEmbeddingKind.learned_absolute,
        add_qkv_bias=config["add_qkv_bias"],
        params_dtype=DTYPES[params_dtype], **extra)


def init_params(model_cfg, seed: int, device=None):
    """The program's own initialiser, run as ONE jitted program on the
    device from the seed, in the type the parameters are used in."""
    from megatronapp_tpu.models.gpt import init_gpt_params
    key = jax.random.PRNGKey(seed % (2 ** 31))
    init = jax.jit(lambda k: init_gpt_params(k, model_cfg)[0])
    if device is not None:
        with jax.default_device(device):
            return init(key)
    return init(key)
