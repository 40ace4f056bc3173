"""Static inference engine: KV-cached autoregressive generation.

Parity with /root/reference/megatron/core/inference/engines/static_engine.py
(StaticInferenceEngine), text_generation_controllers/text_generation_
controller.py (prefill + decode loop, sampling) and
megatron/inference/text_generation/{generation.py,sampling}: greedy,
temperature, top-k, top-p sampling; static preallocated KV cache
(contexts/static_context.py analogue).

TPU-first: prefill is one jit over the prompt; decode is one jitted step
(donated cache) driven by lax.while-free host loop — token-by-token outputs
stream to a callback (the MegaScope per-token streaming contract).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from megatronapp_tpu.config.transformer_config import TransformerConfig
from megatronapp_tpu.models.gpt import (
    gpt_embed, gpt_head, gpt_rope_tables,
)
from megatronapp_tpu.transformer.block import layer_forward
from megatronapp_tpu.scope.hooks import scope_capture


@dataclasses.dataclass
class SamplingParams:
    """Reference common_inference_params/SamplingParams."""
    temperature: float = 1.0
    top_k: int = 0          # 0 = disabled
    top_p: float = 0.0      # 0 = disabled
    greedy: bool = False
    seed: int = 0


def _decode_loop(cfg, prompt_tokens, raw_logits_last, step_fn,
                 max_new_tokens, sampling, eod_id, token_callback):
    """Shared autoregressive sampling loop (one copy for the static,
    mamba, and convenience paths): sampling, padded-vocab masking, eod
    early stop, MegaScope per-token callback. step_fn(next_tok [B]) →
    raw logits [B, V] for the next position."""
    sampling = sampling or SamplingParams()
    b = prompt_tokens.shape[0]
    rng = jax.random.PRNGKey(sampling.seed)
    logits_last = mask_padded_vocab(raw_logits_last, cfg)
    out = [prompt_tokens]
    finished = np.zeros((b,), bool)
    for step in range(max_new_tokens):
        rng, krng = jax.random.split(rng)
        next_tok = sample_logits(logits_last, krng, sampling)
        next_tok = next_tok.astype(jnp.int32)
        tok_host = np.asarray(jax.device_get(next_tok))
        if token_callback is not None:
            token_callback(step, tok_host,
                           np.asarray(jax.device_get(logits_last)))
        if eod_id is not None:
            finished |= tok_host == eod_id
        out.append(next_tok[:, None])
        if eod_id is not None and finished.all():
            break
        if step == max_new_tokens - 1:
            break
        logits_last = mask_padded_vocab(step_fn(next_tok), cfg)
    return np.asarray(jax.device_get(jnp.concatenate(out, axis=1)))


def _generate_text(engine, prompts, max_new_tokens, sampling,
                   token_callback):
    """Shared string-level API (api.py generate_and_post_process parity).

    Prompts of different lengths run as separate batches (no padding
    leaks into causal attention / recurrent state)."""
    assert engine.tokenizer is not None, "tokenizer required"
    eod = getattr(engine.tokenizer, "eod", None)
    texts = []
    for prompt in prompts:
        ids = np.asarray([engine.tokenizer.tokenize(prompt)], np.int32)
        out = engine.generate(ids, max_new_tokens, sampling, eod_id=eod,
                              token_callback=token_callback)
        new_ids = out[0, ids.shape[1]:].tolist()
        if eod is not None and eod in new_ids:
            new_ids = new_ids[: new_ids.index(eod)]
        texts.append(engine.tokenizer.detokenize(new_ids))
    return texts


def mask_padded_vocab(logits: jnp.ndarray, cfg: TransformerConfig
                      ) -> jnp.ndarray:
    """Mask logits for vocab rows beyond the tokenizer's true vocab to -inf.

    Converted checkpoints pad the embedding to a TP-friendly vocab size with
    zero rows; with tied embeddings those ids get logit exactly 0 — often
    above the mean of real logits — and would otherwise be sampleable
    (advisor finding r1)."""
    true_v = cfg.true_vocab_size
    if true_v is None or true_v >= logits.shape[-1]:
        return logits
    ids = jnp.arange(logits.shape[-1])
    return jnp.where(ids < true_v, logits, -1e30)


def sample_logits(logits: jnp.ndarray, rng, params: SamplingParams):
    """logits [B,V] → token ids [B] (generation.py sampling parity)."""
    if params.greedy:
        return jnp.argmax(logits, axis=-1)
    logits = logits / jnp.maximum(params.temperature, 1e-6)
    if params.top_k > 0:
        kth = jnp.sort(logits, axis=-1)[:, -params.top_k][:, None]
        logits = jnp.where(logits < kth, -1e30, logits)
    if params.top_p > 0.0:
        sorted_logits = jnp.sort(logits, axis=-1)[:, ::-1]
        probs = jax.nn.softmax(sorted_logits, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        # Keep the smallest prefix with cumulative prob >= top_p.
        cutoff_idx = jnp.sum(cum < params.top_p, axis=-1)
        cutoff = jnp.take_along_axis(sorted_logits, cutoff_idx[:, None],
                                     axis=-1)
        logits = jnp.where(logits < cutoff, -1e30, logits)
    return jax.random.categorical(rng, logits, axis=-1)


def init_kv_cache(cfg: TransformerConfig, batch: int, max_len: int):
    """Per-layer decode cache (static_context.py analogue).

    Standard attention: K and V [L, B, S_max, Hkv, D]. MLA: the COMPRESSED
    cache — latent [L, B, S_max, kv_lora_rank] + shared roped key
    [L, B, S_max, qk_pos_emb_head_dim] (reference MLA's storage win:
    klat+dpe floats per token instead of 2*Hkv*D)."""
    if cfg.moe_first_k_dense:
        raise ValueError(
            "moe_first_k_dense: the dense-cache engines scan one uniform "
            "stack; serve a model with leading dense layers through the "
            "paged engine (DynamicInferenceEngine / --engine dynamic)")
    if cfg.multi_latent_attention:
        return (jnp.zeros((cfg.num_layers, batch, max_len,
                           cfg.kv_lora_rank), cfg.compute_dtype),
                jnp.zeros((cfg.num_layers, batch, max_len,
                           cfg.qk_pos_emb_head_dim), cfg.compute_dtype))
    shape = (cfg.num_layers, batch, max_len, cfg.num_query_groups,
             cfg.head_dim)
    return (jnp.zeros(shape, cfg.compute_dtype),
            jnp.zeros(shape, cfg.compute_dtype))


def _forward_with_cache(p, tokens, cache, cache_index,
                        cfg: TransformerConfig):
    """tokens [B,S_step] starting at position cache_index →
    (logits [B,S_step,V], cache). Layer loop unrolled (stacked params are
    indexed per layer; caches updated in place via dynamic_update_slice)."""
    b, s = tokens.shape
    h = gpt_embed(p, tokens, cfg, position_offset=cache_index)
    max_len = cache[0].shape[2]
    inv_cos, inv_sin = gpt_rope_tables(cfg, max_len)
    # Slice rope tables for the current positions.
    if inv_cos is not None:
        cos = jax.lax.dynamic_slice_in_dim(inv_cos, cache_index, s)
        sin = jax.lax.dynamic_slice_in_dim(inv_sin, cache_index, s)
    else:
        cos = sin = None

    ck, cv = cache

    def body(carry, inputs):
        hh = carry
        layer_p, k_l, v_l, lid = inputs
        (hh, new_cache), _ = layer_forward(
            layer_p, hh, cfg, cos, sin, None, layer_id=lid,
            kv_cache=(k_l, v_l), cache_index=cache_index)
        return hh, new_cache

    h, new_caches = jax.lax.scan(
        body, h,
        (p["block"], ck, cv, jnp.arange(cfg.num_layers)))
    logits = gpt_head(p, h, cfg)
    return logits, new_caches


class StaticInferenceEngine:
    """generate() over a fixed-shape batch with a preallocated cache."""

    def __init__(self, params, cfg: TransformerConfig,
                 tokenizer=None, max_seq_len: Optional[int] = None):
        self.params = params
        self.cfg = cfg
        self.tokenizer = tokenizer
        self.max_seq_len = max_seq_len or cfg.max_position_embeddings

        self._build_jits()

    def _build_jits(self):
        self._prefill = jax.jit(
            functools.partial(_forward_with_cache, cfg=self.cfg),
            static_argnames=(), donate_argnums=(2,))
        self._decode = jax.jit(
            functools.partial(_forward_with_cache, cfg=self.cfg),
            donate_argnums=(2,))

    def reset_compilation(self):
        """Drop the jitted prefill/decode so the next call re-traces —
        required after toggling MegaScope capture hooks, whose enablement
        is baked in at trace time (scope/hooks.py NOTE)."""
        self._build_jits()

    def generate(self, prompt_tokens: np.ndarray, max_new_tokens: int,
                 sampling: Optional[SamplingParams] = None,
                 eod_id: Optional[int] = None,
                 token_callback: Optional[Callable] = None) -> np.ndarray:
        """prompt_tokens [B, S_prompt] int32 → [B, S_prompt+max_new]."""
        prompt_tokens = jnp.asarray(prompt_tokens, jnp.int32)
        b, s_prompt = prompt_tokens.shape
        total = s_prompt + max_new_tokens
        if total > self.max_seq_len:
            raise ValueError(f"prompt+new ({total}) exceeds max_seq_len "
                             f"({self.max_seq_len})")
        cache = init_kv_cache(self.cfg, b, self.max_seq_len)
        logits, cache = self._prefill(self.params, prompt_tokens, cache, 0)
        state = {"cache": cache, "pos": s_prompt}

        def step_fn(next_tok):
            logits, state["cache"] = self._decode(
                self.params, next_tok[:, None], state["cache"],
                state["pos"])
            state["pos"] += 1
            return logits[:, -1]

        return _decode_loop(self.cfg, prompt_tokens, logits[:, -1],
                            step_fn, max_new_tokens, sampling, eod_id,
                            token_callback)

    def generate_text(self, prompts, max_new_tokens: int,
                      sampling: Optional[SamplingParams] = None,
                      token_callback: Optional[Callable] = None):
        return _generate_text(self, prompts, max_new_tokens, sampling,
                              token_callback)


class MambaInferenceEngine:
    """Server-compatible generation engine for Mamba models — pure-M
    stacks decode with O(1) recurrent state; hybrid (M/attention) stacks
    additionally carry a KV cache sized max_seq_len for the '*' layers
    (reference: the mamba text-generation server under tools/).

    Exposes the same generate/generate_text surface the
    TextGenerationServer drives on StaticInferenceEngine."""

    def __init__(self, params, cfg, mcfg, tokenizer=None,
                 max_seq_len: Optional[int] = None):
        from megatronapp_tpu.models.mamba import (
            mamba_decode_step, mamba_prefill,
        )
        self.params = params
        self.cfg = cfg
        self.mcfg = mcfg
        self.tokenizer = tokenizer
        # Mamba has no positional embeddings — an operator may serve
        # beyond the training context via --max-seq-len. Hybrid stacks
        # contain rope attention layers, so there the trained position
        # range is a hard bound.
        self.max_seq_len = max_seq_len or cfg.max_position_embeddings
        pattern = mcfg.hybrid_pattern or ""
        if set(pattern) - {"M"} and (
                self.max_seq_len > cfg.max_position_embeddings):
            raise ValueError(
                f"hybrid mamba stack: max_seq_len ({self.max_seq_len}) "
                "exceeds the attention layers' trained position range "
                f"({cfg.max_position_embeddings})")
        # jit once per engine — per-request lambdas would re-trace and
        # recompile every call.
        self._build_jits()

    def _build_jits(self):
        from megatronapp_tpu.models.mamba import (
            mamba_decode_step, mamba_prefill,
        )
        cfg, mcfg = self.cfg, self.mcfg
        self._prefill = jax.jit(
            lambda p, t: mamba_prefill(p, t, cfg, mcfg,
                                       max_len=self.max_seq_len))
        self._step = jax.jit(
            lambda p, s, t, i: mamba_decode_step(p, s, t, cfg, mcfg,
                                                 cache_index=i),
            donate_argnums=(1,))

    def reset_compilation(self):
        """Re-trace on next call (after MegaScope hook toggles — see
        StaticInferenceEngine.reset_compilation)."""
        self._build_jits()

    def generate(self, prompt_tokens: np.ndarray, max_new_tokens: int,
                 sampling: Optional[SamplingParams] = None,
                 eod_id: Optional[int] = None,
                 token_callback: Optional[Callable] = None) -> np.ndarray:
        """Same contract as StaticInferenceEngine.generate: full sampling
        (greedy/temperature/top-k/top-p), padded-vocab masking, eod early
        stop, max_seq_len bound."""
        prompt_tokens = jnp.asarray(prompt_tokens, jnp.int32)
        s_prompt = prompt_tokens.shape[1]
        if s_prompt + max_new_tokens > self.max_seq_len:
            raise ValueError(
                f"prompt+new ({s_prompt + max_new_tokens}) exceeds "
                f"max_seq_len ({self.max_seq_len})")
        logits, states = self._prefill(self.params, prompt_tokens)
        box = {"states": states, "pos": s_prompt}

        def step_fn(next_tok):
            logits_last, box["states"] = self._step(
                self.params, box["states"], next_tok,
                jnp.int32(box["pos"]))
            box["pos"] += 1
            return logits_last

        return _decode_loop(self.cfg, prompt_tokens, logits[:, -1],
                            step_fn, max_new_tokens, sampling, eod_id,
                            token_callback)

    def generate_text(self, prompts, max_new_tokens: int,
                      sampling: Optional[SamplingParams] = None,
                      token_callback: Optional[Callable] = None):
        return _generate_text(self, prompts, max_new_tokens, sampling,
                              token_callback)


def beam_search(engine: StaticInferenceEngine, prompt_tokens: np.ndarray,
                max_new_tokens: int, beam_width: int = 4,
                length_penalty: float = 1.0,
                eod_id: Optional[int] = None) -> np.ndarray:
    """Beam search decode (reference generation.py beam_search parity) for a
    single prompt [1, S]."""
    cfg = engine.cfg
    prompt = jnp.asarray(prompt_tokens, jnp.int32)
    assert prompt.shape[0] == 1, "beam search takes a single prompt"
    s_prompt = prompt.shape[1]

    # Expand prompt to beam_width rows; run one shared prefill.
    beams = jnp.tile(prompt, (beam_width, 1))
    cache = init_kv_cache(cfg, beam_width, engine.max_seq_len)
    logits, cache = engine._prefill(engine.params, beams, cache, 0)
    logp = jax.nn.log_softmax(
        mask_padded_vocab(logits[:, -1], cfg).astype(jnp.float32), axis=-1)

    # First step: take top beam_width continuations of the single prompt.
    top_logp, top_idx = jax.lax.top_k(logp[0], beam_width)
    scores = np.asarray(top_logp, np.float64)
    beams = np.concatenate([np.asarray(beams),
                            np.asarray(top_idx)[:, None]], axis=1)
    finished = np.zeros((beam_width,), bool)
    pos = s_prompt

    for _ in range(max_new_tokens - 1):
        if eod_id is not None and finished.all():
            break
        tok = jnp.asarray(beams[:, -1:], jnp.int32)
        logits, cache = engine._decode(engine.params, tok, cache, pos)
        pos += 1
        logp = np.asarray(jax.nn.log_softmax(
            mask_padded_vocab(logits[:, -1], cfg).astype(jnp.float32),
            axis=-1))
        vocab = logp.shape[-1]
        cand = scores[:, None] + np.where(finished[:, None], -1e9, logp)
        if eod_id is not None:
            # Finished beams keep their score on a dummy continuation.
            cand[finished, 0] = scores[finished]
        flat = cand.ravel()
        best = np.argsort(flat)[::-1][:beam_width]
        parents, toks = best // vocab, best % vocab
        scores = flat[best]
        beams = np.concatenate([beams[parents], toks[:, None]], axis=1)
        finished = finished[parents] | (
            (toks == eod_id) if eod_id is not None else False)
        # Reorder the cache rows to follow the surviving beams.
        cache = jax.tree.map(lambda c: c[:, parents], cache)

    lengths = (beams.shape[1] - s_prompt) * np.ones(beam_width)
    final = scores / (lengths ** length_penalty)
    return beams[int(np.argmax(final))][None]
