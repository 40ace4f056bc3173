"""Speculative decoding over the paged-KV engine (ISSUE 4).

Pluggable proposers + an EXACT rejection-sampling verifier for
DynamicInferenceEngine(spec_method=...):

- ``NGramProposer`` ("ngram"): model-free prompt-lookup — the longest
  suffix n-gram of the request's token history is matched against its
  earlier occurrences and the continuation is proposed. Wins on
  repetitive / retrieval / code workloads; zero extra model cost.
- ``MTPProposer`` ("mtp"): self-drafting through the model's own
  multi-token-prediction depth modules (transformer/mtp.py, DeepSeek-V3
  recipe) — depth d predicts the token d+1 positions ahead from the
  previous depth's hidden state and the previous token's embedding. Needs
  ``params["mtp"]`` (cfg.mtp_num_layers > 0); K is capped at the depth.
- ``DraftModelProposer`` ("draft"): a small draft model sharing the
  target vocab/tokenizer (e.g. models/presets.py), with its own dense
  per-slot KV cache. Each round it catches up on tokens the target
  accepted since its last run (<= K+1 single-token steps), then drafts K
  tokens autoregressively; sampled requests draft from the draft's
  warped distribution and hand the verifier the full proposal
  probabilities q.

Verification: all K drafts (plus the mandatory next token) run through
the engine's ONE batched multi-query forward; acceptance is exact
rejection sampling (`_verify_and_sample`):

- greedy requests accept draft i while it equals argmax(target logits at
  its position) — the emitted stream is BIT-IDENTICAL to plain greedy
  decode for every proposer, by construction;
- sampled requests accept draft d with prob min(1, p(d)/q(d)) and on
  rejection sample the residual norm(max(p - q, 0)) — the classic
  speculative-sampling identity, so the emitted distribution equals the
  target's. Deterministic proposers (n-gram, greedy MTP heads) are
  point-mass q: accept with p(d), residual = p with d zeroed — also
  exact. p is warped through the SAME `_warp_logits`
  (temperature/top-k/top-p) the plain sampler uses, and all randomness
  comes from the engine's fold_in chains PRNGKey(seed) ∘ request_id ∘
  step (position i of a round uses step = generated_count + i), so
  streams stay reproducible and batch-composition independent; a fully
  accepted round's bonus token even uses the exact key plain decode
  would have used at that step.
"""

from __future__ import annotations

import functools
import warnings
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from megatronapp_tpu.inference.engine import (
    _forward_with_cache, init_kv_cache, mask_padded_vocab,
)
from megatronapp_tpu.models.gpt import gpt_embed, gpt_head, gpt_rope_tables
from megatronapp_tpu.ops.normalization import rms_norm
from megatronapp_tpu.transformer.block import layer_forward

# fold_in tags off the per-(request, step) chain key: acceptance uniform,
# residual categorical, and the draft model's own proposal sampling draw
# from distinct streams (the chain key itself is reserved for the
# plain-decode/bonus categorical).
_ACCEPT_FOLD = 1
_RESIDUAL_FOLD = 2
_DRAFT_FOLD = 3


# ---------------------------------------------------------------------------
# Exact rejection-sampling verifier
# ---------------------------------------------------------------------------


def _verify_and_sample(logits, drafts, q_lens, q_probs, seeds, rids,
                       base_steps, temps, top_ks, top_ps, greedys, *,
                       point_mass: bool):
    """Batched verification of one speculate round (jittable).

    logits [B, K+1, V] target logits (padded-vocab masked; row i sits at
    the position whose NEXT token is being decided — generated index
    base_steps + i); drafts [B, K]; q_lens [B] = 1 + per-row draft count
    (rows beyond are padding); q_probs [B, K, V] proposal probabilities
    (None when point_mass). Returns (accepted [B] ints in [0, K],
    out_token [B]) — the emitted window is drafts[:accepted] + [out].
    """
    from megatronapp_tpu.inference.dynamic_engine import (
        _request_keys, _warp_logits,
    )
    b, s, v = logits.shape
    k = s - 1
    flat = logits.reshape(b * s, v)
    rep = lambda a: jnp.repeat(a, s)  # noqa: E731
    warped = _warp_logits(flat, rep(temps), rep(top_ks),
                          rep(top_ps)).reshape(b, s, v)
    probs = jax.nn.softmax(warped, axis=-1)

    # Greedy acceptance: draft i == argmax of the target logits that
    # plain decode would have sampled from — bit-identical chains.
    g_acc = drafts == jnp.argmax(logits[:, :k], axis=-1)

    # Sampled acceptance: u * q(d) <= p(d), per-position chain keys.
    steps_i = base_steps[:, None] + jnp.arange(k)[None, :]      # [B, K]
    keys = jax.vmap(lambda sd, rd, st: _request_keys(
        jnp.full((k,), sd, jnp.int32), jnp.full((k,), rd, jnp.int32),
        st))(seeds, rids, steps_i)                              # [B, K, ·]
    u = jax.vmap(jax.vmap(lambda kk: jax.random.uniform(
        jax.random.fold_in(kk, _ACCEPT_FOLD))))(keys)           # [B, K]
    pd = jnp.take_along_axis(probs[:, :k], drafts[..., None],
                             axis=-1)[..., 0]
    if point_mass:
        qd = jnp.ones_like(pd)
    else:
        qd = jnp.take_along_axis(q_probs, drafts[..., None],
                                 axis=-1)[..., 0]
    s_acc = u * qd <= pd

    acc = jnp.where(greedys[:, None], g_acc, s_acc)
    acc = acc & (jnp.arange(k)[None, :] < (q_lens - 1)[:, None])
    a = jnp.sum(jnp.cumprod(acc.astype(jnp.int32), axis=1), axis=1)

    row_logits = jnp.take_along_axis(logits, a[:, None, None],
                                     axis=1)[:, 0]
    row_warped = jnp.take_along_axis(warped, a[:, None, None],
                                     axis=1)[:, 0]
    row_probs = jnp.take_along_axis(probs, a[:, None, None],
                                    axis=1)[:, 0]
    greedy_out = jnp.argmax(row_logits, axis=-1)

    base_key = _request_keys(seeds, rids, base_steps + a)
    # Fully-accepted bonus: the chain key plain decode would use at this
    # step, fed the same warped logits — the streams line up exactly.
    bonus = jax.vmap(jax.random.categorical)(base_key, row_warped)
    # Rejection: residual norm(max(p - q, 0)); p ≈ q underflow falls
    # back to p (acceptance prob was ~1 there anyway).
    d_a = jnp.take_along_axis(drafts, jnp.clip(a, 0, k - 1)[:, None],
                              axis=1)[:, 0]
    if point_mass:
        q_row = jax.nn.one_hot(d_a, v, dtype=row_probs.dtype)
    else:
        q_row = jnp.take_along_axis(
            q_probs, jnp.clip(a, 0, k - 1)[:, None, None], axis=1)[:, 0]
    resid = jnp.maximum(row_probs - q_row, 0.0)
    resid_sum = jnp.sum(resid, axis=-1, keepdims=True)
    resid = jnp.where(resid_sum > 1e-9, resid / resid_sum, row_probs)
    corr_key = jax.vmap(lambda kk: jax.random.fold_in(
        kk, _RESIDUAL_FOLD))(base_key)
    correction = jax.vmap(jax.random.categorical)(
        corr_key, jnp.log(jnp.maximum(resid, 1e-30)))
    rejected = a < (q_lens - 1)
    sampled_out = jnp.where(rejected, correction, bonus)
    out = jnp.where(greedys, greedy_out, sampled_out).astype(jnp.int32)
    return a.astype(jnp.int32), out


def build_verify_sampler(point_mass: bool):
    """Jitted `_verify_and_sample` with the proposer's point-mass mode
    baked in (point-mass engines pass q_probs=None)."""
    return jax.jit(functools.partial(_verify_and_sample,
                                     point_mass=point_mass))


# ---------------------------------------------------------------------------
# Proposers
# ---------------------------------------------------------------------------


class Proposer:
    """Engine-side proposer interface (one instance per engine).

    point_mass: the proposal is deterministic given the context (n-gram
    lookup, greedy MTP heads) — the verifier then treats q as a point
    mass, which keeps rejection sampling exact without materializing q.
    needs_hidden: the proposer consumes the engine's per-slot pre-head
    hidden state (engine._h_last, maintained by the verify rounds and
    chunked prefill)."""

    name = "base"
    point_mass = True
    needs_hidden = False

    def __init__(self, engine):
        self.engine = engine

    # Lifecycle hooks (engine calls these).
    def on_admit(self, slot: int, req):
        pass

    def on_release(self, slot: int):
        pass

    def on_verified(self, slot: int, accepted: int):
        pass

    def reset_compilation(self):
        pass

    def propose(self, k_caps: np.ndarray
                ) -> Tuple[np.ndarray, np.ndarray, Optional[jnp.ndarray]]:
        """k_caps [max_batch]: per-slot draft budget this round. Returns
        (drafts [B, spec_k] int32, counts [B] int32 with counts <=
        k_caps, q_probs [B, spec_k, V] or None for point-mass)."""
        raise NotImplementedError


def _ngram_lookup(tokens: np.ndarray, k: int, max_n: int,
                  min_n: int) -> np.ndarray:
    """Prompt-lookup: most recent earlier occurrence of the longest
    suffix n-gram; returns up to k continuation tokens (possibly 0)."""
    t = np.asarray(tokens)
    length = len(t)
    for n in range(min(max_n, length - 1), min_n - 1, -1):
        pat = t[length - n:]
        hay = t[:length - 1]            # continuation must exist
        if len(hay) < n:
            continue
        win = np.lib.stride_tricks.sliding_window_view(hay, n)
        hits = np.flatnonzero(np.all(win == pat[None], axis=1))
        # Exclude the suffix matching itself (start == length - n).
        hits = hits[hits < length - n]
        if len(hits):
            start = int(hits[-1]) + n   # most recent occurrence
            cont = t[start:start + k]
            if len(cont):
                return cont.astype(np.int32)
    return np.zeros((0,), np.int32)


class NGramProposer(Proposer):
    """Model-free prompt-lookup proposer (n-gram continuation)."""

    name = "ngram"
    point_mass = True

    def __init__(self, engine, max_n: int = 3, min_n: int = 1):
        super().__init__(engine)
        self.max_n = max_n
        self.min_n = min_n

    def propose(self, k_caps):
        eng = self.engine
        b, k = eng.max_batch, eng.spec_k
        drafts = np.zeros((b, k), np.int32)
        counts = np.zeros((b,), np.int32)
        for req in eng.slots:
            if req is None or req.finished:
                continue
            cap = int(k_caps[req.slot])
            if cap <= 0:
                continue
            cont = _ngram_lookup(req.tokens, cap, self.max_n, self.min_n)
            n = len(cont)
            drafts[req.slot, :n] = cont
            counts[req.slot] = n
        return drafts, counts, None


def _mtp_draft(params, h, toks, positions, cfg, k: int):
    """Greedy MTP self-draft chain: depth d combines the previous
    depth's hidden with the previous token's embedding (DeepSeek MTP
    recipe at inference) and scores with the SHARED head. The depth
    layer runs position-local here (S=1): single-token self-attention is
    rope-invariant and degenerate (out == v), so it acts as a learned
    head — proposal quality only; correctness comes from the verifier.
    h [B, H] pre-head hidden at the last verified position; toks [B] the
    pending token. Returns drafts [B, k]."""
    drafts = []
    h_cur = h.astype(cfg.compute_dtype)
    tok = toks
    pos = positions
    for d in range(k):
        dp = params["mtp"][d]
        e = gpt_embed(params, tok[:, None], cfg,
                      position_ids=pos[:, None])[:, 0]
        x = jnp.concatenate(
            [rms_norm(h_cur, dp["hnorm_scale"], cfg.layernorm_epsilon),
             rms_norm(e, dp["enorm_scale"], cfg.layernorm_epsilon)],
            axis=-1).astype(cfg.compute_dtype)
        x = x @ dp["proj"].astype(cfg.compute_dtype)
        (h2, _), _ = layer_forward(dp["layer"], x[:, None], cfg,
                                   None, None, None)
        h_cur = h2[:, 0]
        logits = mask_padded_vocab(
            gpt_head(params, h_cur[:, None], cfg)[:, 0], cfg)
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        drafts.append(tok)
        pos = pos + 1
    return jnp.stack(drafts, axis=1)


class MTPProposer(Proposer):
    """Self-drafting through the model's own MTP depth modules."""

    name = "mtp"
    point_mass = True
    needs_hidden = True

    def __init__(self, engine):
        super().__init__(engine)
        self.depth = len(engine.params.get("mtp") or [])
        self._k = min(engine.spec_k, self.depth)
        self.reset_compilation()

    @staticmethod
    def available(engine) -> bool:
        return bool(engine.params.get("mtp"))

    def reset_compilation(self):
        cfg = self.engine.cfg
        k = self._k
        self._draft = jax.jit(
            lambda p, h, t, pos: _mtp_draft(p, h, t, pos, cfg, k))

    def propose(self, k_caps):
        eng = self.engine
        b, k = eng.max_batch, eng.spec_k
        drafts = np.zeros((b, k), np.int32)
        counts = np.zeros((b,), np.int32)
        caps = np.minimum(np.asarray(k_caps), self._k)
        rows = [r for r in eng.slots
                if r is not None and not r.finished
                and caps[r.slot] > 0 and eng._h_valid[r.slot]]
        if not rows or self._k == 0:
            return drafts, counts, None
        out = np.asarray(jax.device_get(self._draft(
            eng.params, jnp.asarray(eng._h_last),
            jnp.asarray(eng.last_tokens[:, 0].astype(np.int32)),
            jnp.asarray(eng.lengths.astype(np.int32)))))
        for r in rows:
            n = int(caps[r.slot])
            drafts[r.slot, :n] = out[r.slot, :n]
            counts[r.slot] = n
        return drafts, counts, None


def _draft_sample(logits, seeds, rids, steps, temps, top_ks, top_ps,
                  greedys):
    """One draft-chain sampling step: greedy rows argmax, sampled rows
    draw from the draft's warped distribution with the _DRAFT_FOLD
    stream (independent of the verifier's uniforms — a proposal that
    peeked at the acceptance randomness would bias the test). Returns
    (tokens [B], q [B, V] warped proposal probs)."""
    from megatronapp_tpu.inference.dynamic_engine import (
        _request_keys, _warp_logits,
    )
    warped = _warp_logits(logits, temps, top_ks, top_ps)
    q = jax.nn.softmax(warped, axis=-1)
    keys = jax.vmap(lambda kk: jax.random.fold_in(kk, _DRAFT_FOLD))(
        _request_keys(seeds, rids, steps))
    sampled = jax.vmap(jax.random.categorical)(keys, warped)
    toks = jnp.where(greedys, jnp.argmax(logits, axis=-1),
                     sampled).astype(jnp.int32)
    return toks, q


def _decode_step(params, tokens, cache, lengths, active, cfg):
    """One-token decode for every row of a DENSE cache: the draft model's
    step (DraftModelProposer, its one caller), and why attention's per-row
    `cache_positions` arm without a page table stays.

    tokens [B,1] (last token per slot), cache [L,B,Smax,...], lengths [B]
    (tokens already in cache per slot), active [B] bool. Returns
    (last_logits [B,V], new_cache)."""
    max_len = cache[0].shape[2]
    h = gpt_embed(params, tokens, cfg, position_ids=lengths[:, None])
    cos_full, sin_full = gpt_rope_tables(cfg, max_len)
    if cos_full is not None:
        cos = jnp.take(cos_full, lengths, axis=0)[:, None]   # [B,1,half]
        sin = jnp.take(sin_full, lengths, axis=0)[:, None]
    else:
        cos = sin = None

    # Per-row causality: the new token (position lengths[b]) may attend
    # cache positions <= lengths[b]; inactive rows are fully masked except
    # self (keeps the softmax finite; results are discarded).
    kv_pos = jnp.arange(max_len)
    attend = kv_pos[None, :] <= lengths[:, None]             # [B,Smax]
    mask = attend[:, None, None, :]                          # [B,1,1,Smax]

    ck, cv = cache

    def body(carry, layer_in):
        hh = carry
        layer_p, k_l, v_l, lid = layer_in
        (hh, new_cache), _ = layer_forward(
            layer_p, hh, cfg, cos, sin, mask, layer_id=lid,
            kv_cache=(k_l, v_l), cache_index=None,
            cache_positions=lengths)
        return hh, new_cache

    h, new_caches = jax.lax.scan(
        body, h, (params["block"], ck, cv, jnp.arange(cfg.num_layers)),
        unroll=cfg.scan_unroll)
    logits = gpt_head(params, h, cfg)[:, -1]
    return logits, new_caches


class DraftModelProposer(Proposer):
    """Small draft model with its own DENSE per-slot KV cache.

    The draft shares the target's (padded) vocab so its proposal
    distribution q lives in the same space as the target p. Per round it
    (1) catches up on tokens the target accepted since its last run —
    at most K+1 batched single-token steps, all through one jit — then
    (2) drafts K tokens autoregressively, recording q for the verifier.
    Draft KV for rejected tokens needs no rollback: the dense cache
    masks by per-row length and stale rows are overwritten on the next
    catch-up."""

    name = "draft"
    point_mass = False

    def __init__(self, engine, draft_params, draft_cfg):
        super().__init__(engine)
        if draft_cfg.vocab_size != engine.cfg.vocab_size:
            raise ValueError(
                f"draft vocab ({draft_cfg.vocab_size}) must match the "
                f"target vocab ({engine.cfg.vocab_size}) — the rejection "
                "sampler compares p and q over one distribution")
        self.params = draft_params
        self.cfg = draft_cfg
        b = engine.max_batch
        self.cache = init_kv_cache(draft_cfg, b, engine.max_seq_len)
        self.lens = np.zeros((b,), np.int32)
        self._round_base = np.zeros((b,), np.int32)
        self._round_fed = np.zeros((b,), np.int32)
        self._q_zero = None    # lazy [B, K, V] zeros for draft-less rounds
        self.reset_compilation()

    def reset_compilation(self):
        dcfg = self.cfg
        self._prefill_jit = jax.jit(
            functools.partial(_forward_with_cache, cfg=dcfg))
        self._step = jax.jit(
            lambda p, t, c, l, a: _decode_step(p, t, c, l, a, dcfg),
            donate_argnums=(2,))
        self._sample = jax.jit(_draft_sample)

    def on_admit(self, slot, req):
        eng = self.engine
        valid = int(eng.lengths[slot])        # == len(req.tokens) - 1
        tokens = req.tokens[:valid]
        bucket = next((x for x in eng.prefill_buckets if x >= valid),
                      eng.max_seq_len)
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :valid] = tokens
        tmp = init_kv_cache(self.cfg, 1, bucket)
        _, tmp = self._prefill_jit(self.params, jnp.asarray(padded), tmp, 0)
        self.cache = tuple(
            c.at[:, slot, :bucket].set(t[:, 0])
            for c, t in zip(self.cache, tmp))
        self.lens[slot] = valid

    def on_release(self, slot):
        self.lens[slot] = 0
        self._round_fed[slot] = 0

    def on_verified(self, slot, accepted):
        # Draft KV for the accepted prefix [pending, d1..da] is valid —
        # its rows were computed from all-accepted context. Rewind past
        # that (the first rejected draft's row gets overwritten on the
        # next catch-up).
        fed = int(self._round_fed[slot])
        if fed:
            self.lens[slot] = int(self._round_base[slot]) + min(
                accepted + 1, fed)
            self._round_fed[slot] = 0

    def propose(self, k_caps):
        eng = self.engine
        b, k = eng.max_batch, eng.spec_k
        drafts = np.zeros((b, k), np.int32)
        counts = np.zeros((b,), np.int32)
        self._round_fed[:] = 0
        rows = [r for r in eng.slots if r is not None and not r.finished
                and int(k_caps[r.slot]) > 0]
        if not rows:
            # point_mass is False for this proposer, so the verifier
            # still dereferences q — hand it an all-zeros (fully
            # masked-out by counts == 0) buffer.
            if self._q_zero is None:
                self._q_zero = jnp.zeros((b, k, eng.cfg.vocab_size),
                                         jnp.float32)
            return drafts, counts, self._q_zero

        # 1) Catch-up: feed the accepted tokens the draft hasn't seen.
        toks = {r.slot: r.tokens for r in rows}
        while True:
            behind = [s for s, t in toks.items()
                      if self.lens[s] < len(t) - 1]
            if not behind:
                break
            feed = np.zeros((b, 1), np.int32)
            act = np.zeros((b,), bool)
            for s in behind:
                feed[s, 0] = toks[s][self.lens[s]]
                act[s] = True
            _, self.cache = self._step(
                self.params, jnp.asarray(feed), self.cache,
                jnp.asarray(self.lens), jnp.asarray(act))
            for s in behind:
                self.lens[s] += 1

        # 2) Draft chain: K batched steps; per-row sampling params (the
        # engine's shared gather, so greedy rows draft greedily and
        # sampled rows draft from q on the right key chains).
        sp = eng._sampling_rows()
        seeds, rids, base = sp["seeds"], sp["rids"], sp["steps"]
        temps, top_ks = sp["temps"], sp["top_ks"]
        top_ps, greedys = sp["top_ps"], sp["greedys"]
        cur = np.zeros((b, 1), np.int32)
        for r in rows:
            slot = r.slot
            cur[slot, 0] = toks[slot][-1]
            self._round_base[slot] = self.lens[slot]
        k_max = int(max(k_caps[r.slot] for r in rows))
        q_cols = []
        for j in range(k_max):
            act = np.zeros((b,), bool)
            for r in rows:
                if int(k_caps[r.slot]) > j:
                    act[r.slot] = True
            logits, self.cache = self._step(
                self.params, jnp.asarray(cur), self.cache,
                jnp.asarray(self.lens), jnp.asarray(act))
            logits = mask_padded_vocab(logits, eng.cfg)
            tok_dev, q_dev = self._sample(
                logits, jnp.asarray(seeds), jnp.asarray(rids),
                jnp.asarray(base + j), jnp.asarray(temps),
                jnp.asarray(top_ks), jnp.asarray(top_ps),
                jnp.asarray(greedys))
            tok_np = np.asarray(jax.device_get(tok_dev))
            q_cols.append(q_dev)
            for r in rows:
                slot = r.slot
                if int(k_caps[slot]) > j:
                    drafts[slot, j] = tok_np[slot]
                    counts[slot] = j + 1
                    cur[slot, 0] = tok_np[slot]
                    self.lens[slot] += 1
                    self._round_fed[slot] += 1
        # Pad q to [B, K, V]; rows/columns beyond counts are ignored by
        # the verifier's acceptance mask.
        v = q_cols[0].shape[-1]
        while len(q_cols) < k:
            q_cols.append(jnp.zeros((b, v), q_cols[0].dtype))
        return drafts, counts, jnp.stack(q_cols, axis=1)


def make_proposer(method: str, engine, draft_params=None, draft_cfg=None,
                  **kwargs) -> Optional[Proposer]:
    """Build the requested proposer, or None (with a warning) when it is
    unavailable — the engine then falls back to plain decode."""
    from megatronapp_tpu.utils import metrics as telemetry
    if method == "ngram":
        return NGramProposer(engine, **kwargs)
    if method == "mtp":
        if not MTPProposer.available(engine):
            warnings.warn(
                "spec_method='mtp' requested but the model has no MTP "
                "depth modules (cfg.mtp_num_layers == 0 or params lack "
                "'mtp') — falling back to plain decode", stacklevel=2)
            telemetry.inc("spec_proposer_fallbacks")
            return None
        return MTPProposer(engine)
    if method == "draft":
        if draft_params is None or draft_cfg is None:
            warnings.warn(
                "spec_method='draft' requested without draft_params/"
                "draft_cfg — falling back to plain decode", stacklevel=2)
            telemetry.inc("spec_proposer_fallbacks")
            return None
        return DraftModelProposer(engine, draft_params, draft_cfg)
    raise ValueError(f"unknown spec_method {method!r} "
                     "(expected 'draft', 'mtp', or 'ngram')")
