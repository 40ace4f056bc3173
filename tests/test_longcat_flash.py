"""LongCat-Flash-Chat's architecture against its plain float32 reference
(perfbench/models/longcat_flash.py: the published equations in jax.numpy,
MLA unabsorbed with a query latent and both scale corrections, the
shortcut-connected double layer, a router over computing and zero-compute
experts, a share of the experts held), at tiny widths on the CPU with seeded
random weights: 2 double layers, hidden 64, 4 heads, ranks 32/16, 8 + 4
experts of which 4 are held, top-3. Each test fails if the mechanism it
names is left out."""
import dataclasses
import functools
import json
import os
import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from megatronapp_tpu.config.transformer_config import TransformerConfig
from megatronapp_tpu.inference.dynamic_engine import (
    DynamicInferenceEngine, _paged_decode_step, _paged_multiquery_step,
)
from megatronapp_tpu.inference.engine import SamplingParams
from megatronapp_tpu.models.gpt import init_gpt_params
from megatronapp_tpu.models.presets import PRESETS
from megatronapp_tpu.transformer import moe
from megatronapp_tpu.transformer.block import layer_forward
from perfbench import manifest

from jitted import gpt_forward  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
MODEL = manifest.load_module("models", "longcat_flash")
with open(os.path.join(ROOT, "perfbench", "configs",
                       "longcat-flash-chat.json")) as f:
    PUBLISHED = json.load(f)
TINY = dict(PUBLISHED, **MODEL.REHEARSAL)

# float32 on both sides: what is left is the order of summation (the
# program absorbs kv_up into the query and sums experts in sorted groups, the
# reference expands keys and loops over experts); logits are ~0.3 in size and
# the two agree to 2e-6 (measured). A missing scale correction moves them by
# 3e-2 (s_q) and 2e-2 (s_kv), a shortcut added early or a bias that leaks
# into the weights by 1e-2 or more.
TOL_F32 = 1e-4
# bf16 activations and cache against the float32 reference on the same
# float32 weights: 8 bits of mantissa through 4 attention sublayers and the
# x6 router weights; measured 1.2e-2 on logits of ~0.3, the limit is four
# times that. A wrong page, position or plane gives 0.2 or more.
TOL_BF16 = 5e-2


@functools.cache
def _model(compute_dtype=jnp.float32, bias_seed=None):
    """(cfg, params), built once for the cases that ask for the same (none
    writes into the tree); bias_seed draws a non-zero router bias b (the
    configuration assumes zeros: seeded weights route evenly already)."""
    cfg = MODEL.model_config(TINY, "float32", compute_dtype=compute_dtype)
    params = MODEL.init_params(cfg, seed=5)
    if bias_seed is not None:
        mp = params["block"]["first"]["moe"]
        mp["router_bias"] = 0.01 * jax.random.normal(
            jax.random.PRNGKey(bias_seed), mp["router_bias"].shape)
    return cfg, params


def _reference(params, tokens, config=TINY, **kw):
    tokens = jnp.asarray(tokens)
    pos = jnp.broadcast_to(jnp.arange(tokens.shape[1]), tokens.shape)
    return np.asarray(MODEL.reference_logits(
        params, config, tokens, jnp.zeros_like(tokens), pos, **kw))


def _tokens(shape, seed=0):
    return np.random.default_rng(seed).integers(
        0, TINY["vocab_size"], shape).astype(np.int32)


@functools.cache
def _paged_steps(compute_dtype, max_len):
    """The two jitted steps of TINY in a compute type, compiled once."""
    cfg, _ = _model(compute_dtype)
    return (jax.jit(lambda *a: _paged_multiquery_step(*a, cfg, max_len)),
            jax.jit(lambda *a: _paged_decode_step(*a, cfg, max_len)))


def _prefill_then_decode(cfg, params, prompt, n_new, chunk=8, bs=4):
    """The engine's two step functions on a hand-made page table: the
    prompt in [1, chunk] calls (the last one ragged), then n_new greedy
    decode steps. Returns (tokens fed, logits at every position, pools,
    the last decode step's routing counts)."""
    max_len = 64
    nb = max_len // bs
    dt = cfg.compute_dtype
    pages = (jnp.zeros((cfg.kv_planes, nb, bs, cfg.kv_lora_rank), dt),
             jnp.zeros((cfg.kv_planes, nb, bs, cfg.qk_pos_emb_head_dim), dt))
    table = jnp.arange(nb, dtype=jnp.int32)[None]
    active = jnp.ones((1,), bool)
    prefill, decode = _paged_steps(dt, max_len)
    rows, pos, counts = [], 0, None
    while pos < len(prompt):
        count = min(chunk, len(prompt) - pos)
        buf = np.zeros((1, chunk), np.int32)
        buf[0, :count] = prompt[pos:pos + count]
        logits, _, pages = prefill(
            params, jnp.asarray(buf), pages, table,
            jnp.asarray([pos], jnp.int32), jnp.asarray([count], jnp.int32),
            active)
        rows.append(np.asarray(logits[0, :count]))
        pos += count
    seq = list(prompt)
    for _ in range(n_new):
        seq.append(int(np.argmax(rows[-1][-1])))
        logits, counts, pages = decode(
            params, jnp.asarray([[seq[-1]]], jnp.int32), pages, table,
            jnp.asarray([len(seq) - 1], jnp.int32), active)
        rows.append(np.asarray(logits))
    return np.asarray(seq, np.int32), np.concatenate(rows), pages, counts


class TestForward:
    """(a) and (e): the whole-sequence (training-shaped) forward."""

    @pytest.mark.parametrize("bias_seed", [None, 3], ids=["b=0", "b-seeded"])
    def test_gpt_forward_matches_reference(self, bias_seed):
        cfg, params = _model(bias_seed=bias_seed)
        toks = _tokens((2, 24))
        logits, _ = gpt_forward(params, jnp.asarray(toks), cfg)
        ref = _reference(params, toks)
        assert np.abs(ref).max() > 0.1
        assert np.abs(np.asarray(logits) - ref).max() < TOL_F32

    @pytest.mark.parametrize("field,index", [
        ("mla_scale_q_lora", 0), ("mla_scale_kv_lora", 1)])
    def test_each_scale_correction_is_live(self, field, index):
        """A program that drops s_q or s_kv fails (a) by its tolerance, and
        agrees with a reference that drops the same one: the gap is the
        correction's, nothing else's."""
        cfg, params = _model()
        cfg = dataclasses.replace(cfg, **{field: False})
        toks = _tokens((1, 24))
        logits = np.asarray(gpt_forward(params, jnp.asarray(toks), cfg)[0])
        assert np.abs(logits - _reference(params, toks)).max() > 10 * TOL_F32
        scales = tuple(i != index for i in range(2))
        assert np.abs(logits - _reference(params, toks, scales=scales)
                      ).max() < TOL_F32

    def test_shortcut_is_added_after_the_second_ffn(self):
        """The MoE's output joins the stream at the layer's end: a layer
        that adds it where it is computed differs (the second attention
        sublayer would see it)."""
        cfg, params = _model()
        toks = _tokens((1, 16))
        x = jnp.take(params["embedding"]["word"], jnp.asarray(toks), axis=0)
        p0 = jax.tree.map(lambda a: a[0], params["block"])
        (out, _), _ = layer_forward(p0, x, cfg, layer_id=0)
        (x2, _), (_, m) = layer_forward(p0["first"], x, cfg, layer_id=0)
        (early, _), _ = layer_forward(p0["second"], x2 + m, cfg, layer_id=0)
        (late, _), _ = layer_forward(p0["second"], x2, cfg, layer_id=0)
        assert np.abs(np.asarray(out - (late + m))).max() < 1e-6
        assert np.abs(np.asarray(out - early)).max() > 1e-4


def test_reference_passes_pack_whole_requests_as_segments():
    """cells/serve_closed_share.py checks 2-3 requests in one pass of the
    reference: packed as segments, positions restarting, each request reads
    what it reads alone."""
    runner = manifest.load_module("cells", "serve_closed_share")
    assert runner.pack([30, 40, 50, 20, 128, 1], 128) == [
        [0, 1, 2], [3], [4], [5]]
    with pytest.raises(ValueError):
        runner.pack([129], 128)
    _, params = _model(bias_seed=3)
    recs = [types.SimpleNamespace(prompt=_tokens((p,), p), n=n,
                                  toks=_tokens((n,), p + n).tolist())
            for p, n in ((9, 5), (17, 3), (20, 11), (60, 4))]
    together = runner._reference_gaps(MODEL, params, recs, TINY, 64, None)
    for r, got in zip(recs, together):
        seq = np.concatenate([r.prompt, r.toks[:-1]])[None]
        lg = _reference(params, seq)[0, len(r.prompt) - 1:]
        alone = lg.max(-1) - lg[np.arange(r.n), r.toks]
        assert got.shape == (r.n,)
        assert np.abs(got - alone).max() < 1e-5


class TestPagedTwoPlanes:
    """(b), (f): prefill in chunks, then decode, through two planes a
    layer."""

    @pytest.mark.parametrize("dtype,above,below", [
        (jnp.float32, 0.0, TOL_F32), (jnp.bfloat16, TOL_F32, TOL_BF16)],
        ids=["fp32", "bf16"])
    def test_prefill_then_decode_matches_reference(self, dtype, above, below):
        """Chunked prefill (20 tokens in chunks of 8: two chunk edges inside
        the prompt and a ragged tail), then 12 decoded tokens through the
        paged latent pools: the logits at every position against the
        reference's one full forward pass. bf16's gap also lies ABOVE
        float32's limit: the two limits tell the types apart."""
        cfg, params = _model(compute_dtype=dtype, bias_seed=3)
        seq, logits, pages, _ = _prefill_then_decode(
            cfg, params, _tokens((20,), 1), 12)
        assert len(seq) == 32 and logits.shape[0] == 32
        gap = np.abs(logits - _reference(params, seq[None])[0]).max()
        print(f"{dtype.__name__}: largest gap {gap:.3e}")
        assert above <= gap < below
        # every plane holds the 32 cached rows of its own sublayer
        lat = np.asarray(pages[0], np.float32).reshape(cfg.kv_planes, -1,
                                                       cfg.kv_lora_rank)
        assert cfg.kv_planes == 4
        assert (np.abs(lat[:, :32]).sum(-1) > 0).all()
        assert not np.abs(lat[:, 32:]).any()
        assert len({lat[p, :32].tobytes() for p in range(4)}) == 4

    def test_the_latent_is_cached_scaled(self):
        """What the pool holds is c' = s_kv RMS(c): its rows' RMS is s_kv
        times the norm's scale of 1, not 1."""
        cfg, params = _model()
        _, _, pages, _ = _prefill_then_decode(cfg, params,
                                              _tokens((9,), 2), 1)
        rows = np.asarray(pages[0]).reshape(cfg.kv_planes, -1,
                                            cfg.kv_lora_rank)[:, :9]
        rms = np.sqrt((rows ** 2).mean(-1))
        assert np.allclose(rms, (cfg.hidden_size / cfg.kv_lora_rank) ** 0.5,
                           rtol=1e-3)

    def test_engine_pool_has_two_planes_a_layer(self, lend):
        cfg, params = _model()
        eng = lend(_shared_engine())
        line = eng.startup_line()
        for said in ("2 double layers x 2 attention sublayers = 4 planes",
                     "experts=4 held (0..3) of 8 published + 4 zero-compute",
                     "top-3 of 12", "vocabulary=512 rows, a slice of 4096"):
            assert said in line, line
        greedy = SamplingParams(greedy=True)
        reqs = [eng.requests[eng.add_request(_tokens((n,), n), 6, greedy)]
                for n in (10, 19)]
        eng.run_to_completion()
        assert eng.pool.audit()
        pool = eng.stats_snapshot()["pool"]
        per_token = 2 * cfg.num_layers * (
            cfg.kv_lora_rank + cfg.qk_pos_emb_head_dim) * 4    # float32
        assert per_token == MODEL.kv_bytes_per_token(TINY, "float32")
        assert pool["pool_bytes_total"] == 24 * 4 * per_token
        for r in reqs:       # the engine's stream is the reference's argmax
            ref = _reference(params, r.tokens[None])[0]
            n = len(r.prompt)
            assert r.tokens[n:].tolist() == np.argmax(
                ref[n - 1:-1], -1).tolist()


def _moe_layer(held, bias=None, **kw):
    """(cfg, one layer's MoE params for the share `held`) cut from the
    8-expert layer every share is a part of."""
    whole_cfg = MODEL.model_config(
        dict(TINY, n_routed_experts=8), "float32",
        compute_dtype=jnp.float32, **kw)
    whole, _ = moe.init_moe_params(jax.random.PRNGKey(11), whole_cfg, 0.02)
    if bias is not None:
        whole["router_bias"] = jnp.asarray(bias, jnp.float32)
    first, count = held
    cfg = dataclasses.replace(whole_cfg, moe_experts_held=held)
    part = dict(whole, fc1_kernel=whole["fc1_kernel"][first:first + count],
                fc2_kernel=whole["fc2_kernel"][first:first + count])
    return cfg, part, whole


ROUTE = (8, 0, 3, 6.0)      # published experts, first held, top-k, gamma


class TestExpertShares:
    """(c), (d), (g): the expert layer that is told which experts it
    holds."""

    @pytest.mark.parametrize("shares", [
        [(0, 8)], [(0, 4), (4, 4)], [(0, 2), (2, 6)],
        [(0, 3), (3, 3), (6, 2)], [(0, 1), (1, 7)]],
        ids=lambda s: "+".join(f"{a}.{b}" for a, b in s))
    def test_the_shares_add_up_to_the_whole_layer(self, shares):
        """For every split of the 8 experts into shares: the sum over the
        shares of the routed term, plus the identity term counted once, is
        the uncut reference's M(h)."""
        h = jax.random.normal(jax.random.PRNGKey(1), (2, 12, 64))
        _, _, whole = _moe_layer((0, 8))
        routed_ref, identity_ref = MODEL.moe_terms(h, whole, ROUTE)
        total = np.zeros(h.shape, np.float32)
        for held in shares:
            cfg, part, _ = _moe_layer(held)
            out, _ = moe.moe_forward(part, h, cfg)
            total += np.asarray(out - identity_ref)     # the routed term
            # and the reference of the same share says the same
            r, i = MODEL.moe_terms(h, part, (8, held[0], 3, 6.0))
            assert np.abs(np.asarray(out - (r + i))).max() < 1e-5
        assert np.abs(np.asarray(identity_ref)).max() > 1e-2
        assert np.abs(np.asarray(routed_ref)).max() > 1e-4
        assert np.abs(total + np.asarray(identity_ref)
                      - np.asarray(routed_ref + identity_ref)).max() < 1e-5

    def test_the_bias_selects_and_the_weights_stay_unbiased(self):
        bias = 0.01 * np.random.default_rng(4).standard_normal(12)
        cfg, part, _ = _moe_layer((0, 4), bias=bias)
        x = jax.random.normal(jax.random.PRNGKey(2), (64, 64))
        idx_b, w_b, _ = moe._router(part, x, cfg)
        idx_0, w_0, _ = moe._router(dict(part, router_bias=jnp.zeros(12)),
                                    x, cfg)
        probs = np.asarray(jax.nn.softmax(
            x.astype(jnp.float32) @ part["router_kernel"], axis=-1))
        changed = (np.sort(np.asarray(idx_b), -1)
                   != np.sort(np.asarray(idx_0), -1)).any(-1)
        assert 4 < changed.sum() < 60        # the bias moves some selections
        want = np.argsort(-(probs + bias), -1)[:, :3]
        assert (np.sort(want, -1) == np.sort(np.asarray(idx_b), -1)).all()
        # weights: gamma x p, unbiased, not renormalised
        assert np.allclose(np.asarray(w_b), 6.0 * np.take_along_axis(
            probs, np.asarray(idx_b), -1), rtol=1e-6)
        assert not np.allclose(np.asarray(w_b).sum(-1), 6.0)

    def test_a_token_on_identity_experts_alone_costs_no_gemm_row(self):
        """With b = +1 on the 4 zero-compute experts every token's 3 picks
        are identity experts: M(h) = (sum w) h, no row belongs to a group
        (so the grouped GEMMs run no tile), and the counters say so."""
        bias = np.where(np.arange(12) >= 8, 1.0, 0.0)
        cfg, part, _ = _moe_layer((0, 4), bias=bias)
        h = jax.random.normal(jax.random.PRNGKey(3), (1, 10, 64))
        rows = jnp.ones((1, 10), bool)
        out, counts = moe.moe_forward(part, h, cfg, count_rows=rows)
        idx, w, _ = moe._router(part, h.reshape(10, 64), cfg)
        assert (np.asarray(idx) >= 8).all()
        assert np.abs(np.asarray(out[0]) - np.asarray(
            w.sum(-1, keepdims=True) * h[0])).max() < 1e-6
        got = dict(zip(moe.HELD_COUNTS, np.asarray(counts).tolist()))
        assert got == {"assignments": 30, "expert_pairs_touched": 0,
                       "assignments_zero": 30, "assignments_here": 0,
                       "assignments_absent": 0, "here_max_rows": 0}
        slot, count = moe._held_slot(idx.reshape(-1), cfg)
        assert count == 4 and (np.asarray(slot) == 4).all()

    def test_held_counts_against_a_count_by_hand(self):
        """Held experts 2..4 of 8, 4 zero-compute; rows 0, 1 and 3 are
        tokens, row 2 is padding."""
        cfg, _, _ = _moe_layer((2, 3))
        idx = jnp.asarray([[2, 3, 9], [2, 0, 11], [2, 2, 2], [4, 7, 2]])
        rows = jnp.asarray([True, True, False, True])
        got = dict(zip(moe.HELD_COUNTS, np.asarray(
            moe.routing_counts_held(idx, rows, cfg)).tolist()))
        assert got == {"assignments": 9, "expert_pairs_touched": 3,
                       "assignments_zero": 2, "assignments_here": 5,
                       "assignments_absent": 2, "here_max_rows": 3}

    def test_engine_counters_add_up(self):
        cfg, params = _model(bias_seed=3)
        eng = DynamicInferenceEngine(params, cfg, max_batch=2,
                                     max_seq_len=64, paged=True,
                                     num_blocks=24, block_size=4,
                                     prefill_chunk=8)
        for seed in (4, 5):
            eng.add_request(_tokens((10,), seed), 6)
        eng.run_to_completion()
        got = eng.stats_snapshot()["moe"]
        # 2 requests x 5 decode rounds (the first token is prefill's) x
        # top-3 x 2 MoE layers; a round can touch 2 x 4 held pairs.
        assert got["decode_rounds"] == 5 and got["tokens"] == 10
        assert got["assignments"] == 10 * 3 * 2
        assert got["assignments_zero"] + got["assignments_here"] \
            + got["assignments_absent"] == got["assignments"]
        assert min(got["assignments_zero"], got["assignments_here"],
                   got["assignments_absent"]) > 0
        assert got["experts_here"] == 4
        assert got["expert_pairs_possible"] == 5 * 2 * 4
        assert 0 < got["expert_pairs_touched"] <= got["assignments_here"]
        # a layer's busiest held expert got at least the mean and at most
        # all of that layer's rows
        assert got["assignments_here"] / 4 <= got["here_max_rows"] \
            <= got["assignments_here"]
        runner = manifest.load_module("cells", "serve_closed_share")
        assert runner.share_problems(got, TINY) == []
        assert runner.share_problems(dict(got, assignments_absent=0), TINY)
        assert runner.share_problems(dict(got, experts_here=8), TINY)


@functools.cache
def _shared_engine():
    """The engine of `_model()`, compiled once, for the cases that leave it
    as they found it (conftest.py `lend`)."""
    cfg, params = _model()
    return DynamicInferenceEngine(params, cfg, max_batch=2, max_seq_len=64,
                                  paged=True, num_blocks=24, block_size=4,
                                  prefill_chunk=8)


class _Ctx(types.SimpleNamespace):
    ep, cp, tp, dp = 2, 1, 1, 1


def _engine(**kw):
    cfg, params = _model()
    return DynamicInferenceEngine(
        params, cfg, max_batch=2, max_seq_len=64,
        **dict(dict(paged=True, num_blocks=16, block_size=4), **kw))


def _layer(**kw):
    cfg, params = _model()
    p0 = jax.tree.map(lambda a: a[0], params["block"])
    return layer_forward(p0, jnp.zeros((1, 8, 64)), cfg, layer_id=0, **kw)


def _tiny_cfg(**kw):
    base = dict(num_layers=2, hidden_size=64, num_attention_heads=4,
                num_moe_experts=8)
    return TransformerConfig(**dict(base, **kw))


@pytest.mark.parametrize("build,error,said", [
    (lambda: _engine(kv_cache_dtype="int8"), ValueError, "int8/fp8 latent"),
    (lambda: _engine(ctx=_Ctx()), ValueError, "all-to-all between expert"),
    (lambda: _engine(adapter_cache=object()), ValueError, "lora"),
    (lambda: _engine(pool=object()), ValueError, "an injected pool"),
    (lambda: _layer(tp_sharded=True), NotImplementedError, "tp-sharded"),
    (lambda: _layer(fp8={}), NotImplementedError, "fp8"),
    (lambda: _layer(kv_cache=(None, None)), NotImplementedError,
     "a dense (unpaged) KV cache"),
    (lambda: moe.moe_forward({}, jnp.zeros((2, 4, 64)), _model()[0],
                             ctx=_Ctx()), NotImplementedError,
     "no ep all-to-all between shares"),
    (lambda: init_gpt_params(jax.random.PRNGKey(0), _model()[0], pp=2),
     ValueError, "not pipelined"),
    (lambda: _tiny_cfg(num_moe_experts=None, moe_zero_experts=4),
     ValueError, "facts of a dropless MoE model"),
    (lambda: _tiny_cfg(moe_experts_held=(6, 4)), ValueError,
     "no (first, count) within"),
    (lambda: _tiny_cfg(moe_shortcut_double_layer=True, moe_first_k_dense=1),
     ValueError, "no moe_first_k_dense"),
    (lambda: _tiny_cfg(mla_scale_kv_lora=True), ValueError,
     "scale the latents of multi_latent_attention"),
    (lambda: _tiny_cfg(vocab_size=512, vocab_slice_of=256), ValueError,
     "a slice of"),
], ids=["int8-pool", "mesh", "lora", "injected-pool",
        "tp-sharded", "fp8", "dense-kv-cache", "ep-all-to-all", "pp",
        "zero-experts-no-moe", "held-out-of-range", "double+lead-dense",
        "scale-without-mla", "slice-smaller-than-vocab"])
def test_what_it_cannot_do_yet_refuses_in_words(build, error, said):
    """(i)"""
    with pytest.raises(error, match=said.replace("(", r"\(").replace(
            ")", r"\)")):
        build()


def test_deepseek_decode_step_did_not_grow_an_operation(lend):
    """The shared MLA and MoE code serves DeepSeek-V2-Lite with the new
    fields off: its traced decode step (tiny widths, 1 dense + 2 MoE layers)
    launches what it launched before the double layer came (PR 38: 1,345
    launches, 1,360 dispatches) and, since ISSUE 39, one expansion a latent
    call more (`launch_stats`, read off the jaxpr): 3 latent kernels, 15
    loop steps, 1,351 launches (each of the 3 calls' latent sums goes
    through kv_up's value columns after the walk: a product and its
    operand's conversion, where `w_v` was reshaped for the kernel), no
    slice of an expert stack. Since ISSUE 43 the two MoE layers' grouped
    GEMMs are Pallas calls (3 + 4 kernels) whose visit lists are made in the
    step: 63 launches a call where ``lax.ragged_dot``'s zero-padded sizes
    were 4 (1,603; the two calls of a layer share one list once compiled).
    Since ISSUE 60 the two MoE layers move their rows by gathers alone: 18
    equations a layer more (the six slabs of a token's picks written out),
    less the dispatch gather's bounds check, 3 a layer (1,633), and
    `scatters` 0."""
    model = manifest.load_module("models", "deepseek_v2")
    with open(os.path.join(ROOT, "perfbench", "configs",
                           "deepseek-v2-lite.json")) as f:
        tiny = dict(json.load(f), **model.REHEARSAL)
    cfg = model.model_config(tiny, "float32", compute_dtype=jnp.float32)
    eng = DynamicInferenceEngine(
        model.init_params(cfg, seed=5), cfg, max_batch=2, max_seq_len=64,
        paged=True, num_blocks=16, block_size=4, prefill_chunk=8)
    assert eng.stats_snapshot(include_dispatch=True)["decode_dispatch"] == {
        "launches": 1633, "kernels": 7, "loop_steps": 15, "eqns": 1,
        "dispatches_per_step": 1648, "expert_stack_slices": 0, "scatters": 0,
        # ISSUE 46: a step of the latent walk, 16 blocks of 4 rows of
        # 32 + 8 float32 columns, none of them whole tiles
        "page_copies_step": {"paged_decode_latent": 32},
        "page_copy_bytes": {"paged_decode_latent": [4 * 32 * 4, 4 * 8 * 4]},
        "page_copies_kernel": {"paged_decode_latent": 0}}
    moe_stats = eng.stats_snapshot()["moe"]
    assert moe_stats["experts_here"] == 8          # every expert is held
    # and the double layer's own step: two latent kernels a layer, the
    # held experts' stacks read in place
    cfg, _ = _model()
    eng = lend(_shared_engine())
    disp = eng.stats_snapshot(include_dispatch=True)["decode_dispatch"]
    assert cfg.kv_planes == 4
    assert disp["kernels"] == cfg.kv_planes + 2 * cfg.num_layers
    assert disp["expert_stack_slices"] == 0
    assert disp["scatters"] == 0            # a share held, zero-compute picks


# sha256 of the two paged steps' lowered text, by _agent_step_texts; pinned
# at the parent commit (7ea5e97) until ISSUE 60, and re-pinned there on the
# change's own tree: that issue's change IS to these steps' MoE part (the
# scatter-add of the pick rows and `bincount` went, a second sort and a
# gather of the picks' rows came), with the ladder still out of a serving
# step's way, which is what the test below holds.
PARENT_STEP_SHA = {
    ("float32", "prefill"):
        "78016fc5624b6ef8", ("float32", "decode"): "b733621241e60a8c",
    ("bfloat16", "prefill"):
        "07c98fdfe223e567", ("bfloat16", "decode"): "8569f13b6e47714f",
}


def _agent_step_texts(compute_dtype):
    """{step: sha256[:16] of its StableHLO}: the double layer's prefill call
    (1 x 8 rows) and decode round at TINY's widths, traced from shapes."""
    import hashlib
    cfg = MODEL.model_config(TINY, "float32", compute_dtype=compute_dtype)
    params = jax.eval_shape(lambda: MODEL.init_params(cfg, seed=5))
    max_len, bs, chunk = 64, 4, 8
    nb = max_len // bs
    sds = jax.ShapeDtypeStruct
    pages = (sds((cfg.kv_planes, nb, bs, cfg.kv_lora_rank), compute_dtype),
             sds((cfg.kv_planes, nb, bs, cfg.qk_pos_emb_head_dim),
                 compute_dtype))
    table, one = sds((1, nb), jnp.int32), sds((1,), jnp.int32)
    active = sds((1,), bool)
    texts = {
        "prefill": jax.jit(
            lambda *a: _paged_multiquery_step(*a, cfg, max_len)).lower(
            params, sds((1, chunk), jnp.int32), pages, table, one, one,
            active).as_text(),
        "decode": jax.jit(
            lambda *a: _paged_decode_step(*a, cfg, max_len)).lower(
            params, sds((1, 1), jnp.int32), pages, table, one,
            active).as_text()}
    return {k: hashlib.sha256(t.encode()).hexdigest()[:16]
            for k, t in texts.items()}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_the_paged_steps_lower_to_the_parents_text(dtype, monkeypatch):
    """ISSUE 49 gave the held experts' row buffer a ladder of sizes; a call
    as small as a serving step's keeps the one T*k buffer and with it the
    program it had, instruction for instruction: no switch is built
    (`_laddered_rows` is never reached) and the steps' lowered text is the
    parent commit's. (A change to the steps' other code moves these hashes
    too: re-pin them from the commit before it.)"""
    monkeypatch.setattr(moe, "_laddered_rows", None)
    got = _agent_step_texts(dtype)
    assert got == {step: sha for (name, step), sha in PARENT_STEP_SHA.items()
                   if name == jnp.dtype(dtype).name}


class TestThreeSourcesAgree:
    """The preset, the benchmark's configuration file and the catalog's row
    say the same model; the file differs by its three `reduced` keys."""

    def test_file_holds_the_catalog_numbers(self):
        if not os.path.exists(CATALOG):
            pytest.skip("no catalog beside the model-configs guide here")
        with open(CATALOG) as f:
            rows = [json.loads(line) for line in f]
        row = next(r for r in rows if r["name"] == "LongCat-Flash-Chat")
        assert PUBLISHED["source"] == row["source_url"]
        assert PUBLISHED["reduced"] == ["num_layers", "n_routed_experts",
                                        "vocab_size"]
        for key, value in row["config"].items():
            if key in PUBLISHED["reduced"]:
                assert PUBLISHED["published"][key] == value, key
            else:
                assert PUBLISHED[key] == value, key
        assert (PUBLISHED["num_layers"], PUBLISHED["n_routed_experts"],
                PUBLISHED["vocab_size"]) == (4, 16, 16384)
        assert PUBLISHED["router_width"] == 512 + 256
        for said in ("mla_scale_q_lora", "mla_scale_kv_lora", "router_bias",
                     "norm_topk_prob", "init_std", "rope_pairing"):
            assert said in PUBLISHED["assumed"]

    def test_preset_is_the_file_uncut(self):
        preset = PRESETS["longcat-flash-chat"]()
        uncut = dict(PUBLISHED, **PUBLISHED["published"])
        built = MODEL.model_config(uncut, "float32")
        for field in dataclasses.fields(preset):
            a, b = getattr(preset, field.name), getattr(built, field.name)
            if field.name in ("moe_experts_held", "vocab_slice_of"):
                # the uncut file holds every expert and the whole vocabulary
                assert (a, b) in ((None, (0, 512)), (None, 131072))
            elif isinstance(a, float):
                assert a == pytest.approx(b), field.name
            else:
                assert a == b, field.name

    def test_benchmark_entry_points_at_the_file(self):
        with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
            manifest_json = json.load(f)
        entry = next(c for c in manifest_json["configs"]
                     if c["name"] == "longcat-flash-chat")
        assert entry["file"] == "perfbench/configs/longcat-flash-chat.json"
        assert entry["source"] == PUBLISHED["source"]
        assert entry["reduced"] == PUBLISHED["reduced"]
        cell = next(w for w in manifest_json["workloads"]
                    if w["config"] == "longcat-flash-chat")
        assert cell["chips"] == 1 and cell["traffic"] == "agent-closed"
        assert MODEL.kv_bytes_per_token(PUBLISHED, "bfloat16") == 9216
        # a token meets 2 attentions, 2 dense FFNs, the router and
        # 12 x 16 / 768 experts: 638.9M + 0.25 x 37.75M parameters a layer
        assert MODEL.params_per_token(PUBLISHED) == pytest.approx(
            648.3e6, rel=1e-3)
