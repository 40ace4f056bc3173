"""LFM2-24B-A2B's architecture against its plain float32 reference
(perfbench/models/lfm2_moe.py: the published equations in jax.numpy, the
convolution as three shifted products, every expert over every position), at
tiny widths on the CPU with seeded random weights: 5 layers = a leading dense
convolution layer, then (attention, conv, conv, conv) with 8 experts of
which a token takes 2 by sigmoid scores and a NON-ZERO seeded selection
bias, 4 query and 2 key/value heads of 16. Each test fails if the mechanism
it names is left out."""
import dataclasses
import functools
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from megatronapp_tpu.inference.dynamic_engine import DynamicInferenceEngine
from megatronapp_tpu.inference.engine import SamplingParams
from perfbench import manifest

from jitted import gpt_forward  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MODEL = manifest.load_module("models", "lfm2_moe")
with open(os.path.join(ROOT, "perfbench", "configs",
                       "lfm2-24b-a2b.json")) as f:
    PUBLISHED = json.load(f)
TINY = {**PUBLISHED, **MODEL.REHEARSAL}
# Weights at std 0.1, not 0.02: at 64 columns an operator's output is then
# large enough beside the residual stream for every part of it to show in the
# logits (std ~1).
STD = 0.1

# float32 on both sides: what is left is the order of summation (the
# program's grouped GEMMs and paged kernels against the reference's loops);
# the two agree to 2e-6 on logits of std 0.9 (measured). With softmax scores
# they differ by 0.2, with the selection bias dropped by 0.3, with a tail
# not carried across a call's edge or not reset at admission by 0.3 to 1.
TOL_F32 = 1e-4
# bf16 activations, tails and KV rows against the float32 reference on the
# same float32 weights, through 5 layers: up to 0.11 on those logits where
# no near-tie of the router flips (measured); a flipped pick moves a token's
# logits by about 0.2, so the limit is that of a missing mechanism's half.
TOL_BF16 = 0.3
GREEDY = SamplingParams(greedy=True)


_MODELS = {}


def _model(compute_dtype=jnp.float32, tiny=TINY):
    """The tiny model with a seeded, non-zero selection bias (made once a
    shape: no test writes into the tree it is handed)."""
    key = (jnp.dtype(compute_dtype).name, json.dumps(tiny, sort_keys=True))
    if key not in _MODELS:
        _MODELS[key] = _make_model(compute_dtype, tiny)
    return _MODELS[key]


def _make_model(compute_dtype, tiny):
    cfg = MODEL.model_config(tiny, "float32", compute_dtype=compute_dtype,
                             init_method_std=STD)
    params = MODEL.init_params(cfg, seed=5)
    moe = params["block"]["ffn"]["moe"]
    bias = 0.3 * jax.random.normal(jax.random.PRNGKey(7),
                                   moe["router_bias"].shape)
    params["block"]["ffn"]["moe"] = dict(moe, router_bias=bias)
    return cfg, params


def _reference(params, tokens, segment_ids=None, positions=None, **kw):
    tokens = jnp.asarray(tokens)
    if segment_ids is None:
        segment_ids = jnp.zeros_like(tokens)
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(tokens.shape[1]),
                                     tokens.shape)
    return np.asarray(MODEL.reference_logits(
        params, TINY, tokens, jnp.asarray(segment_ids),
        jnp.asarray(positions), **kw))


def _tokens(n, seed=0):
    return np.random.default_rng(seed).integers(
        0, TINY["vocab_size"], (n,)).astype(np.int32)


def _engine(cfg, params, **kw):
    kw = {"max_batch": 3, "max_seq_len": 64, "paged": True, "num_blocks": 24,
          "block_size": 4, "prefill_chunk": 8, **kw}
    return DynamicInferenceEngine(params, cfg, **kw)


@functools.cache
def _shared_engine(dtype=jnp.float32, width=8):
    """The engine of `_model(dtype)` whose prefill calls are `width` wide:
    one a program, compiled once, for the cases (tests/test_lfm2_engine.py's)
    that leave it as they found it. They take it through `lend`
    (conftest.py)."""
    return _engine(*_model(dtype), prefill_chunk=width)


@pytest.fixture
def eng(lend):
    return lend(_shared_engine())


def _recorded(eng, monkeypatch):
    """Wrap the engine's two steps for the case: logits[rid] collects,
    position by position, the logits every call computed for that request."""
    logits = {}
    mq, dec = eng._mq_step, eng._decode

    def mq_step(*a):
        # the engine asks for its last position's logits alone (a[10]);
        # take every position's, and hand it the one it asked for
        logits_all, hid, pools = mq(*a[:10])
        last = int(a[10][0])
        out = (logits_all[:, last:last + 1], hid[:, last:last + 1], pools)
        slot = int(a[9][0])
        logits.setdefault(eng.slots[slot].request_id, []).append(
            np.asarray(logits_all[0, :int(a[6][0])], np.float32))
        return out

    def decode(*a):
        out = dec(*a)
        for slot in np.flatnonzero(np.asarray(a[6])):
            logits[eng.slots[slot].request_id].append(
                np.asarray(out[0][slot:slot + 1], np.float32))
        return out

    monkeypatch.setattr(eng, "_mq_step", mq_step)
    monkeypatch.setattr(eng, "_decode", decode)
    return logits


def _worst_gap(params, req, logits):
    """Largest |engine - reference| over every position of a finished
    request: the reference runs the request's own tokens in one pass."""
    seq = req.tokens[:-1]
    got = np.concatenate(logits[req.request_id])
    assert got.shape[0] == len(seq), (got.shape, len(seq))
    return np.abs(got - _reference(params, seq[None])[0]).max()


class TestForward:
    def test_the_stack_is_the_published_pattern(self):
        cfg, params = _model()
        assert (cfg.num_conv_layers, cfg.num_attention_layers,
                cfg.num_ssm_layers, cfg.kv_planes) == (4, 1, 0, 1)
        # one attention layer: its period is the whole stack
        assert (cfg.attn_layer_period, cfg.attn_layer_offset,
                cfg.moe_first_k_dense) == (5, 1, 1)
        block = params["block"]
        assert block["mixers_conv"]["conv"]["in_kernel"].shape == (4, 64, 192)
        assert block["mixers_conv"]["conv"]["conv_kernel"].shape == (4, 3, 64)
        assert block["mixers_attn"]["attention"]["q_ln_scale"].shape == (1, 16)
        assert block["ffn_lead"]["mlp"]["fc1_kernel"].shape == (1, 64, 192)
        assert block["ffn"]["moe"]["fc1_kernel"].shape == (4, 8, 64, 64)
        assert block["ffn"]["moe"]["router_bias"].shape == (4, 8)
        assert "lead_block" not in params and "output" not in params
        # the cell's cut and the whole published stack, from the same list
        cut = MODEL.model_config(PUBLISHED, "bfloat16")
        assert (cut.num_layers, cut.attn_layer_offset, cut.moe_first_k_dense,
                cut.num_conv_layers, cut.kv_planes) == (9, 1, 1, 7, 2)
        whole = MODEL.model_config(
            {**PUBLISHED, **PUBLISHED["published"]}, "bfloat16")
        assert (whole.num_layers, whole.attn_layer_period,
                whole.attn_layer_offset, whole.moe_first_k_dense,
                whole.num_conv_layers) == (40, 4, 2, 2, 30)
        from megatronapp_tpu.models.presets import PRESETS
        assert dataclasses.replace(
            PRESETS["lfm2-24b-a2b"](), params_dtype=jnp.bfloat16) == whole

    @pytest.mark.parametrize("packed", [False, True],
                             ids=["rows", "segment_ids"])
    def test_gpt_forward_matches_reference(self, packed):
        """Whole sequences; packed, a row holds three documents: a tap never
        reads another segment, attention stays inside one, positions
        restart."""
        cfg, params = _model()
        toks = np.stack([_tokens(40, 1), _tokens(40, 2)])
        seg = pos = None
        if packed:
            seg = np.repeat([[0, 1, 2]], 2, axis=0).repeat(
                [13, 1, 26], axis=1)
            pos = np.concatenate([np.arange(13), np.arange(1),
                                  np.arange(26)])[None].repeat(2, axis=0)
        logits, _ = gpt_forward(params, jnp.asarray(toks), cfg,
                                segment_ids=None if seg is None
                                else jnp.asarray(seg))
        ref = _reference(params, toks, seg, pos)
        assert np.abs(np.asarray(logits) - ref).max() < TOL_F32
        if packed:      # ... and the segments are not a no-op
            assert np.abs(ref - _reference(params, toks)).max() > 0.1

    @pytest.mark.parametrize("layers,lead,types", [
        (9, 2, "ccaccca" "cc"), (7, 1, "cccacc" "c"), (6, 1, "caccca")])
    def test_any_depth_lead_and_offset(self, layers, lead, types):
        """Leading dense layers of both kinds before the first period, whole
        periods under one outer scan, then a partial one."""
        tiny = {**TINY, "num_hidden_layers": layers, "num_dense_layers": lead,
                "layer_types": [{"c": "conv", "a": "full_attention"}[t]
                                for t in types]}
        cfg, params = _model(tiny=tiny)
        toks = jnp.asarray(_tokens(20, 3)[None])
        logits, _ = gpt_forward(params, toks, cfg)
        ref = MODEL.reference_logits(
            params, tiny, toks, jnp.zeros_like(toks),
            jnp.arange(20)[None])
        assert np.abs(np.asarray(logits) - np.asarray(ref)).max() < TOL_F32


class TestRouter:
    def test_sigmoid_scores_biased_selection_unbiased_weights(self):
        from megatronapp_tpu.transformer.moe import _router
        cfg, params = _model()
        moe = jax.tree.map(lambda a: a[0], params["block"]["ffn"]["moe"])
        h = jax.random.normal(jax.random.PRNGKey(3), (64, 64))
        idx, w, aux = _router(moe, h, cfg)
        s = 1 / (1 + np.exp(-np.asarray(h, np.float64)
                            @ np.asarray(moe["router_kernel"], np.float64)))
        b = np.asarray(moe["router_bias"], np.float64)
        want = np.argsort(-(s + b), axis=-1)[:, :2]
        np.testing.assert_array_equal(np.sort(np.asarray(idx), -1),
                                      np.sort(want, -1))
        picked = np.take_along_axis(s, np.asarray(idx), axis=-1)
        np.testing.assert_allclose(
            np.asarray(w), picked / (picked.sum(-1, keepdims=True) + 1e-6),
            rtol=1e-5)
        # the seeded bias changes the selection for a good share of tokens
        unbiased = np.sort(np.argsort(-s, axis=-1)[:, :2], -1)
        assert (np.sort(want, -1) != unbiased).any(axis=-1).mean() > 0.2
        assert float(aux) == 0.0

    @pytest.mark.parametrize("what", ["softmax-scores", "bias-dropped"])
    def test_another_router_fails_the_forward_check(self, what):
        cfg, params = _model()
        toks = _tokens(40, 1)[None]
        if what == "softmax-scores":
            other, tree = dataclasses.replace(
                cfg, moe_router_score="softmax"), params
            route = ("softmax", True)
        else:
            other = cfg
            moe = dict(params["block"]["ffn"]["moe"])
            del moe["router_bias"]
            tree = dict(params, block=dict(
                params["block"], ffn=dict(params["block"]["ffn"], moe=moe)))
            route = ("sigmoid", False)
        logits, _ = gpt_forward(tree, jnp.asarray(toks), other)
        ref = _reference(params, toks)
        assert np.abs(np.asarray(logits) - ref).max() > 100 * TOL_F32
        # the reference's own control computes that other router
        assert np.abs(np.asarray(logits)
                      - _reference(params, toks, route=route)).max() < TOL_F32


@pytest.fixture(scope="module")
def running():
    """An engine one step into a request."""
    eng = _engine(*_model())
    rid = eng.add_request(_tokens(6, 41), 4, GREEDY)
    eng.step()
    return eng, rid


class TestRefusals:
    """What the convolution-only tenant and the hybrid MoE stack cannot do
    yet refuses, once, in words (ROADMAP M2, M4 hold what remains)."""

    @pytest.mark.parametrize("kw,word", [
        ({"spec_method": "ngram"}, "spec_method"),
        ({"spill_host_mb": 1.0}, "spill_host_mb"),
        ({"adapter_cache": object()}, "adapter_cache"),
        ({"pool": object()}, "injected pool"),
        ({"ctx": object()}, "serving mesh"),
        ("export_request", "state snapshots"),
        ("import_request", "state snapshots"),
        ("adopt_request", "state snapshots"),
        ("staging-slots", "no snapshot"),
        ("prefix-reuse", "prefix reuse off"),
    ], ids=lambda v: v if isinstance(v, str) else "-".join(v))
    def test_serving_refuses(self, kw, word, running):
        cfg, params = _model()
        if isinstance(kw, dict):
            with pytest.raises(
                    ValueError,
                    match="gated short-convolution layers.*state snapshots"
            ) as e:
                _engine(cfg, params, **kw)
            assert word in str(e.value)
        elif kw == "staging-slots":
            from megatronapp_tpu.inference.paged_cache import PagedKVCache
            with pytest.raises(ValueError, match=word):
                PagedKVCache(cfg, 2, 32, extra_slots=1)
        elif kw == "prefix-reuse":
            eng, _ = running
            assert eng.pool.enable_prefix_caching is False
            assert word in eng.startup_line()
        else:
            eng, rid = running
            args = {"export_request": (rid,), "import_request": ({},),
                    "adopt_request": (eng.requests[rid], 0, 6)}[kw]
            with pytest.raises(ValueError, match=word) as e:
                getattr(eng, kw)(*args)
            assert "gated short-convolution layers" in str(e.value)

    @pytest.mark.parametrize("kw,word", [
        ({"shortconv_kernel": 3}, "hybrid stack"),
        ({"shortconv_kernel": 1, "attn_layer_period": 4}, "at least 2"),
        # (a state-space stack runs MoE feed-forwards since ISSUE 52:
        # tests/test_granite.py; every layer's, like the other stacks')
        ({"attn_layer_period": 4, "num_moe_experts": 4,
          "moe_layer_freq": 2}, "every layer"),
        ({"attn_layer_period": 4, "shortconv_kernel": 3,
          "multi_latent_attention": True}, "no MLA"),
        # (an aux loss and a share of the experts go through the hybrid
        # layer loop since PR 48: tests/test_mellum.py)
        ({"attn_layer_period": 4, "shortconv_kernel": 3, "num_moe_experts": 4,
          "moe_zero_experts": 2}, "moe_zero_experts"),
        ({"attn_layer_period": 4, "shortconv_kernel": 3, "num_moe_experts": 4,
          "mtp_num_layers": 1}, "or MTP"),
        ({"attn_layer_period": 4, "shortconv_kernel": 3, "num_moe_experts": 4,
          "moe_layer_freq": 2}, "every layer"),
        ({"moe_router_score": "tanh"}, "'softmax' or 'sigmoid'"),
        ({"moe_router_score": "sigmoid"}, "belong to an MoE"),
        ({"moe_router_score": "sigmoid", "num_moe_experts": 4,
          "moe_aux_loss_coeff": 0.01}, "no load-balance loss"),
        ({"moe_router_score": "sigmoid", "num_moe_experts": 4,
          "moe_z_loss_coeff": 0.01}, "z loss"),
    ], ids=lambda v: "-".join(v) if isinstance(v, dict) else None)
    def test_config_refuses(self, kw, word):
        from megatronapp_tpu.config.transformer_config import (
            TransformerConfig,
        )
        with pytest.raises(ValueError, match=word):
            TransformerConfig(**kw)

    @pytest.mark.parametrize("what", ["ep", "tp", "pp", "cp", "router-ep",
                                      "pipelined-init", "tp-sharded-conv",
                                      "lora-conv"])
    def test_training_layouts_refuse(self, what):
        import types
        from megatronapp_tpu.models.gpt import init_gpt_params
        from megatronapp_tpu.transformer.block import (
            block_forward, layer_forward,
        )
        from megatronapp_tpu.transformer.moe import moe_forward
        cfg, params = _model()
        x = jnp.zeros((2, 8, 64))
        if what in ("ep", "tp", "pp", "cp"):
            ctx = types.SimpleNamespace(**{"tp": 1, "ep": 1, "pp": 1, "cp": 1,
                                           "dp": 1, what: 2})
            with pytest.raises(NotImplementedError, match="not written yet"):
                block_forward(params["block"], x, cfg, ctx=ctx)
        elif what == "router-ep":
            moe = jax.tree.map(lambda a: a[0], params["block"]["ffn"]["moe"])
            ctx = types.SimpleNamespace(ep=2, dp=1, cp=1)
            with pytest.raises(NotImplementedError, match="selection bias"):
                moe_forward(moe, x, cfg, ctx=ctx)
        elif what == "pipelined-init":
            with pytest.raises(ValueError, match="not pipelined"):
                init_gpt_params(jax.random.PRNGKey(0), cfg, pp=2)
        else:
            layer = jax.tree.map(lambda a: a[0],
                                 params["block"]["mixers_conv"])
            layer.update(jax.tree.map(lambda a: a[0],
                                      params["block"]["ffn_lead"]))
            kw = ({"tp_sharded": True} if what == "tp-sharded-conv"
                  else {"lora": {}})
            with pytest.raises(NotImplementedError, match="one tp shard"):
                layer_forward(layer, x, cfg, **kw)


class TestRunner:
    """cells/serve_closed_conv.py's own pieces."""

    def test_the_sample_is_the_seeds(self):
        cell = manifest.load_module("cells", "serve_closed_conv")
        rng = np.random.default_rng(0)
        lengths = rng.integers(100, 3000, 400).tolist()
        longest = int(np.argmax(lengths))
        a = cell.draw_sample(lengths, 11, 120_000)
        assert a[0] == longest and len(set(a)) == len(a)
        used = sum(lengths[i] for i in a)
        # within the budget, and filled: nothing left out would still fit
        assert used <= 120_000
        assert min(lengths[i] for i in range(400) if i not in a) \
            > 120_000 - used
        assert cell.draw_sample(lengths, 11, 120_000) == a
        b = cell.draw_sample(lengths, 12, 120_000)
        assert b[0] == longest and set(b) != set(a)
        # another window's completions draw another sample of the same seed
        assert set(cell.draw_sample(lengths[:-1], 11, 120_000)) != set(a)
        assert cell.draw_sample([], 11, 100) == []
        # a budget under the longest request still checks it
        assert cell.draw_sample(lengths, 11, 10) == [longest]

    def test_passes_are_packed_longest_first(self):
        cell = manifest.load_module("cells", "serve_closed_conv")
        lengths = [700, 3000, 1200, 64, 1800, 2300]
        passes = cell.pack_longest_first(lengths, 3072)
        assert sorted(i for p in passes for i in p) == list(range(6))
        assert all(sum(lengths[i] for i in p) <= 3072 for p in passes)
        assert passes == [[1, 3], [5, 0], [4, 2]]
        # serve_closed_share's packer, which fills a pass in the order
        # given, makes the same passes of that order
        share = manifest.load_module("cells", "serve_closed_share")
        order = [i for some in passes for i in some]
        assert [[order[j] for j in some] for some in share.pack(
            [lengths[i] for i in order], 3072)] == passes
        with pytest.raises(ValueError, match="in a pass of"):
            cell.pack_longest_first([4000], 3072)

    def test_a_shifted_tail_is_told(self):
        """The second reading the tail's limit is sized by: the reference's
        own columns one position late (a tail that was not advanced) lie
        their whole size away."""
        cell = manifest.load_module("cells", "serve_closed_conv")
        _, params = _model()
        toks = jnp.asarray(np.stack([_tokens(20, s) for s in (1, 2)]))
        want = MODEL.reference_state(params, TINY, toks)
        late = MODEL.reference_state(params, TINY, toks,
                                     lengths=jnp.asarray([19, 19]))
        assert cell.tail_distance(want, want).tolist() == [0.0] * 4
        assert cell.tail_distance(late, want).min() > 1.5 * cell.TAIL_TOL
        # ... and columns kept at 3 bits of mantissa (the next type below
        # bf16 that a cache might hold) lie past the first layer's limit,
        # which bf16's own rounding (0.004 of their size) stays well under
        coarse = jax.lax.reduce_precision(want, 8, 3)
        fine = jax.lax.reduce_precision(want, 8, 7)
        assert cell.tail_distance(fine, want)[0] < cell.TAIL_FIRST_TOL / 4
        assert cell.tail_distance(coarse, want)[0] > 2 * cell.TAIL_FIRST_TOL
        assert cell.probe_prompts(2048, 3072) == [2049, 2050, 1027]
        assert cell.probe_prompts(32, 128) == [33, 34, 19]
