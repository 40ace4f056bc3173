"""Solar-Open2-250B's architecture against its plain float32 reference
(perfbench/models/solar_open2.py: the published equations in jax.numpy, the
delta rule a position at a time, one held expert at a time), at tiny widths
on the CPU with seeded random weights: 4 layers G K K K of H 96, 6 query
heads over 2 key/value heads of 16 under an elementwise gate, Kimi delta
attention of 4 heads with a [16, 16] state in chunks of 32, 4 held of 8
experts top-3 of width 40 (SwiGLU) under a sigmoid router with a seeded
selection bias, beside a shared expert. Each test fails if the mechanism it
names is left out."""
import copy
import functools
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from megatronapp_tpu.inference.dynamic_engine import DynamicInferenceEngine
from megatronapp_tpu.inference.engine import SamplingParams
from megatronapp_tpu.models.gpt import gpt_forward
from megatronapp_tpu.models.presets import PRESETS
from megatronapp_tpu.transformer import block
from perfbench import manifest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MODEL = manifest.load_module("models", "solar_open2")
with open(os.path.join(ROOT, "perfbench", "configs",
                       "solar-open2-250b.json")) as f:
    PUBLISHED = json.load(f)
TINY = {**PUBLISHED, **MODEL.REHEARSAL}
# Weights at std 0.1, not 0.02: at 96 columns every sublayer's output is then
# large enough beside the residual stream to show in the logits, which have
# a standard deviation of ~1.
STD = 0.1
# float32 on both sides: what is left is the order of summation (the
# program's chunked products and its kernel against the reference's
# sequential recurrence) and the router's 1e-6 against the reference's
# 1e-20. 7e-6 on those logits (measured); the weakest wrong model below
# moves them by 2.7.
TOL_F32 = 1e-4
# bf16 activations, convolution tails and KV rows (the state stays float32)
# against the float32 reference on the same float32 weights: 0.33 on logits
# of standard deviation ~1 (measured); a wrong model gives 2.7 and more.
TOL_BF16 = 0.5
# What each wrong model must move the float32 logits by, at least.
WRONG = 0.5
GREEDY = SamplingParams(greedy=True)


def _seeded_bias(params, seed=11):
    """The routers' selection bias drawn from a seed (the cell's is levelled
    over a calibration pass), large enough to change picks."""
    moe = params["block"]["ffn"]["moe"]
    bias = 0.2 * jax.random.normal(jax.random.PRNGKey(seed),
                                   moe["router_bias"].shape, jnp.float32)
    params = copy.copy(params)
    params["block"] = dict(params["block"], ffn=dict(
        params["block"]["ffn"], moe=dict(moe, router_bias=bias)))
    return params


@functools.lru_cache(maxsize=None)
def _model(compute_dtype=jnp.float32):
    cfg = MODEL.model_config(TINY, "float32", compute_dtype=compute_dtype,
                             init_method_std=STD)
    return cfg, _seeded_bias(MODEL._init_params(cfg, seed=5))


def _reference(params, tokens, tiny=TINY, **control):
    tokens = jnp.asarray(tokens)
    return np.asarray(MODEL.reference_logits(
        params, tiny, tokens, jnp.zeros_like(tokens), None, **control))


def _tokens(n, seed=0):
    return np.random.default_rng(seed).integers(
        0, TINY["vocab_size"], (n,)).astype(np.int32)


def _engine(cfg, params, **kw):
    kw = {"max_batch": 3, "max_seq_len": 64, "paged": True, "num_blocks": 24,
          "block_size": 4, "prefill_chunk": 8, **kw}
    return DynamicInferenceEngine(params, cfg, **kw)


def _recorded(eng):
    """Wrap the engine's two steps: logits[rid] collects, position by
    position, the logits every call computed for that request."""
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

    eng._mq_step, eng._decode = mq_step, decode
    return logits


def _served(cfg, params, prompts, new_tokens=6, **kw):
    """(the engine, [(a request's tokens but the last, its logits position
    by position)]) of `prompts` served together through the paged pools."""
    eng = _engine(cfg, params, **kw)
    logits = _recorded(eng)
    reqs = [eng.add_request(p, new_tokens, GREEDY) for p in prompts]
    while eng.has_work:
        eng.step()
    return eng, reqs, [(eng.requests[r].tokens[:-1],
                        np.concatenate(logits[r])) for r in reqs]


class TestForward:
    def test_the_whole_forward_is_the_reference(self):
        cfg, params = _model()
        tokens = np.stack([_tokens(70, 1), _tokens(70, 2)])
        logits = np.asarray(gpt_forward(params, jnp.asarray(tokens), cfg)[0])
        want = _reference(params, tokens)
        assert np.abs(logits - want).max() < TOL_F32
        assert want.std() > 0.5

    def test_bf16_compute_stays_near_it(self):
        cfg, params = _model(jnp.bfloat16)
        tokens = _tokens(70, 3)[None]
        logits = np.asarray(gpt_forward(params, jnp.asarray(tokens), cfg)[0],
                            np.float32)
        assert np.abs(logits - _reference(params, tokens)).max() < TOL_BF16

    @pytest.mark.parametrize("control", [
        dict(beta_factor=1.0), dict(one_decay=True), dict(no_conv=True),
        dict(no_gate=True)], ids=lambda c: next(iter(c)))
    def test_a_wrong_model_moves_the_logits_past_the_limit(self, control):
        """Each control of tools/solar_control.py is a model the program is
        NOT: the reference with it lies far from the program's logits."""
        cfg, params = _model()
        tokens = _tokens(70, 1)[None]
        logits = np.asarray(gpt_forward(params, jnp.asarray(tokens), cfg)[0])
        wrong = _reference(params, tokens, **control)
        assert np.abs(logits - wrong).max() > WRONG
        with pytest.raises(TypeError, match="no control"):
            _reference(params, tokens, no_such=True)

    def test_matrices_at_three_bits_move_the_logits_past_the_limit(
            self, monkeypatch):
        cfg, params = _model()
        tokens = _tokens(70, 1)[None]
        right = _reference(params, tokens)
        monkeypatch.setattr(MODEL, "_f32", lambda a: (
            jax.lax.reduce_precision(a.astype(jnp.float32), 8, 3)
            if a.ndim >= 2 else a.astype(jnp.float32)))
        # a fresh trace: the jitted layers read _f32 when they are traced
        jitted = (MODEL._mixer, MODEL._moe_terms, MODEL._head)
        for fn in jitted:
            fn.clear_cache()
        wrong = _reference(params, tokens)
        monkeypatch.undo()
        for fn in jitted:
            fn.clear_cache()
        assert np.abs(wrong - right).max() > WRONG

    def test_segments_are_sequences_of_their_own(self):
        """The reference packs requests as segments (the cell's sample):
        attention, the convolutions and the recurrence stay inside one."""
        _, params = _model()
        a, b = _tokens(20, 4), _tokens(30, 5)
        packed = np.concatenate([a, b])[None]
        segments = np.concatenate([np.zeros(20), np.ones(30)]).astype(
            np.int32)[None]
        got = np.asarray(MODEL.reference_logits(
            params, TINY, jnp.asarray(packed), jnp.asarray(segments), None))
        np.testing.assert_allclose(got[0, 20:], _reference(params, b[None])[0],
                                   atol=1e-4)


PROMPTS = (21, 9, 37)


@functools.lru_cache(maxsize=None)
def _three_served():
    """Prompts of 21 (calls of 8, 8, 5), 9 (8, 1) and 37, six tokens each
    through the pool's kernel, on an engine with room."""
    cfg, params = _model()
    return _served(cfg, params, [_tokens(n, n) for n in PROMPTS])


class TestPagedEngine:
    def test_prefill_calls_then_decode_are_the_reference(self):
        """Every position's logits against the reference's full forward,
        and each slot's S against the reference's recurrence."""
        _, params = _model()
        eng, reqs, served = _three_served()
        for tokens, logits in served:
            want = _reference(params, tokens[None])[0]
            assert logits.shape == want.shape
            assert np.abs(logits - want).max() < TOL_F32
        rows = np.stack([np.pad(t, (0, 42 - len(t)))
                         for t, _ in served])
        want = MODEL.reference_state(params, TINY, jnp.asarray(rows),
                                     [len(t) for t, _ in served])
        held = jnp.stack([eng.pool.state[0][:, eng.requests[r].slot]
                          for r in reqs], axis=1)
        assert held.shape == want.shape == (3, 3, 16, 64)
        gaps = jnp.sqrt(jnp.sum(jnp.square(held - want), axis=(0, 2, 3))
                        / jnp.sum(jnp.square(want), axis=(0, 2, 3)))
        assert float(gaps.max()) < 1e-5
        state = eng.stats_snapshot()["state"]
        assert (state["kind"], state["mixer"], state["heads"], state[
            "resets"], state["bytes_per_slot"]) == (
                "ssm", "kda", 4, 3, MODEL.state_bytes_per_slot(
                    {**TINY, "serve": {"params_dtype": "float32"}},
                    "float32"))
        assert "3 Kimi-delta-attention layers x" in eng.startup_line()
        moe = eng.stats_snapshot()["moe"]
        assert moe["assignments_here"] and moe["assignments_absent"]
        assert moe["assignments"] == moe["tokens"] * 3 * 4

    def test_a_state_rounded_to_bf16_reads_as_one(self):
        """The control of the state's precision: the reference's recurrence
        with S rounded to bf16 at every position holds nothing finer; the
        engine's float32 pool nearly everything."""
        state_cell = manifest.load_module("cells", "serve_closed_state")
        _, params = _model()
        eng, reqs, served = _three_served()
        held = eng.pool.state[0][:, eng.requests[reqs[0]].slot]
        assert state_cell._fine_share(held, "bfloat16") > 0.99
        rounded = MODEL.reference_state(
            params, TINY, jnp.asarray(served[0][0][None]),
            state_dtype="bfloat16")
        assert state_cell._fine_share(rounded, "bfloat16") == 0.0

    def test_preemption_recomputes_the_state(self):
        """A pool too small for three sequences preempts one, which starts
        again from zeros in whatever slot it gets: the streams are those of
        the engine with room."""
        cfg, params = _model()
        roomy, reqs, _ = _three_served()
        tight, reqs_t, _ = _served(cfg, params,
                                   [_tokens(n, n) for n in PROMPTS],
                                   num_blocks=20)
        assert tight.pool.stats["preemptions"] > 0
        assert tight.stats_snapshot()["state"]["dropped"] > 0
        for a, b in zip(reqs, reqs_t):
            assert list(roomy.requests[a].tokens) == list(
                tight.requests[b].tokens)

    @pytest.mark.parametrize("kw,what", [
        (dict(spec_method="ngram"), "spec_method"),
        (dict(spill_host_mb=1), "spill_host_mb"),
    ], ids=["rewind", "snapshot"])
    def test_what_the_tenant_lacks_is_refused_by_name(self, kw, what):
        cfg, params = _model()
        with pytest.raises(ValueError, match="recurrent mixers") as e:
            _engine(cfg, params, **kw)
        assert what in str(e.value)

    def test_prefix_reuse_is_off(self):
        cfg, params = _model()
        eng = _engine(cfg, params)
        assert not eng.pool.enable_prefix_caching
        assert "prefix reuse off" in eng.startup_line()


class TestShare:
    def test_eight_shares_add_up_to_the_uncut_layer(self):
        """The routed parts of the 8 ranks' shares (one expert each of 8)
        plus the shared expert counted once are the uncut reference layer;
        and the program's layer with a share is its share's part."""
        whole_cfg = {**TINY, "n_routed_experts": 8,
                     "expert_share": {"first": 0}}
        cfg_w = MODEL.model_config(whole_cfg, "float32",
                                   init_method_std=STD)
        whole = _seeded_bias(MODEL._init_params(cfg_w, seed=5))
        x = jax.random.normal(jax.random.PRNGKey(2), (2, 12, 96))
        routed_w, shared_w = MODEL.reference_layer_terms(whole, whole_cfg,
                                                         x, 1)
        total = jnp.zeros_like(x)
        moe = whole["block"]["ffn"]["moe"]
        for first in range(8):
            tiny = {**TINY, "n_routed_experts": 1,
                    "expert_share": {"first": first}}
            part = dict(whole, block=dict(whole["block"], ffn=dict(
                whole["block"]["ffn"], moe=dict(
                    moe, fc1_kernel=moe["fc1_kernel"][:, first:first + 1],
                    fc2_kernel=moe["fc2_kernel"][:, first:first + 1]))))
            routed, shared = MODEL.reference_layer_terms(part, tiny, x, 1)
            np.testing.assert_allclose(shared, shared_w, atol=1e-6)
            total = total + routed
            if first in (0, 5):     # the program's layer on this share
                cfg = MODEL.model_config(tiny, "float32",
                                         init_method_std=STD,
                                         compute_dtype=jnp.float32)
                layer_p = block.layer_params(part["block"], ("ffn",),
                                             {"ffn": 1})
                (got, _), _ = block.layer_forward(layer_p, x, cfg,
                                                  layer_id=1)
                np.testing.assert_allclose(got, x + routed + shared,
                                           atol=2e-5)
        np.testing.assert_allclose(total, routed_w, atol=2e-5)
        assert float(jnp.abs(routed_w).max()) > 0.05


def test_the_preset_is_the_published_model():
    """The preset's sizes are the catalog row's, its parameters the
    published 250B, and the configuration file's share 3,308,377,920."""
    cfg = PRESETS["solar-open2-250b"]()
    pub = PUBLISHED["published"]
    assert (cfg.num_layers, cfg.hidden_size, cfg.num_attention_heads,
            cfg.num_query_groups, cfg.kv_channels, cfg.vocab_size,
            cfg.num_moe_experts, cfg.moe_router_topk,
            cfg.moe_ffn_hidden_size, cfg.kda_heads, cfg.ssm_head_dim) == (
                pub["num_hidden_layers"], PUBLISHED["hidden_size"],
                PUBLISHED["num_attention_heads"],
                PUBLISHED["num_key_value_heads"], PUBLISHED["head_dim"],
                pub["vocab_size"], pub["n_routed_experts"],
                PUBLISHED["num_experts_per_tok"],
                PUBLISHED["moe_intermediate_size"],
                PUBLISHED["linear_attn_config"]["num_heads"],
                PUBLISHED["linear_attn_config"]["head_dim"])
    assert [i for i in range(48) if cfg.layer_is_attention(i)] == pub[
        "gqa_layers"]
    from megatronapp_tpu.models.gpt import init_gpt_params

    def count(c):
        tree = jax.eval_shape(lambda k: init_gpt_params(k, c)[0],
                              jax.random.PRNGKey(0))
        return sum(a.size for a in jax.tree.leaves(tree))

    assert count(cfg) == 250_288_105_216
    cut = MODEL.model_config(PUBLISHED, "bfloat16")
    assert count(cut) == 3_308_377_920
    assert (cut.moe_experts_held, cut.vocab_slice_of,
            cut.scaled_init_layers) == ((0, 40), 196608, 48)
    assert MODEL.state_bytes_per_slot(PUBLISHED, "float32") == 13_025_280
    assert MODEL.kv_bytes_per_token(PUBLISHED, "bfloat16") * 16 == 65_536


def test_the_calibrated_bias_levels_the_experts_load():
    """models/solar_open2.py sets a seeded model's selection bias to what
    levels the experts' load over a calibration pass (nemotron_h's rule):
    over the positions it was levelled on, no expert gets 1.2 times the mean
    load, where zeros leave the busiest with more; and ``init_params`` hands
    the tree over with that bias in it."""
    cfg = MODEL.model_config(TINY, "float32", init_method_std=STD)
    params = MODEL.init_params(cfg, seed=5)
    ffns = params["block"]["ffn"]
    assert float(jnp.abs(ffns["moe"]["router_bias"]).max()) > 0
    # a stream with a direction every position shares, as a seeded stack's
    x = jax.random.normal(jax.random.PRNGKey(4), (1, 512, 96)) \
        + 2.0 * jax.random.normal(jax.random.PRNGKey(5), (96,))
    bias = MODEL._levelled_bias(x, ffns, jnp.int32(0), eps=1e-5, top_k=3)
    flat = MODEL._rms_norm(x, ffns["ln2_scale"][0], 1e-5).reshape(-1, 96)

    def busiest(b):
        picked = MODEL.router_weights(flat, ffns["moe"]["router_kernel"][0],
                                      b, 3, 1.0) > 0
        load = picked.sum(axis=0)
        return float(load.max() / load.mean())

    assert busiest(bias) < 1.2 < busiest(jnp.zeros_like(bias))
