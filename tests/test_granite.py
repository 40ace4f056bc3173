"""granite-4.0-h-small's architecture against its plain float32 reference
(perfbench/models/granite_moe_hybrid.py: the published equations in
jax.numpy, the Mamba-2 recurrence a position at a time, one held expert at a
time), at tiny widths on the CPU with seeded random weights: 4 layers of
which layer 1 attends (2 key/value heads under 4 query heads, no positional
term), Mamba-2 mixers of 4 heads x 32 columns with a [32, 16] state a head
in chunks of 16 positions, 4 held of 8 experts top-3 beside a shared expert.
Each test fails if the mechanism it names is left out."""
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
from megatronapp_tpu.transformer import ssm
from perfbench import manifest

from jitted import gpt_forward  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MODEL = manifest.load_module("models", "granite_moe_hybrid")
with open(os.path.join(ROOT, "perfbench", "configs",
                       "granite-4.0-h-small.json")) as f:
    PUBLISHED = json.load(f)
TINY = {**PUBLISHED, **MODEL.REHEARSAL}
# Weights at std 0.1, not 0.02: at 64 columns a mixer's output is then large
# enough beside the residual stream (times 0.22) for every part of it to
# show in the logits.
STD = 0.1
# float32 on both sides: what is left is the order of summation (the
# program's chunked products and its kernel against the reference's
# sequential recurrence). The model module makes the embedding's rows at
# 1/96 of the other matrices' scale (its EMBEDDING_INIT_SHRINK), so the
# logits here have a standard deviation of 5e-4; the two sides agree to
# 1.4e-9 on them (measured). A multiplier taken as 1 moves them by 3e-4 to
# 0.035.
TOL_F32 = 1e-8
# bf16 activations, convolution tail and KV rows (the state stays float32)
# against the float32 reference on the same float32 weights: 5.9e-5 on those
# logits (measured); a missing mechanism gives 3e-4 and more.
TOL_BF16 = 1.5e-4
GREEDY = SamplingParams(greedy=True)


def _model(compute_dtype=jnp.float32, tiny=TINY):
    if tiny is TINY:
        return _tiny_model(compute_dtype)
    cfg = MODEL.model_config(tiny, "float32", compute_dtype=compute_dtype,
                             init_method_std=STD)
    return cfg, MODEL.init_params(cfg, seed=5)


@functools.cache
def _tiny_model(compute_dtype):
    """(cfg, params) of TINY in a compute type, built once for every case
    (and for tests/test_granite_engine.py's)."""
    cfg = MODEL.model_config(TINY, "float32", compute_dtype=compute_dtype,
                             init_method_std=STD)
    return cfg, MODEL.init_params(cfg, seed=5)


def _reference(params, tokens, tiny=TINY, **control):
    tokens = jnp.asarray(tokens)
    return np.asarray(MODEL.reference_logits(
        params, tiny, tokens, jnp.zeros_like(tokens), None, **control))


@pytest.fixture(scope="module")
def reference40():
    """The reference's logits of the two 40-token rows TestForward reads:
    a position at a time, so worked out once."""
    return _reference(_model()[1], np.stack([_tokens(40, 1), _tokens(40, 2)]))


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
    one a program, compiled once, for the cases (here and in
    tests/test_granite_engine.py) that leave it as they found it. They take
    it through `lend` (conftest.py)."""
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


# ---- the mixer ---------------------------------------------------------------

def _mixer_inputs(s, bsz=2, heads=4, p=8, n=16, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    e = heads * p
    return dict(
        x=jax.random.normal(ks[0], (bsz, s, e)),
        dt=jax.nn.softplus(jax.random.normal(ks[1], (bsz, s, heads))),
        a=-jnp.exp(jax.random.normal(ks[2], (heads,))),
        b=jax.random.normal(ks[3], (bsz, s, n)),
        c=jax.random.normal(ks[4], (bsz, s, n)),
        d=jax.random.normal(ks[5], (heads,)),
        h0=jax.random.normal(ks[6], (bsz, n, e)))


def _recurrence(x, dt, a, b, c, d, h0):
    """The state a position at a time, in the program's layout [B, N, E]."""
    p = x.shape[-1] // dt.shape[-1]
    h, ys = h0, []

    def wide(t):
        return jnp.repeat(t, p, axis=-1)

    for t in range(x.shape[1]):
        h = jnp.exp(wide(dt[:, t] * a))[:, None, :] * h \
            + (wide(dt[:, t]) * x[:, t])[:, None, :] * b[:, t, :, None]
        ys.append(jnp.sum(h * c[:, t, :, None], axis=1) + wide(d) * x[:, t])
    return jnp.stack(ys, axis=1), h


class TestChunkedScan:
    @pytest.mark.parametrize("s,chunk", [
        (1, 8), (5, 8), (8, 8), (9, 8), (21, 8), (16, 4), (7, 1)],
        ids=["one-position", "under-a-chunk", "a-whole-chunk",
             "across-an-edge", "no-multiple", "four-chunks", "chunks-of-one"])
    @pytest.mark.parametrize("carried", [False, True],
                             ids=["from-zeros", "from-a-state"])
    def test_against_the_recurrence(self, s, chunk, carried):
        t = _mixer_inputs(s)
        h0 = t.pop("h0") if carried else None
        t.pop("h0", None)
        y, h = ssm.ssd_chunked(t["x"], t["dt"], t["a"], t["b"], t["c"],
                               t["d"], chunk, h0)
        y_ref, h_ref = _recurrence(
            **t, h0=jnp.zeros_like(h) if h0 is None else h0)
        np.testing.assert_allclose(y, y_ref, rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(h, h_ref, rtol=2e-4, atol=2e-4)

    def test_a_position_whose_dt_is_zero_leaves_the_state(self):
        t = _mixer_inputs(12)
        h0 = t.pop("h0")
        dt = t["dt"].at[:, 7:].set(0.0)
        _, h = ssm.ssd_chunked(t["x"], dt, t["a"], t["b"], t["c"], t["d"],
                               8, h0)
        _, short = ssm.ssd_chunked(t["x"][:, :7], dt[:, :7], t["a"],
                                   t["b"][:, :7], t["c"][:, :7], t["d"], 8,
                                   h0)
        np.testing.assert_allclose(h, short, rtol=1e-5, atol=1e-5)

    def test_two_calls_carry_the_state_across(self):
        t = _mixer_inputs(20)
        h0 = t.pop("h0")
        whole = ssm.ssd_chunked(t["x"], t["dt"], t["a"], t["b"], t["c"],
                                t["d"], 8, h0)
        cut = {k: (v[:, :11], v[:, 11:]) for k, v in t.items()
               if k in ("x", "dt", "b", "c")}
        y1, h1 = ssm.ssd_chunked(cut["x"][0], cut["dt"][0], t["a"],
                                 cut["b"][0], cut["c"][0], t["d"], 8, h0)
        y2, h2 = ssm.ssd_chunked(cut["x"][1], cut["dt"][1], t["a"],
                                 cut["b"][1], cut["c"][1], t["d"], 8, h1)
        np.testing.assert_allclose(jnp.concatenate([y1, y2], 1), whole[0],
                                   rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(h2, whole[1], rtol=2e-4, atol=2e-4)


class TestMixer:
    def _mixer(self):
        cfg, params = _model()
        p = jax.tree.map(lambda a: a[0],
                         params["block"]["mixers_ssm"]["ssm"])
        return cfg, ssm.ssm_dims(cfg), p

    def test_leaves_are_the_published_ones(self):
        cfg, dims, p = self._mixer()
        e, n, heads = 128, 16, 4
        assert dims.heads == heads and dims.chunk == 16
        assert {k: v.shape for k, v in p.items()} == {
            "in_kernel": (64, 2 * e + 2 * n + heads),
            "conv_kernel": (4, e + 2 * n), "conv_bias": (e + 2 * n,),
            "dt_bias": (heads,), "A_log": (heads,), "D": (heads,),
            "norm_scale": (e,), "out_kernel": (e, 64)}
        assert cfg.ssm_conv_channels == e + 2 * n

    def test_padding_by_counts_neither_advances_nor_enters_the_tail(self):
        cfg, dims, p = self._mixer()
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 20, 64))
        counts = jnp.asarray([20, 13])
        out, (tail, h) = ssm.ssm_forward(p, x, cfg, dims, counts=counts)
        short, (tail_s, h_s) = ssm.ssm_forward(p, x[1:, :13], cfg, dims)
        np.testing.assert_allclose(out[1, :13], short[0], atol=1e-5)
        np.testing.assert_allclose(h[1], h_s[0], atol=1e-5)
        np.testing.assert_allclose(tail[1], tail_s[0], atol=1e-6)
        assert tail.shape == (2, 3, cfg.ssm_conv_channels)

    @pytest.mark.parametrize("cut", [3, 16, 17], ids=[
        "inside-a-chunk", "at-a-chunk's-edge", "one-past-it"])
    def test_a_state_carried_across_prefill_calls(self, cut):
        cfg, dims, p = self._mixer()
        x = jax.random.normal(jax.random.PRNGKey(2), (1, 40, 64))
        whole, (tail_w, h_w) = ssm.ssm_forward(p, x, cfg, dims)
        first, state = ssm.ssm_forward(p, x[:, :cut], cfg, dims)
        rest, (tail, h) = ssm.ssm_forward(p, x[:, cut:], cfg, dims,
                                          state=state)
        np.testing.assert_allclose(jnp.concatenate([first, rest], 1), whole,
                                   atol=2e-5)
        np.testing.assert_allclose(h, h_w, atol=2e-5)
        np.testing.assert_allclose(tail, tail_w, atol=1e-6)

    def test_a_decode_step_is_the_next_position(self):
        """S == 1 on a state: the plain form of the kernel's update."""
        cfg, dims, p = self._mixer()
        x = jax.random.normal(jax.random.PRNGKey(3), (2, 11, 64))
        whole, (_, h_w) = ssm.ssm_forward(p, x, cfg, dims)
        _, state = ssm.ssm_forward(p, x[:, :10], cfg, dims)
        last, (_, h) = ssm.ssm_forward(p, x[:, 10:], cfg, dims, state=state)
        np.testing.assert_allclose(last[:, 0], whole[:, 10], atol=2e-5)
        np.testing.assert_allclose(h, h_w, atol=2e-5)

    def test_the_gated_norm_is_live(self):
        cfg, dims, p = self._mixer()
        x = jax.random.normal(jax.random.PRNGKey(4), (1, 9, 64))
        out, _ = ssm.ssm_forward(p, x, cfg, dims)
        other, _ = ssm.ssm_forward(
            dict(p, norm_scale=p["norm_scale"] * 2.0), x, cfg, dims)
        np.testing.assert_allclose(other, 2.0 * out, rtol=1e-5, atol=1e-6)


class TestKernel:
    @pytest.mark.parametrize("block,tiles", [(1 << 20, 1), (16 * 128 * 4, 4),
                                             (16 * 256 * 4, 2)],
                             ids=["one-tile", "four-tiles", "two-tiles"])
    @pytest.mark.parametrize("rows_of_a", [1, 16], ids=["a-head", "a-[N,E]"])
    def test_against_the_plain_update_with_e_tiled(self, monkeypatch, block,
                                                   tiles, rows_of_a):
        """ssm_update (interpreted), its grid over tiles of E where a plane
        is larger than a block, A as one row for every n (Mamba-2) or [N, E]
        (Mamba-1): the plain update's y and h' for the active slots, and
        nothing else touched."""
        from megatronapp_tpu.ops.pallas import ssm_update as mod
        monkeypatch.setattr(mod, "BLOCK_BYTES", block)
        layers, slots, n, e = 2, 5, 16, 512
        assert e // mod._tile(n, e) == tiles
        ks = jax.random.split(jax.random.PRNGKey(0), 7)
        pool = jax.random.normal(ks[0], (layers, slots, n, e))
        dt = jax.nn.softplus(jax.random.normal(ks[1], (slots, e)))
        u = jax.random.normal(ks[2], (slots, e))
        b = jax.random.normal(ks[3], (slots, n))
        c = jax.random.normal(ks[4], (slots, n))
        a_t = -jnp.exp(jax.random.normal(ks[5], (rows_of_a, e)))
        d = jax.random.normal(ks[6], (e,))
        active = jnp.asarray([True, False, True, True, False])
        y, new = jax.jit(mod.ssm_update)(pool, jnp.int32(1), dt, u, b, c,
                                         a_t, d, active)
        y_ref, h_ref = mod.ssm_update_reference(pool[1], dt, u, b, c, a_t, d)
        on = np.asarray(active)
        np.testing.assert_allclose(np.asarray(y)[on], np.asarray(y_ref)[on],
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(new[1])[on],
                                   np.asarray(h_ref)[on], rtol=1e-5,
                                   atol=1e-5)
        np.testing.assert_array_equal(np.asarray(new[1])[~on],
                                      np.asarray(pool[1])[~on])
        assert not np.asarray(y)[~on].any()
        np.testing.assert_array_equal(np.asarray(new[0]), np.asarray(pool[0]))

    def test_tiles_at_the_published_shapes(self):
        from megatronapp_tpu.ops.pallas.ssm_update import _tile
        assert _tile(16, 5120) == 5120          # Jamba: the plane whole
        assert _tile(128, 8192) == 2048         # Granite: 1 MiB a block
        assert _tile(128, 8192 + 64) == 8192 + 64   # no whole lane tiles

    def test_decode_step_runs_one_kernel_a_layer_loop(self, eng):
        disp = eng.stats_snapshot(include_dispatch=True)["decode_dispatch"]
        # a Mamba-2 layer: ssm_update; the attention layer: paged_append x 2
        # + paged_decode; every layer: two grouped GEMMs
        assert disp["kernels"] == 3 * 1 + 1 * 3 + 4 * 2, disp


# ---- the model ----------------------------------------------------------------

class TestForward:
    def test_gpt_forward_matches_reference(self, reference40):
        cfg, params = _model()
        assert cfg.num_ssm_layers == 3 and cfg.num_attention_layers == 1
        assert cfg.moe_experts_held == (0, 4) and cfg.moe_router_width == 8
        block = params["block"]
        assert block["mixers_ssm"]["ssm"]["in_kernel"].shape[0] == 3
        assert block["mixers_attn"]["attention"]["q_kernel"].shape[0] == 1
        assert block["ffn"]["moe"]["fc1_kernel"].shape[:2] == (4, 4)
        assert block["ffn"]["moe"]["router_kernel"].shape == (4, 64, 8)
        toks = np.stack([_tokens(40, 1), _tokens(40, 2)])
        logits = gpt_forward(params, jnp.asarray(toks), cfg)[0]
        ref = reference40
        assert ref.std() > 3e-4
        assert np.abs(np.asarray(logits) - ref).max() < TOL_F32

    @pytest.mark.parametrize("field,control", [
        ("embedding_multiplier", {}),
        ("attention_multiplier", {"attention_multiplier": 0.25}),
        ("residual_multiplier", {"residual_multiplier": 1.0}),
        ("logits_scaling", {})])
    def test_each_multiplier_is_told_from_one(self, field, control,
                                              reference40):
        """The program with one of the four scalars at its default (1, or
        1 / sqrt(head size) = 0.25) is not the model (the logits move by
        0.0009 for the one attention layer's scale, 0.08 to 7.6 for the
        others: measured), and the reference with the same wrong fact is
        that program."""
        cfg, params = _model()
        wrong = dataclasses.replace(cfg, **{
            field: None if field == "attention_multiplier" else 1.0})
        toks = _tokens(40, 1)[None]
        logits = np.asarray(gpt_forward(params, jnp.asarray(toks), wrong)[0])
        assert np.abs(logits - reference40[:1]).max() > 1e4 * TOL_F32
        if control:
            assert np.abs(logits - _reference(params, toks, **control)).max() \
                < TOL_F32

    def test_any_period_and_offset(self):
        tiny = {**TINY, "num_hidden_layers": 7, "layer_types": [
            "mamba", "mamba", "attention", "mamba", "mamba", "attention",
            "mamba"]}
        cfg, params = _model(tiny=tiny)
        assert (cfg.attn_layer_period, cfg.attn_layer_offset) == (3, 2)
        toks = _tokens(20, 3)[None]
        logits = gpt_forward(params, jnp.asarray(toks), cfg)[0]
        assert np.abs(np.asarray(logits)
                      - _reference(params, toks, tiny)).max() < TOL_F32

    def test_the_reference_keeps_segments_apart(self):
        """What the cell's packed reference pass rests on: two requests as
        segments of one row read as each alone (attention, the convolution
        and the recurrence all stay inside a segment)."""
        _, params = _model()
        a, b = _tokens(13, 7), _tokens(9, 8)
        row = jnp.asarray(np.concatenate([a, b]))[None]
        segments = jnp.asarray([0] * 13 + [1] * 9)[None]
        packed = np.asarray(MODEL.reference_logits(params, TINY, row,
                                                   segments, None))[0]
        np.testing.assert_allclose(packed[:13], _reference(params, a[None])[0],
                                   atol=1e-6)
        np.testing.assert_allclose(packed[13:], _reference(params, b[None])[0],
                                   atol=1e-6)

    def test_granite_trains_a_step(self):
        """The chunked scan differentiates (its backward is autodiff's, not
        a written one): a step down the gradient lowers the loss."""
        from megatronapp_tpu.models.gpt import gpt_loss
        cfg, params = _model()
        toks = jnp.asarray(_tokens(20, 1)[None])

        def loss(p):
            return gpt_loss(p, toks[:, :-1], toks[:, 1:], None, cfg)[0]

        @jax.jit    # one program: eagerly, a compile a primitive (20 s)
        def step(params):
            first, grads = jax.value_and_grad(loss)(params)
            return first, loss(jax.tree.map(lambda p, g: p - 0.05 * g,
                                            params, grads))

        first, lower = step(params)
        assert np.isfinite(float(first)) and float(lower) < float(first)


class TestShares:
    def test_the_shares_add_up_to_the_uncut_layer(self):
        """Two chips share each layer's 8 experts, 4 each: the held parts
        of both shares plus the shared expert, counted ONCE, are the uncut
        reference's second half; and the program's layer with a share is
        the reference's with that share."""
        whole_cfg = {**TINY, "num_local_experts": 8}
        _, whole = _model(tiny=whole_cfg)
        x = jax.random.normal(jax.random.PRNGKey(9), (2, 12, 64))
        routed_all, shared = MODEL.reference_layer_terms(whole, whole_cfg, x,
                                                         2)
        parts = []
        for first in (0, 4):
            share_cfg = {**TINY, "expert_share": {"first": first}}
            moe = whole["block"]["ffn"]["moe"]
            held = dict(whole, block=dict(whole["block"], ffn=dict(
                whole["block"]["ffn"], moe=dict(
                    moe, fc1_kernel=moe["fc1_kernel"][:, first:first + 4],
                    fc2_kernel=moe["fc2_kernel"][:, first:first + 4]))))
            routed, shared_again = MODEL.reference_layer_terms(
                held, share_cfg, x, 2)
            np.testing.assert_array_equal(shared_again, shared)
            parts.append(routed)
            # the program's MoE with this share against the reference's
            from megatronapp_tpu.transformer.moe import moe_forward
            cfg = MODEL.model_config(share_cfg, "float32",
                                     compute_dtype=jnp.float32)
            layer = jax.tree.map(lambda a: a[2], held["block"]["ffn"])
            u = MODEL._rms_norm(x, layer["ln2_scale"], 1e-5)
            out = moe_forward(layer["moe"], u, cfg)[0]
            np.testing.assert_allclose(
                cfg.residual_multiplier * out, routed + shared, atol=2e-5)
        assert float(jnp.abs(parts[0]).max()) > 1e-3
        np.testing.assert_allclose(parts[0] + parts[1], routed_all, atol=2e-5)

    def test_the_ten_weights_are_renormalised(self):
        flat = jax.random.normal(jax.random.PRNGKey(1), (6, 64))
        w = 0.05 * jax.random.normal(jax.random.PRNGKey(2), (64, 8))
        model = MODEL.router_weights(flat, w, 3)
        np.testing.assert_allclose(model.sum(-1), 1.0, rtol=1e-6)
        assert ((model > 0).sum(-1) == 3).all()
        control = MODEL.router_weights(flat, w, 3, renormalise=False)
        assert (control.sum(-1) < 0.999).all()
        # softmax over the chosen = the softmax over all, renormalised
        np.testing.assert_allclose(
            control / control.sum(-1, keepdims=True), model, rtol=1e-5)


class TestCounts:
    def test_the_cut_is_the_one_the_file_states(self):
        cfg = MODEL.model_config(PUBLISHED, "bfloat16")
        from megatronapp_tpu.models.gpt import init_gpt_params
        shapes = jax.eval_shape(lambda k: init_gpt_params(k, cfg)[0],
                                jax.random.PRNGKey(0))
        n = sum(x.size for x in jax.tree.leaves(shapes))
        assert n == 4_757_211_776
        moe = shapes["block"]["ffn"]["moe"]
        assert moe["fc1_kernel"].shape == (10, 36, 4096, 1536)
        assert moe["router_kernel"].shape == (10, 4096, 72)
        assert shapes["block"]["mixers_ssm"]["ssm"]["in_kernel"].shape == (
            9, 4096, 16768)
        assert (cfg.attention_multiplier, cfg.embedding_multiplier,
                cfg.residual_multiplier, cfg.logits_scaling) == (
            1 / 128, 12.0, 0.22, 16.0)
        assert cfg.scaled_init_layers == 40 and cfg.vocab_slice_of == 100352
        assert cfg.ssm_conv_channels == 8448

    def test_operations_a_token(self):
        # one layer's chunked scan: 2 x (256 x 128 + 256 x 8192 + 2 x 128 x
        # 8192) = 8.45 MFLOP a position
        assert MODEL.ssd_flops_per_token(PUBLISHED) == 8_454_144
        per_token = MODEL.params_per_token(PUBLISHED)
        # outside the experts 1,154.3M less norms and vectors; 5 held picks
        # a layer; the head
        assert 1.15e9 + 10 * 5 * 9_437_184 + 205e6 < per_token < 1.84e9
        assert MODEL.flops_per_token(PUBLISHED, 2048) > 6 * per_token

    def test_the_mixers_facts_reach_the_config_from_flags(self):
        """--ssm-heads and its three neighbours (config/arguments.py's
        hybrid group, which the server tool shares) make the state-space
        layers of a flag-built model Mamba-2."""
        from megatronapp_tpu.config.arguments import (
            build_parser, configs_from_args,
        )
        args = build_parser().parse_args([
            "--num-layers", "4", "--hidden-size", "64",
            "--num-attention-heads", "4", "--vocab-size", "128",
            "--max-position-embeddings", "64", "--seq-length", "16",
            "--micro-batch-size", "1", "--global-batch-size", "1",
            "--position-embedding-type", "none",
            "--attn-layer-period", "4", "--attn-layer-offset", "1",
            "--ssm-heads", "4", "--ssm-head-dim", "32",
            "--ssm-state-dim", "16", "--ssm-chunk-size", "8"])
        model = configs_from_args(args)[0]
        assert (model.ssm_heads, model.ssm_head_dim, model.ssm_state_dim,
                model.ssm_chunk_size) == (4, 32, 16, 8)
        assert model.num_ssm_layers == 3 and model.ssm_conv_channels == 160

