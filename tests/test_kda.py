"""Kimi delta attention's two forms against the token recurrence on the CPU
(transformer/kda.py's chunked pass, ops/pallas/kda_update.py's decode kernel
in interpret mode), at edges of chunks and sub-chunks, from a state that is
not zero, and under the strong decay that overflows the textbook factors."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from megatronapp_tpu.models.presets import PRESETS
from megatronapp_tpu.ops.pallas.kda_update import (
    heads_step, kda_update, kda_update_reference,
)
from megatronapp_tpu.transformer import block, kda


def recurrence(q, k, v, g, beta, s0):
    """S' = diag(exp g) S; S = S' + b k (v - S'^T k)^T; o = S^T q, a token
    at a time."""
    def step(s, xs):
        q_, k_, v_, g_, b_ = xs
        s = jnp.exp(g_)[..., None] * s
        u = b_[..., None] * (v_ - jnp.einsum("bhkv,bhk->bhv", s, k_))
        s = s + k_[..., None] * u[:, :, None, :]
        return s, jnp.einsum("bhkv,bhk->bhv", s, q_)

    s, o = jax.lax.scan(step, s0, tuple(
        jnp.moveaxis(t, 1, 0) for t in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1), s


def _inputs(s, decay, seed=0, b=2, heads=3, d=16):
    """Unit keys with a common direction (as silu's outputs have), b in (0,
    2), log decays -decay x softplus(.)."""
    rng = np.random.default_rng(seed)

    def unit(x):
        return x / np.linalg.norm(x, axis=-1, keepdims=True)

    q = unit(rng.standard_normal((b, s, heads, d))) * d ** -0.5
    k = unit(rng.standard_normal((b, s, heads, d)) + 0.7)
    v = rng.standard_normal((b, s, heads, d))
    g = -decay * np.log1p(np.exp(rng.standard_normal((b, s, heads, d))
                                 + (2.0 if decay > 1 else 0.0)))
    beta = 2 / (1 + np.exp(-rng.standard_normal((b, s, heads))))
    s0 = rng.standard_normal((b, heads, d, d))
    return [jnp.asarray(t, jnp.float32) for t in (q, k, v, g, beta, s0)]


@pytest.mark.parametrize("s,chunk,decay,from_state", [
    (7, 64, 1.0, True),         # under one sub-chunk
    (64, 64, 1.0, False),       # one whole chunk from zeros
    (65, 64, 0.1, True),        # one position past a chunk's edge
    (150, 64, 1.0, True),       # three chunks, the last padded
    (150, 64, 16.0, True),      # A = 16: a chunk's sum of g passes -1,000
    (100, 32, 16.0, False),     # chunks of two sub-chunks
], ids=["7", "64", "65", "150", "150-A16", "100-chunk32-A16"])
def test_the_chunked_pass_is_the_recurrence(s, chunk, decay, from_state):
    q, k, v, g, beta, s0 = _inputs(s, decay)
    if not from_state:
        s0 = jnp.zeros_like(s0)
    if decay > 1:
        # the textbook factor K * exp(-G) overflows float32 here
        assert float(jnp.min(jnp.cumsum(g, 1)[:, :min(s, chunk)])) < -1000
    with jax.default_matmul_precision("highest"):
        want_o, want_s = recurrence(q, k, v, g, beta, s0)
        got_o, got_s = jax.jit(kda.kda_chunked, static_argnums=5)(
            q, k, v, g, beta, chunk, s0)
    assert bool(jnp.isfinite(got_o).all() & jnp.isfinite(got_s).all())
    np.testing.assert_allclose(got_o, want_o, atol=5e-6)
    np.testing.assert_allclose(got_s, want_s, atol=5e-6)
    assert float(jnp.abs(want_o).max()) > 0.1


def test_a_position_without_decay_and_beta_leaves_the_state():
    """What kda_forward makes of a call's padding (g 0, b 0)."""
    q, k, v, g, beta, s0 = _inputs(40, 1.0)
    g, beta = g.at[:, 25:].set(0.0), beta.at[:, 25:].set(0.0)
    _, got = kda.kda_chunked(q, k, v, g, beta, 64, s0)
    _, want = recurrence(q[:, :25], k[:, :25], v[:, :25], g[:, :25],
                         beta[:, :25], s0)
    np.testing.assert_allclose(got, want, atol=5e-6)


def test_the_unit_lower_inverse():
    lower = jnp.tril(jax.random.normal(jax.random.PRNGKey(0), (3, 16, 16)),
                     -1)
    inv = kda._unit_lower_inverse(lower)
    np.testing.assert_allclose(
        inv @ (jnp.eye(16) + lower), jnp.broadcast_to(jnp.eye(16),
                                                      (3, 16, 16)), atol=2e-4)


@pytest.mark.parametrize("heads", [4, 32], ids=["one-tile", "two-tiles"])
def test_the_decode_kernel_is_the_plain_update(heads):
    """[128, 128] states a head at the published size: running slots get
    the plain update, an inactive slot's plane and every other layer's are
    the bits that came in, its output 0."""
    slots, layers, d = 5, 2, 128
    assert heads_step(d, d, heads) == min(heads, 16)
    rng = np.random.default_rng(1)

    def draw(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.float32)

    pool = draw(layers, slots, d, heads * d)
    q, v = draw(slots, heads, d), draw(slots, heads, d)
    k = draw(slots, heads, d)
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    alpha = jnp.asarray(rng.uniform(0, 1, (slots, heads, d)), jnp.float32)
    beta = jnp.asarray(rng.uniform(0, 2, (slots, heads)), jnp.float32)
    active = jnp.asarray([True, False, True, True, False])
    o, new = jax.jit(kda_update)(pool, 1, q, k, v, alpha, beta, active)
    want_o, want_h = kda_update_reference(pool[1], q, k, v, alpha, beta)
    np.testing.assert_allclose(o[active], want_o[active], atol=2e-4)
    np.testing.assert_allclose(new[1][active], want_h[active], atol=2e-5)
    assert not np.asarray(o[~active]).any()
    np.testing.assert_array_equal(new[1][~active], pool[1][~active])
    np.testing.assert_array_equal(new[0], pool[0])
    # the plain update is the recurrence's step
    s0 = jnp.swapaxes(pool[1].reshape(slots, d, heads, d), 1, 2)
    rec_o, _ = recurrence(q[:, None], k[:, None], v[:, None],
                          jnp.log(alpha)[:, None], beta[:, None], s0)
    np.testing.assert_allclose(want_o, rec_o[:, 0].reshape(slots, -1),
                               atol=2e-4)


class TestLayer:
    cfg = PRESETS["solar-open2-tiny"](compute_dtype=jnp.float32,
                                      init_method_std=0.1)

    def _layer(self):
        p, _ = block.HALF_INITS["mixers_kda"](
            jax.random.PRNGKey(3), cfg=self.cfg, out_std=0.05)
        return p

    def test_calls_of_uneven_width_then_tokens_are_the_whole_sequence(self):
        """kda_forward carried call to call (5, then 40 of which 3 are
        padding, then one token at a time) against one whole call."""
        p = self._layer()["kda"]
        x = jax.random.normal(jax.random.PRNGKey(4), (2, 45, 64))
        whole, (tail_w, h_w) = kda.kda_forward(p, x, self.cfg)
        out1, state = kda.kda_forward(p, x[:, :5], self.cfg)
        padded = jnp.pad(x[:, 5:42], ((0, 0), (0, 3), (0, 0)))
        out2, state = kda.kda_forward(p, padded, self.cfg, state=state,
                                      counts=jnp.asarray([37, 37]))
        outs = [out1, out2[:, :37]]
        for t in range(42, 45):
            o, state = kda.kda_forward(p, x[:, t:t + 1], self.cfg,
                                       state=state)
            outs.append(o)
        np.testing.assert_allclose(jnp.concatenate(outs, 1), whole,
                                   atol=2e-5)
        np.testing.assert_allclose(state[1], h_w, atol=2e-5)
        np.testing.assert_allclose(state[0], tail_w, atol=1e-6)

    @pytest.mark.parametrize("kw,what", [
        (dict(segment_ids=jnp.zeros((1, 4), jnp.int32)), "packed segments"),
        (dict(tp_sharded=True), "tp-sharded"),
        (dict(lora={}), "lora"),
    ], ids=["segments", "tp", "lora"])
    def test_refused_by_name(self, kw, what):
        with pytest.raises(NotImplementedError, match=what):
            block.layer_forward(self._layer(), jnp.zeros((1, 4, 64)),
                                self.cfg, **kw)

    def test_the_config_says_what_kda_heads_goes_with(self):
        with pytest.raises(ValueError, match="kda_heads"):
            PRESETS["solar-open2-tiny"](ssm_heads=2)
        with pytest.raises(ValueError, match="attention_gate_elementwise"):
            PRESETS["solar-open2-tiny"](attention_output_gate=False)
        cfg = self.cfg
        assert cfg.stack_plan[:5] == tuple(
            (m, "ffn") for m in ("mixers_attn",) + ("mixers_kda",) * 3
            + ("mixers_attn",))
        assert (cfg.num_kda_layers, cfg.num_ssm_layers,
                cfg.num_recurrent_layers, cfg.num_conv_layers) == (3, 3, 3, 0)
        assert (cfg.ssm_inner, cfg.ssm_conv_channels) == (32, 96)
        big = PRESETS["solar-open2-250b"]()
        assert (big.ssm_state_dim, big.ssm_inner, big.ssm_conv_channels,
                big.num_kda_layers, big.num_attention_layers) == (
                    128, 8192, 24576, 36, 12)
