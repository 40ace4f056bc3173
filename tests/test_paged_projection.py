"""The paged steps keep their q/kv projection flat; nothing else does.

`attention_forward` holds the projections' results behind a
`lax.optimization_barrier` in the one branch where a layer's kernels arrive
as `lax.scan`'s slice of a stack at a few rows of tokens: a paged `kv_cache`
on one device (ISSUE 51; what that buys is a compiled step's business:
tests/test_chip_compile.py::test_engine_paged_steps_at_cell_shapes). Here, on
the CPU:

- training is untouched: `gpt_loss` and `block_forward` under `jax.grad`
  trace to the jaxpr they trace to with the barrier taken out, and hold no
  barrier;
- the arithmetic is untouched: every logits row a paged engine computes (each
  `[1, W]` prefill call's, each decode round's) equals, bit for bit, the row
  of an engine traced with the barrier taken out, which is the parent's
  formulation line for line: dense, GQA with bias, qk-layernorm, gated and
  sliding-window layers, EVA layers, rows with LoRA adapters.
"""
import contextlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import test_evabyte as evabyte
import test_laguna as laguna
from megatronapp_tpu.config.transformer_config import (
    PositionEmbeddingKind, TransformerConfig,
)
from megatronapp_tpu.inference.dynamic_engine import DynamicInferenceEngine
from megatronapp_tpu.inference.engine import SamplingParams
from megatronapp_tpu.inference.lora import (
    AdapterCache, AdapterRegistry, LoraAdapter,
)
from megatronapp_tpu.models.gpt import gpt_loss, init_gpt_params
from megatronapp_tpu.transformer.block import block_forward

GREEDY = SamplingParams(greedy=True)


def _cfg(**kw):
    d = dict(num_layers=2, hidden_size=64, num_attention_heads=4,
             vocab_size=128, max_position_embeddings=64,
             compute_dtype=jnp.float32, remat_policy="none")
    d.update(kw)
    return TransformerConfig(**d)


def _params(cfg, seed=7):
    """Seeded weights with every bias and norm scale away from its initial
    0 or 1, so that a term applied on the wrong side of the barrier shows."""
    params, _ = init_gpt_params(jax.random.PRNGKey(seed), cfg)
    key = jax.random.PRNGKey(seed + 1)
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(key, len(leaves))
    return jax.tree.unflatten(tree, [
        a + 0.1 * jax.random.normal(k, a.shape, a.dtype) if a.ndim <= 2
        else a for a, k in zip(leaves, keys)])


@contextlib.contextmanager
def _barrier(monkeypatch, out: bool):
    """`jax.lax.optimization_barrier` counted (yields the one-item count),
    and with `out` taken out: the parent's lines."""
    calls = [0]
    real = jax.lax.optimization_barrier

    def barrier(x):
        calls[0] += 1
        return x if out else real(x)

    with monkeypatch.context() as m:
        m.setattr(jax.lax, "optimization_barrier", barrier)
        yield calls


# ---------------------------------------------------------------------------
# Training keeps today's lines
# ---------------------------------------------------------------------------

def _loss_jaxpr(cfg, params):
    tokens = jnp.zeros((2, 16), jnp.int32)

    def loss(p):            # a fresh closure a trace: jax keeps a function's
        return gpt_loss(p, tokens, tokens, None, cfg)[0]

    return jax.make_jaxpr(jax.value_and_grad(loss))(params)


def _block_grad_jaxpr(cfg, params):
    x = jnp.zeros((2, 16, cfg.hidden_size), cfg.compute_dtype)

    def out(p, x):
        return jnp.sum(block_forward(p, x, cfg)[0])

    return jax.make_jaxpr(jax.grad(out, argnums=(0, 1)))(params["block"], x)


@pytest.mark.parametrize("trace", [_loss_jaxpr, _block_grad_jaxpr],
                         ids=["gpt_loss", "block_forward-grad"])
@pytest.mark.parametrize("kind", ["gqa-bias", "qk-layernorm"])
def test_training_traces_what_it_traced(monkeypatch, trace, kind):
    cfg = _cfg(num_query_groups=2, add_qkv_bias=kind == "gqa-bias",
               qk_layernorm=kind == "qk-layernorm",
               remat_policy="selective")
    params = _params(cfg)
    with _barrier(monkeypatch, out=False) as calls:
        here = str(trace(cfg, params))
    assert calls == [0] and "optimization_barrier" not in here
    with _barrier(monkeypatch, out=True):
        assert str(trace(cfg, params)) == here


# ---------------------------------------------------------------------------
# The paged steps compute what they computed
# ---------------------------------------------------------------------------

def _rows(eng, requests):
    """Serve `requests` ((prompt, answer length, add_request's keywords), ...)
    to the end; every logits array the engine's two steps returned, in
    order: a prefill call's [1, 1, V], a decode round's [B, V]."""
    rows = []
    mq, dec = eng._mq_step, eng._decode

    def mq_step(*a):
        out = mq(*a)
        rows.append(("prefill", np.asarray(out[0])))
        return out

    def decode(*a):
        out = dec(*a)
        rows.append(("decode", np.asarray(out[0])[np.asarray(a[6])]))
        return out

    eng._mq_step, eng._decode = mq_step, decode
    for prompt, n, kw in requests:
        eng.add_request(prompt, n, GREEDY, **kw)
    eng.run_to_completion()
    eng.pool.audit()
    return rows


def _dense(**kw):
    cfg = _cfg(**kw)
    return cfg, _params(cfg), dict(max_seq_len=48, block_size=8,
                                   prefill_chunk=8)


def _laguna():
    cfg = laguna.model.model_config(laguna.tiny_config(), "float32",
                                    compute_dtype=jnp.float32)
    return cfg, laguna.model.init_params(cfg, 11), dict(
        max_seq_len=96, block_size=4, num_blocks=64, prefill_chunk=16)


def _eva():
    cfg, params = evabyte._model()
    return cfg, params, dict(max_seq_len=192, num_blocks=96, block_size=4,
                             prefill_chunk=8)


CASES = {
    "dense": lambda: _dense(
        position_embedding=PositionEmbeddingKind.learned_absolute),
    "dense-bf16": lambda: _dense(
        position_embedding=PositionEmbeddingKind.learned_absolute,
        add_qkv_bias=True, compute_dtype=jnp.bfloat16),
    "gqa-bias": lambda: _dense(num_query_groups=2, add_qkv_bias=True),
    "qk-layernorm": lambda: _dense(num_query_groups=2, qk_layernorm=True),
    "gated-window": _laguna,
    "eva": _eva,
}
CASES["lora"] = CASES["gqa-bias"]


@pytest.mark.parametrize("case", list(CASES))
def test_paged_steps_equal_the_parents_formulation(monkeypatch, case):
    cfg, params, kw = CASES[case]()
    lora = case == "lora"
    rng = np.random.default_rng(3)
    vocab = cfg.true_vocab_size or cfg.vocab_size
    requests = [(rng.integers(0, vocab, n).astype(np.int32), m,
                 {"adapter_id": f"t{i}"} if lora else {})
                for i, (n, m) in enumerate(((19, 9), (5, 12)))]

    def served():
        cache = None
        if lora:
            reg = AdapterRegistry()
            for i in range(len(requests)):
                reg.register(LoraAdapter.random(f"t{i}", cfg, rank=4,
                                                seed=10 + i, scale=2.0))
            cache = AdapterCache(cfg, reg, max_resident=2, rank=4)
        return _rows(DynamicInferenceEngine(
            params, cfg, max_batch=2, paged=True, adapter_cache=cache, **kw),
            requests)

    with _barrier(monkeypatch, out=False) as calls:
        got = served()
    # one barrier a traced attention body: the branch is the one taken
    assert calls[0] >= 2
    with _barrier(monkeypatch, out=True):
        want = served()
    assert [k for k, _ in got] == [k for k, _ in want]
    assert {"prefill", "decode"} <= {k for k, _ in got}
    for (kind, a), (_, b) in zip(got, want):
        assert a.dtype == b.dtype and a.shape == b.shape
        assert np.array_equal(a, b), (kind, np.abs(
            a.astype(np.float32) - b.astype(np.float32)).max())
