"""One cache behind the continuous-batching engine (PR 44): (a) every cell of
paged_cache.TENANT_LACKS, on a two-layer model of each kind of per-slot
state; (b) the dense slot cache's switch is refused, as a constructor
argument and as a flag; (c) the draft proposer's dense `_decode_step`
against gpt_forward at ragged per-row lengths."""
import functools
import inspect
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from megatronapp_tpu.config.transformer_config import TransformerConfig
from megatronapp_tpu.inference.dynamic_engine import DynamicInferenceEngine
from megatronapp_tpu.inference.engine import init_kv_cache
from megatronapp_tpu.inference.paged_cache import TENANT_LACKS, check_tenants
from megatronapp_tpu.inference.speculative import _decode_step
from megatronapp_tpu.models.gpt import gpt_forward, init_gpt_params

BASE = dict(num_layers=2, hidden_size=64, num_attention_heads=4,
            vocab_size=128, max_position_embeddings=64,
            compute_dtype=jnp.float32, remat_policy="none")
MLA = dict(multi_latent_attention=True, kv_lora_rank=32, qk_head_dim=16,
           qk_pos_emb_head_dim=8, v_head_dim=16)
KINDS = {
    "ssm": dict(attn_layer_period=2, attn_layer_offset=1),
    "conv": dict(attn_layer_period=2, attn_layer_offset=1,
                 shortconv_kernel=3),
    "eva": dict(eva_window_size=32, eva_chunk_size=4),
    "window": dict(attn_layer_period=2, attn_layer_offset=0,
                   sliding_window=8, num_query_groups=2,
                   sliding_window_heads=8),
    "double": dict(MLA, num_moe_experts=8, moe_zero_experts=4,
                   moe_router_topk=3, moe_experts_held=(0, 4),
                   moe_ffn_hidden_size=32, moe_shortcut_double_layer=True),
}
# What this PR found each kind to lack, stated apart from the table.
LACKS = {
    "ssm": {"rewind", "snapshot", "handoff", "adapters", "shard", "prefix"},
    "conv": {"rewind", "snapshot", "handoff", "adapters", "shard", "prefix"},
    "eva": {"rewind", "snapshot", "handoff", "adapters", "shard", "quantize",
            "prefix"},
    "window": {"rewind", "snapshot", "handoff", "adapters", "shard",
               "quantize", "prefix"},
    "double": {"handoff", "adapters", "shard", "quantize"},
}
# capability -> (the constructor argument that asks for it, how a refusal
# names the asking, what an engine that has it shows)
ASKS = {
    "rewind": ({"spec_method": "ngram"}, "spec_method",
               lambda e: e.spec_method == "ngram"),
    "snapshot": ({"spill_host_mb": 1.0}, "spill_host_mb",
                 lambda e: e.spill is not None),
    "handoff": ({"pool": object()}, "an injected pool", None),
    "adapters": ({"adapter_cache": object()}, "adapter_cache", None),
    "shard": ({"ctx": object()}, "ctx", None),
    "quantize": ({"kv_cache_dtype": "int8"}, "kv_cache_dtype 'int8'",
                 lambda e: e.pool.quantized),
    "prefix": ({"enable_prefix_caching": True}, "prefix reuse",
               lambda e: e.pool.enable_prefix_caching),
}
# the calls that move a request, by the capability each takes
MOVES = {"snapshot": (("export_request", (0,)), ("import_request", ({},))),
         "handoff": (("adopt_request", (None, 0, 0)),)}


@functools.lru_cache(maxsize=None)
def _model(kind):
    cfg = TransformerConfig(**BASE, **KINDS[kind])
    return cfg, init_gpt_params(jax.random.PRNGKey(0), cfg)[0]


def _engine(kind, **kw):
    cfg, params = _model(kind)
    return DynamicInferenceEngine(
        params, cfg, **{"max_batch": 2, "max_seq_len": 64, "block_size": 4,
                        "num_blocks": 16, "prefill_chunk": 8, **kw})


@functools.lru_cache(maxsize=None)
def _plain_engine(kind):
    return _engine(kind)


def test_the_table_holds_what_was_found():
    assert {k: set(row[2]) for k, row in TENANT_LACKS.items()} == LACKS
    for kind in KINDS:
        cfg, _ = _model(kind)
        assert [k for k, row in TENANT_LACKS.items() if row[0](cfg)] == [kind]
        assert check_tenants(cfg) == LACKS[kind]
    assert check_tenants(TransformerConfig(**BASE), {
        cap: how for cap, (_, how, _) in ASKS.items()}) == frozenset()


@pytest.mark.parametrize("kind,cap", [(k, c) for k in KINDS for c in ASKS])
def test_every_cell_of_the_table(kind, cap):
    """A capability the kind lacks refuses at construction, by a message
    that opens with the kind's sentence and names what was asked for (prefix
    reuse, asked for by default, is switched off and said so), and so do the
    calls that move a request; one it has builds an engine that shows it."""
    kw, named, shows = ASKS[cap]
    why = TENANT_LACKS[kind][1]
    if cap == "prefix":
        eng = _engine(kind, **kw)
        assert bool(shows(eng)) is (cap not in LACKS[kind])
        assert ("prefix reuse" in eng.startup_line()) is (cap in LACKS[kind])
    elif cap in LACKS[kind]:
        with pytest.raises(ValueError) as e:
            _engine(kind, **kw)
        assert str(e.value).startswith(why + ": cannot serve it with "
                                       + named + " (")
        for call, args in MOVES.get(cap, ()):
            with pytest.raises(ValueError) as e:
                getattr(_plain_engine(kind), call)(*args)
            assert why in str(e.value) and call + " (" in str(e.value)
    elif shows is None:
        pytest.fail(f"no model of this test asks a {kind} cache for {cap}")
    else:
        assert shows(_engine(kind, **kw))
        if cap == "snapshot":
            # not refused by the table: an unknown request is no session
            assert _plain_engine(kind).export_request(10 ** 6) is None


def test_a_refusal_lists_everything_asked_for_that_is_lacking():
    with pytest.raises(ValueError) as e:
        _engine("double", spec_method="ngram", ctx=object(),
                kv_cache_dtype="fp8", adapter_cache=object())
    said = str(e.value)
    assert "spec_method" not in said
    assert said.index("adapter_cache (") < said.index("; ctx (") < said.index(
        "; kv_cache_dtype 'fp8' (")


class TestTheSwitchIsGone:
    def test_paged_false_is_refused(self):
        sig = inspect.signature(DynamicInferenceEngine.__init__)
        assert sig.parameters["paged"].default is True
        cfg = TransformerConfig(**BASE)
        with pytest.raises(ValueError, match="PR 44"):
            DynamicInferenceEngine(None, cfg, max_batch=1, paged=False)

    def test_the_server_tool_refuses_the_flag(self, monkeypatch, capsys):
        from tools import run_text_generation_server as tool
        monkeypatch.setattr(sys, "argv", [
            "run_text_generation_server.py", "--engine", "dynamic",
            "--paged-kv-cache"])
        with pytest.raises(SystemExit) as e:
            tool.main()
        assert e.value.code == 2
        assert ("unrecognized arguments: --paged-kv-cache"
                in capsys.readouterr().err)


@pytest.mark.parametrize("mla", [False, True], ids=["gqa", "mla"])
def test_dense_decode_step_matches_gpt_forward_at_ragged_lengths(mla):
    """Three rows fed a token a step, each at its own position, the shorter
    ones standing still once they end: every step's logits are the whole
    sequence's at that row's position."""
    cfg = TransformerConfig(**BASE, **(MLA if mla else
                                       {"num_query_groups": 2}))
    params = init_gpt_params(jax.random.PRNGKey(11), cfg)[0]
    rng = np.random.default_rng(4)
    seqs = [rng.integers(0, 128, n).astype(np.int32) for n in (9, 3, 14)]
    want = [np.asarray(gpt_forward(params, jnp.asarray(s[None]), cfg)[0][0])
            for s in seqs]
    step = jax.jit(lambda t, c, l: _decode_step(
        params, t, c, l, jnp.ones((3,), bool), cfg))
    cache = init_kv_cache(cfg, 3, 16)
    for t in range(max(map(len, seqs))):
        at = np.asarray([min(t, len(s) - 1) for s in seqs], np.int32)
        tokens = np.asarray([[s[i]] for s, i in zip(seqs, at)], np.int32)
        logits, cache = step(jnp.asarray(tokens), cache, jnp.asarray(at))
        for b, i in enumerate(at):
            np.testing.assert_allclose(np.asarray(logits[b]), want[b][i],
                                       atol=2e-5, rtol=2e-5)
