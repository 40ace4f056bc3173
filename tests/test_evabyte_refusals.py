"""What a model with EVA attention refuses, each by a message that names
chunk summaries, and what it counts (stats_snapshot()["eva"], the decode
round's span attributes, the summariser kernel), on test_evabyte.py's tiny
model: window 32, chunk 4, block 4 at H 64, float32 on the CPU."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from megatronapp_tpu.inference.dynamic_engine import DynamicInferenceEngine
from megatronapp_tpu.models.gpt import gpt_forward
from megatronapp_tpu.models.presets import PRESETS
from megatronapp_tpu.transformer import eva

from test_evabyte import C, GREEDY, W, _engine, _model, _tokens


# ---- (g) what such a model refuses ------------------------------------------

@pytest.fixture(scope="module")
def shared_engine():
    """One engine for the cases that ask it what it refuses or run a
    request through it and leave it idle (conftest.py `lend`)."""
    return _engine(*_model())


@pytest.fixture
def eng(shared_engine, lend):
    return lend(shared_engine)


class TestRefusals:
    def test_prefix_reuse_is_off_and_said(self, eng):
        assert eng.pool.enable_prefix_caching is False
        line = eng.startup_line()
        for word in ("window 32", "every 4", "prefix reuse (off)",
                     "spec_method", "export/import/adopt", "lora",
                     "quantized pool", "chunk summaries"):
            assert word in line, (word, line)
        prompt = _tokens(40, 1)
        for _ in range(2):
            eng.add_request(prompt, 2, GREEDY)
            eng.run_to_completion()
        assert eng.pool.stats["prefix_hit_tokens"] == 0

    @pytest.mark.parametrize("kw,word", [
        ({"spec_method": "ngram"}, "spec_method"),
        ({"spill_host_mb": 1.0}, "spill_host_mb"),
        ({"adapter_cache": object()}, "adapter_cache"),
        ({"pool": object()}, "an injected pool"),
        ({"ctx": object()}, "ctx"),
        ({"kv_cache_dtype": "int8"}, "quantized pool"),
    ], ids=lambda v: v if isinstance(v, str) else "")
    def test_construction_refuses(self, kw, word):
        cfg, params = _model()
        with pytest.raises(ValueError, match="chunk summaries") as e:
            _engine(cfg, params, **kw)
        assert word in str(e.value)

    @pytest.mark.parametrize("call", ["adopt_request", "export_request",
                                      "import_request"])
    def test_moving_a_request_refuses(self, call, eng):
        args = {"adopt_request": (None, 0, 0), "export_request": (0,),
                "import_request": ({},)}[call]
        with pytest.raises(ValueError, match="chunk summaries"):
            getattr(eng, call)(*args)

    @pytest.mark.parametrize("call,args", [
        ("rewind", (0, 1)), ("export_slot", (0, 1)),
        ("import_slot", (0, {"kv_cache_dtype": "bf16"})),
        ("transfer_slot", (0, 1))])
    def test_the_pool_refuses(self, call, args, eng):
        with pytest.raises(ValueError, match="chunk summaries"):
            getattr(eng.pool, call)(*args)

    def test_sizes_that_do_not_fit_the_pages_refuse(self):
        cfg, params = _model()
        with pytest.raises(ValueError, match="must equal block_size"):
            _engine(cfg, params, block_size=8)
        with pytest.raises(ValueError, match="prefill_chunk"):
            _engine(cfg, params, prefill_chunk=24)
        from megatronapp_tpu.config.transformer_config import (
            TransformerConfig,
        )
        with pytest.raises(ValueError, match="eva_window_size"):
            TransformerConfig(eva_window_size=30, eva_chunk_size=4)
        with pytest.raises(ValueError, match="EVA attention"):
            TransformerConfig(eva_window_size=32, eva_chunk_size=4,
                              multi_latent_attention=True)
        with pytest.raises(ValueError, match="untied"):
            TransformerConfig(num_pred_heads=8)

    def test_the_other_paths_refuse(self):
        from megatronapp_tpu.inference.engine import init_kv_cache
        from megatronapp_tpu.models.gpt import gpt_loss
        from megatronapp_tpu.transformer.attention import attention_forward
        cfg, params = _model()
        toks = jnp.asarray(_tokens(16, 1)[None])
        with pytest.raises(ValueError, match="window term"):
            gpt_forward(params, toks, cfg, segment_ids=jnp.zeros_like(toks))
        with pytest.raises(NotImplementedError, match="num_pred_heads"):
            gpt_loss(params, toks, toks, None, cfg)
        layer = jax.tree.map(lambda a: a[0], params["block"]["attention"])
        with pytest.raises(ValueError, match="dense cache"):
            attention_forward(layer, jnp.zeros((1, 1, 64)), cfg,
                              kv_cache=init_kv_cache(cfg, 1, 32),
                              cache_index=0)


# ---- (h) counters and span attributes ---------------------------------------

class TestCounters:
    def test_stats_and_span_attributes(self, monkeypatch):
        cfg, params = _model()
        eng = _engine(cfg, params, max_batch=2)
        spans = []
        real = eng._span

        def span(name, rid=None, ring=None, **attrs):
            spans.append((name, attrs))
            return real(name, rid, ring=ring, **attrs)

        monkeypatch.setattr(eng, "_span", span)
        for n, new in ((W + 6, 40), (9, 30)):
            eng.add_request(_tokens(n, n), new, GREEDY)
        eng.run_to_completion()
        st = eng.stats_snapshot()["eva"]
        assert (st["layers"], st["window"], st["chunk"]) == (2, W, C)
        rounds = [a for n, a in spans if n == "engine.decode_round"]
        calls = [a for n, a in spans if n == "engine.prefill_call"]
        assert st["decode_rounds"] == len(rounds) == 39
        # request 0 decodes from T = 38 to 76, request 1 from 9 to 37
        lens = [list(range(W + 6, W + 45)), list(range(9, 38))]
        walked = sum(int(eva.rows_walked(cfg, t)) for ts in lens for t in ts)
        full = sum(t + 1 for ts in lens for t in ts)
        assert st["rows_walked"] == walked == sum(a["kv_rows"]
                                                  for a in rounds)
        assert st["rows_full_attention"] == full
        assert sum(a["kv_tokens"] for a in rounds) == full - sum(
            len(ts) for ts in lens)
        first = rounds[0]
        assert first["batch"] == 2
        assert first["kv_rows"] == (W // C + 6 + 1) + (9 + 1)
        assert first["summary_rows"] == W // C
        assert first["kv_blocks"] == (W // C + 6) // 4 + 1 + 9 // 4 + 1
        # every chunk that filled was pooled once: prefill calls of 8 pool
        # two each, decode rounds the rest
        written = (W + 45) // C + 38 // C
        assert st["summary_rows_written"] == written == (
            sum(a["summaries"] for a in rounds)
            + sum(a["summaries"] for a in calls))
        assert st["windows_closed"] == 2 + 1
        assert st["blocks_freed"] == 3 * (W // 4)
        paged = eng.stats_snapshot()["paged"]
        assert paged["blocks_live"] == sum(a["kv_blocks"] for a in rounds)

    def test_a_model_without_eva_reports_false(self):
        from megatronapp_tpu.models.gpt import init_gpt_params
        cfg = PRESETS["gpt2-125m"](num_layers=1, hidden_size=32,
                                   num_attention_heads=2, vocab_size=64,
                                   max_position_embeddings=32)
        params, _ = init_gpt_params(jax.random.PRNGKey(0), cfg)
        eng = DynamicInferenceEngine(params, cfg, max_batch=1, paged=True)
        assert eng.stats_snapshot()["eva"] is False
        assert "eva" not in eng.startup_line()

    def test_summariser_kernel_against_the_plain_pooling(self):
        """kernel_gen.eva_summary in place on a pool: the pooled rows land
        where they are sent, dropped ones nowhere, nothing else moves."""
        from megatronapp_tpu.ops.pallas import kernel_gen
        rng = np.random.default_rng(0)
        k, v = (jnp.asarray(rng.normal(size=(2, 10, 4, 3, 16)), jnp.float32)
                for _ in range(2))
        phi, mu = (jnp.asarray(rng.normal(size=(3, 16)), jnp.float32)
                   for _ in range(2))
        src = jnp.asarray([2, 5, 7, 1], jnp.int32)
        dst = jnp.asarray([8, 10, 9, 8], jnp.int32)     # 10: dropped
        off = jnp.asarray([0, 1, 3, 2], jnp.int32)
        nk, nv = kernel_gen.eva_summary(k, v, phi, mu, 1, src, dst, off,
                                        scale=0.25)
        wk, wv = np.array(k), np.array(v)
        for s_, d_, o_ in ((2, 8, 0), (7, 9, 3), (1, 8, 2)):
            wk[1, d_, o_], wv[1, d_, o_] = (
                np.asarray(a) for a in eva.summarise(k[1, s_], v[1, s_], phi,
                                                     mu, 0.25))
        np.testing.assert_allclose(np.asarray(nk), wk, atol=1e-6)
        np.testing.assert_allclose(np.asarray(nv), wv, atol=1e-6)
