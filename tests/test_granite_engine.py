"""granite-4.0-h-small through the paged engine at tiny widths on the CPU
(tests/test_granite.py holds the model, its sizes and the helpers): prefill
then decode through the paged cache against the reference's full forward
pass, by logits; the state tenant (admission resets the state, a slot keeps
it while it idles, preemption drops it, rounds run ahead); the share's
counters through a state-space stack; what the tenants refuse."""
import dataclasses

import numpy as np
import pytest

import jax.numpy as jnp

from perfbench import manifest
from test_granite import (
    GREEDY, MODEL, PUBLISHED, TINY, TOL_BF16, TOL_F32, _engine, _model,
    _recorded, _shared_engine, _tokens, _worst_gap, eng,  # noqa: F401
)


class TestEngine:
    @pytest.mark.parametrize("dtype,tol,width,n", [
        (jnp.float32, TOL_F32, 8, 18), (jnp.bfloat16, TOL_BF16, 8, 18),
        (jnp.float32, TOL_F32, 32, 34)],
        ids=["float32-8", "bfloat16-8", "float32-32-two-chunks"])
    def test_chunked_prefill_then_decode(self, dtype, tol, width, n, lend,
                                         monkeypatch):
        """Prefill in calls of `width` (8: half a chunk of 16; 32: two
        chunks a call), 2 past a call's edge so that the
        convolution's tail and the state cross it, then 12 decode rounds
        through ssm_update: the LOGITS at every position are the
        reference's full forward pass's."""
        _, params = _model(dtype)
        eng = lend(_shared_engine(dtype, width))
        logits = _recorded(eng, monkeypatch)
        was = eng.stats_snapshot()["state"]
        req = eng.requests[eng.add_request(_tokens(n, 4), 13, GREEDY)]
        eng.run_to_completion()
        assert _worst_gap(params, req, logits) < tol
        state = eng.stats_snapshot()["state"]
        assert {k: state[k] - was[k]
                for k in ("resets", "dropped", "prefill_scans")} == {
            "resets": 1, "dropped": 0, "prefill_scans": -(-n // width) * 3}
        assert (state["kind"], state["mixer"], state["heads"],
                state["state_dim"], state["conv_channels"]) == (
            "ssm", "mamba2", 4, 16, 160)

    def test_continuous_batching_and_slot_reuse(self, eng, monkeypatch):
        """Requests of different lengths admitted at different steps; the
        fourth runs in the slot the first left, whose state it must not
        see; a slot that idles while others decode keeps its state."""
        _, params = _model()
        logits = _recorded(eng, monkeypatch)
        start = eng.stats_snapshot()

        def add(n, seed, new):
            return eng.requests[eng.add_request(_tokens(n, seed), new,
                                                GREEDY)]

        reqs = [add(5, 10, 3), add(11, 11, 9)]
        eng.step()
        reqs.append(add(17, 12, 8))
        idle = reqs[0].slot
        while eng.slots[idle] is not None:
            eng.step()
        before = [np.asarray(p[:, idle]) for p in eng.pool.state]
        assert np.abs(before[0]).max() > 0
        eng.step()
        for was, pool in zip(before, eng.pool.state):
            np.testing.assert_array_equal(was, np.asarray(pool[:, idle]))
        reqs.append(add(9, 13, 6))
        eng.step()
        assert reqs[3].slot == idle
        eng.run_to_completion()
        for req in reqs:
            assert _worst_gap(params, req, logits) < TOL_F32
        stats = eng.stats_snapshot()
        assert stats["state"]["resets"] - start["state"]["resets"] == 4
        # rounds dispatched before the one before them was read: the state
        # pools ride the run-ahead loop like the pages
        assert stats["steps"]["rounds_ahead"] \
            > start["steps"]["rounds_ahead"]

    def test_pools_and_bytes(self, lend):
        eng = lend(_shared_engine(jnp.bfloat16))
        k, v = eng.pool.pages
        assert k.shape == v.shape == (1, 24, 4, 2, 16)
        state, conv = eng.pool.state
        assert state.shape == (3, 3, 16, 128) and state.dtype == jnp.float32
        assert conv.shape == (3, 3, 3 * 160) and conv.dtype == jnp.bfloat16
        stated = {**TINY, "serve": {"params_dtype": "bfloat16"}}
        assert eng.stats_snapshot()["state"]["bytes_per_slot"] == \
            MODEL.state_bytes_per_slot(stated, "float32") == \
            3 * (16 * 128 * 4 + 3 * 160 * 2)
        assert MODEL.state_bytes_per_slot(PUBLISHED, "float32") == 38_204_928
        assert MODEL.kv_bytes_per_token(PUBLISHED, "bfloat16") == 4096

    def test_the_counters_add_up_through_a_state_space_stack(self, eng):
        """assignments_here + assignments_absent = tokens x top-k x layers,
        both above 0, over the plain decode rounds of a stack whose mixers
        are Mamba-2."""
        was = eng.stats_snapshot()["moe"]
        for seed, n in ((30, 9), (31, 14), (32, 6)):
            eng.add_request(_tokens(n, seed), 10, GREEDY)
        eng.run_to_completion()
        moe = eng.stats_snapshot()["moe"]
        # this run's: the counts (what the engine holds is no count)
        moe = {k: v if k == "experts_here" else v - was[k]
               for k, v in moe.items()}
        picks = moe["tokens"] * 3 * 4
        assert moe["assignments"] == picks > 0
        assert moe["assignments_here"] + moe["assignments_absent"] == picks
        assert moe["assignments_here"] > 0 < moe["assignments_absent"]
        assert moe["experts_here"] == 4 and moe["assignments_zero"] == 0
        cell = manifest.load_module("cells", "serve_closed_rag")
        assert cell.share_problems(moe, TINY) == []
        assert cell.share_problems(dict(moe, assignments_absent=0), TINY)
        assert cell.share_problems(dict(moe, experts_here=8), TINY)

    def test_the_prefill_call_says_its_chunks(self, lend, monkeypatch):
        from megatronapp_tpu.inference.dynamic_engine import (
            choose_prefill_width, prefill_call_costs,
        )
        cfg, params = _model()
        stream, flops = prefill_call_costs(cfg, params)
        plain = dataclasses.replace(cfg, ssm_heads=0)
        assert flops - prefill_call_costs(plain, params)[1] == \
            3 * 2.0 * (16 * 16 + 16 * 128 + 2 * 16 * 128)
        assert choose_prefill_width(cfg, params, 64, 4,
                                    device_kind="TPU v5 lite") in (
            16, 32, 64)
        eng = lend(_shared_engine(jnp.float32, 32))
        seen = []
        real = eng._span

        def span(name, *a, **attrs):
            if name == "engine.prefill_call":
                seen.append(attrs)
            return real(name, *a, **attrs)

        monkeypatch.setattr(eng, "_span", span)
        eng.add_request(_tokens(40, 33), 2, GREEDY)
        eng.run_to_completion()
        assert [a["ssd_chunks"] for a in seen] == [2, 2]
        assert [a["tokens"] for a in seen] == [32, 8]


class TestRefusals:
    @pytest.mark.parametrize("kw,word", [
        ({"spec_method": "ngram"}, "spec_method"),
        ({"spill_host_mb": 1.0}, "spill_host_mb"),
        ({"adapter_cache": object()}, "adapter_cache"),
        ({"pool": object()}, "injected pool"),
        ({"ctx": object()}, "serving mesh"),
        ({"kv_cache_dtype": "int8"}, "kv_cache_dtype 'int8'"),
    ])
    def test_construction_refuses(self, kw, word):
        cfg, params = _model()
        with pytest.raises(ValueError) as e:
            _engine(cfg, params, **kw)
        assert word in str(e.value)

    def test_prefix_reuse_is_off_and_said(self, eng):
        from megatronapp_tpu.inference.paged_cache import TENANT_LACKS
        cfg, _ = _model()
        assert TENANT_LACKS["ssm"][0](cfg) and TENANT_LACKS["double"][0](cfg)
        assert eng.pool.enable_prefix_caching is False
        assert "prefix reuse off" in eng.startup_line()

    @pytest.mark.parametrize("kw,word", [
        # PR 54 wrote groups of heads and an inner width of heads x head
        # columns: what is refused now is a group that is no whole number
        # of heads, and no chunk
        ({"ssm_groups": 3}, "a whole number of heads a group"),
        ({"ssm_chunk_size": 0}, "ssm_heads x ssm_head_dim"),
        ({"ssm_inner_norms": True}, "Mamba-2"),
        ({"attn_layer_period": None}, "Mamba-2"),
    ])
    def test_config_refuses_what_is_not_written(self, kw, word):
        cfg, _ = _model()
        with pytest.raises(ValueError, match=word):
            dataclasses.replace(cfg, **kw)

    def test_the_multiplier_is_for_plain_attention(self):
        from megatronapp_tpu.config.transformer_config import (
            TransformerConfig,
        )
        with pytest.raises(ValueError, match="no MLA"):
            TransformerConfig(multi_latent_attention=True,
                              attention_multiplier=0.01)


class TestStateControl:
    def test_the_bf16_recurrence_is_told_from_the_float32_one(self, eng):
        """The second reading the cell's limit on the state's precision is
        sized by; and the reference's state in the program's layout is what
        a slot holds."""
        cell = manifest.load_module("cells", "serve_closed_state")
        _, params = _model()
        tokens = np.stack([_tokens(40, s) for s in (1, 2)])
        fine = {t: cell._fine_share(MODEL.reference_state(
            params, TINY, jnp.asarray(tokens), state_dtype=t), "bfloat16")
            for t in ("float32", "bfloat16")}
        assert fine["float32"] > 0.99 > cell.FINE_SHARE > fine["bfloat16"]
        assert fine["bfloat16"] == 0.0
        req = eng.requests[eng.add_request(tokens[0][:30], 11, GREEDY)]
        eng.run_to_completion()
        want = MODEL.reference_state(
            params, TINY, jnp.asarray(req.tokens[None, :-1]))
        held = eng.pool.state[0][:, req.slot]
        np.testing.assert_allclose(held, want[:, 0], rtol=1e-4, atol=1e-5)

    def test_lengths_freeze_the_reference_state(self):
        _, params = _model()
        tokens = jnp.asarray(np.stack([_tokens(20, 1), _tokens(20, 2)]))
        cut = MODEL.reference_state(params, TINY, tokens,
                                    lengths=jnp.asarray([20, 12]))
        alone = MODEL.reference_state(params, TINY, tokens[1:, :12])
        np.testing.assert_allclose(cut[:, 1], alone[:, 0], atol=1e-6)


