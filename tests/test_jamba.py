"""AI21-Jamba2-3B's architecture against its plain float32 reference
(perfbench/models/jamba.py: the published equations in jax.numpy, the
recurrence as a sequential scan over positions), at tiny widths on the CPU
with seeded random weights: 8 layers of which 1 and 5 attend (period 4,
offset 1) with one key/value head, state 8, expand 2, the inner norms on.
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
from perfbench import manifest

from jitted import gpt_forward  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MODEL = manifest.load_module("models", "jamba")
with open(os.path.join(ROOT, "perfbench", "configs", "jamba2-3b.json")) as f:
    PUBLISHED = json.load(f)
TINY = {**PUBLISHED, **MODEL.REHEARSAL, "num_hidden_layers": 8}
# Weights at std 0.1, not 0.02: at 64 columns a mixer's output is then large
# enough beside the residual stream for every part of it to show in the
# logits (std ~1).
STD = 0.1

# float32 on both sides: what is left is the order of summation (the
# program's associative scan and its kernel against the reference's
# sequential recurrence); the two agree to 3e-6 on logits of std 0.8
# (measured). Without the inner norms they differ by 0.41; with padding not
# masked out of a chunk, a slot's state not reset at admission or the
# convolution's tail not carried across a chunk's edge, by 0.5 to 0.86.
TOL_F32 = 1e-4
# bf16 activations, convolution tail and KV rows (h stays float32) against
# the float32 reference on the same float32 weights, through 8 layers: 0.057
# to 0.071 on those logits (measured over three requests); the limit is
# three times that, and under half of what a missing mechanism gives.
TOL_BF16 = 0.2
GREEDY = SamplingParams(greedy=True)


@functools.cache
def _model(compute_dtype=jnp.float32):
    """(cfg, params) of a compute type, built once for every case."""
    cfg = MODEL.model_config(TINY, "float32", compute_dtype=compute_dtype,
                             init_method_std=STD)
    return cfg, MODEL.init_params(cfg, seed=5)


def _reference(params, tokens):
    tokens = jnp.asarray(tokens)
    return np.asarray(MODEL.reference_logits(
        params, TINY, tokens, jnp.zeros_like(tokens), None))


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
    one a program, compiled once, for the cases that leave it as they
    found it. They take it through `lend` (conftest.py)."""
    return _engine(*_model(dtype), prefill_chunk=width)


@pytest.fixture
def eng(lend):
    return lend(_shared_engine())


@pytest.fixture(scope="module")
def reference40():
    """The reference's logits of the two 40-token rows TestForward reads."""
    return _reference(_model()[1], np.stack([_tokens(40, 1), _tokens(40, 2)]))


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
    def test_gpt_forward_matches_reference(self, reference40):
        cfg, params = _model()
        assert cfg.num_ssm_layers == 6 and cfg.num_attention_layers == 2
        block = params["block"]
        assert block["mixers_ssm"]["ssm"]["in_kernel"].shape[0] == 6
        assert block["mixers_attn"]["attention"]["q_kernel"].shape[0] == 2
        assert block["ffn"]["mlp"]["fc1_kernel"].shape[0] == 8
        toks = np.stack([_tokens(40, 1), _tokens(40, 2)])
        logits, _ = gpt_forward(params, jnp.asarray(toks), cfg)
        assert np.abs(np.asarray(logits) - reference40).max() < TOL_F32

    def test_inner_norms_are_live(self, reference40):
        cfg, params = _model()
        other = dataclasses.replace(cfg, ssm_inner_norms=False)
        ssm = params["block"]["mixers_ssm"]["ssm"]
        bare = {k: v for k, v in ssm.items() if not k.endswith("_ln_scale")}
        without = dict(params, block=dict(
            params["block"], mixers_ssm=dict(params["block"]["mixers_ssm"],
                                             ssm=bare)))
        toks = _tokens(40, 1)[None]
        logits, _ = gpt_forward(without, jnp.asarray(toks), other)
        assert np.abs(np.asarray(logits) - reference40[:1]).max() \
            > 10 * TOL_F32

    @pytest.mark.parametrize("layers,period,offset", [(7, 3, 2), (5, 4, 0)])
    def test_any_period_and_offset(self, layers, period, offset):
        """Whole periods under one outer scan, then a partial one; the
        attention layer first, last or in the middle of its period."""
        tiny = {**TINY, "num_hidden_layers": layers,
                "attn_layer_period": period, "attn_layer_offset": offset}
        cfg = MODEL.model_config(tiny, "float32", compute_dtype=jnp.float32,
                                 init_method_std=STD)
        params = MODEL.init_params(cfg, seed=6)
        toks = jnp.asarray(_tokens(20, 3)[None])
        logits, _ = gpt_forward(params, toks, cfg)
        ref = MODEL.reference_logits(params, tiny, toks,
                                     jnp.zeros_like(toks), None)
        assert np.abs(np.asarray(logits) - np.asarray(ref)).max() < TOL_F32

    def test_hybrid_trains_from_flags(self):
        from megatronapp_tpu.config.arguments import (
            build_parser, configs_from_args,
        )
        from megatronapp_tpu.parallel.mesh import build_mesh
        from megatronapp_tpu.training.train import pretrain_gpt
        args = build_parser().parse_args([
            "--num-layers", "4", "--hidden-size", "64",
            "--num-attention-heads", "4", "--num-query-groups", "1",
            "--ffn-hidden-size", "128", "--vocab-size", "128",
            "--max-position-embeddings", "64", "--seq-length", "16",
            "--micro-batch-size", "2", "--global-batch-size", "2",
            "--train-iters", "3", "--log-interval", "1", "--lr", "1e-3",
            "--normalization", "RMSNorm", "--swiglu",
            "--disable-bias-linear", "--position-embedding-type", "none",
            "--attn-layer-period", "4", "--attn-layer-offset", "1",
            "--ssm-inner-norms"])
        model, par, train, opt = configs_from_args(args)
        assert (model.attn_layer_period, model.attn_layer_offset) == (4, 1)
        assert model.ssm_inner_norms and model.num_ssm_layers == 3
        ctx = build_mesh(par, devices=jax.devices()[:1])
        res = pretrain_gpt(model, par, train, opt, ctx=ctx)
        assert len(res.losses) == 3 and np.all(np.isfinite(res.losses))
        assert res.losses[-1] < res.losses[0]


class TestEngine:
    @pytest.mark.parametrize("dtype,tol,width,n", [
        (jnp.float32, TOL_F32, 8, 18), (jnp.bfloat16, TOL_BF16, 8, 18),
        (jnp.float32, TOL_F32, 32, 34), (jnp.float32, TOL_F32, None, 0),
        (jnp.float32, TOL_F32, 7, 16)],
        ids=["float32-8", "bfloat16-8", "float32-32", "float32-a-v5e's",
             "float32-odd-7"])
    def test_chunked_prefill_then_decode(self, dtype, tol, width, n, lend,
                                         monkeypatch):
        """18 tokens in chunks of 8: two full calls and one of 2, under 3
        past the chunk's edge, so its convolution tail reaches back into
        the call before and h crosses the edge; then 12 decode rounds
        through ssm_update. The same 2 past the edge of a call of 32
        (every engine's width before ISSUE 35), of the width the engine
        chooses for these shapes on a v5e, and of 7, which divides no
        prompt: the logits at every position are the reference's whatever
        the width."""
        from megatronapp_tpu.inference.dynamic_engine import (
            choose_prefill_width,
        )
        cfg, params = _model(dtype)
        if width is None:
            # float32 weights: 481 flops a byte; held to a max_seq_len
            width = choose_prefill_width(cfg, params, 16, 4,
                                         device_kind="TPU v5 lite")
            assert width == 16
            n = width + 2
        assert n % width == 2
        eng = lend(_shared_engine(dtype, width))
        logits = _recorded(eng, monkeypatch)
        was = eng.stats_snapshot()
        req = eng.requests[eng.add_request(_tokens(n, 4), 13, GREEDY)]
        eng.run_to_completion()
        assert _worst_gap(params, req, logits) < tol
        now = eng.stats_snapshot()
        state = {k: now["state"][k] - was["state"][k]
                 for k in ("resets", "dropped", "prefill_scans")}
        calls = -(-n // width)
        assert state == {"resets": 1, "dropped": 0,
                         "prefill_scans": calls * cfg.num_ssm_layers}
        assert now["prefill"]["calls"] - was["prefill"]["calls"] == calls
        assert now["prefill"]["tokens"] - was["prefill"]["tokens"] == n
        assert now["prefill"]["width"] == width
        assert now["prefill"]["fill_share"] == round(
            now["prefill"]["tokens"] / (now["prefill"]["calls"] * width), 4)

    def test_chunk_scan_in_blocks(self, monkeypatch):
        """A call wider than the scan's block (ISSUE 35: a prefill call of
        256 positions over blocks of 64) scans block after block, h carried
        between them and the last block padded: calls of 8 over blocks of
        3, 2 past a call's edge."""
        from megatronapp_tpu.transformer import ssm
        monkeypatch.setattr(ssm, "SCAN_BLOCK", 3)
        cfg, params = _model()
        eng = _engine(cfg, params)      # its own: another program
        logits = _recorded(eng, monkeypatch)
        req = eng.requests[eng.add_request(_tokens(18, 4), 13, GREEDY)]
        eng.run_to_completion()
        assert _worst_gap(params, req, logits) < TOL_F32

    def test_continuous_batching_and_slot_reuse(self, eng, monkeypatch):
        """Requests of different lengths admitted at different steps; the
        fourth runs in the slot the first left, whose state it must not
        see; a slot that idles while others decode keeps its state."""
        _, params = _model()
        logits = _recorded(eng, monkeypatch)
        resets = eng.stats_snapshot()["state"]["resets"]
        def add(n, seed, new):
            return eng.requests[eng.add_request(_tokens(n, seed), new,
                                                GREEDY)]

        reqs = [add(5, 10, 3), add(11, 11, 9)]
        eng.step()
        reqs.append(add(17, 12, 8))
        idle = reqs[0].slot
        while eng.slots[idle] is not None:
            eng.step()      # ... until the first request's slot idles, dirty
        before = [np.asarray(eng.pool.state[0][:, idle]),
                  np.asarray(eng.pool.state[1][:, idle])]
        assert np.abs(before[0]).max() > 0
        eng.step()
        np.testing.assert_array_equal(
            before[0], np.asarray(eng.pool.state[0][:, idle]))
        np.testing.assert_array_equal(
            before[1], np.asarray(eng.pool.state[1][:, idle]))
        reqs.append(add(9, 13, 6))
        eng.step()
        assert reqs[3].slot == idle
        eng.run_to_completion()
        for req in reqs:
            assert _worst_gap(params, req, logits) < TOL_F32
        assert eng.stats_snapshot()["state"]["resets"] - resets == 4

    def test_preempted_request_is_recomputed(self, eng):
        """A pool too small for its load preempts; the state goes with the
        slot and the request's tokens are those of an unpreempted run."""
        prompts = [_tokens(10, 20), _tokens(9, 21)]

        def run(eng):
            rids = [eng.add_request(p, 12, GREEDY) for p in prompts]
            out = eng.run_to_completion()
            return [out[r].tolist() for r in rids]

        preemptions = eng.pool.stats["preemptions"]
        whole = run(eng)
        assert eng.pool.stats["preemptions"] == preemptions
        # its own: a pool of 8 blocks is what preempts
        eng = _engine(*_model(), max_batch=2, num_blocks=8)
        tight = run(eng)
        assert eng.pool.stats["preemptions"] >= 1
        state = eng.stats_snapshot()["state"]
        assert state["dropped"] == eng.pool.stats["preemptions"]
        assert state["resets"] == 2 + state["dropped"]
        assert tight == whole

    def test_kv_pools_hold_the_attention_layers_planes(self, eng):
        k, v = eng.pool.pages
        assert k.shape == v.shape == (2, 24, 4, 1, 16)
        ssm, conv = eng.pool.state
        assert ssm.shape == (6, 3, 8, 128) and ssm.dtype == jnp.float32
        assert conv.shape == (6, 3, 3 * 128)
        was = np.asarray(k)
        req = eng.requests[eng.add_request(_tokens(5, 30), 3, GREEDY)]
        eng.step()
        blocks = eng.pool.page_table[req.slot][:2].copy()
        eng.run_to_completion()
        k = np.asarray(eng.pool.pages[0])
        # 5 prompt rows and 2 decoded rows written in each attention
        # layer's plane (whatever an earlier request left there)
        for plane in range(2):
            rows = (k != was)[plane, blocks].reshape(8, -1)
            assert rows.any(axis=1).tolist() == [True] * 7 + [False]
        stats = eng.stats_snapshot()
        assert stats["pool"]["bytes_per_block"] == \
            4 * MODEL.kv_bytes_per_token(TINY, "float32")
        assert stats["state"]["bytes_per_slot"] == \
            6 * (8 * 128 * 4 + 3 * 128 * 4)
        assert stats["pool"]["pool_bytes_total"] == \
            24 * stats["pool"]["bytes_per_block"] \
            + 3 * stats["state"]["bytes_per_slot"]

    def test_state_bytes_are_the_stated_type(self, lend):
        """What cells/serve_closed_state.py holds the engine to: h float32,
        the tail in the compute type."""
        eng = lend(_shared_engine(jnp.bfloat16))
        stated = {**TINY, "serve": {"params_dtype": "bfloat16"}}
        assert eng.stats_snapshot()["state"]["bytes_per_slot"] == \
            MODEL.state_bytes_per_slot(stated, "float32") == \
            6 * (8 * 128 * 4 + 3 * 128 * 2)
        assert MODEL.state_bytes_per_slot(stated, "bfloat16") < \
            MODEL.state_bytes_per_slot(stated, "float32")
        assert MODEL.state_bytes_per_slot(PUBLISHED, "float32") == 9_318_400
        assert MODEL.kv_bytes_per_token(PUBLISHED, "bfloat16") == 1024

    def test_a_dense_model_has_no_state(self):
        from megatronapp_tpu.config.transformer_config import (
            TransformerConfig,
        )
        from megatronapp_tpu.models.gpt import init_gpt_params
        cfg = TransformerConfig(num_layers=2, hidden_size=32,
                                num_attention_heads=2, vocab_size=64,
                                max_position_embeddings=32,
                                compute_dtype=jnp.float32)
        eng = DynamicInferenceEngine(
            init_gpt_params(jax.random.PRNGKey(0), cfg)[0], cfg, max_batch=2,
            max_seq_len=32, paged=True)
        assert eng.pool.state is None and eng.pool.pages[0].shape[0] == 2
        assert eng.stats_snapshot()["state"] is False
        assert "state" not in eng.startup_line()


class TestRefusals:
    """What would need a snapshot of the recurrent state refuses, once, in
    words (ROADMAP M4 holds what remains)."""

    def test_prefix_reuse_is_off_and_said(self, eng):
        assert eng.pool.enable_prefix_caching is False
        assert "prefix reuse off" in eng.startup_line()
        prompt = _tokens(16, 40)
        for _ in range(2):
            eng.add_request(prompt, 2, GREEDY)
        eng.run_to_completion()
        assert eng.pool.stats["prefix_hit_tokens"] == 0

    @pytest.mark.parametrize("kw,word", [
        ({"spec_method": "ngram"}, "spec_method"),
        ({"spill_host_mb": 1.0}, "spill_host_mb"),
        ({"adapter_cache": object()}, "adapter_cache"),
        ({"pool": object()}, "injected pool"),
        ({"ctx": object()}, "serving mesh"),
    ])
    def test_construction_refuses(self, kw, word):
        cfg, params = _model()
        with pytest.raises(ValueError, match="state snapshots") as e:
            _engine(cfg, params, **kw)
        assert word in str(e.value)

    @pytest.mark.parametrize("call", ["export_request", "import_request",
                                      "adopt_request"])
    def test_moving_a_request_refuses(self, call, eng):
        rid = eng.add_request(_tokens(6, 41), 4, GREEDY)
        eng.step()
        args = {"export_request": (rid,), "import_request": ({},),
                "adopt_request": (eng.requests[rid], 0, 6)}[call]
        with pytest.raises(ValueError, match="state snapshots"):
            getattr(eng, call)(*args)
        assert eng.park_request(rid) is False       # no spill tier to park in
        eng.run_to_completion()

    def test_staging_slots_refuse(self):
        from megatronapp_tpu.inference.paged_cache import PagedKVCache
        cfg, _ = _model()
        with pytest.raises(ValueError, match="no snapshot"):
            PagedKVCache(cfg, 2, 32, extra_slots=1)

    def test_config_refuses_what_the_stack_cannot_hold(self):
        from megatronapp_tpu.config.transformer_config import (
            TransformerConfig,
        )
        with pytest.raises(ValueError, match="attn_layer_offset"):
            TransformerConfig(attn_layer_period=4, attn_layer_offset=4)
        # (a state-space stack may run MoE feed-forwards since ISSUE 52:
        # tests/test_granite.py; what a hybrid stack's experts still
        # refuse is a frequency other than every layer)
        assert TransformerConfig(attn_layer_period=4,
                                 num_moe_experts=4).num_ssm_layers == 1
        with pytest.raises(ValueError, match="moe_layer_freq"):
            TransformerConfig(attn_layer_period=4, num_moe_experts=4,
                              moe_layer_freq=2)


class TestKernel:
    def test_ssm_update_against_the_plain_update(self):
        """ssm_update (interpreted) advances the active slots' plane of one
        layer as mamba_mixer_step's plain update does, returns its y, and
        touches neither an inactive slot nor another layer's plane."""
        from megatronapp_tpu.ops.pallas.ssm_update import (
            ssm_update, ssm_update_reference,
        )
        layers, slots, n, e = 3, 5, 8, 256
        ks = jax.random.split(jax.random.PRNGKey(0), 7)
        pool = jax.random.normal(ks[0], (layers, slots, n, e))
        dt = jax.nn.softplus(jax.random.normal(ks[1], (slots, e)))
        u = jax.random.normal(ks[2], (slots, e))
        b = jax.random.normal(ks[3], (slots, n))
        c = jax.random.normal(ks[4], (slots, n))
        a_t = -jnp.exp(jax.random.normal(ks[5], (n, e)))
        d = jax.random.normal(ks[6], (e,))
        active = jnp.asarray([True, False, True, True, False])
        y, new = jax.jit(ssm_update)(pool, jnp.int32(1), dt, u, b, c, a_t, d,
                                     active)
        y_ref, h_ref = ssm_update_reference(pool[1], dt, u, b, c, a_t, d)
        on = np.asarray(active)
        np.testing.assert_allclose(np.asarray(y)[on], np.asarray(y_ref)[on],
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(new[1])[on],
                                   np.asarray(h_ref)[on], rtol=1e-5,
                                   atol=1e-5)
        np.testing.assert_array_equal(np.asarray(new[1])[~on],
                                      np.asarray(pool[1])[~on])
        assert not np.asarray(y)[~on].any()
        for other in (0, 2):
            np.testing.assert_array_equal(np.asarray(new[other]),
                                          np.asarray(pool[other]))

    def test_decode_step_runs_one_kernel_a_layer_loop(self, eng):
        """The traced decode step holds ssm_update once a scanned run of
        state-space layers (not once a layer: the stack is not unrolled)."""
        disp = eng.stats_snapshot(include_dispatch=True)["decode_dispatch"]
        # a layer: ssm_update, or paged_append x 2 + paged_decode
        assert disp["kernels"] == 6 * 1 + 2 * 3, disp


MS = 1_000_000
CHAT_READERS = [
    "decode_round_ms.chat", "decode_wait_ms_round.chat",
    "host_gap_ms_round.chat", "ssm_update_ms_round.chat",
    "ssm_update_roofline_pct.chat", "paged_decode_ms_round.chat",
    "sampler_sort_ms_round.chat"]


def _ev(name, start_ms, end_ms, **info):
    return [name, round(start_ms * MS), round((end_ms - start_ms) * MS), info]


def _traced_run(device, host, stats=None):
    from perfbench import trace_reduce
    trace = {"planes": [
        {"name": "/device:TPU:0",
         "lines": [{"name": "XLA Ops", "events": device}]},
        {"name": "/host:CPU", "lines": [{"name": "stepper", "events": host}]}]}
    return {"trace": trace, "config": PUBLISHED,
            "peaks": {"hbm_bytes_per_s": 819e9},
            "device_summary": trace_reduce.device_summary(trace),
            "xplane_stats": stats, "engine_stats": {}}


class TestReaders:
    """The chat cell's per-layer readers on a hand-built run: a 10 ms
    window with two decode rounds of 128 and 64 rows."""
    KERNEL = {"op": "custom-call", "target": "tpu_custom_call"}

    def test_on_a_run_that_names_everything(self):
        device = [
            _ev("ssm_update.8", 1, 2, **self.KERNEL),
            _ev("paged_decode.9", 2, 2.5, **self.KERNEL),
            _ev("sort.6", 3, 4.5, op="sort", shape="(f32[128,65536]{0,1}"),
            _ev("sort.61", 4.5, 4.6, op="sort", shape="(pred[128]{0}"),
            _ev("ssm_update.8", 6, 6.5, **self.KERNEL),
            _ev("sort.9", 7, 7.5, op="sort", shape="(f32[1,65536]{1,0}")]
        host = [_ev("bench.window", 0, 10),
                _ev("mta.engine.decode_round", 1, 5),
                _ev("mta.engine.decode.wait", 2, 4.5),
                _ev("mta.engine.decode_round", 6, 8),
                _ev("mta.engine.prefill", 8, 10)]
        stats = {"spans": [_ev("mta.engine.decode_round", 1, 5, batch=128),
                           _ev("mta.engine.decode_round", 6, 8, batch=64)]}
        run = _traced_run(device, host, stats)
        read = {n: manifest.load_reader(n)(run) for n in CHAT_READERS}
        assert read["decode_round_ms.chat"] == pytest.approx(3.0)
        assert read["decode_wait_ms_round.chat"] == pytest.approx(1.25)
        assert read["ssm_update_ms_round.chat"] == pytest.approx(0.75)
        assert read["paged_decode_ms_round.chat"] == pytest.approx(0.25)
        # the two sorts over float32 keys, not the one over 128 flags
        assert read["sampler_sort_ms_round.chat"] == pytest.approx(1.0)
        # idle: 0-1, 2.5-3, 4.6-6, 6.5-7, 7.5-10, less prefill's 8-10
        assert read["host_gap_ms_round.chat"] == pytest.approx(
            (1 + .5 + 1.4 + .5 + .5) / 2)
        from perfbench import ssm_bytes
        least = ssm_bytes.ssm_update_bytes(PUBLISHED, 192)
        assert least == 192 * 26 * 2 * 16 * 5120 * 4
        assert read["ssm_update_roofline_pct.chat"] == pytest.approx(
            100 * least / 819e9 / 1.5e-3)

    @pytest.mark.parametrize("name", CHAT_READERS)
    def test_a_program_without_the_names_reads_zero(self, name):
        run = _traced_run([_ev("fusion.1", 0, 9, op="fusion")],
                          [_ev("bench.window", 0, 10)])
        assert manifest.load_reader(name)(run) == 0.0


# Run perfbench/run.py with the state kernel's result rounded to bf16 on its
# way into the float32 pool: what the pool's size cannot tell.
ROUNDED = """
import jax
from megatronapp_tpu.ops.pallas import ssm_update as mod
real = mod.ssm_update
def rounded(pool, *a):
    y, pool = real(pool, *a)
    return y, jax.lax.reduce_precision(pool, 8, 7)
mod.ssm_update = rounded
"""
RUN = "import runpy; runpy.run_path('perfbench/run.py', run_name='__main__')"


class TestRunner:
    """cells/serve_closed_state.py through the benchmark's own command at
    tiny widths on the CPU: the cell as it stands is correct; a step that
    keeps h at bf16's precision in the float32 pool is not, by the state's
    check and by nothing else."""

    @pytest.mark.parametrize("patch,correct", [("", True), (ROUNDED, False)],
                             ids=["as-it-stands", "h-rounded-to-bf16"])
    def test_the_state_is_held_to_its_stated_type(self, patch, correct):
        import subprocess
        import sys
        out = subprocess.run(
            [sys.executable, "-c", patch + RUN, "--workload",
             "serve.jamba2-3b.chat-closed", "--seed", "3000000029",
             "--seconds", "2", "--trace", "0"],
            capture_output=True, text=True, cwd=ROOT, timeout=900,
            env=dict(os.environ, JAX_PLATFORMS="cpu", PERFBENCH_REHEARSAL="1",
                     PYTHONPATH=ROOT))
        assert out.returncode == 0, out.stderr[-3000:]
        line = json.loads(out.stdout.strip().splitlines()[-1])
        assert line["correct"] is correct, out.stderr[-3000:]
        assert line["failed"] == 0
        assert line["notes"]["state_bytes_per_slot"] == \
            MODEL.state_bytes_per_slot(
                {**TINY, "num_hidden_layers": 4,
                 "serve": {"params_dtype": "bfloat16"}}, "float32")
        problems = [ln for ln in out.stderr.splitlines()
                    if "not correct" in ln]
        assert len(problems) == (0 if correct else 1)
        if not correct:
            assert "recurrent state" in problems[0]


class TestStateControl:
    """The second reading cells/serve_closed_state.py's limit is sized by:
    the reference's own recurrence read as the runner reads a slot. In
    float32 nearly every element of h is finer than bf16 holds; rounded to
    bf16 at every position (the nearest type below the stated one) none is,
    which the runner's limit calls not correct."""

    def test_the_bf16_recurrence_is_told_from_the_float32_one(self):
        cell = manifest.load_module("cells", "serve_closed_state")
        _, params = _model()
        tokens = jnp.asarray(np.stack([_tokens(40, s) for s in (1, 2)]))
        fine = {t: cell._fine_share(
            MODEL.reference_state(params, TINY, tokens, t), "bfloat16")
            for t in ("float32", "bfloat16")}
        assert fine["float32"] > 0.99 > cell.FINE_SHARE > fine["bfloat16"]
        assert fine["bfloat16"] == 0.0
