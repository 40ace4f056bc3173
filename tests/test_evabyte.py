"""EvaByte's architecture against its plain float32 reference
(perfbench/models/evabyte.py: the published equations in jax.numpy, every
chunk's summary from the whole sequence, a dense masked softmax a window),
at tiny widths on the CPU with seeded random weights: window 32, chunk 4 and
block 4 at H 64, so that a 150-byte sequence closes four windows. float32 on
both sides. Each test fails if the mechanism it names is left out. Published
sizes appear only in shape tests. What such a model refuses and counts is in
test_evabyte_refusals.py (which takes its tiny model from here), the server
tool in test_evabyte_server.py: three files, so that `--dist loadfile` does
not charge them all to one worker."""
import functools
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from megatronapp_tpu.inference.dynamic_engine import DynamicInferenceEngine
from megatronapp_tpu.inference.engine import SamplingParams
from megatronapp_tpu.models.presets import PRESETS
from megatronapp_tpu.transformer import eva
from perfbench import manifest

from jitted import gpt_forward  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MODEL = manifest.load_module("models", "evabyte")
with open(os.path.join(ROOT, "perfbench", "configs",
                       "evabyte-6.5b.json")) as f:
    PUBLISHED = json.load(f)
W, C = 32, 4
TINY = {**PUBLISHED, **MODEL.REHEARSAL, "window_size": W, "chunk_size": C,
        "vocab_size": 40, "num_pred_heads": 2, "max_position_embeddings": 256}
# Weights at std 0.1, not 0.01275: at 64 columns the attention's output is
# then large enough beside the residual stream for a wrong row, a missing mu
# or a summary from the wrong chunk to show in the logits (std ~0.7).
STD = 0.1
# float32 against float32: what is left is the order of summation (the
# paged kernels' blockwise softmax against the reference's dense one). The
# two agree to ~2e-6 (measured); a summary rounded to bf16 moves a logit by
# ~2e-3, a dropped mu by ~0.03, a window that is not closed by ~0.5.
TOL = 2e-4
GREEDY = SamplingParams(greedy=True)


@functools.cache
def _model(seed=5):
    """(cfg, params), built once for every case here, in
    tests/test_evabyte_refusals.py and in tests/test_paged_projection.py:
    none writes into the tree it is handed."""
    cfg = MODEL.model_config(TINY, "float32", compute_dtype=jnp.float32,
                             init_method_std=STD)
    params = MODEL.init_params(cfg, seed=seed)
    # norm offsets away from 0, so that 1 + g is not 1
    key = jax.random.PRNGKey(seed + 1)
    for name in ("ln1_scale", "ln2_scale"):
        key, k = jax.random.split(key)
        params["block"][name] = 0.2 * jax.random.normal(
            k, params["block"][name].shape)
    params["final_ln_scale"] = 0.2 * jax.random.normal(
        key, params["final_ln_scale"].shape)
    return cfg, params


def _reference(params, tokens, config=TINY):
    tokens = jnp.asarray(tokens)
    pos = jnp.broadcast_to(jnp.arange(tokens.shape[1]), tokens.shape)
    return np.asarray(MODEL.reference_logits(
        params, config, tokens, jnp.zeros_like(tokens), pos))


def _tokens(n, seed=0):
    return np.random.default_rng(seed).integers(
        0, TINY["vocab_size"], (n,)).astype(np.int32)


def _engine(cfg, params, **kw):
    kw = {"max_batch": 4, "max_seq_len": 192, "paged": True,
          "num_blocks": 96, "block_size": 4, "prefill_chunk": 8, **kw}
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
        rid = next(r.request_id for r in eng.slots if r is not None
                   and np.array_equal(eng.pool.page_table[r.slot],
                                      np.asarray(a[4][0])))
        logits.setdefault(rid, []).append(
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


def _worst_gap(params, req, logits):
    """Largest |engine - reference| over every position of a finished
    request: the reference runs the request's own tokens in one pass."""
    seq = req.tokens[:-1]
    got = np.concatenate(logits[req.request_id])
    assert got.shape[0] == len(seq), (got.shape, len(seq))
    return np.abs(got - _reference(params, seq[None])[0]).max()


def _held(pool, length):
    """Blocks a slot holds once position `length` has its capacity: the
    blocks of R(length) rows, and those of the open window's summaries,
    written as its chunks fill and not yet seen."""
    bs = pool.block_size
    rows = int(eva.rows_walked(pool.cfg, length))
    return -(-rows // bs) + (length % W // C) // bs + 1


# ---- (a) the reference itself ----------------------------------------------

class TestReference:
    def test_a_window_longer_than_the_sequence_is_causal_attention(self):
        cfg, params = _model()
        toks = np.stack([_tokens(24, 1), _tokens(24, 2)])
        wide = {**TINY, "window_size": 64}
        got = _reference(params, toks, wide)
        # plain causal softmax attention, written here: no window, no chunk
        f32 = jnp.float32
        block = jax.tree.map(lambda a: a.astype(f32), params["block"])
        heads, d = TINY["num_attention_heads"], 16
        pos = jnp.arange(24, dtype=f32)
        inv = 1.0 / (1e5 ** (jnp.arange(0, d, 2, dtype=f32) / d))
        cos, sin = jnp.cos(pos[:, None] * inv), jnp.sin(pos[:, None] * inv)

        def rope(x):
            a, b = x[..., :d // 2], x[..., d // 2:]
            c, s = cos[None, :, None], sin[None, :, None]
            return jnp.concatenate([a * c - b * s, b * c + a * s], -1)

        def norm(x, g):
            return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                                     + 1e-5) * (1 + g)

        with jax.default_matmul_precision("highest"):
            x = params["embedding"]["word"][jnp.asarray(toks)]
            for i in range(cfg.num_layers):
                lp = jax.tree.map(lambda a: a[i], block)
                y = norm(x, lp["ln1_scale"])
                q = rope((y @ lp["attention"]["q_kernel"]).reshape(
                    2, 24, heads, d))
                kv = (y @ lp["attention"]["kv_kernel"]).reshape(
                    2, 24, 2 * heads, d)
                k, v = rope(kv[:, :, :heads]), kv[:, :, heads:]
                sc = jnp.einsum("bqhd,bkhd->bhqk", q, k) * d ** -0.5
                sc = jnp.where(jnp.tril(jnp.ones((24, 24), bool)), sc,
                               -jnp.inf)
                o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(sc, -1), v)
                x = x + o.reshape(2, 24, -1) @ lp["attention"]["out_kernel"]
                z = norm(x, lp["ln2_scale"])
                gate, up = jnp.split(z @ lp["mlp"]["fc1_kernel"], 2, -1)
                x = x + (jax.nn.silu(gate) * up) @ lp["mlp"]["fc2_kernel"]
            want = norm(x, params["final_ln_scale"]) @ params["output"]
        assert got.shape == (2, 24, 80)
        assert np.abs(got - np.asarray(want)).max() < 1e-5

    def test_equals_a_loop_over_positions(self):
        """`_eva` against the issue's equations, one query at a time."""
        rng = np.random.default_rng(3)
        s, h, d, w, c = 22, 2, 8, 8, 2
        q, k, v = (rng.normal(size=(1, s, h, d)).astype(np.float32)
                   for _ in range(3))
        phi, mu = (rng.normal(size=(h, d)).astype(np.float32) * 0.5
                   for _ in range(2))
        scale = d ** -0.5
        want = np.zeros((s, h, d), np.float32)
        for hd in range(h):
            kk, vv = k[0, :, hd], v[0, :, hd]
            for t in range(s):
                lo = w * (t // w)
                keys, vals = list(kk[lo:t + 1]), list(vv[lo:t + 1])
                for ch in range((w // c) * (t // w)):
                    rows = slice(c * ch, c * ch + c)
                    a = np.exp(scale * kk[rows] @ phi[hd])
                    a /= a.sum()
                    keys.append(a @ kk[rows] + mu[hd])
                    vals.append(a @ vv[rows])
                e = np.exp(scale * np.stack(keys) @ q[0, t, hd])
                want[t, hd] = (e / e.sum()) @ np.stack(vals)
        pad = ((0, 0), (0, -s % w), (0, 0), (0, 0))
        with jax.default_matmul_precision("highest"):
            got = MODEL._eva(*(jnp.pad(jnp.asarray(a), pad)
                               for a in (q, k, v)),
                             jnp.asarray(phi), jnp.asarray(mu), w, c)
        assert np.abs(np.asarray(got)[0, :s] - want).max() < 1e-5

    def test_rows_walked(self):
        for t, rows in ((0, 1), (2047, 2048), (2048, 129), (6000, 2161),
                        (16383, 2944)):
            assert MODEL.rows_walked(PUBLISHED, t) == rows
            assert eva.rows_walked(PRESETS["evabyte-6.5b"](num_layers=1),
                                   t) == rows


# ---- (b) the whole-sequence form -------------------------------------------

class TestForward:
    def test_gpt_forward_matches_reference(self):
        cfg, params = _model()
        at = params["block"]["attention"]
        assert at["eva_phi"].shape == at["eva_mu"].shape == (2, 4, 16)
        assert params["output"].shape == (64, 40 * 2)
        toks = np.stack([_tokens(150, 1), _tokens(150, 2)])
        logits, _ = gpt_forward(params, jnp.asarray(toks), cfg)
        assert np.abs(np.asarray(logits) - _reference(params, toks)).max() \
            < TOL

    def test_mu_and_the_unit_offset_are_live(self):
        cfg, params = _model()
        toks = _tokens(70, 3)[None]
        base = np.asarray(gpt_forward(params, jnp.asarray(toks), cfg)[0])
        block = params["block"]
        no_mu = dict(params, block=dict(block, attention=dict(
            block["attention"],
            eva_mu=jnp.zeros_like(block["attention"]["eva_mu"]))))
        moved = np.asarray(gpt_forward(no_mu, jnp.asarray(toks), cfg)[0])
        # the first window sees no summary; every later position does
        assert np.abs(moved - base)[0, :W].max() == 0
        assert np.abs(moved - base)[0, W:].max() > 10 * TOL
        plain = MODEL.model_config(TINY, "float32",
                                   compute_dtype=jnp.float32,
                                   norm_unit_offset=False)
        moved = np.asarray(gpt_forward(params, jnp.asarray(toks), plain)[0])
        assert np.abs(moved - base).max() > 0.1

    def test_published_shapes(self):
        cfg = PRESETS["evabyte-6.5b"]()
        assert (cfg.num_layers, cfg.hidden_size, cfg.head_dim) == (32, 4096,
                                                                   128)
        assert cfg.num_query_groups == cfg.num_attention_heads == 32
        assert (cfg.eva_window_size, cfg.eva_chunk_size) == (2048, 16)
        shapes = jax.eval_shape(
            lambda k: __import__("megatronapp_tpu.models.gpt", fromlist=["x"])
            .init_gpt_params(k, PRESETS["evabyte-6.5b"](num_layers=1))[0],
            jax.random.PRNGKey(0))
        layer = sum(int(np.prod(a.shape))
                    for a in jax.tree.leaves(shapes["block"]))
        assert layer == 202_391_552
        assert shapes["embedding"]["word"].shape == (320, 4096)
        assert shapes["output"].shape == (4096, 320 * 8)
        assert MODEL.kv_bytes_per_token(PUBLISHED, "bfloat16") == 131_072
        # the configuration file keeps every published number but the depth
        with open("/opt/skills/guides/model-configs/architectures.jsonl") \
                as f:
            rows = [json.loads(line) for line in f]
        source = next(r for r in rows if r["name"] == "EvaByte")
        differs = [k for k, v in source["config"].items()
                   if PUBLISHED[k] != v]
        assert differs == ["num_hidden_layers"] == PUBLISHED["reduced"]
        assert PUBLISHED["source"] == source["source_url"]


# ---- (c)-(f) the paged engine ----------------------------------------------

class TestEngine:
    @pytest.fixture(scope="class")
    def served(self):
        """One engine (and so one compile of its two steps) for the cases
        that differ only in their requests."""
        cfg, params = _model()
        eng = _engine(cfg, params)
        return params, eng, _recorded(eng)

    @pytest.mark.parametrize("prompt", [
        W - 1, W, W + 1,                # a window's edge -1, 0, +1
        2 * W + C - 1, 2 * W + C + 1,   # a chunk's edge -1, +1, two closed
        5], ids=lambda n: f"prompt{n}")
    def test_chunked_prefill_then_decode(self, served, prompt):
        """Prefill in calls of 8, then decode across closed windows: the
        logits at EVERY position against the reference's full pass."""
        params, eng, logits = served
        closed = eng.pool.eva_stats["windows_closed"]
        new = 150 - prompt
        req = eng.requests[eng.add_request(_tokens(prompt, prompt), new,
                                           GREEDY)]
        eng.run_to_completion()
        assert len(req.tokens) == 150
        assert _worst_gap(params, req, logits) < TOL
        assert eng.pool.eva_stats["windows_closed"] - closed == 148 // W
        eng.pool.audit()
        assert eng.pool.blocks_in_use() == 0

    @pytest.mark.parametrize("width", [32, None],
                             ids=["a-window", "chosen-for-a-v5e"])
    def test_a_wider_call_stops_at_a_window_edge(self, monkeypatch, width):
        """ISSUE 35: a prompt of two windows and 7 bytes in calls of a whole
        window (32: more would straddle an edge) and of the width the
        engine chooses for these shapes on a v5e under a max_seq_len of 16
        (with more room it is the window): neither divides it, each call stops at its window's
        edge, pools the chunks it fills and is cut into query tiles of 8 by
        the ragged kernel; the logits at every position are the
        reference's."""
        from megatronapp_tpu.inference.dynamic_engine import (
            choose_prefill_width,
        )
        from megatronapp_tpu.ops.pallas import kernel_gen
        cfg, params = _model()
        if width is None:
            kind = "TPU v5 lite"
            assert choose_prefill_width(cfg, params, 192, 4,
                                        device_kind=kind) == W
            width = choose_prefill_width(cfg, params, 16, 4,
                                         device_kind=kind)
            assert width == 16
        monkeypatch.setattr(kernel_gen, "_query_vmem_budget",
                            lambda *a: 100_000)
        eng = _engine(cfg, params, prefill_chunk=width)
        logits = _recorded(eng)
        n = 2 * W + 7
        req = eng.requests[eng.add_request(_tokens(n, n), 100 - n, GREEDY)]
        eng.run_to_completion()
        assert _worst_gap(params, req, logits) < TOL
        assert eng.pool.eva_stats["windows_closed"] == 98 // W
        pre = eng.stats_snapshot()["prefill"]
        assert pre["calls"] == 2 * (W // width) + 1 and pre["tokens"] == n
        eng.pool.audit()

    def test_four_slots_in_four_windows_share_a_round(self, served):
        params, eng, logits = served
        reqs = [eng.requests[eng.add_request(_tokens(n, n), 30, GREEDY)]
                for n in (3, W + 5, 2 * W + 9, 3 * W + 2)]
        eng.step()
        assert sorted(int(n) // W for n in eng.lengths) == [0, 1, 2, 3]
        assert all(r.slot >= 0 for r in reqs)
        eng.run_to_completion()
        for req in reqs:
            assert _worst_gap(params, req, logits) < TOL

    def test_a_slot_holds_the_blocks_of_its_rows(self):
        """At every step a slot's blocks are those of R(T) rows and of the
        open window's unseen summaries; a closed window's blocks can be
        taken at once; the pool ends empty."""
        cfg, params = _model()
        eng = _engine(cfg, params, max_batch=2, num_blocks=40)
        pool = eng.pool
        assert pool.max_blocks_per_seq == W // 4 + 2 * (192 // W)
        reqs = [eng.requests[eng.add_request(_tokens(n, n), 140 - n, GREEDY)]
                for n in (W + 3, 7)]
        freed_seen = 0
        while eng.has_work:
            before = pool.eva_stats["windows_closed"]
            eng.step()
            pool.audit()
            for req in reqs:
                if req.slot >= 0 and not req.finished:
                    # capacity stands for the last position written, the
                    # row of the round in flight (ISSUE 47) among them
                    assert len(pool.slot_blocks(req.slot)) == _held(
                        pool, int(eng.lengths[req.slot]) - 1
                        + eng._owed(req, eng._round)), req.slot
            # a closed window's blocks are on the free list at once: what
            # the slots do not hold can be taken
            assert pool.free_blocks() == pool.num_blocks - sum(
                len(pool.slot_blocks(s)) for s in range(2))
            freed_seen += pool.eva_stats["windows_closed"] - before
        stats = eng.stats_snapshot()["eva"]
        assert freed_seen == stats["windows_closed"] == 138 // W + 138 // W
        assert stats["blocks_freed"] == stats["windows_closed"] * (W // 4)
        assert stats["max_blocks_slot"] <= pool.max_blocks_per_seq
        assert stats["max_blocks_slot"] == max(
            _held(pool, t) for t in range(139))
        assert pool.blocks_in_use() == 0 and pool.free_blocks() == 40
        # 139 cached rows of full attention would hold 35 blocks a slot
        assert stats["max_blocks_slot"] < 139 // 4

    def test_preempted_request_is_recomputed(self):
        """A pool too small for its load preempts; the summaries go with
        the slot and the request's logits are an unpreempted run's."""
        cfg, params = _model()
        prompts = [_tokens(W + 10, 20), _tokens(W - 3, 21)]

        def run(num_blocks):
            eng = _engine(cfg, params, max_batch=2, num_blocks=num_blocks)
            logits = _recorded(eng)
            reqs = [eng.requests[eng.add_request(p, 50, GREEDY)]
                    for p in prompts]
            eng.run_to_completion()
            eng.pool.audit()
            return reqs, logits, eng

        reqs, logits, eng = run(40)
        assert eng.pool.stats["preemptions"] == 0
        whole = [r.tokens.tolist() for r in reqs]
        reqs, logits, eng = run(19)
        assert eng.pool.stats["preemptions"] >= 1
        assert [r.tokens.tolist() for r in reqs] == whole
        for req in reqs:
            # a preempted request's positions were computed twice: the
            # last computation of each is the one that continued
            seq = req.tokens[:-1]
            got = np.concatenate(logits[req.request_id])[-len(seq):]
            assert got.shape[0] == len(seq)
        assert eng.pool.blocks_in_use() == 0
