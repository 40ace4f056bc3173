"""NVIDIA-Nemotron-3-Nano-30B-A3B's architecture against its plain float32
reference (perfbench/models/nemotron_h.py: the published equations in
jax.numpy, the Mamba-2 recurrence a position at a time, one held expert at a
time), at tiny widths on the CPU with seeded random weights: the pattern
MEMEM*E (a scanned run of two (M, E) units, then M, *, E one after the
other), H 96, Mamba-2 mixers of 4 heads x 16 columns (64, which is not 2 x
96) in 2 groups with a [16, 16] state a head in chunks of 16 positions, 6
query heads over 2 key/value heads of 16, 4 held of 8 experts top-3 of width
40 (relu^2, no gate) under a sigmoid router with a seeded selection bias,
beside a shared expert of 72. Each test fails if the mechanism it names is
left out."""
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
from megatronapp_tpu.models.presets import (
    NEMOTRON_3_NANO_PATTERN, PRESETS,
)
from megatronapp_tpu.ops.pallas.ssm_update import (
    ssm_update, ssm_update_reference,
)
from megatronapp_tpu.config.transformer_config import PATTERN_STACKS
from megatronapp_tpu.transformer import block, ssm
from perfbench import manifest

from jitted import gpt_forward  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MODEL = manifest.load_module("models", "nemotron_h")
with open(os.path.join(ROOT, "perfbench", "configs",
                       "nemotron-3-nano-30b-a3b.json")) as f:
    PUBLISHED = json.load(f)
TINY = {**PUBLISHED, **MODEL.REHEARSAL}
# Weights at std 0.1, not 0.02: at 96 columns every sublayer's output is
# then large enough beside the residual stream to show in the logits, which
# have a standard deviation of ~1.
STD = 0.1
# float32 on both sides: what is left is the order of summation (the
# program's chunked products and its kernel against the reference's
# sequential recurrence) and the router's 1e-6 against the reference's
# 1e-20 (3e-7 of a weight). 7e-6 on those logits (measured); the weakest
# wrong model below moves them by 0.5.
TOL_F32 = 1e-4
# bf16 activations, convolution tail and KV rows (the state stays float32)
# against the float32 reference on the same float32 weights: 0.05-0.08 on
# logits of standard deviation ~1 (measured); a wrong model gives 0.5 and
# more.
TOL_BF16 = 0.2
# What each wrong model must move the float32 logits by, at least: four
# hundred times what the right one differs by.
WRONG = 0.04
GREEDY = SamplingParams(greedy=True)


def _seeded_bias(params, seed=11):
    """The routers' selection bias drawn from a seed (the cell's is levelled
    over a calibration pass: the last test below), large enough to change
    picks."""
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
    return cfg, _seeded_bias(MODEL.init_params(cfg, seed=5))


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


@functools.cache
def _shared_engine(dtype=jnp.float32):
    """The engine of `_model(dtype)`, compiled once, for the cases that
    leave it as they found it. They take it through `lend` (conftest.py)."""
    return _engine(*_model(dtype))


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


def _served(eng, monkeypatch, prompts, new_tokens=6):
    """[(its tokens but the last, its logits position by position)] of
    `prompts` served together through `eng`'s paged pools."""
    logits = _recorded(eng, monkeypatch)
    reqs = [eng.add_request(p, new_tokens, GREEDY) for p in prompts]
    while eng.has_work:
        eng.step()
    return [(eng.requests[r].tokens[:-1],
             np.concatenate(logits[r])) for r in reqs]


# ---- the pattern -----------------------------------------------------------

class TestPattern:
    @pytest.mark.parametrize("pattern,runs", [
        ("MEMEM*EMEMEM*", [("ME", 2), ("M", 1), ("*", 1), ("EM", 3),
                           ("*", 1)]),
        (NEMOTRON_3_NANO_PATTERN, [("MEMEM*E", 5), ("ME", 3), ("M", 1),
                                   ("*", 1), ("EM", 4), ("E", 1)]),
        ("MEM*EME", [(c, 1) for c in "MEM*EME"]),
        ("MMMM", [("M", 4)]),
        ("MEMEMEME", [("ME", 4)]),
    ])
    def test_runs_spell_the_pattern(self, pattern, runs):
        assert block.tandem_runs(pattern) == runs
        assert "".join(u * r for u, r in runs) == pattern
        # a plan's entries repeat as its letters do
        assert block.tandem_runs(tuple((c,) for c in pattern)) == [
            (tuple((c,) for c in u), r) for u, r in runs]

    @pytest.mark.parametrize("pattern", [
        "MEMEM*E", "MEMEM*EMEMEM*", NEMOTRON_3_NANO_PATTERN, "*M-E-M*"])
    @pytest.mark.parametrize("scan_runs", [True, False])
    def test_the_loop_visits_every_layer_in_order(self, pattern, scan_runs):
        """(layer id, kind, index among its kind) as run() is handed them,
        through scanned runs (traced indices) and written out alike."""
        cfg = PRESETS["nemotron-3-nano-30b-a3b"](
            num_layers=len(pattern), layer_pattern=pattern)
        kinds = [PATTERN_STACKS[c] for c in "M*E-"]

        def run(carry, layer, rows, lid):
            seen, at = carry
            kind, = layer
            row = jnp.stack([jnp.int32(lid), jnp.int32(kinds.index(kind)),
                             jnp.int32(rows[kind])])
            return jax.lax.dynamic_update_slice(
                seen, row[None], (at, 0)), at + 1

        seen, at = block.layer_loop(
            cfg, (jnp.zeros((len(pattern), 3), jnp.int32), jnp.int32(0)),
            run, scan_runs=scan_runs)
        want = [(i, "M*E-".index(c), pattern[:i].count(c))
                for i, c in enumerate(pattern)]
        assert int(at) == len(pattern)
        assert np.asarray(seen).tolist() == [list(w) for w in want]

    @pytest.mark.parametrize("over,words", [
        (dict(layer_pattern="MEMEM*EM"), "one\nsublayer a layer"),
        (dict(layer_pattern="MEMXM*E"), "one\nsublayer a layer"),
        (dict(attn_layer_period=4), "no attn_layer_period"),
        (dict(num_moe_experts=None, moe_router_score="softmax",
              moe_router_selection_bias=False,
              moe_shared_expert_intermediate_size=None),
         "'E' layers"),
        (dict(ssm_heads=0), "Mamba-2 mixers"),
        (dict(ssm_groups=3), "a whole number of heads a group"),
        (dict(residual_multiplier=0.5), "residual multiplier"),
    ])
    def test_refusals(self, over, words):
        with pytest.raises(ValueError) as e:
            PRESETS["nemotron-3-nano-30b-a3b"](**{
                "num_layers": 7, "layer_pattern": "MEMEM*E", **over})
        assert " ".join(words.split()) in " ".join(str(e.value).split())

    def test_the_model_module_refuses_a_dense_layer_by_name(self):
        with pytest.raises(SystemExit) as e:
            MODEL.model_config({**TINY, "hybrid_override_pattern": "MEM-M*E"},
                               "float32")
        assert "'-'" in str(e.value) and "dense feed-forward" in str(e.value)
        with pytest.raises(SystemExit):
            MODEL.model_config({**TINY, "num_hidden_layers": 9}, "float32")

    def test_counts_by_kind(self):
        """Planes are counted from the pattern: an E layer owns none."""
        cfg = PRESETS["nemotron-3-nano-30b-a3b"](num_layers=13)
        assert cfg.layer_pattern == "MEMEM*EMEMEM*"
        assert (cfg.num_ssm_layers, cfg.kv_planes, cfg.num_moe_layers) == (
            6, 2, 5)
        assert cfg.ssm_inner == 4096 != cfg.ssm_expand * cfg.hidden_size
        assert cfg.ssm_conv_channels == 4096 + 2 * 8 * 128
        whole = PRESETS["nemotron-3-nano-30b-a3b"]()
        assert (whole.num_ssm_layers, whole.kv_planes,
                whole.num_moe_layers) == (23, 6, 23)

    def test_a_dense_layer_runs_through_the_mlp(self):
        """'-' stands in no published position, but the stack takes it
        through transformer/mlp.py and does not skip it."""
        cfg = MODEL.model_config(TINY, "float32", init_method_std=STD)
        import dataclasses
        with_dense = dataclasses.replace(cfg, layer_pattern="MEM-M*E")
        # the program's own initialiser: the model module's calibrates the
        # routers through its reference, which refuses the letter
        params = MODEL._init_params(with_dense, seed=5)
        assert set(params["block"]) == {"mixers_ssm", "mixers_attn", "ffn",
                                        "ffn_dense"}
        assert params["block"]["ffn_dense"]["mlp"]["fc1_kernel"].shape == (
            1, 96, 40)
        tok = jnp.asarray(_tokens(12)[None])
        got = gpt_forward(params, tok, with_dense)[0]
        zeroed = jax.tree.map(lambda a: a, params)
        zeroed["block"] = dict(params["block"], ffn_dense=jax.tree.map(
            jnp.zeros_like, params["block"]["ffn_dense"]))
        assert float(jnp.abs(
            got - gpt_forward(zeroed, tok, with_dense)[0]).max()) > WRONG


# ---- the mixer -------------------------------------------------------------

def _mixer_inputs(s, bsz=2, heads=4, p=8, n=16, groups=2, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    e = heads * p
    return dict(
        x=jax.random.normal(ks[0], (bsz, s, e)),
        dt=jax.nn.softplus(jax.random.normal(ks[1], (bsz, s, heads))),
        a=-jnp.exp(jax.random.normal(ks[2], (heads,))),
        b=jax.random.normal(ks[3], (bsz, s, groups, n)),
        c=jax.random.normal(ks[4], (bsz, s, groups, n)),
        d=jax.random.normal(ks[5], (heads,)),
        h0=jax.random.normal(ks[6], (bsz, n, e)))


def _recurrence(x, dt, a, b, c, d, h0):
    """The state a position at a time, in the program's layout [B, N, E],
    a column of E reading its group's B and C."""
    p = x.shape[-1] // dt.shape[-1]
    width = x.shape[-1] // b.shape[2]
    h, ys = h0, []

    def wide(t):
        return jnp.repeat(t, p, axis=-1)

    def cols(t):            # [B, G, N] -> [B, N, E]
        return jnp.repeat(jnp.swapaxes(t, 1, 2), width, axis=2)

    for t in range(x.shape[1]):
        h = jnp.exp(wide(dt[:, t] * a))[:, None, :] * h \
            + (wide(dt[:, t]) * x[:, t])[:, None, :] * cols(b[:, t])
        ys.append(jnp.sum(h * cols(c[:, t]), axis=1) + wide(d) * x[:, t])
    return jnp.stack(ys, axis=1), h


class TestGroups:
    @pytest.mark.parametrize("s,chunk", [(19, 8), (8, 8), (5, 16), (33, 16)])
    def test_chunked_scan_is_the_recurrence(self, s, chunk):
        """Chunks that end inside, at and past the sequence, from a state
        that came in: y and the state as the recurrence gives them."""
        args = _mixer_inputs(s)
        y, h = ssm.ssd_chunked(chunk=chunk, **args)
        want_y, want_h = _recurrence(**args)
        assert float(jnp.abs(y - want_y).max()) < 2e-4
        assert float(jnp.abs(h - want_h).max()) < 2e-4

    def test_group_zero_for_every_head_is_another_scan(self):
        args = _mixer_inputs(19)
        y, _ = ssm.ssd_chunked(chunk=8, **args)
        one = dict(args, b=args["b"][:, :, 0], c=args["c"][:, :, 0])
        y1, _ = ssm.ssd_chunked(chunk=8, **one)
        assert float(jnp.abs(y - y1).max()) > 0.5

    @pytest.mark.parametrize("n,e,groups", [(16, 64, 2), (16, 256, 4),
                                            (8, 128, 1)])
    def test_update_kernel_against_the_plain_update(self, n, e, groups):
        """The decode step's kernel with a B and C a group (a tile of E
        inside one group), running slots alone advanced, in place."""
        slots, layers = 5, 3
        ks = jax.random.split(jax.random.PRNGKey(3), 7)
        pool = jax.random.normal(ks[0], (layers, slots, n, e))
        dt = jax.nn.softplus(jax.random.normal(ks[1], (slots, e)))
        u = jax.random.normal(ks[2], (slots, e))
        shape = (slots, groups, n) if groups > 1 else (slots, n)
        b, c = (jax.random.normal(k, shape) for k in ks[3:5])
        a_t = -jnp.exp(jax.random.normal(ks[5], (1, e)))
        d = jax.random.normal(ks[6], (e,))
        active = jnp.asarray([True, False, True, True, False])
        y, new = ssm_update(pool, 1, dt, u, b, c, a_t, d, active)
        want_y, want_h = ssm_update_reference(pool[1], dt, u, b, c, a_t, d)
        on = np.asarray(active)
        np.testing.assert_allclose(np.asarray(y)[on], np.asarray(want_y)[on],
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(new[1])[on],
                                   np.asarray(want_h)[on], rtol=1e-5,
                                   atol=1e-5)
        assert np.array_equal(np.asarray(new[1])[~on],
                              np.asarray(pool[1])[~on])
        assert np.array_equal(np.asarray(new[0]), np.asarray(pool[0]))
        if groups > 1:      # group 0's B and C for all columns: another y
            flat, _ = ssm_update_reference(pool[1], dt, u, b[:, 0], c[:, 0],
                                           a_t, d)
            assert float(jnp.abs(flat - want_y).max()) > 0.5


# ---- the model -------------------------------------------------------------

CONTROLS = {"norm_all": dict(norm_all=True), "one_group": dict(one_group=True),
            "relu": dict(power=1), "no_scale": dict(routed_scaling_factor=1.0)}


class TestForward:
    def test_whole_sequences_against_the_reference(self):
        cfg, params = _model()
        tok = np.stack([_tokens(40, 1), _tokens(40, 2)])
        got = np.asarray(gpt_forward(params, jnp.asarray(tok), cfg)[0])
        assert np.abs(got - _reference(params, tok)).max() < TOL_F32

    @pytest.mark.parametrize("wrong", sorted(CONTROLS))
    def test_each_wrong_model_fails(self, wrong):
        """A norm over all columns, B and C of group 0 for every head, relu
        for relu^2, or the routed scale 2.5 left out: each moves the logits
        by hundreds of times the tolerance."""
        cfg, params = _model()
        tok = _tokens(40, 1)[None]
        got = np.asarray(gpt_forward(params, jnp.asarray(tok), cfg)[0])
        gap = np.abs(got - _reference(params, tok, **CONTROLS[wrong])).max()
        assert gap > WRONG > 100 * TOL_F32

    def test_the_selection_bias_changes_picks(self):
        """Without the seeded bias in the reference's top-k the logits
        move: the program reads it."""
        cfg, params = _model()
        tok = _tokens(40, 1)[None]
        got = np.asarray(gpt_forward(params, jnp.asarray(tok), cfg)[0])
        moe = params["block"]["ffn"]["moe"]
        unbiased = dict(params, block=dict(params["block"], ffn=dict(
            params["block"]["ffn"], moe=dict(moe, router_bias=jnp.zeros_like(
                moe["router_bias"])))))
        assert np.abs(got - _reference(unbiased, tok)).max() > WRONG

    def test_bf16_compute_against_the_float32_reference(self):
        cfg, params = _model(jnp.bfloat16)
        tok = _tokens(40, 3)[None]
        got = np.asarray(gpt_forward(params, jnp.asarray(tok), cfg)[0],
                         np.float32)
        assert np.abs(got - _reference(params, tok)).max() < TOL_BF16


class TestPagedEngine:
    def test_prefill_then_decode_against_the_reference(self, lend,
                                                       monkeypatch):
        """Prompts that end inside a call, on a call's edge, past a chunk's
        edge (calls of 8, chunks of 16: 21 crosses both) and in one call,
        served together and decoded through the pools: every position's
        logits against the reference's full forward of the same tokens."""
        _, params = _model()
        prompts = [_tokens(n, n) for n in (21, 16, 5)]
        eng = lend(_shared_engine())
        was = eng.stats_snapshot()["moe"]
        served = _served(eng, monkeypatch, prompts)
        for seq, got in served:
            assert got.shape[0] == len(seq)
            assert np.abs(got - _reference(params, seq[None])[0]).max() \
                < TOL_F32
        stats = eng.stats_snapshot()
        # this run's: the counts (what the engine holds is no count)
        state, moe = stats["state"], {
            k: v if k == "experts_here" else v - was[k]
            for k, v in stats["moe"].items()}
        assert (state["kind"], state["mixer"], state["layers"]) == (
            "ssm", "mamba2", 3)
        assert state["bytes_per_slot"] == MODEL.state_bytes_per_slot(
            {**TINY, "serve": {"params_dtype": "float32"}}, "float32")
        assert [p.shape[0] for p in eng.pool.pages] == [1, 1]
        assert [p.shape[0] for p in eng.pool.state] == [3, 3]
        picks = moe["tokens"] * 3 * 3       # top-3, three E layers
        assert moe["assignments"] == picks > 0
        assert moe["assignments_here"] + moe["assignments_absent"] == picks
        assert moe["assignments_here"] and moe["assignments_absent"]
        assert moe["experts_here"] == 4
        assert moe["expert_pairs_possible"] == 3 * 4 * moe["decode_rounds"]

    def test_bf16_engine_against_the_float32_reference(self, lend,
                                                       monkeypatch):
        _, params = _model(jnp.bfloat16)
        served = _served(lend(_shared_engine(jnp.bfloat16)), monkeypatch,
                         [_tokens(21, 7), _tokens(9, 8)])
        for seq, got in served:
            assert np.abs(got - _reference(params, seq[None])[0]).max() \
                < TOL_BF16

    def test_the_state_read_back_is_the_references(self, lend):
        _, params = _model()
        eng = lend(_shared_engine())
        prompt = _tokens(19, 4)
        rid = eng.add_request(prompt, 4, GREEDY)
        while eng.has_work:
            eng.step()
        req = eng.requests[rid]
        read = req.tokens[:-1]
        want = MODEL.reference_state(params, TINY, jnp.asarray(read[None]))
        got = eng.pool.state[0][:, req.slot]
        assert float(jnp.abs(got - want[:, 0]).max()) < 1e-4


class TestShare:
    def test_the_shares_add_up_to_the_uncut_layer(self):
        """The routed parts of shares (0, 4) and (4, 4) of 8 experts plus
        the shared expert counted once are the uncut reference layer; and
        the program's expert layer with a share is its share's part."""
        whole_cfg = {**TINY, "n_routed_experts": 8, "expert_share":
                     {"first": 0}}
        cfg_w = MODEL.model_config(whole_cfg, "float32",
                                   init_method_std=STD)
        whole = _seeded_bias(MODEL.init_params(cfg_w, seed=5))
        x = jax.random.normal(jax.random.PRNGKey(2), (2, 12, 96))
        routed_w, shared_w = MODEL.reference_layer_terms(whole, whole_cfg,
                                                         x, 1)
        total = jnp.zeros_like(x)
        for first in (0, 4):
            tiny = {**TINY, "expert_share": {"first": first}}
            moe = whole["block"]["ffn"]["moe"]
            part = dict(whole, block=dict(whole["block"], ffn=dict(
                whole["block"]["ffn"], moe=dict(
                    moe, fc1_kernel=moe["fc1_kernel"][:, first:first + 4],
                    fc2_kernel=moe["fc2_kernel"][:, first:first + 4]))))
            routed, shared = MODEL.reference_layer_terms(part, tiny, x, 1)
            np.testing.assert_allclose(shared, shared_w, atol=1e-6)
            total = total + routed
            # the program's layer on this share: the same part
            cfg = MODEL.model_config(tiny, "float32", init_method_std=STD,
                                     compute_dtype=jnp.float32)
            layer_p = block.layer_params(part["block"], ("ffn",), {"ffn": 1})
            (got, _), _ = block.layer_forward(layer_p, x, cfg, layer_id=3)
            np.testing.assert_allclose(got, x + routed + shared, atol=2e-5)
            assert float(jnp.abs(routed).max()) > WRONG
        np.testing.assert_allclose(total, routed_w, atol=2e-5)


# ---- the plan of every stack of several kinds -------------------------------

# (preset, what it is built with): (the layers that attend, (attention,
# window, recurrent, state-space, convolution, expert layers)) as the eight
# properties of TransformerConfig gave them at 67a371b, before they counted
# over stack_plan: the six hybrid presets at their published depths, the
# depths, periods and offsets of test_jamba.py's and test_granite.py's
# test_any_period_and_offset, three leading dense layers, a uniform stack.
PLANNED = [
    ("jamba2-3b", {}, [7, 21], (2, 0, 26, 26, 0, 0)),
    ("lfm2-24b-a2b", {}, list(range(2, 40, 4)), (10, 0, 30, 0, 30, 38)),
    ("laguna-xs.2", {}, list(range(0, 40, 4)), (10, 30, 0, 0, 0, 39)),
    ("mellum2-12b-a2.5b", {}, list(range(3, 28, 4)), (7, 21, 0, 0, 0, 28)),
    ("granite-4.0-h-small", {}, [5, 15, 25, 35], (4, 0, 36, 36, 0, 40)),
    ("nemotron-3-nano-30b-a3b", {}, [5, 12, 19, 26, 33, 42],
     (6, 0, 23, 23, 0, 23)),
    ("jamba2-3b", dict(num_layers=7, attn_layer_period=3,
                       attn_layer_offset=2), [2, 5], (2, 0, 5, 5, 0, 0)),
    ("jamba2-3b", dict(num_layers=5, attn_layer_period=4,
                       attn_layer_offset=0), [0, 4], (2, 0, 3, 3, 0, 0)),
    ("granite-4.0-h-small", dict(num_layers=7, attn_layer_period=3,
                                 attn_layer_offset=2), [2, 5],
     (2, 0, 5, 5, 0, 7)),
    ("lfm2-24b-a2b", dict(num_layers=9, moe_first_k_dense=3), [2, 6],
     (2, 0, 7, 0, 7, 6)),
    ("gpt2-125m", {}, list(range(12)), (12, 0, 0, 0, 0, 0)),
]
MIXER_LETTER = {"mixers_attn": "A", "mixers_ssm": "S", "mixers_conv": "S",
                "mixers_swa": "S"}


def _letter(layer):
    """A for an attention layer, S for a layer of the other kind, lower case
    for a leading dense layer; a single sublayer that is no mixer: F."""
    letter = MIXER_LETTER.get(layer[0], "F")
    return letter.lower() if "ffn_lead" in layer else letter


def _python_walk(monkeypatch, cfg, scan_runs):
    """([(layer id, entry, rows)] as layer_loop hands them to run, the nest
    it built: a letter a layer, "(...)xN" a scan of N turns), with
    lax.scan replaced by a Python loop over its turns: no program is
    traced."""
    visits, nest = [], [[]]

    def scan(turn, carry, turns):
        nest.append([])
        for j in np.asarray(turns).tolist():
            carry, _ = turn(carry, j)
            if j == 0:
                unit = "".join(nest.pop())
                nest.append([])         # the later turns spell the same
        nest.pop()
        nest[-1].append(f"({unit})x{len(turns)}")
        return carry, None

    def run(carry, layer, rows, lid):
        visits.append((lid, layer, dict(rows)))
        nest[-1].append(_letter(layer))
        return carry

    monkeypatch.setattr(jax.lax, "scan", scan)
    block.layer_loop(cfg, None, run, scan_runs=scan_runs)
    assert len(nest) == 1
    return visits, "".join(nest[0])


@pytest.mark.parametrize("preset,over,attends,counts", PLANNED, ids=[
    "-".join([p] + [str(v) for v in o.values()]) for p, o, _, _ in PLANNED])
def test_the_plan_spells_the_kinds_and_the_counts(monkeypatch, preset, over,
                                                  attends, counts):
    cfg = PRESETS[preset](**over)
    plan = cfg.stack_plan
    assert [i for i in range(cfg.num_layers)
            if cfg.layer_is_attention(i)] == attends
    assert (cfg.num_attention_layers, cfg.num_window_layers,
            cfg.num_recurrent_layers, cfg.num_ssm_layers,
            cfg.num_conv_layers, cfg.num_moe_layers) == counts
    assert cfg.kv_planes == len(attends)
    assert cfg.hybrid_stack is (plan is not None)
    if plan is None:
        return
    assert len(plan) == cfg.num_layers
    assert [i for i, layer in enumerate(plan)
            if "mixers_attn" in layer] == attends
    assert [i for i, layer in enumerate(plan) if "ffn_lead" in layer] \
        == list(range(cfg.moe_first_k_dense))
    # the walker: every layer once, in order, its rows the layers before it
    # that name the same stack; scanned runs and written out alike
    for scan_runs in (True, False):
        visits, _ = _python_walk(monkeypatch, cfg, scan_runs)
        assert visits == [
            (i, layer, {k: sum(k in before for before in plan[:i])
                        for k in layer})
            for i, layer in enumerate(plan)]


@pytest.mark.parametrize("preset,served,trained", [
    # ROADMAP D15's nests at the published (layers, period, offset, leading
    # dense layers); the differentiated walk keeps the scan over the unit
    # and writes its runs out
    ("jamba2-3b", "((S)x7A(S)x6)x2", "(SSSSSSSASSSSSS)x2"),
    ("granite-4.0-h-small", "((S)x5A(S)x4)x4", "(SSSSSASSSS)x4"),
    ("mellum2-12b-a2.5b", "((S)x3A)x7", "(SSSA)x7"),
    ("laguna-xs.2", "a((S)x3A)x9(S)x3", "a(SSSA)x9SSS"),
    ("lfm2-24b-a2b", "ss(A(S)x3)x9AS", "ss(ASSS)x9AS"),
])
def test_the_published_stacks_nests(monkeypatch, preset, served, trained):
    cfg = PRESETS[preset]()
    assert _python_walk(monkeypatch, cfg, True)[1] == served
    assert _python_walk(monkeypatch, cfg, False)[1] == trained


def test_one_periods_share_is_written_out_when_differentiated(monkeypatch):
    """The share-training cell's four layers: no scan at all under a
    gradient (a scanned run of three held 14.00 GiB against 10.49, PERF.md
    PR 48), one over the run when served."""
    cfg = PRESETS["mellum2-12b-a2.5b"](num_layers=4)
    assert _python_walk(monkeypatch, cfg, False)[1] == "SSSA"
    assert _python_walk(monkeypatch, cfg, True)[1] == "(S)x3A"


def test_the_calibrated_bias_levels_the_experts_load():
    """models/nemotron_h.py sets a seeded model's selection bias to what
    levels the experts' load over a calibration pass (the published bias is
    trained to): over the positions it was levelled on, no expert gets 1.2
    times the mean load, where zeros leave the busiest with more; and
    ``init_params`` hands the tree over with that bias in it."""
    cfg, _ = _model()
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

