"""Laguna-XS.2's architecture through the paged engine at tiny widths on the
CPU (tests/test_laguna.py's model; blocks of 4 rows, a window of 8): logits
of prefill then decode against perfbench/models/laguna.py's float32
reference, past the window and past a block's return, through a prefill
call wider than the window, with two slots of different lengths in one
round; the window planes' allocator; preemption and re-admission."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import test_laguna as base
from megatronapp_tpu.inference.dynamic_engine import DynamicInferenceEngine
from megatronapp_tpu.inference.engine import SamplingParams
from megatronapp_tpu.inference.paged_cache import PagedKVCache

model = base.model
BS, WINDOW = 4, base.WINDOW
GREEDY = SamplingParams(greedy=True)


@pytest.fixture(scope="module")
def tiny():
    config = base.tiny_config()
    cfg = model.model_config(config, "float32", compute_dtype=jnp.float32)
    return config, cfg, model.init_params(cfg, 11)


def _engine(tiny, **kw):
    _, cfg, params = tiny
    return DynamicInferenceEngine(params, cfg, **{
        "max_batch": 2, "max_seq_len": 96, "block_size": BS,
        "num_blocks": 64, "prefill_chunk": 16, **kw})


def _recorded(eng):
    """Every logits row the engine sampled from, by slot: the prefill
    call's last row and each decode round's rows."""
    rows = {}
    mq, dec = eng._mq_step, eng._decode

    def mq_step(*a):
        out = mq(*a)
        rows.setdefault("prefill", []).append(np.asarray(out[0][0, 0]))
        return out

    def decode(*a):
        out = dec(*a)
        rows.setdefault("decode", []).append(
            (np.asarray(a[6]), np.asarray(out[0])))
        return out

    eng._mq_step, eng._decode = mq_step, decode
    return rows


def _gaps(tiny, req, prompt_len):
    """How far below the reference's maximum each emitted token lies, and
    the reference's rows that predict them."""
    config, _, params = tiny
    toks = np.asarray(req.tokens)
    seq = jnp.asarray(toks[:-1][None])
    ref = model.reference_logits(params, config, seq, jnp.zeros_like(seq),
                                 jnp.arange(seq.shape[1])[None])
    rows = np.asarray(ref[0, prompt_len - 1:])
    picked = rows[np.arange(len(rows)), toks[prompt_len:]]
    return rows.max(-1) - picked, rows


@pytest.fixture(scope="module")
def served(tiny):
    """Two requests through one engine: a prompt of 37 (three prefill calls
    of 16, each wider than the window of 8) and one of 9, decoded 20 and 30
    tokens side by side, the allocator audited after every step."""
    eng = _engine(tiny)
    rows = _recorded(eng)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 512, n).astype(np.int32) for n in (37, 9)]
    rids = [eng.add_request(p, n, GREEDY)
            for p, n in zip(prompts, (20, 30))]
    held = []
    while eng.has_work:
        eng.step()
        eng.pool.audit()
        st = eng.pool.window_stats
        assert st["blocks_taken"] - st["blocks_given_back"] == \
            eng.pool.window_blocks_held()
        held.append([len(eng.pool.window_slot_blocks(s)) for s in range(2)])
    return eng, rows, prompts, [eng.requests[r] for r in rids], held


def test_prefill_then_decode_against_the_reference(tiny, served):
    """Every emitted token is the reference's own argmax (float32 on both
    sides), 19 and 29 positions past prompts of 37 and 9: past the window,
    past many returned blocks, two slots of different lengths a round."""
    eng, _, prompts, reqs, _ = served
    for req, prompt in zip(reqs, prompts):
        gaps, _ = _gaps(tiny, req, len(prompt))
        assert len(gaps) == req.max_new_tokens
        assert gaps.max() < 1e-4, gaps


def test_the_sampled_rows_are_the_references_rows(tiny, served):
    """Logits, not tokens: the prefill's last row and the decode rounds'
    rows of the long request against the reference's."""
    eng, rows, prompts, reqs, _ = served
    _, ref = _gaps(tiny, reqs[0], len(prompts[0]))
    np.testing.assert_allclose(rows["prefill"][2], ref[0], atol=3e-5)
    slot = reqs[0].slot if reqs[0].slot >= 0 else 0
    mine = [lg[slot] for active, lg in rows["decode"] if active[slot]]
    for got, want in zip(mine[:19], ref[1:]):
        np.testing.assert_allclose(got, want, atol=3e-5)


def test_a_slot_holds_its_window_and_gives_the_rest_back(served):
    """Between steps a decoding slot holds at most window / bs + 2 blocks on
    the window planes whatever its length; every block is given back at the
    end; the full planes keep every row."""
    eng, _, _, _, held = served
    bound = eng.pool.window_blocks_slot
    assert bound == WINDOW // BS + 2
    assert max(max(h) for h in held) <= bound
    st = eng.stats_snapshot()["window"]
    assert st["blocks_taken"] == st["blocks_given_back"] > 20
    assert st["blocks_held"] == 0 and st["blocks_slot_bound"] == bound
    # during a prefill call a slot also holds the call's rows
    assert bound < st["max_blocks_slot"] <= bound + 16 // BS + 1
    assert st["num_blocks"] == 2 * bound + 16 // BS + 1
    assert st["rows_walked"] < 0.5 * st["rows_full_walk"]
    assert (st["planes_full"], st["planes_window"], st["window"]) == (
        2, 3, WINDOW)
    # 57 + 39 positions were cached on the full planes: 15 + 10 blocks
    assert eng.stats_snapshot()["pool"]["peak_blocks_in_use"] >= 20


def test_the_startup_line_names_both_kinds_of_plane(served):
    line = served[0].startup_line()
    assert "planes=2 full (6 query heads, 64 blocks) + 3 sliding-window " \
        "(8 query heads, window 8, 13 blocks" in line
    assert "prefix reuse (off)" in line and "8 experts, top-2 by sigmoid" \
        in line


def test_a_block_given_back_is_reusable_at_once():
    """The pool alone: blocks wholly behind the next query's window go back
    before the call's own are taken, so a pool of window / bs + 2 blocks
    serves a sequence of any length a row at a time."""
    cfg = base.model.model_config(base.tiny_config(), "float32")
    pool = PagedKVCache(cfg, max_batch=1, max_seq_len=400, block_size=BS,
                        num_blocks=8, window_call_rows=1)
    assert pool.num_window_blocks == WINDOW // BS + 2 + 1 + 1
    seen = set()
    for at in range(400):
        assert pool.window_ensure(0, at)
        owned = pool.window_slot_blocks(0)
        seen.update(owned)
        first = int(pool._window_first[0])
        assert first == max(at - (WINDOW - 1), 0) // BS
        assert len(owned) == at // BS + 1 - first <= WINDOW // BS + 1
        assert list(pool.window_table[0, first:first + len(owned)]) == owned
        pool.audit()
    assert seen == set(range(pool.num_window_blocks))   # all reused
    pool.release(0, np.zeros((1,), np.int32), 0)
    pool.audit()
    assert pool.window_blocks_held() == 0
    assert not pool.window_ensure(0, 0, 40)     # a call wider than sized for
    pool.release(0, np.zeros((1,), np.int32), 0)
    pool.audit()


def test_a_call_wider_than_the_window_keeps_its_own_rows_and_the_windows():
    cfg = base.model.model_config(base.tiny_config(), "float32")
    pool = PagedKVCache(cfg, max_batch=2, max_seq_len=96, block_size=BS,
                        num_blocks=8, window_call_rows=16)
    assert pool.window_ensure(1, 0, 16)
    assert len(pool.window_slot_blocks(1)) == 4
    assert pool.window_ensure(1, 16, 16)     # rows 9..31: blocks 2..7
    assert int(pool._window_first[1]) == (16 - 7) // BS == 2
    assert len(pool.window_slot_blocks(1)) == 6
    pool.window_trim(1, 32)                  # next query at 32 sees 25..32
    assert int(pool._window_first[1]) == 6
    assert len(pool.window_slot_blocks(1)) == 2
    pool.audit()


def test_preemption_and_readmission_reproduce_the_logits(tiny):
    """A pool too small for both requests: the younger one is preempted, its
    window blocks go back, and its second life recomputes the same stream
    the reference gives."""
    eng = _engine(tiny, num_blocks=14)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, 512, n).astype(np.int32) for n in (21, 18)]
    rids = [eng.add_request(p, 14, GREEDY) for p in prompts]
    while eng.has_work:
        eng.step()
        eng.pool.audit()
    assert eng.stats_snapshot()["pool"]["preemptions"] >= 1
    for rid, prompt in zip(rids, prompts):
        gaps, _ = _gaps(tiny, eng.requests[rid], len(prompt))
        assert len(gaps) == 14 and gaps.max() < 1e-4, gaps
    st = eng.stats_snapshot()["window"]
    assert st["blocks_taken"] == st["blocks_given_back"]


def test_the_decode_round_says_what_the_window_walks_read(tiny):
    """The span attributes the cell's readers take: window_blocks and
    window_rows from the lengths, by hand."""
    eng = _engine(tiny)
    seen = []
    span = eng._span

    def spying(name, *a, **kw):
        if name == "engine.decode_round":
            seen.append(kw)
        return span(name, *a, **kw)

    eng._span = spying
    eng.add_request(np.arange(30, dtype=np.int32), 3, GREEDY)
    while eng.has_work:
        eng.step()
    first = seen[0]
    # length 30, the round appends row 30: the window layers see 23..30,
    # blocks 5..7; a full walk reads rows 0..30
    assert first["window_blocks"] == 3 and first["window_rows"] == 31 - 20
    assert first["kv_blocks"] == 8 and first["kv_tokens"] == 30
    # 8 full-plane blocks of 2 planes and 3 window-plane blocks of 3
    row = 2 * 2 * 16 * 4
    assert first["bytes_held"] == 8 * BS * 2 * row + 3 * BS * 3 * row


def test_the_other_models_steps_take_one_table(tiny):
    """A model without window layers is handed the one table, as before."""
    from megatronapp_tpu.config.transformer_config import TransformerConfig
    from megatronapp_tpu.models.gpt import init_gpt_params
    cfg = TransformerConfig(num_layers=2, hidden_size=64,
                            num_attention_heads=4, vocab_size=128,
                            max_position_embeddings=64,
                            compute_dtype=jnp.float32)
    eng = DynamicInferenceEngine(
        init_gpt_params(jax.random.PRNGKey(0), cfg)[0], cfg, max_batch=2,
        max_seq_len=32, block_size=4, num_blocks=16, prefill_chunk=8)
    assert not eng.has_window and eng.pool.window_pages is None
    assert not isinstance(eng._tables(), tuple)
    assert eng.stats_snapshot()["window"] is False
    assert "sliding-window" not in eng.startup_line()
