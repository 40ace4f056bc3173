"""LFM2-24B-A2B through the paged engine against its plain float32 reference
(tests/test_lfm2_moe.py has the model, the helpers and the whole-sequence
tests; this file is its engine half, a file of its own so that the tier-1
run's workers share the two): chunked prefill and decode through the cached
columns, slots turning over, preemption, the tenant's size and content, the
`moe` counters, a cancel that races admission, and the decode steps of the
models that do not use the new fields."""
import json
import os

import numpy as np
import pytest

import jax.numpy as jnp

from perfbench import manifest
from test_lfm2_moe import (
    GREEDY, MODEL, PUBLISHED, ROOT, TINY, TOL_BF16, TOL_F32, _engine, _model,
    _recorded, _shared_engine, _tokens, _worst_gap, eng,  # noqa: F401
)


class TestEngine:
    @pytest.mark.parametrize("dtype,tol,width,n", [
        (jnp.float32, TOL_F32, 8, 18), (jnp.bfloat16, TOL_BF16, 8, 18),
        (jnp.float32, TOL_F32, 1, 4), (jnp.float32, TOL_F32, 2, 5)],
        ids=["float32-8", "bfloat16-8", "float32-edge-1-after-the-start",
             "float32-edge-2-after-the-start"])
    def test_chunked_prefill_then_decode(self, dtype, tol, width, n, lend,
                                         monkeypatch):
        """18 tokens in calls of 8: two full ones and one of 2, whose taps
        reach back into the call before; calls of 1 and 2, so that a call's
        edge lies 1 and 2 tokens after the sequence's start, the tail's
        zeros are read and a new tail is one old column and one new. Then
        12 decode rounds through the cached columns.
        The logits at every position are the reference's."""
        _, params = _model(dtype)
        eng = lend(_shared_engine(dtype, width))
        logits = _recorded(eng, monkeypatch)
        was = eng.stats_snapshot()["state"]
        req = eng.requests[eng.add_request(_tokens(n, 4), 13, GREEDY)]
        eng.run_to_completion()
        assert _worst_gap(params, req, logits) < tol
        state = eng.stats_snapshot()["state"]
        calls = -(-n // width)
        assert (state["kind"], state["layers"],
                state["resets"] - was["resets"],
                state["dropped"] - was["dropped"],
                state["prefill_scans"] - was["prefill_scans"]) == (
                    "conv", 4, 1, 0, calls * 4)
        eng.pool.audit()

    def test_continuous_batching_and_slot_reuse(self, eng, monkeypatch):
        """Requests of different lengths admitted at different steps; the
        fourth runs in the slot the first left, whose columns it must not
        see; a slot that idles while others decode keeps its columns."""
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
        before = np.asarray(eng.pool.state[0][:, idle])
        assert np.abs(before).max() > 0
        eng.step()
        np.testing.assert_array_equal(
            before, np.asarray(eng.pool.state[0][:, idle]))
        reqs.append(add(9, 13, 6))
        eng.step()
        assert reqs[3].slot == idle
        eng.run_to_completion()
        for req in reqs:
            assert _worst_gap(params, req, logits) < TOL_F32
        assert eng.stats_snapshot()["state"]["resets"] - resets == 4
        eng.pool.audit()

    def test_preempted_request_is_recomputed(self, eng):
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
        eng.pool.audit()

    def test_the_tenant_is_the_tails_alone(self, eng):
        """The pools: one plane for the one attention layer, two key/value
        heads; the second tenant [conv layers, slots, 2 x H] in the compute
        type and no h; its bytes in the pool's total; a finished slot's two
        columns a layer are the reference's."""
        _, params = _model()
        k, v = eng.pool.pages
        assert k.shape == v.shape == (1, 24, 4, 2, 16)
        tails, = eng.pool.state
        assert tails.shape == (4, 3, 2 * 64) and tails.dtype == jnp.float32
        req = eng.requests[eng.add_request(_tokens(11, 30), 5, GREEDY)]
        eng.run_to_completion()
        held = np.asarray(tails := eng.pool.state[0])[:, req.slot].reshape(
            4, 1, 2, 64)
        want = np.asarray(MODEL.reference_state(
            params, TINY, jnp.asarray(req.tokens[:-1][None])))
        assert want.shape == held.shape and np.abs(want).max() > 0.01
        np.testing.assert_allclose(held, want, atol=1e-5)
        # a row padded behind its length reads the same columns
        padded = np.concatenate([req.tokens[:-1], _tokens(6, 31)])[None]
        np.testing.assert_allclose(want, np.asarray(MODEL.reference_state(
            params, TINY, jnp.asarray(padded),
            lengths=jnp.asarray([len(req.tokens) - 1]))), atol=1e-6)
        stats = eng.stats_snapshot()
        assert stats["pool"]["bytes_per_block"] == \
            4 * MODEL.kv_bytes_per_token(TINY, "float32") == 4 * 2 * 2 * 16 * 4
        assert stats["state"]["bytes_per_slot"] == \
            MODEL.state_bytes_per_slot(TINY, "float32") == 4 * 2 * 64 * 4
        assert stats["pool"]["pool_bytes_total"] == \
            24 * stats["pool"]["bytes_per_block"] \
            + 3 * stats["state"]["bytes_per_slot"]
        assert eng.pool.bytes_total == stats["pool"]["pool_bytes_total"]
        eng.pool.audit()
        # the published widths: what the cell's runner holds the engine to
        assert MODEL.kv_bytes_per_token(PUBLISHED, "bfloat16") == 4096
        assert MODEL.state_bytes_per_slot(PUBLISHED, "bfloat16") == 57_344
        assert round(MODEL.params_per_token(PUBLISHED) / 1e6) == 648

    def test_moe_counters_against_a_count_by_hand(self, eng):
        """One request alone: every decode round routes 1 token through 4
        MoE layers to 2 distinct experts each. Then two together. The
        traced decode step cuts no layer's experts out of the stacks (they
        are read through the layer id), holds the paged kernels once a
        scanned run, and the engine says what it runs."""
        was = eng.stats_snapshot()["moe"]

        def moe_since():     # this case's: the counts (experts_here is none)
            return {k: v if k == "experts_here" else v - was[k]
                    for k, v in eng.stats_snapshot()["moe"].items()}
        disp = eng.stats_snapshot(include_dispatch=True)["decode_dispatch"]
        assert disp["expert_stack_slices"] == 0, disp
        assert disp["scatters"] == 0, disp      # rows move by gathers alone
        # paged_append x 2, decode; the grouped GEMM x 2 a run of MoE layers
        assert disp["kernels"] == 3 + 2 * 4, disp
        line = eng.startup_line()
        for word in ("4 gated short-convolution layers x 2048 B a slot",
                     "prefix reuse off", "8 experts", "sigmoid"):
            assert word in line, line
        eng.add_request(_tokens(6, 40), 8, GREEDY)
        eng.run_to_completion()
        moe = moe_since()
        rounds = 7          # the first token is the prefill's
        assert moe == {
            "decode_rounds": rounds, "tokens": rounds,
            "assignments": rounds * 2 * 4, "assignments_here": rounds * 2 * 4,
            "assignments_zero": 0, "assignments_absent": 0,
            "expert_pairs_touched": rounds * 2 * 4,
            "here_max_rows": rounds * 4, "experts_here": 8,
            "expert_pairs_possible": rounds * 4 * 8}
        for n in (5, 9):
            eng.add_request(_tokens(n, 41 + n), 6, GREEDY)
        eng.run_to_completion()
        moe = moe_since()
        assert moe["tokens"] == rounds + 2 * 5
        assert moe["assignments"] == moe["tokens"] * 2 * 4
        cell = manifest.load_module("cells", "serve_closed_conv")
        assert cell.counter_problems(moe, TINY) == []
        assert cell.counter_problems(dict(moe, tokens=moe["tokens"] + 1),
                                     TINY)
        assert cell.counter_problems(dict(moe, experts_here=4), TINY)

    def test_a_cancel_that_races_admission_searches_again(self, eng):
        """The stepper's admission pops from the waiting queue while a
        canceller's thread searches it; a deque that changed under the
        search raises (RuntimeError from `in`, IndexError from `remove`),
        which took a whole run down in the cell's drain: 192 waiting under
        384 cancels. The search is made again."""
        from collections import deque

        class Changing(deque):
            raises = [RuntimeError("deque mutated during iteration"),
                      IndexError("deque mutated during remove().")]

            def remove(self, item):
                if self.raises:
                    raise self.raises.pop()
                return super().remove(item)

        rids = [eng.add_request(_tokens(5, s), 6, GREEDY) for s in (1, 2)]
        eng.waiting = Changing(eng.waiting)
        assert eng.abort_request(rids[1]) == "waiting"
        assert not Changing.raises and len(eng.waiting) == 1
        eng.step()                      # admits the other one
        assert eng.abort_request(rids[0]) == "running"
        assert eng.abort_request(rids[1]) is None       # already aborted
        eng.run_to_completion()
        eng.pool.audit()
        eng.waiting = deque(eng.waiting)


# What the shared layer loop, router and tenant trace for the models that do
# not use the new fields: the decode step of the tiny Jamba and
# DeepSeek-V2-Lite (their modules' REHEARSAL sizes, 3 slots), read off the
# parent commit (8990e6e) with the same lines; since ISSUE 43 with the
# MoE layers' four grouped GEMMs as Pallas calls (3 + 4 kernels, 63 launches
# a call for its visit list where the zero-padded sizes were 4); since ISSUE 51
# with the barrier that keeps the paged q/kv projection flat, one equation in
# the attention layers' scanned body; since ISSUE 57 with the rows and layer
# ids of Jamba's one period Python ints where the period's index was a traced
# 0 (60 scalar equations fewer; `launches` counts equations before fusion);
# since ISSUE 60 with DeepSeek's two MoE layers' rows moved by gathers alone
# (15 equations a layer more: the six slabs of a token's picks written out,
# less the dispatch gather's bounds check; no scatter in either step; Jamba's
# step is the parent's).
PARENT_DISPATCH = {
    "jamba": ("jamba2-3b", {"launches": 754, "kernels": 6, "loop_steps": 2,
                            "expert_stack_slices": 0, "scatters": 0}),
    "deepseek_v2": ("deepseek-v2-lite", {
        "launches": 1897, "kernels": 7, "loop_steps": 21,
        "expert_stack_slices": 0, "scatters": 0}),
}


@pytest.mark.parametrize("name", sorted(PARENT_DISPATCH))
def test_the_other_models_decode_step_did_not_grow(name):
    file, pinned = PARENT_DISPATCH[name]
    model = manifest.load_module("models", name)
    with open(os.path.join(ROOT, "perfbench", "configs", file + ".json")) as f:
        tiny = {**json.load(f), **model.REHEARSAL}
    cfg = model.model_config(tiny, "float32", compute_dtype=jnp.float32)
    eng = _engine(cfg, model.init_params(cfg, seed=5))
    disp = eng.stats_snapshot(include_dispatch=True)["decode_dispatch"]
    assert {k: disp[k] for k in pinned} == pinned
