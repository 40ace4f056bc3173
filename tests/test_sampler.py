"""The batched sampler does the work its rows ask for (ISSUE 33).

`_sample_batched` orders the vocabulary only for a live, sampling row
with top-k or top-p, and then once. Held three ways: bit-equality of
tokens and warped logits against a frozen copy of the two-sort sampler
it replaces, the structure of the traced program (no `sort` outside the
ordered branch, one inside), and the engine's `sampler` counters on a
tiny model with free slots."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from megatronapp_tpu.config.transformer_config import TransformerConfig
from megatronapp_tpu.inference.dynamic_engine import (
    DynamicInferenceEngine, _request_keys, _sample_batched, _warp_logits,
)
from megatronapp_tpu.inference.engine import SamplingParams
from megatronapp_tpu.models.gpt import init_gpt_params


# ---- the plain reference: the sampler as it was before ISSUE 33 ----------
def _frozen_warp_logits(logits, temps, top_ks, top_ps):
    v = logits.shape[-1]
    x = logits / jnp.maximum(temps[:, None], 1e-6)
    sorted_desc = jnp.sort(x, axis=-1)[:, ::-1]
    k_idx = jnp.clip(top_ks - 1, 0, v - 1)
    kth = jnp.take_along_axis(sorted_desc, k_idx[:, None], axis=-1)
    x = jnp.where((top_ks[:, None] > 0) & (x < kth), -1e30, x)
    sorted2 = jnp.sort(x, axis=-1)[:, ::-1]
    probs = jax.nn.softmax(sorted2, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    cutoff_idx = jnp.sum(cum < top_ps[:, None], axis=-1)
    cutoff = jnp.take_along_axis(sorted2, cutoff_idx[:, None], axis=-1)
    return jnp.where((top_ps[:, None] > 0.0) & (x < cutoff), -1e30, x)


def _frozen_sample_batched(logits, seeds, rids, steps, temps, top_ks,
                           top_ps, greedys, tail=None):
    keys = _request_keys(seeds, rids, steps)
    x = _frozen_warp_logits(logits, temps, top_ks, top_ps)
    sampled = jax.vmap(jax.random.categorical)(keys, x)
    toks = jnp.where(greedys, jnp.argmax(logits, axis=-1),
                     sampled).astype(jnp.int32)
    return toks if tail is None else jnp.concatenate([toks, tail])


V = 512
ROWS = 6


def _logits(case):
    rng = np.random.default_rng(33)
    x = rng.normal(size=(ROWS, V)).astype(np.float32) * 3.0
    # A padded vocabulary's masked tail, as mask_padded_vocab leaves it.
    x[:, V - 16:] = -1e30
    if case == "ties_at_kth":
        # Rows whose k-th largest value is shared by several entries,
        # some of them beyond the k-th place: top-k keeps every tie.
        x = np.minimum(x, np.float32(9.0))
        x[:, 100:140] = np.float32(9.5)
        x[:, 7] = np.float32(11.0)
    return x


def _rows(case):
    """temps, top_ks, top_ps, greedys, tail of a case."""
    t = np.full(ROWS, 0.8, np.float32)
    k = np.zeros(ROWS, np.int32)
    p = np.zeros(ROWS, np.float32)
    g = np.zeros(ROWS, bool)
    tail = None
    if case == "all_greedy":
        g[:] = True
        k[1], p[2] = 5, 0.9         # a greedy row's filters are not read
    elif case == "temperature_only":
        t[:] = [0.5, 0.8, 1.0, 1.3, 2.0, 1e-8]
    elif case == "top_k_only":
        k[:] = [1, 2, 20, 100, V - 16, 7]
    elif case == "top_p_only":
        p[:] = [0.1, 0.5, 0.9, 0.99, 1.0, 1e-6]
    elif case == "top_k_and_top_p":
        k[:] = [5, 50, 20, 200, 3, 1]
        p[:] = [0.9, 0.5, 0.99, 0.3, 1.0, 0.9]
    elif case == "mixed_with_empty_slot":
        # greedy, temperature only, top-k, top-p, both, and an empty
        # slot with _sampling_rows' defaults.
        g[:] = [True, False, False, False, False, True]
        k[:] = [0, 0, 10, 0, 40, 0]
        p[:] = [0.0, 0.0, 0.0, 0.8, 0.95, 0.0]
        t[5] = 1.0
    elif case == "ties_at_kth":
        k[:] = [2, 3, 10, 41, 42, 30]
        p[:] = [0.0, 0.9, 0.0, 0.5, 0.0, 0.99]
    elif case == "top_k_ge_v":
        k[:] = [V, V + 1, 10 * V, V - 1, V - 15, 2 ** 30]
        p[3:] = 0.9
    elif case == "tail":
        g[:3] = True
        k[3:] = 12
        tail = np.asarray([17, 4], np.int32)
    else:
        raise AssertionError(case)
    return t, k, p, g, tail


CASES = ["all_greedy", "temperature_only", "top_k_only", "top_p_only",
         "top_k_and_top_p", "mixed_with_empty_slot", "ties_at_kth",
         "top_k_ge_v", "tail"]


class TestAgainstTwoSortSampler:
    @pytest.mark.parametrize("case", CASES)
    def test_tokens_bit_equal(self, case):
        t, k, p, g, tail = _rows(case)
        logits = jnp.asarray(_logits(case))
        seeds = jnp.asarray([123, 123, 7, 0, 99, 0], jnp.int32)
        rids = jnp.arange(ROWS, dtype=jnp.int32)
        new, old = jax.jit(_sample_batched), jax.jit(_frozen_sample_batched)
        for step in range(4):       # several draws of each row's chain
            steps = jnp.full((ROWS,), step, jnp.int32)
            args = (logits, seeds, rids, steps, jnp.asarray(t),
                    jnp.asarray(k), jnp.asarray(p), jnp.asarray(g),
                    None if tail is None else jnp.asarray(tail))
            got, want = np.asarray(new(*args)), np.asarray(old(*args))
            assert got.dtype == want.dtype == np.int32
            np.testing.assert_array_equal(got, want)
        if tail is not None:
            np.testing.assert_array_equal(got[ROWS:], tail)

    @pytest.mark.parametrize("case", CASES)
    def test_warped_logits_bit_equal(self, case):
        """Every row, a greedy one's filters included: the speculative
        verifier warps greedy rows too."""
        t, k, p, _, _ = _rows(case)
        args = (jnp.asarray(_logits(case)), jnp.asarray(t), jnp.asarray(k),
                jnp.asarray(p))
        got = np.asarray(jax.jit(_warp_logits)(*args))
        want = np.asarray(jax.jit(_frozen_warp_logits)(*args))
        np.testing.assert_array_equal(got.view(np.uint32),
                                      want.view(np.uint32))
        if case == "ties_at_kth":       # the case holds what it says
            kept = (got[0] > -1e29).sum(), (got[2] > -1e29).sum()
            assert kept == (41, 41)     # k = 2 and 10, 40 ties at the 2nd


def _sort_paths(jaxpr, path=()):
    """Where each `sort` of a traced program sits: the conditional
    branches around it, outermost first (`cond1` is a `lax.cond`'s true
    branch, `cond0` its false one; other nesting, a `jit`, adds nothing)."""
    paths = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "sort":
            paths.append(path)
        elif eqn.primitive.name == "cond":
            for i, branch in enumerate(eqn.params["branches"]):
                paths += _sort_paths(branch.jaxpr, path + (f"cond{i}",))
        else:
            for sub in jax.core.jaxprs_in_params(eqn.params):
                paths += _sort_paths(sub, path)
    return paths


class TestTracedProgram:
    def test_one_sort_and_only_in_the_ordered_branch(self):
        """What keeps a later edit from putting the sorts back into a
        greedy round: the traced `_sample_batched` holds one `sort`, in
        the true branch (a row asks for an order) of the conditional
        inside the false branch (not every row is greedy) of its outer
        conditional; none at the top level or in any other branch."""
        b, v = 4, 256
        closed = jax.make_jaxpr(_sample_batched)(
            jnp.zeros((b, v), jnp.float32), *(jnp.zeros((b,), jnp.int32),) * 3,
            jnp.ones((b,), jnp.float32), jnp.zeros((b,), jnp.int32),
            jnp.zeros((b,), jnp.float32), jnp.zeros((b,), bool),
            jnp.zeros((2,), jnp.int32))
        assert _sort_paths(closed.jaxpr) == [("cond0", "cond1")]

    def test_warp_logits_alone_holds_one_conditional_sort(self):
        """The speculative verifier's call sites get the same program."""
        closed = jax.make_jaxpr(_warp_logits)(
            jnp.zeros((3, 64), jnp.float32), jnp.ones((3,), jnp.float32),
            jnp.zeros((3,), jnp.int32), jnp.zeros((3,), jnp.float32))
        assert _sort_paths(closed.jaxpr) == [("cond1",)]


def _cfg():
    return TransformerConfig(
        num_layers=2, hidden_size=64, num_attention_heads=4,
        num_query_groups=2, vocab_size=128, max_position_embeddings=64,
        compute_dtype=jnp.float32, remat_policy="none")


@pytest.fixture(scope="module")
def model():
    cfg = _cfg()
    params, _ = init_gpt_params(jax.random.PRNGKey(3), cfg)
    return params, cfg


def _engine(model, max_batch, **kw):
    params, cfg = model
    return DynamicInferenceEngine(
        params, cfg, max_batch=max_batch, max_seq_len=64,
        prefill_buckets=(16,), paged=True, block_size=8, prefill_chunk=8,
        **kw)


def _prompts():
    rng = np.random.default_rng(5)
    return [rng.integers(0, 128, n).astype(np.int32) for n in (5, 9, 12)]


class TestEngineCounters:
    def test_greedy_traffic_orders_nothing_with_free_slots(self, model):
        eng = _engine(model, max_batch=4)       # 2 requests: 2 free slots
        greedy = SamplingParams(greedy=True, top_k=50, top_p=0.9)
        ids = [eng.add_request(p, 6, greedy) for p in _prompts()[:2]]
        res = eng.run_to_completion()
        s = eng.stats_snapshot()["sampler"]
        assert s["rounds_greedy"] > 0 and s["prefills_greedy"] == 2
        assert s["rounds_sampled"] == s["rounds_ordered"] == 0
        assert s["prefills_sampled"] == s["prefills_ordered"] == 0
        assert all(len(res[i]) for i in ids)

    def test_temperature_alone_samples_without_ordering(self, model):
        eng = _engine(model, max_batch=3)
        eng.add_request(_prompts()[0], 5, SamplingParams(greedy=True))
        eng.add_request(_prompts()[1], 5,
                        SamplingParams(temperature=0.7, seed=3))
        eng.run_to_completion()
        s = eng.stats_snapshot()["sampler"]
        assert s["rounds_sampled"] > 0 and s["prefills_sampled"] == 1
        assert s["rounds_ordered"] == s["prefills_ordered"] == 0

    def test_one_top_p_request_orders_and_neighbours_keep_their_tokens(
            self, model):
        """Batch-composition independence across the sampler's three
        programs: greedy requests beside a top-p one emit what each
        emits alone (rounds that take argmax only), and the top-p
        request what it emits alone."""
        prompts = _prompts()
        greedy = SamplingParams(greedy=True)
        nucleus = SamplingParams(temperature=0.8, top_p=0.9, seed=11)

        def solo(prompt, sampling, rid):
            eng = _engine(model, max_batch=4)
            for _ in range(rid):        # the key chain folds in the id
                eng._ids.__next__()
            i = eng.add_request(prompt, 6, sampling)
            assert i == rid
            return eng.run_to_completion()[i].tolist()

        eng = _engine(model, max_batch=4)
        ids = [eng.add_request(prompts[0], 6, greedy),
               eng.add_request(prompts[1], 6, nucleus),
               eng.add_request(prompts[2], 6, greedy)]
        res = eng.run_to_completion()
        s = eng.stats_snapshot()["sampler"]
        assert s["rounds_ordered"] > 0 and s["prefills_ordered"] == 1
        assert s["prefills_greedy"] == 2
        for i, (p, sp) in zip(ids, zip(prompts, (greedy, nucleus, greedy))):
            assert res[i].tolist() == solo(p, sp, i)

    @pytest.mark.parametrize("sampling", [
        SamplingParams(greedy=True),
        SamplingParams(temperature=0.05, top_k=20, seed=123)],
        ids=["greedy", "top_k"])
    def test_speculative_round_ignores_empty_slots(self, model, sampling):
        """The verifier reads `_sampling_rows` too: an empty slot's
        defaults (greedy now) change no stream. One request in a batch
        of three, against the same request in a batch of one."""
        def run(max_batch):
            eng = _engine(model, max_batch=max_batch, spec_method="ngram",
                          spec_k=2)
            # A repeating prompt, so that the n-gram proposer drafts.
            prompt = np.tile(_prompts()[0], 3)
            i = eng.add_request(prompt, 8, sampling)
            out = eng.run_to_completion()[i].tolist()
            assert eng.spec_stats["rounds"] > 0
            return out

        assert run(3) == run(1)
