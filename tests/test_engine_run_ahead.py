"""The plain decode loop runs one round ahead (ISSUE 47): round n+1 is
dispatched before round n's tokens are read. What the serving cells do not
exercise of it: a late `eod_id` stop, sampling rows, cancellation, expiry
and preemption with a round in flight, speculation beside it, and the
hybrid, sliding-window and MoE tenants.

The reference of a stream is the static engine (greedy), the same request
run alone (sampled: the fold_in chain makes a stream independent of its
batch), or `_serial`: the same engine with every round dispatched and read
at once, the loop that never runs ahead.

Section (g), ISSUE 53: an admitted request's first token is sampled on the
device and read after the next round's dispatch, the round taking it as its
row's operand there."""
import contextlib
import functools
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from megatronapp_tpu.config.transformer_config import TransformerConfig
from megatronapp_tpu.inference.dynamic_engine import DynamicInferenceEngine
from megatronapp_tpu.inference.engine import (
    SamplingParams, StaticInferenceEngine,
)
from megatronapp_tpu.models.gpt import init_gpt_params

GREEDY = SamplingParams(greedy=True)
VOCAB = 128


@pytest.fixture(scope="module")
def tiny():
    cfg = TransformerConfig(
        num_layers=2, hidden_size=64, num_attention_heads=4,
        num_query_groups=2, vocab_size=VOCAB, max_position_embeddings=96,
        compute_dtype=jnp.float32)
    params, _ = init_gpt_params(jax.random.PRNGKey(3), cfg)
    return cfg, params


def _engine(tiny, **kw):
    cfg, params = tiny
    return DynamicInferenceEngine(params, cfg, **{
        "max_batch": 3, "max_seq_len": 96, "block_size": 8, **kw})


@pytest.fixture(scope="module")
def engines(tiny):
    """One engine for each set of construction arguments the cases ask
    for, compiled once."""
    return functools.cache(lambda **kw: _engine(tiny, **kw))


def _as_new(eng):
    """An idle engine as a new one is, but for its counters
    (`_steps(eng, since)`): no prefix stored, no finished request kept."""
    eng.pool.flush_prefix_cache()
    for rid in list(eng.requests):
        eng.pop_request(rid)
    return eng


@pytest.fixture
def engine(engines, lend):
    """engine(**kw) -> the module's engine of those arguments, idle
    (conftest.py `lend` holds the case to handing it back so) and as
    new."""
    return lambda **kw: _as_new(lend(engines(**kw)))


@contextlib.contextmanager
def _serial(eng):
    """`eng` with the loop that never runs ahead, for the body: a round is
    dispatched and read in the step that stages it."""
    eng._plain_round = eng._plain_round_inner
    try:
        yield eng
    finally:
        del eng._plain_round


def _prompts(n, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, VOCAB, int(k)).astype(np.int32)
            for k in rng.integers(4, 14, n)]


def _drive(eng, arrivals=(), act=None, max_steps=400):
    """Step `eng` until it has no work; `arrivals` {step index: [kwargs of
    add_request]} go in before that step, `act(eng, step index)` runs
    before each step. -> ({rid: tokens in the order the events delivered
    them}, all events). Holds the step's contract on the way: the record
    equals the stream."""
    arrivals = dict(arrivals)
    streams, seen, k = {}, [], 0
    while eng.has_work or arrivals:
        for kw in arrivals.pop(k, ()):
            eng.add_request(**kw)
        if act is not None:
            act(eng, k)
        ev = eng.step()
        seen.append(ev)
        for rid, tok in ev["tokens"]:
            streams.setdefault(rid, []).append(int(tok))
        k += 1
        assert k < max_steps
    assert eng._round is None
    for rid, toks in streams.items():
        if rid in eng.requests:        # (a migrated session's begin elsewhere)
            record = [int(t) for t in eng.requests[rid].generated]
            assert toks == record[-len(toks):]
    return streams, seen


def _steps(eng, since=None):
    """The step counters, or what they gained since the reading `since`."""
    def gained(now, was):
        return {k: gained(v, was[k]) if isinstance(v, dict) else v - was[k]
                for k, v in now.items() if k != "slowest"}
    now = eng.stats_snapshot()["steps"]
    return now if since is None else gained(now, since)


# ---- (a) streams -----------------------------------------------------------
MIXED_NEW = (6, 12, 3, 9, 20, 4, 1, 2)


def _mixed(sampling_of):
    """Eight requests over three slots, ending by count in different
    rounds, five of them arriving while the others run."""
    prompts = _prompts(len(MIXED_NEW))
    kws = [dict(prompt_tokens=p, max_new_tokens=m, sampling=sampling_of(i),
                request_id=10 + i)
           for i, (p, m) in enumerate(zip(prompts, MIXED_NEW))]
    return kws, {0: kws[:3], 2: kws[3:5], 5: kws[5:6], 9: kws[6:]}


def test_greedy_streams_equal_the_static_engine(tiny, engine):
    cfg, params = tiny
    kws, arrivals = _mixed(lambda i: GREEDY)
    eng = engine()
    was, emitted = _steps(eng), eng.spec_stats["emitted_tokens"]
    streams, _ = _drive(eng, arrivals)
    static = StaticInferenceEngine(params, cfg)
    for kw in kws:
        p, m = kw["prompt_tokens"], kw["max_new_tokens"]
        ref = np.asarray(static.generate(p[None], m, GREEDY))[0]
        assert streams[kw["request_id"]] == ref[len(p):len(p) + m].tolist()
    st = _steps(eng, was)
    assert st["rounds_ahead"] > 0 and st["overrun_rows"] == 0
    assert eng.spec_stats["emitted_tokens"] - emitted == sum(
        m - 1 for m in MIXED_NEW)       # a request's first is its prefill's
    assert eng.pool.blocks_in_use() == 0
    eng.pool.audit()


def test_sampled_streams_equal_the_request_run_alone(engine):
    def sampling_of(i):
        return (GREEDY if i % 3 == 2 else SamplingParams(
            temperature=0.9, top_k=(0, 20)[i % 2], top_p=(0.0, 0.8)[i % 2],
            seed=100 + i))
    kws, arrivals = _mixed(sampling_of)
    streams, _ = _drive(engine(), arrivals)
    with _serial(engine(enable_prefix_caching=False)) as alone:
        for kw in kws:
            got, _ = _drive(alone, {0: [kw]})
            assert streams[kw["request_id"]] == got[kw["request_id"]]
    assert any(len(set(s)) > 2 for s in streams.values())


# ---- (b) a stop on eod_id is learned a round late --------------------------
EOD_AT = 5


def _eod_case(alone):
    """A prompt and an `eod_id` that its sampled stream of 16 reaches at
    index EOD_AT for the first time: neither the first decode round's
    token nor the last. (A tiny model's greedy stream repeats one token.)"""
    sp = SamplingParams(temperature=1.5, seed=3)
    for seed in range(40):
        prompt = _prompts(1, seed=seed)[0]
        gen = _drive(alone, {0: [dict(
            prompt_tokens=prompt, max_new_tokens=16, sampling=sp,
            request_id=0)]})[0][0]
        if gen.index(gen[EOD_AT]) == EOD_AT:
            return prompt, gen, gen[EOD_AT], sp
    raise AssertionError("no stream whose sixth token is new to it")


def test_eod_stop_drops_the_overrun_row(engine):
    with _serial(engine(enable_prefix_caching=False)) as alone:
        prompt, gen, eod, sp = _eod_case(alone)
    other = _prompts(1, seed=99)[0]

    def run(eng):
        _as_new(eng)
        was, emitted = _steps(eng), eng.spec_stats["emitted_tokens"]
        told = eng.stats_snapshot().get("tenants", {}).get(
            "t", {"tokens": 0})["tokens"]
        kw = dict(prompt_tokens=prompt, max_new_tokens=16, sampling=sp,
                  eod_id=eod, tenant="t", request_id=0)
        # a neighbour that runs through, so that the round ahead of the
        # eod has a row to keep as well as one to drop
        streams, seen = _drive(eng, {0: [kw, dict(
            prompt_tokens=other, max_new_tokens=12, sampling=GREEDY,
            request_id=1)]})
        after = (eng.lengths.copy(), eng.pool.free_blocks(),
                 eng.pool.evictable_blocks(), eng.pool.blocks_in_use(),
                 eng.spec_stats["emitted_tokens"] - emitted,
                 eng.stats_snapshot()["tenants"]["t"]["tokens"] - told)
        eng.pool.audit()
        # the same prompt and its answer again: a prefix hit on the blocks
        # the stopped request registered
        hits = eng.pool.stats["prefix_hit_tokens"]
        again, _ = _drive(eng, {0: [dict(
            prompt_tokens=np.concatenate(
                [prompt, gen[:EOD_AT]]).astype(np.int32),
            max_new_tokens=6, sampling=GREEDY, request_id=77)]})
        assert eng.pool.stats["prefix_hit_tokens"] > hits
        return streams, seen, after, again[77], \
            _steps(eng, was)["overrun_rows"]

    eng = engine()
    streams, seen, after, again, overruns = run(eng)
    with _serial(eng):
        s_streams, _, s_after, s_again, s_overruns = run(eng)
    assert streams[0] == gen[:EOD_AT + 1] and streams[0][-1] == eod
    assert streams == s_streams and again == s_again
    # the over-run token (the eod's successor) is in no event
    assert sum(len(ev["tokens"]) for ev in seen) == EOD_AT + 1 + 12
    assert (overruns, s_overruns) == (1, 0)
    for got, want in zip(after, s_after):
        assert np.array_equal(got, want), (after, s_after)


# ---- (c) stopped or moved with a round in flight ---------------------------
@pytest.mark.parametrize("how", ["abort", "expire"])
def test_stopped_with_a_round_in_flight(tiny, engine, how):
    cfg, params = tiny
    prompts = _prompts(3, seed=7)
    kws = [dict(prompt_tokens=p, max_new_tokens=14, sampling=GREEDY,
                request_id=i) for i, p in enumerate(prompts)]

    def run(eng):
        _as_new(eng)
        in_flight, was = [], _steps(eng)

        def act(eng, k):
            if k != 4:
                return
            in_flight.append(eng._round is not None
                             and 1 in [r.request_id
                                       for r in eng._round.rows.values()])
            if how == "abort":
                assert eng.abort_request(1) == "running"
            else:
                eng.requests[1].deadline_s = time.monotonic() - 1.0
        streams, seen = _drive(eng, {0: kws}, act)
        eng.pool.audit()
        return streams, seen, in_flight[0], (
            eng.pool.free_blocks(), eng.pool.evictable_blocks(),
            eng.pool.blocks_in_use(), eng.lengths.tolist()), \
            _steps(eng, was)["overrun_rows"]

    eng = engine()
    streams, seen, in_flight, after, overruns = run(eng)
    with _serial(eng):
        s_streams, _, s_in_flight, s_after, _ = run(eng)
    assert in_flight and not s_in_flight
    static = StaticInferenceEngine(params, cfg)
    for i in (0, 2):                     # the survivors
        ref = np.asarray(static.generate(prompts[i][None], 14, GREEDY))[0]
        assert streams[i] == ref[len(prompts[i]):].tolist()
    # the stopped request got what it had when it was stopped, as in the
    # loop that never ran ahead, and the row in flight was dropped
    assert streams == s_streams and after == s_after
    assert 0 < len(streams[1]) < 14
    assert overruns >= 1
    if how == "expire":
        assert [ev["expired"] for ev in seen].count([1]) == 1
    assert any(1 in ev["finished"] for ev in seen)


def test_preemption_with_a_round_in_flight(tiny, engine):
    """Two slots over five 8-token blocks: the pool runs out under a round
    in flight. Nothing runs ahead then, and the next step preempts the
    victim the serial loop preempts, at the same token."""
    cfg, params = tiny
    prompts = _prompts(2, seed=3)
    prompts = [np.resize(p, 9) for p in prompts]

    def run(eng):
        _as_new(eng)
        streams, seen = _drive(eng, {0: [
            dict(prompt_tokens=p, max_new_tokens=12, sampling=GREEDY,
                 priority=i, request_id=i) for i, p in enumerate(prompts)]})
        eng.pool.audit()
        return streams, seen

    ahead = engine(max_batch=2, max_seq_len=48, num_blocks=5)
    was, preemptions = _steps(ahead), ahead.pool.stats["preemptions"]
    streams, seen = run(ahead)
    assert ahead.pool.stats["preemptions"] - preemptions == 1
    assert _steps(ahead, was)["rounds_ahead"] > 0
    with _serial(ahead):
        s_streams, s_seen = run(ahead)
    assert [ev["preempted"] for ev in seen] == [
        ev["preempted"] for ev in s_seen]
    static = StaticInferenceEngine(params, cfg)
    for i, p in enumerate(prompts):
        ref = np.asarray(static.generate(p[None], 12, GREEDY))[0]
        assert streams[i] == ref[len(p):].tolist() == s_streams[i]
    assert ahead.pool.blocks_in_use() == 0


def test_a_migrated_session_leaves_as_of_its_last_token_read(tiny, engine):
    """Nothing is fetched for an export: the payload is the session as of
    the last token read, its row of the round in flight is dropped when the
    slot goes, and the destination samples that token again, the same."""
    prompt = _prompts(1, seed=5)[0]
    # the destination its own: two engines at once
    src, dst = engine(), _engine(tiny)
    was = _steps(src)
    rid = src.add_request(prompt, 10, GREEDY, request_id=900)
    delivered = []
    for _ in range(3):
        delivered += [t for _, t in src.step()["tokens"]]
    assert src._round is not None
    payload = src.export_request(rid)
    assert payload["valid_len"] == len(prompt) + len(delivered) - 1
    assert dst.import_request(payload)
    assert len(src._round.rows) == 1 \
        and _steps(src, was)["overrun_rows"] == 0
    src.release_exported(rid)
    assert src._round is None and _steps(src, was)["overrun_rows"] == 1
    assert not src.has_work
    streams, _ = _drive(dst)
    cfg, params = tiny
    ref = np.asarray(StaticInferenceEngine(params, cfg).generate(
        prompt[None], 10, GREEDY))[0][len(prompt):].tolist()
    assert delivered + streams[rid] == ref
    src.pool.audit()
    dst.pool.audit()


def _listen_for_compiles():
    """-> (the compile events so far, a function that stops listening): what
    perfbench/common.CompileCounter counts."""
    compiles = []

    def on_compile(event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(event)

    jax.monitoring.register_event_duration_secs_listener(on_compile)

    def stop():
        unregister = getattr(
            jax.monitoring,
            "_unregister_event_duration_listener_by_callback", None)
        if unregister is not None:
            unregister(on_compile)
    return compiles, stop


# ---- (d) the order of the host's work ---------------------------------------
def test_next_round_is_dispatched_before_the_fetch(engine, monkeypatch):
    eng = engine()
    compiles, stop = _listen_for_compiles()
    try:
        # a lone request's first four tokens meet every program there is
        _drive(eng, {0: [dict(prompt_tokens=_prompts(1)[0], max_new_tokens=4,
                              sampling=GREEDY)]})
        traces = (eng.decode_traces, eng.mq_traces)
        warm = len(compiles)
        before = _steps(eng)
        log, inner, get = [], eng._decode, jax.device_get

        def decode(*a):
            log.append("decode")
            return inner(*a)

        def device_get(x):
            log.append("get")
            return get(x)

        monkeypatch.setattr(eng, "_decode", decode)
        monkeypatch.setattr(jax, "device_get", device_get)
        streams, _ = _drive(eng, {0: [dict(
            prompt_tokens=_prompts(1, seed=1)[0], max_new_tokens=33,
            sampling=GREEDY, request_id=5)]})
    finally:
        stop()
    assert len(streams[5]) == 33
    assert (eng.decode_traces, eng.mq_traces) == traces
    assert len(compiles) == warm, compiles[warm:]
    # the prefill's first sample is a fetch too, behind the dispatch of the
    # request's first two rounds (ISSUE 53) and before the first round's
    assert log[:4] == ["decode", "decode", "get", "get"]
    dispatched = [i for i, what in enumerate(log) if what == "decode"]
    fetched = [i for i, what in enumerate(log) if what == "get"][1:]
    assert len(dispatched) == len(fetched) == 32
    early = sum(dispatched[n + 1] < fetched[n] for n in range(31))
    assert early == 31
    st = _steps(eng)
    rounds = st["decode_round"]["count"] - before["decode_round"]["count"]
    assert rounds == 32
    assert (st["rounds_ahead"] - before["rounds_ahead"]) / rounds >= 0.9


def test_spans_and_counters(engine):
    """One `decode_round` span a round, `ahead` on it, the phases' names as
    they were, `rounds_ahead` and `overrun_rows` beside them."""
    from megatronapp_tpu.trace.request_trace import get_request_tracer
    rt = get_request_tracer()
    rt.configure(enabled=True)
    eng = engine()
    was, model_steps = _steps(eng), eng.spec_stats["model_steps"]
    try:
        _drive(eng, {0: [dict(prompt_tokens=_prompts(1)[0],
                              max_new_tokens=9, sampling=GREEDY)]})
        rounds = [r for r in rt.dump()
                  if r["name"] == "decode-step" and r["ph"] == "B"]
    finally:
        rt.configure(enabled=False)
    st = _steps(eng, was)
    assert len(rounds) == 8 == st["decode_round"]["count"]
    assert [r["args"]["ahead"] for r in rounds] == [0] + [1] * 7
    assert all(r["args"]["batch"] == 1 and "kv_tokens" in r["args"]
               and "kv_blocks" in r["args"] for r in rounds)
    assert st["rounds_ahead"] == 7 and st["overrun_rows"] == 0
    for phase in ("decode.stage", "decode.wait", "decode.record"):
        assert st[phase]["count"] == 8
    assert eng.spec_stats["model_steps"] - model_steps == 8


# ---- (e) speculation stays as it was ---------------------------------------
def test_speculative_rounds_do_not_run_ahead(tiny):
    cfg, params = tiny
    eng = _engine(tiny, spec_method="ngram", spec_k=3)  # the one that does
    prompts = [np.tile(p[:4], 4) for p in _prompts(2, seed=2)]
    seen_round = []
    streams, _ = _drive(
        eng, {0: [dict(prompt_tokens=p, max_new_tokens=12, sampling=GREEDY)
                  for p in prompts]},
        act=lambda eng, k: seen_round.append(eng._round))
    assert seen_round and all(r is None for r in seen_round)
    st = _steps(eng)
    assert st["rounds_ahead"] == 0 and st["overrun_rows"] == 0
    # a proposer reads the host's tokens: the first sample is fetched at the
    # admission itself (ISSUE 53)
    assert st["first_samples_ahead"] == 0 and st["admitted"] == 2
    static = StaticInferenceEngine(params, cfg)
    for rid, p in enumerate(prompts):
        ref = np.asarray(static.generate(p[None], 12, GREEDY))[0]
        assert streams[rid] == ref[len(p):].tolist()


# ---- (f) the other tenants ------------------------------------------------
def test_hybrid_state_tenant(lend, monkeypatch):
    """Tiny Jamba (tests/test_jamba.py, and its engine): state-space layers
    advance a state a round; the logits of every position are the plain
    reference's."""
    import test_jamba as base
    _, params = base._model()
    eng = _as_new(lend(base._shared_engine()))
    was, dropped = _steps(eng), eng.stats_snapshot()["state"]["dropped"]
    asks = ((18, 4, 13), (9, 5, 6), (11, 6, 9), (7, 7, 4))
    with monkeypatch.context() as patch:
        logits = base._recorded(eng, patch)
        reqs = [eng.requests[eng.add_request(
            base._tokens(n, s), m, GREEDY, request_id=i)]
            for i, (n, s, m) in enumerate(asks)]
        streams, _ = _drive(eng)
    assert _steps(eng, was)["rounds_ahead"] > 0
    for req in reqs:
        assert base._worst_gap(params, req, logits) < base.TOL_F32
    with _serial(_as_new(eng)):
        for i, (n, s, m) in enumerate(asks):
            eng.add_request(base._tokens(n, s), m, GREEDY, request_id=i)
        assert _drive(eng)[0] == streams
    assert eng.stats_snapshot()["state"]["dropped"] == dropped


def test_sliding_window_tenant():
    """Tiny Laguna (tests/test_laguna_engine.py): the window planes give
    blocks back a round ahead of the read; streams and the allocator's
    counts are the serial loop's."""
    import test_laguna_engine as base
    config = base.base.tiny_config()
    cfg = base.model.model_config(config, "float32",
                                  compute_dtype=jnp.float32)
    tiny = (config, cfg, base.model.init_params(cfg, 11))
    rng = np.random.default_rng(3)
    asks = [(rng.integers(0, cfg.vocab_size, n).astype(np.int32), m)
            for n, m in ((21, 14), (6, 20), (13, 5))]

    def run(eng):
        was = eng.stats_snapshot()["window"]
        for i, (p, m) in enumerate(asks):
            eng.add_request(p, m, GREEDY, request_id=i)
        streams, _ = _drive(eng)
        w = eng.stats_snapshot()["window"]
        return streams, (w["blocks_taken"] - was["blocks_taken"],
                         w["blocks_given_back"] - was["blocks_given_back"],
                         w["blocks_held"],
                         w["rows_walked"] - was["rows_walked"],
                         eng.pool.free_blocks())

    eng = base._engine(tiny)    # ahead, then serial: one engine's programs
    streams, counts = run(eng)
    assert _steps(eng)["rounds_ahead"] > 0
    with _serial(_as_new(eng)):
        s_streams, s_counts = run(eng)
    assert streams == s_streams
    # the serial loop counts no walk (its rounds are the fallback's)
    assert counts[:3] == s_counts[:3] and counts[4] == s_counts[4]
    assert counts[2] == 0 and counts[3] > 0


def test_moe_tenant_counts_a_round_once():
    """Tiny DeepSeek-V2 (tests/test_deepseek_v2.py): the routing counts
    ride behind the tokens of the round they belong to, and are counted
    when that round is read, once."""
    import test_deepseek_v2 as base
    cfg, params = base._model()

    # ahead, then serial: one engine's programs
    eng = DynamicInferenceEngine(
        params, cfg, max_batch=2, max_seq_len=64, num_blocks=16,
        block_size=4, prefill_chunk=8)

    def run(eng):
        was = eng.stats_snapshot()["moe"]
        for i, (seed, m) in enumerate(((4, 5), (5, 9), (6, 3))):
            eng.add_request(base._tokens((10,), seed), m, GREEDY,
                            request_id=i)
        streams = _drive(eng)[0]
        return streams, {k: v - was[k]
                         for k, v in eng.stats_snapshot()["moe"].items()}

    streams, moe = run(eng)
    ahead = _steps(eng)
    with _serial(_as_new(eng)):
        s_streams, s_moe = run(eng)
    assert streams == s_streams
    assert moe["decode_rounds"] == ahead["decode_round"]["count"]
    assert moe["tokens"] == sum(m - 1 for m in (5, 9, 3)) == s_moe["tokens"]
    layers = cfg.num_layers - cfg.moe_first_k_dense
    assert moe["assignments"] == moe["tokens"] * cfg.moe_router_topk * layers
    assert moe["assignments"] == s_moe["assignments"]
    # (the pairs touched follow who shares a round, which a slot's extra
    # round of rest changes)
    assert moe["tokens"] * layers <= moe["expert_pairs_touched"] \
        <= moe["assignments"]
    assert ahead["rounds_ahead"] > 0


# ---- (g) an admission's first token is read after the next dispatch ---------
def _first_in_its_step(seen):
    """The step's contract for an admission: the request's first token is
    in the events of the step that admitted it, and nothing of the request
    came before."""
    got = set()
    for ev in seen:
        toks = [rid for rid, _ in ev["tokens"]]
        for rid in ev["admitted"]:
            assert rid in toks and rid not in got, (rid, ev)
        got.update(toks)


def _sampling(kind, i):
    return GREEDY if kind == "greedy" else SamplingParams(
        temperature=0.9, top_k=(0, 20)[i % 2], top_p=(0.0, 0.8)[i % 2],
        seed=200 + i)


@pytest.mark.parametrize("kind", ["greedy", "sampled"])
def test_admissions_under_a_round_in_flight(engine, kind):
    """One request runs; three more are admitted by ONE step under its
    round in flight, two by another, one alone: every stream is that of the
    request run alone in the loop that never runs ahead, and every first
    sample was read behind a dispatch."""
    prompts = _prompts(7, seed=11)
    news = (24, 5, 9, 2, 7, 11, 3)
    kws = [dict(prompt_tokens=p, max_new_tokens=m, sampling=_sampling(kind, i),
                request_id=20 + i)
           for i, (p, m) in enumerate(zip(prompts, news))]
    eng = engine(max_batch=4, enable_prefix_caching=False)
    was = _steps(eng)
    in_flight = []
    streams, seen = _drive(
        eng, {0: kws[:1], 3: kws[1:4], 9: kws[4:6], 14: kws[6:]},
        act=lambda eng, k: in_flight.append(eng._round is not None))
    _first_in_its_step(seen)
    assert [len(ev["admitted"]) for ev in seen if ev["admitted"]] \
        == [1, 3, 2, 1]
    assert all(in_flight[k] for k in (3, 9, 14))
    with _serial(engine(enable_prefix_caching=False)) as alone:
        s_was = _steps(alone)
        for kw in kws:
            got, _ = _drive(alone, {0: [kw]})
            assert streams[kw["request_id"]] == got[kw["request_id"]]
        assert _steps(alone, s_was)["first_samples_ahead"] == 0
    st = _steps(eng, was)
    assert st["first_samples_ahead"] == st["admitted"] == 7
    assert st["prefill.sample"]["count"] == 7 and st["overrun_rows"] == 0
    assert eng.pool.blocks_in_use() == 0
    eng.pool.audit()


@pytest.mark.parametrize("how, overruns", [
    ("eod", 1), ("abort", 1), ("expire", 1), ("count", 0)])
def test_a_first_token_that_ends_its_request(engine, monkeypatch, how,
                                             overruns):
    """A request admitted under a round in flight whose first token is its
    last: on `eod_id` or stopped from outside between the dispatch and the
    fetch, the row the round ahead runs for it is an over-run; by count
    (`max_new_tokens` 1) the engine knows before the dispatch, and no row
    runs."""
    runner, late = _prompts(2, seed=21)
    eng = engine(enable_prefix_caching=False)   # serial, then ahead
    with _serial(eng):
        want, _ = _drive(eng, {0: [
            dict(prompt_tokens=runner, max_new_tokens=12, sampling=GREEDY,
                 request_id=0)], 1: [
            dict(prompt_tokens=late, max_new_tokens=6, sampling=GREEDY,
                 request_id=1)]})
    was = _steps(_as_new(eng))
    kw = dict(prompt_tokens=late, sampling=GREEDY, request_id=1,
              max_new_tokens=1 if how == "count" else 6,
              eod_id=want[1][0] if how == "eod" else None)
    read_first = eng._read_first
    rows_ahead = []

    def stopped_before_the_fetch(out, ahead=0):
        if eng._first:
            assert ahead == 1 and list(eng._first) == [1]
            rows_ahead.append(sorted(
                r.request_id for r in eng._round.rows.values()))
            if how == "abort":
                assert eng.abort_request(1) == "running"
            elif how == "expire":
                eng.requests[1].deadline_s = time.monotonic() - 1.0
                assert eng.expire_overdue() == [1]
        return read_first(out, ahead)

    def act(eng, k):
        if k == 3:
            assert eng._round is not None
            monkeypatch.setattr(eng, "_read_first", stopped_before_the_fetch)
    streams, seen = _drive(eng, {0: [dict(
        prompt_tokens=runner, max_new_tokens=12, sampling=GREEDY,
        request_id=0)], 3: [kw]}, act)
    _first_in_its_step(seen)
    # the admitted row rides in the round ahead unless it ends by count
    assert rows_ahead == [[0] if how == "count" else [0, 1]]
    assert streams[0] == want[0] and streams[1] == want[1][:1]
    assert eng.requests[1].finished
    st = _steps(eng, was)
    assert st["overrun_rows"] == overruns
    assert st["first_samples_ahead"] == st["admitted"] == 2
    assert any(1 in ev["finished"] for ev in seen)
    assert eng.pool.blocks_in_use() == 0 and not eng._first
    eng.pool.audit()


def test_a_first_fetch_that_raises_rolls_the_admission_back(engine,
                                                            monkeypatch):
    """As the `kv-quant-write` drill (tests/test_resilience.py), a step
    later: the fetch of the first of two first tokens raises. Both
    admissions are rolled back (blocks released, slots cleared, their rows
    of the round ahead dropped, the requests back at the head of the queue
    in their order), and the retry streams what a clean run streams."""
    prompts = _prompts(3, seed=31)
    kws = [dict(prompt_tokens=p, max_new_tokens=m, sampling=GREEDY,
                request_id=i) for i, (p, m) in enumerate(zip(prompts,
                                                             (14, 6, 8)))]
    arrivals = {0: kws[:1], 3: kws[1:]}
    eng = engine(enable_prefix_caching=False)   # clean, then with the fault
    clean, _ = _drive(eng, arrivals)
    was = _steps(_as_new(eng))
    get, armed, faults = jax.device_get, [], []

    def device_get(x):
        if armed and eng._first:
            armed.pop()
            raise RuntimeError("device lost")
        return get(x)

    monkeypatch.setattr(jax, "device_get", device_get)
    streams, k = {}, 0
    arrivals = dict(arrivals)
    while eng.has_work or arrivals:
        for kw in arrivals.pop(k, ()):
            eng.add_request(**kw)
        if k == 3:
            armed.append(True)
            used = eng.pool.blocks_in_use()
        try:
            ev = eng.step()
        except RuntimeError:
            faults.append(k)
            eng.pool.audit()                # rollback left no leak/skew
            assert eng.pool.blocks_in_use() == used
            assert [r and r.request_id for r in eng.slots] == [0, None, None]
            assert [r.request_id for r in eng.waiting] == [1, 2]
            assert [eng.requests[i].slot for i in (1, 2)] == [-1, -1]
            assert eng._round is None and not eng._first
            assert eng._first_tokens is None
            assert not eng.requests[1].generated
            k += 1
            continue
        for rid, tok in ev["tokens"]:
            streams.setdefault(rid, []).append(int(tok))
        k += 1
        assert k < 200
    assert faults == [3] and streams == clean
    st = _steps(eng, was)
    # the two rows of the round ahead, and the step's round was not read
    assert st["overrun_rows"] == 2
    assert st["admitted"] == 3 and st["admit_steps"] == 2
    assert eng.pool.blocks_in_use() == 0
    eng.pool.audit()


def test_an_admission_under_a_round_in_flight_compiles_nothing(tiny):
    """What perfbench/cells/serve_closed.py holds a run to: after ONE
    warm-up request alone (two prefill calls, four tokens), a run that
    admits under a round in flight, several requests in one step among
    them, compiles nothing and traces neither step again. (Shapes no other
    test of this process has: the jits' caches are the process's.)"""
    eng = _engine(tiny, max_batch=5, max_seq_len=88, prefill_chunk=8,
                  enable_prefix_caching=False)
    compiles, stop = _listen_for_compiles()
    try:
        _drive(eng, {0: [dict(
            prompt_tokens=(np.arange(eng.prefill_chunk + 8) % 97).astype(
                np.int32), max_new_tokens=4, sampling=GREEDY)]})
        warm = len(compiles)
        traces = (eng.decode_traces, eng.mq_traces)
        assert warm > 0 and traces == (1, 1)
        prompts = _prompts(6, seed=41)
        kws = [dict(prompt_tokens=p, max_new_tokens=m, sampling=GREEDY)
               for p, m in zip(prompts, (20, 6, 1, 9, 4, 12))]
        _, seen = _drive(eng, {0: kws[:1], 2: kws[1:4], 6: kws[4:]})
    finally:
        stop()
    assert len(compiles) == warm, compiles[warm:]
    assert (eng.decode_traces, eng.mq_traces) == traces
    st = _steps(eng)
    assert st["admitted"] == 7 and st["first_samples_ahead"] == 7
    assert [len(ev["admitted"]) for ev in seen if ev["admitted"]] \
        == [1, 3, 2]
