"""Telemetry spine tests (ISSUE 12): the metrics registry
(utils/metrics.py — histogram percentile accuracy vs numpy, Prometheus
text golden, disabled-path overhead pin), the request-lifecycle ring
tracer (trace/request_trace.py — every B has a matching E across the
full lifecycle including expire/preempt), the server's GET /metrics
(bucket-derived p99 consistent with the histogram estimate) and
GET /trace endpoints, and the disaggregated two-mesh merged-trace
smoke (+ the stats_snapshot include_dispatch satellite)."""

import asyncio
import dataclasses
import time
from collections import defaultdict

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from megatronapp_tpu.config.transformer_config import TransformerConfig
from megatronapp_tpu.inference.dynamic_engine import (
    DynamicInferenceEngine,
)
from megatronapp_tpu.inference.engine import SamplingParams
from megatronapp_tpu.models.gpt import init_gpt_params
from megatronapp_tpu.trace.request_trace import (
    DECODE_PID, PREFILL_PID, get_request_tracer,
)
from megatronapp_tpu.utils import metrics
from megatronapp_tpu.utils.metrics import Histogram


@pytest.fixture(autouse=True)
def _telemetry_isolation():
    """Every test starts and ends with telemetry off and the trace ring
    empty — the registry and tracer are process-global singletons."""
    metrics.disable()
    rt = get_request_tracer()
    rt.configure(enabled=False)
    rt.reset()
    yield
    metrics.disable()
    rt.configure(enabled=False)
    rt.reset()


def _gqa_cfg():
    return TransformerConfig(
        num_layers=2, hidden_size=64, num_attention_heads=4,
        num_query_groups=2, vocab_size=128, max_position_embeddings=64,
        compute_dtype=jnp.float32)


def _pair_records(recs):
    """Stack-pair B/E records by (pid, tid, name) — the same key the
    aggregation machinery uses. Returns (unmatched_B, orphan_E)."""
    stacks = defaultdict(list)
    orphan_e = []
    for r in recs:
        key = (r["pid"], r["tid"], r["name"])
        if r["ph"] == "B":
            stacks[key].append(r)
        elif r["ph"] == "E":
            if not stacks[key]:
                orphan_e.append(key)
            else:
                stacks[key].pop()
    unmatched = {k: len(v) for k, v in stacks.items() if v}
    return unmatched, orphan_e


# ---------------------------------------------------------------------------
class TestHistogram:
    """Log-bucket percentile estimation pinned against numpy: geometric
    interpolation inside a bucket bounds the relative error by one
    growth factor."""

    @pytest.mark.parametrize("dist", ["lognormal", "uniform", "bimodal"])
    def test_percentiles_match_numpy(self, dist):
        rng = np.random.default_rng(0)
        if dist == "lognormal":
            samples = rng.lognormal(3.0, 1.0, 20000)
        elif dist == "uniform":
            samples = rng.uniform(0.5, 200.0, 20000)
        else:
            # 40/60 split so no tested percentile falls in the empty
            # gap between the modes (where ANY estimator — numpy's
            # linear interpolation included — is arbitrary).
            samples = np.concatenate([rng.normal(5.0, 0.5, 8000),
                                      rng.normal(500.0, 20.0, 12000)])
            samples = np.clip(samples, 0.01, None)
        growth = 1.1
        h = Histogram(lo=1e-2, hi=1e5, growth=growth)
        for s in samples:
            h.observe(float(s))
        assert h.count == len(samples)
        for q in (50, 90, 99):
            est = h.percentile(q)
            true = float(np.percentile(samples, q))
            ratio = est / true
            assert 1 / growth <= ratio <= growth, (
                f"{dist} p{q}: est {est:.3f} vs numpy {true:.3f} "
                f"(ratio {ratio:.4f} outside one bucket width)")

    def test_empty_overflow_and_stats(self):
        h = Histogram(lo=1.0, hi=100.0, growth=10.0)
        assert h.percentile(99) == 0.0        # empty
        for v in (0.5, 5.0, 50.0, 5000.0):    # incl. under- and overflow
            h.observe(v)
        assert h.count == 4
        assert h.counts[-1] == 1              # 5000 overflowed
        st = h.stats()
        assert st["count"] == 4 and st["sum"] == pytest.approx(5055.5)
        # p99 lands in the overflow bucket → reported at the hi edge.
        assert h.percentile(99) >= 100.0

    def test_ewma(self):
        from megatronapp_tpu.utils.metrics import Ewma
        e = Ewma(alpha=0.5)
        e.observe(10.0)
        assert e.value == 10.0
        e.observe(20.0)
        assert e.value == pytest.approx(15.0)


# ---------------------------------------------------------------------------
class TestPrometheusRender:
    def test_golden_text(self):
        """Exact text-format golden for a tiny registry: counter, gauge,
        EWMA-as-gauge, and a histogram with cumulative le buckets +
        _sum/_count."""
        reg = metrics.enable()
        metrics.inc("requests_total", 3)
        metrics.set_gauge("queue_depth", 7)
        metrics.observe_ewma("chunk_s", 0.5)
        h = reg.histogram("lat_ms", lo=1.0, hi=100.0, growth=10.0)
        for v in (0.5, 5.0, 50.0, 5000.0):
            h.observe(v)
        text = metrics.render_prometheus()
        assert text == (
            "# TYPE requests_total counter\n"
            "requests_total 3\n"
            "# TYPE queue_depth gauge\n"
            "queue_depth 7\n"
            "# TYPE chunk_s_ewma gauge\n"
            "chunk_s_ewma 0.5\n"
            "# TYPE lat_ms histogram\n"
            'lat_ms_bucket{le="1"} 1\n'
            'lat_ms_bucket{le="10"} 2\n'
            'lat_ms_bucket{le="100"} 3\n'
            'lat_ms_bucket{le="+Inf"} 4\n'
            "lat_ms_sum 5055.5\n"
            "lat_ms_count 4\n")

    def test_name_sanitization(self):
        metrics.enable()
        metrics.inc("weird-name.with:colon")
        text = metrics.render_prometheus()
        assert "weird_name_with:colon 1" in text

    def test_disabled_render_is_comment(self):
        assert metrics.render_prometheus().startswith("#")


# ---------------------------------------------------------------------------
class TestDisabledPath:
    def test_disabled_overhead_pinned(self):
        """Acceptance: the disabled path is ONE dict-truthiness check —
        2e6 site calls through the disabled registry finish in well
        under a second of budget even on the noisy 2-core CI container
        (the chaos-registry bound; ~1.2 µs/call would be 2.4 s)."""
        assert not metrics.enabled()
        t0 = time.perf_counter()
        for _ in range(1_000_000):
            metrics.inc("site_a")
            metrics.observe("site_b", 1.0)
        dt = time.perf_counter() - t0
        assert dt < 2.5, f"disabled metrics path too slow: {dt:.2f}s/2e6"

    def test_disabled_calls_are_noops(self):
        metrics.inc("c", 5)
        metrics.observe("h", 1.0)
        metrics.set_gauge("g", 2.0)
        assert metrics.counter_value("c") == 0.0
        assert metrics.snapshot() == {"enabled": False}
        # Enable → the earlier calls left no trace.
        metrics.enable()
        assert metrics.counter_value("c") == 0.0

    def test_disable_drops_state(self):
        metrics.enable()
        metrics.inc("c", 5)
        metrics.disable()
        metrics.enable()
        assert metrics.counter_value("c") == 0.0


# ---------------------------------------------------------------------------
class TestRequestLifecycleTrace:
    def test_full_lifecycle_every_b_has_matching_e(self):
        """A serving run that exercises retire AND preempt AND expire:
        every B record pairs with an E on the same (pid, tid, name)
        timeline, and the lifecycle stage names all appear."""
        rt = get_request_tracer()
        rt.configure(enabled=True)
        metrics.enable()
        cfg = _gqa_cfg()
        params, _ = init_gpt_params(jax.random.PRNGKey(3), cfg)
        # num_blocks=5 < demand → decode-time pool pressure → preempt.
        eng = DynamicInferenceEngine(
            params, cfg, max_batch=2, max_seq_len=48,
            prefill_buckets=(16,), paged=True, block_size=8,
            num_blocks=5)
        rng = np.random.default_rng(0)
        prompts = [rng.integers(0, 128, n).astype(np.int32)
                   for n in (9, 9, 5)]
        rids = [
            eng.add_request(prompts[0], 12, SamplingParams(greedy=True),
                            priority=0),
            eng.add_request(prompts[1], 12, SamplingParams(greedy=True),
                            priority=1),
            # Mid-flight deadline → the expiry sweep aborts it.
            eng.add_request(prompts[2], 8, SamplingParams(greedy=True),
                            deadline_s=time.monotonic() + 0.2),
        ]
        res = eng.run_to_completion()
        assert len(res) == 3
        assert eng.pool.stats["preemptions"] >= 1
        recs = rt.dump()
        unmatched, orphan_e = _pair_records(recs)
        assert not unmatched, f"unmatched B spans: {unmatched}"
        assert not orphan_e, f"orphan E spans: {orphan_e}"
        names = {r["name"] for r in recs}
        assert {"admit", "request", "queue-wait", "prefill", "decode",
                "decode-step", "retire", "preempt", "expire"} <= names
        # Counters and spans agree: the drilled preemption was counted.
        assert metrics.counter_value("paged_preemptions") >= 1
        assert metrics.counter_value("serving_deadline_expired") >= 1
        # TTFT is observed EXACTLY once per request that got a first
        # token: a preempted request's resume is not re-observed, and a
        # request that expired while still queued never produced one.
        got_first = sum(1 for rid, p in zip(rids, prompts)
                        if len(res[rid]) > len(p))
        ttft = metrics.registry().histograms["serving_ttft_ms"]
        assert ttft.count == got_first
        # Chrome render through the aggregate machinery works.
        trace = rt.chrome_trace()
        assert any(e["ph"] == "X" and e["name"] == "request"
                   for e in trace["traceEvents"])

    def test_abort_closes_spans(self):
        rt = get_request_tracer()
        rt.configure(enabled=True)
        cfg = _gqa_cfg()
        params, _ = init_gpt_params(jax.random.PRNGKey(3), cfg)
        eng = DynamicInferenceEngine(
            params, cfg, max_batch=1, max_seq_len=48,
            prefill_buckets=(16,), paged=True, block_size=8)
        rid1 = eng.add_request(np.arange(5, dtype=np.int32), 8,
                               SamplingParams(greedy=True))
        rid2 = eng.add_request(np.arange(7, dtype=np.int32), 8,
                               SamplingParams(greedy=True))
        eng.step()                      # rid1 running, rid2 waiting
        assert eng.abort_request(rid2) == "waiting"   # queue-wait open
        assert eng.abort_request(rid1) == "running"
        eng.step()                      # retires rid1
        eng.pop_request(rid1), eng.pop_request(rid2)
        unmatched, orphan_e = _pair_records(rt.dump())
        assert not unmatched and not orphan_e
        names = {r["name"] for r in rt.dump()}
        assert "abort" in names

    def test_ring_is_bounded(self):
        rt = get_request_tracer()
        rt.configure(enabled=True, capacity=64)
        for i in range(1000):
            rt.instant("tick", i)
        assert len(rt.dump()) == 64
        rt.configure(enabled=True, capacity=16384)

    def test_disabled_emits_nothing(self):
        rt = get_request_tracer()
        assert not rt.enabled
        rt.begin("x", 0)
        rt.end("x", 0)
        rt.instant("y", 0)
        rt.finish(0, "retire")
        assert rt.dump() == []


# ---------------------------------------------------------------------------
def _pressure_engine(**kw):
    """Two slots over five 8-token blocks: decode-time pool pressure
    preempts the lower-priority request once (as the lifecycle test)."""
    cfg = _gqa_cfg()
    params, _ = init_gpt_params(jax.random.PRNGKey(3), cfg)
    eng = DynamicInferenceEngine(
        params, cfg, max_batch=2, max_seq_len=48, prefill_buckets=(16,),
        paged=True, block_size=8, num_blocks=5, **kw)
    rng = np.random.default_rng(0)
    for i, n in enumerate((9, 9)):
        eng.add_request(rng.integers(0, 128, n).astype(np.int32), 12,
                        SamplingParams(greedy=True), priority=i)
    return eng


def _inside(inner, outer):
    return outer[1] <= inner[1] and \
        inner[1] + inner[2] <= outer[1] + outer[2]


class TestStepSpansAndCounters:
    """ISSUE 26: the engine names its own phases. One ``span()`` site per
    phase feeds the profiler (``mta.*`` on the host plane of the device
    trace's own file), the always-on ``stats_snapshot()["steps"]`` and the
    request ring."""

    @pytest.mark.parametrize("spec", [None, "ngram"])
    def test_profiler_sees_nested_program_spans(self, tmp_path, spec):
        from perfbench import common, trace_reduce, xplane_stats
        cfg = _gqa_cfg()
        params, _ = init_gpt_params(jax.random.PRNGKey(3), cfg)
        eng = DynamicInferenceEngine(
            params, cfg, max_batch=2, max_seq_len=48, prefill_buckets=(16,),
            paged=True, block_size=8, spec_method=spec, spec_k=2)
        prompt = np.tile(np.arange(4, dtype=np.int32), 3)
        eng.add_request(prompt, 6, SamplingParams(greedy=True))
        eng.step()                                  # compiles, untraced
        # The first traced step admits: a round is in flight by then on
        # the plain path (ISSUE 47).
        eng.add_request(np.arange(20, 29, dtype=np.int32), 4,
                        SamplingParams(greedy=True))
        admitted = []
        common.start_trace(str(tmp_path))
        try:
            while eng.has_work:
                admitted.append(len(eng.step()["admitted"]))
        finally:
            jax.profiler.stop_trace()
        spans = trace_reduce.host_spans(
            trace_reduce.load_xplane(str(tmp_path)), prefix="mta.")
        by_name = defaultdict(list)
        for ev in spans:
            by_name[ev[0]].append(ev)
        steps = by_name["mta.engine.step"]
        rounds = by_name["mta.engine.decode_round"]
        waits = by_name["mta.engine.decode.wait"]
        assert steps and rounds and len(waits) == len(rounds)
        # Nested by time on the stepper's one thread: step > round > wait.
        for r in rounds:
            assert sum(_inside(r, s) for s in steps) == 1
        for w in waits:
            assert sum(_inside(w, r) for r in rounds) == 1
        assert len(by_name["mta.engine.decode.record"]) == len(rounds)
        # A plain round is staged inside the span of the round before it
        # (ISSUE 47: it is dispatched before that one's tokens are read),
        # so the window's last round stages nothing, and its first was
        # staged before the trace began.
        stages = by_name["mta.engine.decode.stage"]
        assert len(stages) == len(rounds) - (spec is None)
        for g in stages:
            assert sum(_inside(g, r) for r in rounds) == 1
        assert by_name["mta.engine.retire"]
        # No span of the program is named outside the interface.
        assert {n.split(".")[1] for n in by_name} <= {"engine"}
        # ISSUE 50: a stage is its three children (the sampler's part, the
        # host's arrays, the step's dispatch), one after the other. A
        # speculative round that proposed nothing falls back to a plain
        # one read at once, whose sampler waits for the fetch.
        for child in ("sample", "put", "dispatch"):
            kids = by_name["mta.engine.decode.stage." + child]
            assert len(kids) == len(stages) or (
                spec and child == "sample" and 1 <= len(kids) < len(stages))
            for kid in kids:
                assert sum(_inside(kid, g) for g in stages) == 1
        # The first sample of the admitted request: after the prompt's
        # one call; where a proposer reads the host's tokens, inside its
        # prefill, else (ISSUE 53) inside the step's round, behind the stage
        # of the round ahead and before the wait for the round in flight.
        (sample,), (prefill,) = (by_name["mta.engine.prefill.sample"],
                                 by_name["mta.engine.prefill"])
        (call,) = by_name["mta.engine.prefill_call"]
        assert _inside(call, prefill)
        assert call[1] + call[2] <= sample[1]
        if spec:
            assert _inside(sample, prefill)
        else:
            assert prefill[1] + prefill[2] <= sample[1]
            (rnd,) = [r for r in rounds if _inside(sample, r)]
            (stage,) = [g for g in stages if _inside(g, rnd)]
            (wait,) = [w for w in waits if _inside(w, rnd)]
            assert stage[1] + stage[2] <= sample[1]
            assert sample[1] + sample[2] <= wait[1]
        # A step carries no attribute of its own: what it admitted is the
        # prefill spans inside it, what it read the round inside it (what
        # perfbench/admission_spans.py counts).
        said = defaultdict(list)
        for e in xplane_stats.load(str(tmp_path))["spans"]:
            said[e[0]].append(e)
        steps, rounds = said["mta.engine.step"], said["mta.engine.decode_round"]
        assert len(steps) == len(rounds) == len(admitted)   # a round a step
        assert admitted[0] == 1 and sum(admitted) == 1
        for s, want, rnd in zip(steps, admitted, rounds):
            assert not s[3] and _inside(rnd, s)
            assert sum(_inside(p, s)
                       for p in said["mta.engine.prefill"]) == want
            assert int(rnd[3].get("ahead", 0)) == int(spec is None)
        # The round in flight at the admission had the first request alone.
        assert [int(r[3]["batch"]) for r in rounds[:2]] \
            == ([1, 2] if spec is None else [2, 2])
        (call,), (sample,) = (said["mta.engine.prefill_call"],
                              said["mta.engine.prefill.sample"])
        assert {k: int(v) for k, v in call[3].items()} \
            == {"tokens": 9, "width": eng.prefill_chunk}
        assert int(sample[3]["rid"]) == 1
        assert int(sample[3]["ahead"]) == int(spec is None)

    def test_paged_walk_counters(self, monkeypatch):
        """ISSUE 29: a plain decode round names the blocks its paged
        kernel walks (`kv_blocks` beside `kv_tokens`: the round appends a
        row and reads it), and `stats_snapshot()["paged"]` sums them
        against what the running slots' table rows could name."""
        eng = _pressure_engine()
        bs, table = eng.pool.block_size, eng.pool.page_table.shape[1]
        spans, walked = [], []
        span, new_round = eng._span, eng._new_round

        def spy(name, *a, **kw):
            if name == "engine.decode_round":
                spans.append(kw)
            return span(name, *a, **kw)

        def walk(rows, after=None):
            # The lengths a round's step sees. Since ISSUE 47 the round is
            # staged while the one before it is unread, whose rows count,
            # and its span opens when its own tokens are read.
            walked.append(eng._lengths_after(after)[list(rows)].tolist())
            return new_round(rows, after)

        monkeypatch.setattr(eng, "_span", spy)
        monkeypatch.setattr(eng, "_new_round", walk)
        while eng.has_work:
            eng.step()
        assert spans and len(spans) == len(walked)
        rounds = list(zip(spans, walked))
        for kw, lens in rounds:
            assert kw["batch"] == len(lens)
            assert kw["kv_tokens"] == sum(lens)
            assert kw["kv_blocks"] == sum(-(-(n + 1) // bs) for n in lens)
        paged = eng.stats_snapshot()["paged"]
        assert paged == {
            "decode_rounds": len(rounds),
            "blocks_live": sum(kw["kv_blocks"] for kw, _ in rounds),
            "blocks_table": sum(kw["batch"] for kw, _ in rounds) * table}
        assert 0 < paged["blocks_live"] <= paged["blocks_table"]

    @pytest.mark.parametrize("width", [None, 8, 5])
    def test_prefill_call_counters(self, monkeypatch, width):
        """ISSUE 35: every `mta.engine.prefill_call` names the call's
        `width` beside its real `tokens`, and `stats_snapshot()["prefill"]`
        counts calls, tokens, width and `fill_share` = tokens / (calls x
        width) exactly, for prompts of 3, 8, 13 and 21 tokens: at the
        engine's own choice (32 on a CPU), at 8 and at 5."""
        cfg = _gqa_cfg()
        params, _ = init_gpt_params(jax.random.PRNGKey(3), cfg)
        kw = {} if width is None else {"prefill_chunk": width}
        eng = DynamicInferenceEngine(
            params, cfg, max_batch=4, max_seq_len=48, paged=True,
            block_size=8, enable_prefix_caching=False, **kw)
        width = width or 32
        assert eng.prefill_chunk == width
        assert eng.stats_snapshot()["prefill"] == {
            "calls": 0, "tokens": 0, "width": width, "fill_share": 0.0}
        calls = []
        span = eng._span

        def spy(name, *a, **kw):
            if name == "engine.prefill_call":
                calls.append(kw)
            return span(name, *a, **kw)

        monkeypatch.setattr(eng, "_span", spy)
        prompts = (3, 8, 13, 21)
        rng = np.random.default_rng(1)
        for n in prompts:
            eng.add_request(rng.integers(0, 128, n).astype(np.int32), 2,
                            SamplingParams(greedy=True))
        eng.run_to_completion()
        want = sum(-(-n // width) for n in prompts)
        assert len(calls) == want
        assert all(c["width"] == width and 1 <= c["tokens"] <= width
                   for c in calls)
        assert sum(c["tokens"] for c in calls) == sum(prompts)
        assert eng.stats_snapshot()["prefill"] == {
            "calls": want, "tokens": 45, "width": width,
            "fill_share": round(45 / (want * width), 4)}

    def test_step_counters(self):
        eng = _pressure_engine()
        calls, admitting, prompt_tokens = 0, set(), 0
        while eng.has_work:
            waiting = {r.request_id: len(r.tokens) for r in eng.waiting}
            ev = eng.step()
            calls += 1
            if ev["admitted"]:
                admitting.add(calls)
            prompt_tokens += sum(waiting[rid] for rid in ev["admitted"])
        st = eng.stats_snapshot()["steps"]
        pool = eng.pool.stats
        assert pool["preemptions"] == 1
        assert st["step"]["count"] == calls
        # Two requests, one of them admitted again after its preemption.
        assert st["queue_wait"]["count"] == 3
        assert st["prefill"]["count"] == 3
        assert st["queue_wait"]["max_s"] <= st["queue_wait"]["total_s"]
        # Tokens the model ran + tokens the prefix cache served = prompt
        # tokens (a resumed request's prompt includes what it generated);
        # the pool and spec_stats count them, `steps` does not again.
        assert pool["prefill_tokens"] + pool["prefix_hit_tokens"] \
            == prompt_tokens
        assert eng.spec_stats["emitted_tokens"] + st["prefill"]["count"] \
            == 24
        assert set(st) == set(eng.step_stats.PHASES) | {
            "slowest", "rounds_ahead", "overrun_rows", "admit_steps",
            "admitted", "first_samples_ahead"}
        total = {p: st[p]["total_s"] for p in st
                 if isinstance(st[p], dict)}
        assert total["admit"] + total["capacity"] + total["decode_round"] \
            + total["retire"] <= total["step"]
        # (ISSUE 53: the first samples are read inside the step's round,
        # behind its dispatches)
        assert total["decode.stage"] + total["prefill.sample"] \
            + total["decode.wait"] + total["decode.record"] \
            <= total["decode_round"]
        assert total["prefill_call"] <= total["prefill"] <= total["admit"]
        assert st["admit_steps"] == len(admitting)
        assert st["admitted"] == st["prefill.sample"]["count"] == 3
        assert st["first_samples_ahead"] == 3
        stage = ("decode.stage.sample", "decode.stage.put",
                 "decode.stage.dispatch")
        assert sum(total[p] for p in stage) <= total["decode.stage"]
        assert {st[p]["count"] for p in stage[1:]} \
            == {st["decode.stage"]["count"]}
        # Where no round is in flight (the first, and the one after the
        # preemption) the sampler waits for the fetch.
        assert st["decode.stage.sample"]["count"] == st["rounds_ahead"] \
            < st["decode.stage"]["count"]
        for row in (st[p] for p in total):
            assert 0 <= row["max_s"] <= row["total_s"] or row["count"] == 0
        # The flight recorder: pure decode rounds only, longest first.
        slowest = st["slowest"]
        assert 0 < len(slowest) <= 8
        assert not {r["step"] for r in slowest} & admitting
        walls = [r["wall_s"] for r in slowest]
        assert walls == sorted(walls, reverse=True)
        for r in slowest:
            assert r["batch"] >= 1 and "decode.wait" in r["phases"]
            assert sum(v for p, v in r["phases"].items()
                       if p in ("capacity", "decode_round", "retire")) \
                <= r["phases"]["step"] == r["wall_s"]
        import json
        json.dumps(st)                      # GET /stats serves it

    def test_flight_recorder_keeps_the_eight_longest(self):
        from megatronapp_tpu.inference.dynamic_engine import StepStats
        st = StepStats()
        before = st.totals()
        for i, wall in enumerate([5, 1, 9, 3, 7, 2, 8, 6, 4, 10, 0.5]):
            st.add("step", wall)
            st.note_round(float(wall), batch=2, before=before)
            before = st.totals()
        got = st.snapshot()["slowest"]
        assert [r["wall_s"] for r in got] == [10, 9, 8, 7, 6, 5, 4, 3]
        assert [r["step"] for r in got] == [10, 3, 7, 5, 8, 1, 9, 4]
        assert all(r["phases"] == {"step": r["wall_s"]} for r in got)

    @pytest.mark.parametrize("ring", [False, True])
    def test_failing_step_closes_every_span_and_counts_once(self, ring):
        rt = get_request_tracer()
        rt.configure(enabled=ring)
        eng = _pressure_engine()
        eng.step()

        def boom(logits, tail=None):
            raise RuntimeError("device lost")

        eng._sample_all = boom
        before = eng.stats_snapshot()["steps"]
        with pytest.raises(RuntimeError, match="device lost"):
            eng.step()
        after = eng.stats_snapshot()["steps"]
        for phase in ("step", "decode_round", "decode.stage",
                      "decode.stage.sample", "decode.stage.put",
                      "decode.stage.dispatch", "decode.wait"):
            assert after[phase]["count"] == before[phase]["count"] + 1
        assert after["decode.record"] == before["decode.record"]
        assert after["slowest"] == before["slowest"]
        assert None not in rt._open             # no step-level span open
        unmatched, orphan_e = _pair_records(
            [r for r in rt.dump() if r["tid"] == 0])
        assert not unmatched and not orphan_e
        if ring:
            last = [r for r in rt.dump() if r["name"] == "decode-step"][-1]
            assert last["ph"] == "E" and last["args"] == {"error": True}

    @pytest.mark.parametrize("spec", [None, "ngram"])
    def test_the_spans_inside_a_step_say_what_it_admitted(self, monkeypatch,
                                                         spec):
        """ISSUE 50: a step opens one `prefill` span and one
        `prefill.sample` an admitted request and at most one
        `decode_round`, through a preemption and the second admission it
        brings (what perfbench/admission_spans.py counts, the step span
        itself carrying no attribute); `admit_steps` and `admitted` count
        the same."""
        eng = _pressure_engine(**(
            {"spec_method": spec, "spec_k": 2} if spec else {}))
        opened = []
        span = eng._span

        def spy(name, *a, **kw):
            opened.append((name, span(name, *a, **kw)))
            return opened[-1][1]

        monkeypatch.setattr(eng, "_span", spy)
        admit_steps = admitted = 0
        while eng.has_work:
            del opened[:]
            ev = eng.step()
            by_name = defaultdict(list)
            for name, sp in opened:
                by_name[name].append(sp)
            (step,) = by_name["engine.step"]
            assert not step.attrs and not step.late
            for name in ("engine.prefill", "engine.prefill.sample"):
                assert [sp.rid for sp in by_name[name]] == ev["admitted"]
            assert len(by_name["engine.decode_round"]) == bool(ev["tokens"])
            admit_steps += bool(ev["admitted"])
            admitted += len(ev["admitted"])
        st = eng.stats_snapshot()["steps"]
        assert (st["admit_steps"], st["admitted"]) \
            == (admit_steps, admitted) == (2, 3)
        assert st["rounds_ahead"] == (0 if spec else
                                      st["decode.stage.sample"]["count"])

    @pytest.mark.parametrize("site, closed", [
        ("_sample", ("step", "admit", "prefill", "prefill_call")),
        ("device_get", ("step", "admit", "decode_round", "prefill.sample")),
        ("_decode", ("step", "decode_round", "decode.stage",
                     "decode.stage.put", "decode.stage.dispatch"))])
    def test_a_failure_inside_a_new_span_closes_it(self, monkeypatch, site,
                                                   closed):
        """ISSUE 50: the first sample and the stage's children close and
        count once when the call inside them raises; the counters count
        nothing of a step that did not finish. (ISSUE 53: the first
        sample's dispatch lies in the prefill, its span is the fetch,
        inside the step's round.)"""
        rt = get_request_tracer()
        rt.configure(enabled=True)
        eng = _pressure_engine()
        if site == "_decode":
            eng.step()                      # admits both; one round ahead
            eng._round = None               # so that the next one stages

        def boom(*a, **kw):
            raise RuntimeError("device lost")

        monkeypatch.setattr(*((jax, site) if site == "device_get"
                              else (eng, site)), boom)
        before = eng.stats_snapshot()["steps"]
        with pytest.raises(RuntimeError, match="device lost"):
            eng.step()
        after = eng.stats_snapshot()["steps"]
        for phase in closed:
            assert after[phase]["count"] == before[phase]["count"] + 1
        assert after["admit_steps"] == before["admit_steps"]
        assert after["admitted"] == before["admitted"]
        assert None not in rt._open
        unmatched, orphan_e = _pair_records(
            [r for r in rt.dump() if r["tid"] == 0])
        assert not unmatched and not orphan_e

    def test_queue_wait_is_one_measurement(self):
        """/metrics' serving_queue_wait_ms and /stats' steps.queue_wait
        come from the same clock reading."""
        metrics.enable()
        eng = _pressure_engine()
        eng.run_to_completion()
        st = eng.stats_snapshot()["steps"]["queue_wait"]
        h = metrics.registry().histograms["serving_queue_wait_ms"]
        assert h.count == st["count"] == 3
        assert h.sum == pytest.approx(st["total_s"] * 1e3, rel=1e-9)

    def test_driver_counts_deliveries(self):
        from megatronapp_tpu.inference.server import DynamicBatchingDriver
        cfg = _gqa_cfg()
        params, _ = init_gpt_params(jax.random.PRNGKey(3), cfg)
        eng = DynamicInferenceEngine(
            params, cfg, max_batch=2, max_seq_len=48, prefill_buckets=(16,),
            paged=True, block_size=8)
        driver = DynamicBatchingDriver(eng)
        got = []
        _, done = driver.submit(np.arange(5, dtype=np.int32), 4,
                                SamplingParams(greedy=True),
                                token_cb=lambda rid, tok: got.append(tok))
        assert done.wait(timeout=120)
        deliver = driver.stats()["deliver"]
        steps = eng.stats_snapshot()["steps"]["step"]["count"]
        assert len(got) == 4 and 1 <= deliver["count"] <= steps
        assert 0 < deliver["max_s"] <= deliver["total_s"]


# ---------------------------------------------------------------------------
class TestServerEndpoints:
    def _server(self):
        from megatronapp_tpu.data.tokenizers import NullTokenizer
        from megatronapp_tpu.inference.server import TextGenerationServer
        cfg = _gqa_cfg()
        params, _ = init_gpt_params(jax.random.PRNGKey(3), cfg)
        eng = DynamicInferenceEngine(
            params, cfg, tokenizer=NullTokenizer(128), max_batch=2,
            max_seq_len=48, prefill_buckets=(16,), paged=True,
            block_size=8)
        return TextGenerationServer(eng)

    @staticmethod
    def _parse_buckets(text, name):
        """Parse `name_bucket{le=...}` cumulative counts from the
        exposition text → ([le_bounds], [cumulative]), +Inf last."""
        bounds, cums = [], []
        for line in text.splitlines():
            if line.startswith(f'{name}_bucket{{le="'):
                le = line.split('le="')[1].split('"')[0]
                bounds.append(float("inf") if le == "+Inf" else float(le))
                cums.append(int(line.rsplit(" ", 1)[1]))
        return bounds, cums

    def test_stats_compiles_nothing(self):
        """What GET /stats returns, on a warmed paged engine: the decode
        step's launch counts come off its jaxpr, so no backend compile
        fires, and a second call serves the cached dict."""
        srv = self._server()
        eng = srv.engine
        eng.add_request(np.arange(1, 6, dtype=np.int32), 3,
                        SamplingParams(greedy=True))
        eng.run_to_completion()
        compiles = []

        def on_duration(event, secs, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                compiles.append(secs)

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        try:
            first = srv.stats_snapshot()
            second = srv.stats_snapshot()
        finally:
            jax.monitoring.unregister_event_duration_listener(on_duration)
        assert not compiles
        disp = first["decode_dispatch"]
        assert disp["kernels"] > 0
        assert disp["expert_stack_slices"] == 0     # a dense model
        assert disp["scatters"] == 0        # its pages are appended by a kernel
        assert "compiled" not in disp
        assert second["decode_dispatch"] is disp

    @pytest.mark.parametrize("kind", ["dense", "latent", "heads-in-rows"])
    def test_stats_name_the_walks_page_copies(self, kind):
        """ISSUE 46: `decode_dispatch` says what a step of each paged walk
        copies, by kernel name, from the traced step's shapes: the copies
        a step (pages x pools), one copy's bytes a pool, and how many of
        them the kernel starts itself (none at these widths: a page of 16
        or 8 columns is no whole tiles; ISSUE 56: all of them at two
        key/value heads of 128, whose page goes in as [8 x 2, 128] rows)."""
        kw = dict(multi_latent_attention=True, kv_lora_rank=32,
                  qk_head_dim=16, qk_pos_emb_head_dim=8,
                  v_head_dim=16) if kind == "latent" else {}
        if kind == "heads-in-rows":
            kw = dict(kv_channels=128)
        cfg = dataclasses.replace(_gqa_cfg(), **kw)
        params, _ = init_gpt_params(jax.random.PRNGKey(3), cfg)
        eng = DynamicInferenceEngine(
            params, cfg, max_batch=2, max_seq_len=48, prefill_buckets=(16,),
            paged=True, block_size=8)
        disp = eng.stats_snapshot(include_dispatch=True)["decode_dispatch"]
        name = "paged_decode_latent" if kind == "latent" else "paged_decode"
        rows = ([32, 8] if kind == "latent"
                else [cfg.num_query_groups * cfg.head_dim] * 2)
        assert disp["page_copies_step"] == {name: 48 // 8 * 2}
        assert disp["page_copy_bytes"] == {name: [8 * 4 * n for n in rows]}
        assert disp["page_copies_kernel"] == {
            name: 48 // 8 * 2 if kind == "heads-in-rows" else 0}

    def test_stats_endpoint_serves_step_counters(self):
        """GET /stats: the engine's `steps` and the driver's deliveries,
        with nothing switched on."""
        srv = self._server()

        async def run():
            from aiohttp.test_utils import TestClient
            from aiohttp.test_utils import TestServer as ATestServer
            client = TestClient(ATestServer(srv.build_app()))
            await client.start_server()
            resp = await client.put("/api", json={
                "prompts": ["1 2 3"], "tokens_to_generate": 4,
                "greedy": True})
            assert resp.status == 200
            stats = await (await client.get("/stats")).json()
            health = await (await client.get("/healthz")).json()
            await client.close()
            return stats, health

        stats, health = asyncio.run(run())
        steps = stats["steps"]
        assert steps["step"]["count"] == 3      # the first admits and decodes
        assert steps["queue_wait"]["count"] == 1
        assert steps["decode_round"]["count"] == 3
        # ISSUE 50: what a step admitted, and the new phases.
        assert (steps["admit_steps"], steps["admitted"]) == (1, 1)
        assert steps["prefill.sample"]["count"] == 1
        assert steps["decode.stage.sample"]["count"] \
            == steps["rounds_ahead"] == 2
        assert steps["decode.stage.put"]["count"] \
            == steps["decode.stage.dispatch"]["count"] \
            == steps["decode.stage"]["count"] == 3
        assert stats["pool"]["prefill_tokens"] == 3
        assert stats["prefill"] == {"calls": 1, "tokens": 3, "width": 32,
                                    "fill_share": round(3 / 32, 4)}
        assert stats["driver_deliver"]["count"] >= 1
        assert health["stepper"]["deliver"]["count"] \
            == stats["driver_deliver"]["count"]

    def test_metrics_endpoint_and_p99_consistency(self):
        """GET /metrics serves Prometheus text whose token-interval
        buckets are consistent with the histogram's own p99 estimate:
        the estimate falls inside the bucket the exported cumulative
        counts put the 99th percentile in (acceptance criterion)."""
        metrics.enable()
        srv = self._server()

        async def run():
            from aiohttp.test_utils import TestClient
            from aiohttp.test_utils import TestServer as ATestServer
            client = TestClient(ATestServer(srv.build_app()))
            await client.start_server()
            resp = await client.put("/api", json={
                "prompts": ["1 2 3", "4 5"], "tokens_to_generate": 8,
                "greedy": True})
            assert resp.status == 200
            resp = await client.get("/metrics")
            assert resp.status == 200
            assert "text/plain" in resp.headers["Content-Type"]
            text = await resp.text()
            await client.close()
            return text

        text = asyncio.run(run())
        assert "# TYPE serving_requests_admitted counter" in text
        assert "serving_requests_admitted 2" in text
        assert "# TYPE decode_interval_ms histogram" in text
        assert "serving_active_slots" in text       # live gauge export
        bounds, cums = self._parse_buckets(text, "decode_interval_ms")
        assert bounds and bounds[-1] == float("inf")
        total = cums[-1]
        assert total > 0
        h = metrics.registry().histograms["decode_interval_ms"]
        p99 = h.percentile(99)
        # The bucket that first covers rank 0.99*total must contain the
        # histogram's own p99 estimate.
        rank = 0.99 * total
        idx = next(i for i, c in enumerate(cums) if c >= rank)
        upper = bounds[idx]
        lower = bounds[idx - 1] if idx > 0 else 0.0
        assert lower <= p99 <= (upper if upper != float("inf")
                                else p99 + 1), (
            f"p99 estimate {p99} outside exported bucket "
            f"({lower}, {upper}]")

    def test_metrics_endpoint_disabled_registry(self):
        srv = self._server()

        async def run():
            from aiohttp.test_utils import TestClient
            from aiohttp.test_utils import TestServer as ATestServer
            client = TestClient(ATestServer(srv.build_app()))
            await client.start_server()
            resp = await client.get("/metrics")
            text = await resp.text()
            status = resp.status
            await client.close()
            return status, text

        status, text = asyncio.run(run())
        assert status == 200                 # stable scrape target
        assert text.startswith("#")

    def test_trace_endpoint(self):
        rt = get_request_tracer()
        rt.configure(enabled=True)
        srv = self._server()

        async def run():
            from aiohttp.test_utils import TestClient
            from aiohttp.test_utils import TestServer as ATestServer
            client = TestClient(ATestServer(srv.build_app()))
            await client.start_server()
            resp = await client.put("/api", json={
                "prompts": ["1 2 3"], "tokens_to_generate": 4,
                "greedy": True})
            assert resp.status == 200
            resp = await client.get("/trace")
            assert resp.status == 200
            trace = await resp.json()
            await client.close()
            return trace

        trace = asyncio.run(run())
        names = {e["name"] for e in trace["traceEvents"]}
        assert {"request", "prefill", "decode", "retire"} <= names

    def test_trace_endpoint_404_when_disabled(self):
        srv = self._server()

        async def run():
            from aiohttp.test_utils import TestClient
            from aiohttp.test_utils import TestServer as ATestServer
            client = TestClient(ATestServer(srv.build_app()))
            await client.start_server()
            resp = await client.get("/trace")
            status = resp.status
            await client.close()
            return status

        assert asyncio.run(run()) == 404


# ---------------------------------------------------------------------------
class TestDisaggTelemetry:
    def test_two_mesh_merged_trace_and_slo_percentiles(self, devices8):
        """Acceptance: a full disagg request lifecycle produces ONE
        merged Chrome trace — prefill-mesh and decode-mesh rows, paired
        spans for admit/prefill/handoff/adopt/decode/retire — and the
        SLO section reports histogram-backed token-interval + TTFT
        percentiles. Also the include_dispatch satellite: the facade
        accepts the kwarg and reports the decode engine's dispatch
        stats."""
        from megatronapp_tpu.inference.disagg import DisaggServingEngine
        rt = get_request_tracer()
        rt.configure(enabled=True)
        metrics.enable()
        cfg = _gqa_cfg()
        params, _ = init_gpt_params(jax.random.PRNGKey(3), cfg)
        eng = DisaggServingEngine(
            params, cfg, max_batch=2, max_seq_len=48,
            prefill_buckets=(16,), block_size=8, prefill_chunk=8,
            prefill_slots=1, devices=devices8)
        rng = np.random.default_rng(0)
        r1 = eng.add_request(rng.integers(0, 128, 12).astype(np.int32),
                             6, SamplingParams(greedy=True))
        r2 = eng.add_request(rng.integers(0, 128, 9).astype(np.int32),
                             6, SamplingParams(greedy=True))
        res = eng.run_to_completion()
        assert sorted(res) == sorted([r1, r2])

        recs = rt.dump()
        unmatched, orphan_e = _pair_records(recs)
        assert not unmatched, f"unmatched B spans: {unmatched}"
        assert not orphan_e
        assert {r["pid"] for r in recs} == {DECODE_PID, PREFILL_PID}
        names = {r["name"] for r in recs}
        assert {"admit", "queue-wait", "prefill", "prefill-chunk",
                "handoff-parked", "adopt", "decode", "decode-step",
                "retire", "request"} <= names
        # Prefill spans sit on the prefill-mesh row, decode on decode's.
        assert all(r["pid"] == PREFILL_PID for r in recs
                   if r["name"] in ("prefill", "prefill-chunk"))
        assert all(r["pid"] == DECODE_PID for r in recs
                   if r["name"] == "decode")

        trace = rt.chrome_trace()
        rows = {e["pid"]: e["args"]["name"]
                for e in trace["traceEvents"]
                if e["ph"] == "M" and e["name"] == "process_name"}
        assert rows == {DECODE_PID: "decode-mesh",
                        PREFILL_PID: "prefill-mesh"}

        snap = eng.stats_snapshot(include_dispatch=True)
        assert "decode_dispatch" in snap       # the satellite fix
        slo = snap["disagg"]["slo"]
        assert slo["decode_intervals"] > 0
        for key in ("interval_p50_ms", "interval_p90_ms",
                    "interval_p99_ms", "ttft_p50_ms", "ttft_p99_ms"):
            assert slo[key] > 0.0
        assert slo["interval_p50_ms"] <= slo["interval_p99_ms"]
        # The histogram percentile never exceeds the recorded worst
        # interval by more than one bucket width.
        assert (slo["interval_p99_ms"]
                <= slo["worst_interval_ms"] * eng.interval_hist.growth)

    def test_save_and_offline_aggregate(self, tmp_path):
        """The ring saves as a benchmark-data-*.json that the offline
        aggregator (trace/aggregate.py CLI path) stitches into a Chrome
        trace file."""
        from megatronapp_tpu.trace.aggregate import aggregate_dir
        rt = get_request_tracer()
        rt.configure(enabled=True)
        rt.instant("admit", 0)
        rt.begin("request", 0)
        rt.begin("decode", 0)
        rt.finish(0, "retire")
        path = rt.save(trace_dir=str(tmp_path))
        assert path.endswith(".json")
        out = tmp_path / "aggregated.json"
        trace = aggregate_dir(str(tmp_path), str(out))
        assert out.exists()
        names = {e["name"] for e in trace["traceEvents"]}
        assert {"request", "decode", "retire"} <= names
