"""``"runner": "serve_closed"``: the paged continuous-batching engine under its
stepper thread (``DynamicBatchingDriver``), fed token ids by a closed loop.

``driver.submit(prompt_ids, n, SamplingParams(greedy=True), token_cb=...)`` is
what ``PUT /api`` and ``/ws`` both call; HTTP and the tokenizer are left out.
Every token's callback is timed with the host clock. The loop keeps
``clients_per_slot`` requests per decode slot in flight: a caller sends its
next request when its last completes. The mix runs until the engine has
delivered ``ramp_tokens`` tokens (set-up: the batch fills), and the window
opens at the end of that engine step and lasts ``--seconds``: it opens at a
point of the work, not of the clock, so a host that stalls during set-up
does not move the window against the trace (see ``run_cell``). A traced run
profiles the window's last seconds. The span around
``engine.step()`` is the harness's own, put there by wrapping the bound
method from here; the spans inside it (prefill, decode round) are private
methods of today's engine, wrapped where they exist, and only name the idle
gaps of the breakdown.

The model comes from the configuration's ``model`` module and the requests
from the traffic file's ``kind`` generator (``env["model"]``,
``env["generator"]``): this file knows neither by name.
"""

from __future__ import annotations

import dataclasses
import functools
import gc
import json
import os
import queue
import threading
import time
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from perfbench import common

# The emitted (greedy) token's reference logit may lie this far below the
# reference's own maximum at that position. The engine computes in bf16
# (weights, activations, cache) where the reference is float32 throughout;
# with random weights the logits have a standard deviation near 1 and the
# two largest of 50257 lie about 0.25 apart on average, so rounding flips
# the winner now and then but never by much: over 32 chip runs the worst gap
# was 0.089, and 0.123 over the 15,000 tokens of the runs that checked every
# request (PERF.md, PR 25); the limit is twice that. A token read through a
# wrong page, position or mask lies about 4 below the maximum. The limit does
# not tell a lower-precision cache: see the pool's size, below.
LOGIT_TOL = 0.25
TRACE_S = 4.0           # length of the profiled sub-window
REF_BATCH = 8           # requests to one pass of the reference
# What a CPU rehearsal puts in the configuration's ``serve`` section.
REHEARSAL = {"serve": {"max_batch": 4, "max_seq_len": 128, "num_blocks": 48}}


@dataclasses.dataclass(eq=False)
class _Rec:
    prompt: np.ndarray
    n: int
    sent: float = 0.0
    rid: int = -1
    done: Optional[threading.Event] = None
    times: List[float] = dataclasses.field(default_factory=list)
    toks: List[int] = dataclasses.field(default_factory=list)
    error: Optional[str] = None


def _spanned(fn, name):
    @functools.wraps(fn)
    def inner(*a, **kw):
        with jax.profiler.TraceAnnotation(name):
            return fn(*a, **kw)
    return inner


def run_cell(env) -> dict:
    from megatronapp_tpu.inference.dynamic_engine import (
        DynamicInferenceEngine,
    )
    from megatronapp_tpu.inference.engine import SamplingParams
    from megatronapp_tpu.inference.server import DynamicBatchingDriver

    config, mix, say = env["config"], env["traffic"], env["say"]
    model = env["model"]
    sv = config["serve"]
    dev = env["devices"][0]
    seconds = env["seconds"]
    scale = sv["max_seq_len"] / mix["max_total_len"] \
        if env["rehearsal"] else 1.0
    model_cfg = model.model_config(config, sv["params_dtype"])
    compiles = common.CompileCounter()
    params = jax.block_until_ready(
        model.init_params(model_cfg, env["seed"], dev))
    engine = DynamicInferenceEngine(
        params, model_cfg, max_batch=sv["max_batch"],
        max_seq_len=sv["max_seq_len"], paged=True,
        num_blocks=sv["num_blocks"])
    driver = DynamicBatchingDriver(engine)
    say(f"perfbench: pool {engine.pool.num_blocks} blocks x "
        f"{engine.pool.bytes_per_block} B = "
        f"{engine.pool.bytes_total / 1e9:.2f} GB, max_batch "
        f"{engine.max_batch}, prefill_chunk {engine.prefill_chunk}")

    steps = []    # (t0, t1, occupied slots after the step, tokens emitted)
    inner_step = engine.step
    # The stepper waits at `gate` while the callers' first requests go in, so
    # that its first step finds them all and every run admits them alike.
    # `ramp["left"]` counts the ramp's tokens down once the mix runs; the
    # step that brings it to 0 opens the window.
    gate, opened = threading.Event(), threading.Event()
    gate.set()
    ramp = {"left": None, "t_open": None}

    def step():
        gate.wait()
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.engine_step"):
            ev = inner_step()
        t1 = time.perf_counter()
        steps.append((t0, t1, sum(1 for r in engine.slots if r is not None),
                      len(ev["tokens"])))
        if ramp["left"] is not None and ramp["t_open"] is None:
            ramp["left"] -= len(ev["tokens"])
            if ramp["left"] <= 0:
                ramp["t_open"] = t1
                opened.set()
        return ev

    engine.step = step
    # The collector's runs stop every thread of the process; the notes say
    # how much of the window they took.
    gc_runs = []    # (start, seconds)

    def on_gc(phase, info):
        if phase == "start":
            gc_runs.append([time.perf_counter(), 0.0])
        elif gc_runs:
            gc_runs[-1][1] = time.perf_counter() - gc_runs[-1][0]

    gc.callbacks.append(on_gc)
    for private, span in (("_paged_prefill_chunked", "bench.prefill"),
                          ("_plain_round", "bench.decode_round")):
        if callable(getattr(engine, private, None)):
            setattr(engine, private, _spanned(getattr(engine, private), span))
    sampling = SamplingParams(greedy=True)
    finished: "queue.Queue[_Rec]" = queue.Queue()

    def on_token(rec: _Rec, rid, tok):
        rec.times.append(time.perf_counter())
        rec.toks.append(tok)
        if len(rec.toks) == rec.n:
            finished.put(rec)

    def send(rec: _Rec):
        rec.sent = time.perf_counter()
        try:
            rec.rid, rec.done = driver.submit(
                rec.prompt, rec.n, sampling,
                token_cb=functools.partial(on_token, rec))
        except Exception as e:  # noqa: BLE001 — a refused request fails
            rec.error = f"{type(e).__name__}: {e}"

    # ---- warm-up: one [1, prefill_chunk] call and one decode step -------
    warm = _Rec(np.arange(engine.prefill_chunk + 8, dtype=np.int32) % 997, 4)
    send(warm)
    if warm.error or not warm.done.wait(timeout=1100):
        raise SystemExit(f"perfbench: warm-up request failed: {warm.error}")
    finished.get(timeout=10)
    traces_before = (engine.decode_traces, engine.mq_traces)
    compiles_before = compiles.count
    del steps[:]

    # ---- the mix: ramp, then the window ----------------------------------
    recs: List[_Rec] = []
    stop = threading.Event()
    stream = env["generator"].requests(
        mix, env["seed"], config["vocab_size"], scale)

    def send_next():
        req = next(stream)
        recs.append(_Rec(req.prompt, req.max_new_tokens))
        send(recs[-1])

    def closed_loop():
        while not stop.is_set():
            try:
                finished.get(timeout=0.05)
            except queue.Empty:
                continue
            send_next()

    gate.clear()
    ramp["left"] = mix["ramp_tokens"]
    t_ramp = time.perf_counter()
    for _ in range(mix["clients_per_slot"] * engine.max_batch):
        send_next()
    gate.set()
    gen = threading.Thread(target=closed_loop, name="perfbench-load",
                           daemon=True)
    gen.start()
    if not opened.wait(timeout=600):
        raise SystemExit(f"perfbench: the engine did not deliver the ramp's "
                         f"{mix['ramp_tokens']} tokens in 600 s")
    t_open = ramp["t_open"]
    t_close = t_open + seconds
    if env["trace_dir"]:
        time.sleep(max(0.0, t_close - TRACE_S - 2.0 - time.perf_counter()))
        common.start_trace(env["trace_dir"])
        with jax.profiler.TraceAnnotation("bench.window"):
            time.sleep(max(0.5, t_close - 0.5 - time.perf_counter()))
        jax.profiler.stop_trace()
    time.sleep(max(0.0, t_close - time.perf_counter()))
    stop.set()
    gen.join(timeout=120)
    t_offered_end = time.perf_counter()

    # ---- drain: cancel what is still in flight -------------------------------
    for rec in recs:
        if rec.done is not None and not rec.done.is_set():
            driver.cancel(rec.rid)
    deadline = time.perf_counter() + 120
    for rec in recs:
        if rec.done is not None:
            rec.done.wait(timeout=max(0.0, deadline - time.perf_counter()))
    t_drained = time.perf_counter()
    gc.callbacks.remove(on_gc)
    traces_after = (engine.decode_traces, engine.mq_traces)
    compiles_in_window = compiles.count - compiles_before

    # ---- statistics --------------------------------------------------------
    problems = []
    # The cache is of the type the configuration states. An int8 pool passed
    # the logit check below with gaps no larger than bf16's (PERF.md, PR 25),
    # so its size, which the type fixes, is what holds it.
    stated = model.kv_bytes_per_token(config, sv["kv_cache_dtype"]) \
        * engine.pool.block_size
    if engine.pool.bytes_per_block != stated:
        problems.append(f"a pool block takes {engine.pool.bytes_per_block} B"
                        f" where {sv['kv_cache_dtype']} takes {stated}")
    # The requests of the window are those that completed in it, and any
    # the engine refused, whenever that was.
    good = [r for r in recs if len(r.toks) == r.n
            and t_open <= r.times[-1] < t_close]
    failed = [r for r in recs if r.error]
    counted = good + failed
    ttft = [(r.times[0] - r.sent) * 1e3 for r in good]
    gaps = [(b - a) * 1e3 for r in good for a, b in zip(r.times, r.times[1:])]
    # Tokens delivered by the engine steps of the window, whichever request
    # they belong to. A step that straddles an edge of the window counts in
    # proportion to the part of it inside: its tokens (24 at once after a
    # decode round) would otherwise move the rate by 0.65% with 20 ms of
    # jitter in where the edge falls.
    def delivered_between(lo, hi):
        return sum(k * (min(t1, hi) - max(t0, lo)) / (t1 - t0)
                   for t0, t1, _, k in steps if t1 > lo and t0 < hi)

    delivered = delivered_between(t_open, t_close)
    in_window = [s for s in steps if t_open <= s[0] < t_close]
    # Steps of the same work take the same time, run after run, to a
    # millisecond; a run differs from the next by single steps that take 50
    # or 100 ms longer (PERF.md, PR 25). Counted here among the full decode
    # rounds: those over 1.3 times the median.
    rounds = [t1 - t0 for t0, t1, _, k in in_window if k == engine.max_batch]
    usual = float(np.median(rounds)) if rounds else 0.0
    hiccups = [d - usual for d in rounds if d > 1.3 * usual]
    gc_in_window = [d for t, d in gc_runs if t_open <= t < t_close]
    if failed:
        problems.append(f"{len(failed)} of {len(counted)} requests failed: "
                        f"{failed[0].error}")
    if traces_after != traces_before:
        problems.append(f"decode/multiquery traces rose in the window: "
                        f"{traces_before} -> {traces_after}")
    if compiles_in_window:
        problems.append(f"{compiles_in_window} compilations in the window")
    if driver.restarts:
        problems.append(f"{driver.restarts} engine step failures")
    vocab = config["vocab_size"]
    if any(not 0 <= t < vocab for r in good for t in r.toks):
        problems.append("a token outside the vocabulary")
    # The streamed tokens are the engine's own record of each request
    # (popping the record also frees it).
    for r in good:
        kept = driver.result_tokens(r.rid)
        if kept is None or list(kept[len(r.prompt):]) != r.toks:
            problems.append(f"request {r.rid}: the streamed tokens differ "
                            "from the engine's record")
            break
    stats = engine.stats_snapshot()
    if env.get("keep_dir"):
        os.makedirs(env["keep_dir"], exist_ok=True)
        with open(os.path.join(env["keep_dir"], f"{env['cell']['name']}."
                               f"{env['seed']}.steps.json"), "w") as f:
            json.dump({"t_ramp": t_ramp, "t_open": t_open,
                       "t_close": t_close, "steps": steps}, f)

    # ---- every completed request against the plain reference --------------
    t_ref = time.perf_counter()
    engine.pool.pages = None        # the stepper is parked; free the pool
    token_gaps = []         # one per emitted token of the checked requests
    for lo in range(0, len(good), REF_BATCH):
        some = good[lo:lo + REF_BATCH]
        for r, gaps_r in zip(some, _reference_gaps(
                model, params, some, config,
                int(round(mix["max_total_len"] * scale)), dev)):
            if gaps_r.max() > LOGIT_TOL:
                say(f"perfbench: request {r.rid} (prompt {len(r.prompt)}, "
                    f"{r.n} new tokens) lies {gaps_r.max():.3f} below the "
                    "reference")
            token_gaps.append(gaps_r)
    token_gaps = np.concatenate(token_gaps) if token_gaps else np.zeros(1)
    worst = float(token_gaps.max())
    say(f"perfbench: {len(good)} requests against the float32 reference: "
        f"largest gap below the maximum logit {worst:.4f} (tolerance "
        f"{LOGIT_TOL}); took {time.perf_counter() - t_ref:.1f}s")
    if not good:
        problems.append("no completed request to check")
    if not worst <= LOGIT_TOL:
        problems.append(f"an emitted token's reference logit lies {worst:.3f}"
                        f" below the maximum (> {LOGIT_TOL})")

    end_to_end = {"setup_s": t_open - env["t_start"]}
    if delivered:
        end_to_end["serve_tok_s"] = delivered / seconds
    return {
        "kind": "serve",
        "correct": not problems, "problems": problems,
        "attempted": len(counted), "failed": len(failed),
        "end_to_end": end_to_end,
        "ttft_ms": ttft, "itl_ms": gaps,
        "engine_steps": in_window, "max_batch": engine.max_batch,
        "engine_stats": stats,
        "notes": {
            "requests": len(counted), "output_tokens": round(delivered, 2),
            "engine_steps": len(in_window),
            "ramp_s": t_open - t_ramp,
            "decode_hiccups": len(hiccups),
            "decode_hiccup_s": float(sum(hiccups)),
            "gc_runs": len(gc_in_window),
            "gc_s": float(sum(gc_in_window)),
            "gc_longest_ms": 1e3 * max(gc_in_window, default=0.0),
            # The rate of the step the window closes in. The window opens
            # at a point of the work and closes by the clock, so a run that
            # loses d seconds to the host loses d times this rate, not d
            # times the mean: the figure's noise depends on it.
            "close_edge_tok_s": next(
                (k / (t1 - t0) for t0, t1, _, k in steps
                 if t0 <= t_close < t1), 0.0),
            "backlog_at_end": sum(
                1 for r in recs if r.done is not None and r.sent < t_close
                and (len(r.toks) < r.n or r.times[-1] > t_close)),
            "drain_s": t_drained - t_offered_end,
            "reference_checked": len(good),
            "reference_worst_gap": worst,
            # Not held to a limit yet: what a lower-precision cache or
            # weights would move (PERF.md, PR 25).
            "reference_mean_gap": float(token_gaps.mean()),
            "reference_not_argmax_share": float((token_gaps > 0).mean()),
            "compile_s": compiles.seconds,
            "preemptions": stats["pool"]["preemptions"],
            "peak_blocks_in_use": stats["pool"]["peak_blocks_in_use"],
        },
    }


def _reference_gaps(model, params, recs: List[_Rec], config: dict,
                    limit: int, dev) -> List[np.ndarray]:
    """For each request, how far below the reference's maximum logit each
    emitted token lies. The reference is fed the prompt and the
    engine's own answer, one request a row; position P-1+i predicts answer
    token i. Always REF_BATCH rows of the mix's longest request, rounded up
    to a multiple of 256, so that the reference compiles one shape."""
    padded = -(-limit // 256) * 256
    tokens = np.zeros((REF_BATCH, padded), np.int32)
    for i, r in enumerate(recs):
        seq = np.concatenate([r.prompt, np.asarray(r.toks[:-1], np.int32)])
        tokens[i, :len(seq)] = seq
    positions = np.broadcast_to(
        np.arange(padded, dtype=np.int32) % config["max_position_embeddings"],
        tokens.shape)
    with jax.default_device(dev):
        lg = model.reference_logits(
            params, config, jnp.asarray(tokens),
            jnp.zeros(tokens.shape, jnp.int32), jnp.asarray(positions))
        gaps = []
        for i, r in enumerate(recs):
            first = len(r.prompt) - 1
            rows = lg[i, first:first + r.n, :config["vocab_size"]]
            picked = rows[jnp.arange(r.n), jnp.asarray(r.toks)]
            gaps.append(np.asarray(jnp.max(rows, axis=-1) - picked))
        return gaps
