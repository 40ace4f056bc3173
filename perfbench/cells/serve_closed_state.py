"""``"runner": "serve_closed_state"``: ``serve_closed``'s loop, unchanged, for
a model whose engine keeps a recurrent state a slot beside its KV pages
(state-space layers); every completed request against the float32 reference
as ``serve_closed_rows`` holds it, several requests a pass; then two checks
of that state's type.

**The reference pass.** ``serve_closed_rows`` asks for one request a pass,
padded to 1024 or 2048 positions: 260 requests a window took 5 to 6 minutes
here and the whole run longer than the driver allows one (PERF.md, PR 32).
So this runner calls ``serve_closed.run_cell`` with a pass of its own, which
is handed all of a window's requests at once and holds ``PASS_TOKENS`` tokens:
the requests sorted by length, longest first, as many rows as fit at the
longest one's length rounded up to the mix's longest or a half or quarter
of it (three shapes). One request a row, from position 0, as the model
module asks; the head runs a row at a time over the answer's positions (a
multiple of ``serve_closed_rows.ANSWER_STEP``). What is compared and its
limit are ``serve_closed``'s: every emitted token of every completed
request, ``LOGIT_TOL``.

**Its size.** The engine's ``stats_snapshot()["state"]["bytes_per_slot"]``
against the model module's ``state_bytes_per_slot(config,
serve.state_dtype)``, as ``serve_closed`` holds the pool's blocks. That
tells a pool of another type. It does not tell a step that rounds ``h`` on
its way into a float32 pool, and the logit limit does not either.

**Its precision.** After the window and the drain, the engine that was
measured (its stepper, compiled steps and state pools) serves a few more
requests, alone: prompts that end just past a chunk's edge, answers decoded
by the state kernel, every one ``PROBE_TOKENS`` long. A finished request's
slot keeps its state until the next admission, so the pool's rows are read
back. ``state_fine_share`` is the share of their elements that the next
type below the stated one cannot hold (float32 stated: elements that differ
from their bf16 rounding), held above ``FINE_SHARE``: a float32 recurrence
leaves all but a few in 100,000 so, one that rounds ``h`` to bf16 anywhere
on the way to the pool none. The control is ``model.reference_state`` with
``h`` rounded to that lower type at every position, read the same way: 0.0
in ten chip runs (PERF.md, PR 32) and in ``tests/test_jamba.py``; a run does
not compute it again (two more passes and their compiles, for a note).

Why not a distance to the reference's final state: the engine's activations
are bf16 where the reference's are float32, and through 28 layers that moves
``h`` further (0.057-0.093 of a layer's largest) than rounding ``h`` itself
does (0.011-0.019; PERF.md, PR 32). A state that is another request's, or
was not reset, is wrong by its whole size, and the logits say so.

A program without such a counter or pools (or a model without state) is not
correct here: this runner is for cells whose state is part of the deployment.
"""

from __future__ import annotations

import functools
from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from perfbench import manifest, xplane_stats

_closed = manifest.load_module("cells", "serve_closed")
_rows = manifest.load_module("cells", "serve_closed_rows")
REHEARSAL = _closed.REHEARSAL
PASS_TOKENS = 8192      # rows x positions of one pass of the reference

BELOW = {"float32": "bfloat16"}     # the next type below the stated one
PROBE_TOKENS = 128      # tokens every probe's state has read at its end
# Between the two readings (PERF.md, PR 32): the engine's 0.99995 over its
# seeds, the control's 0.
FINE_SHARE = 0.5


@functools.partial(jax.jit, static_argnames=("size",))
def _cut(x, row, first, size: int):
    """x [B,S,H] -> x[row, first:first + size]: traced offsets, so a program
    a shape and not one a request."""
    return jax.lax.dynamic_slice(
        x, (row, first, 0), (1, size, x.shape[2]))[0]


@functools.partial(jax.jit, static_argnames=("vocab",))
def _below_max(logits, picked, vocab: int):
    """logits [size, V'], picked [size] -> how far each row's picked logit
    lies below the row's maximum over the first `vocab` columns."""
    logits = logits[:, :vocab]
    return jnp.max(logits, axis=-1) - jnp.take_along_axis(
        logits, picked[:, None], axis=-1)[:, 0]


def _reference_gaps(model, params, recs, config: dict, limit: int,
                    dev) -> List[np.ndarray]:
    """``serve_closed._reference_gaps`` for all of a window's requests:
    how far below the reference's maximum logit each emitted token lies,
    in the order of `recs`. Position P-1+i predicts answer token i."""
    top = -(-limit // 4) * 4
    seqs = [np.concatenate([r.prompt, np.asarray(r.toks[:-1], np.int32)])
            for r in recs]
    left = sorted(range(len(recs)), key=lambda i: -len(seqs[i]))
    gaps = [None] * len(recs)
    with jax.default_device(dev):
        while left:
            padded = min(s for s in (top // 4, top // 2, top)
                         if s >= len(seqs[left[0]]))
            rows = max(1, PASS_TOKENS // padded)
            some, left = left[:rows], left[rows:]
            tokens = np.zeros((rows, padded), np.int32)
            for row, i in enumerate(some):
                tokens[row, :len(seqs[i])] = seqs[i]
            x = model.reference_hidden(params, config, jnp.asarray(tokens),
                                       jnp.zeros(tokens.shape, jnp.int32))
            for row, i in enumerate(some):
                r = recs[i]
                size = min(padded, -(-r.n // _rows.ANSWER_STEP)
                           * _rows.ANSWER_STEP)
                first = min(len(r.prompt) - 1, padded - size)
                skip = len(r.prompt) - 1 - first
                picked = np.zeros(size, np.int32)
                picked[skip:skip + r.n] = r.toks
                lg = model.reference_head(
                    params, config,
                    _cut(x, jnp.int32(row), jnp.int32(first), size=size))
                gaps[i] = np.asarray(_below_max(
                    lg, jnp.asarray(picked),
                    vocab=config["vocab_size"]))[skip:skip + r.n]
    return gaps


def _probe_shapes(chunk: int, total: int):
    """(prompt length, answer length) of each probe; a request that answers
    n tokens has read prompt + n - 1 (its last token is never fed). Prompts
    2 past one chunk's edge (the convolution's tail reaches back across it),
    1 past the second's, and nearly the whole length."""
    return [(p, total + 1 - p) for p in (chunk + 2, 2 * chunk + 1, total - 1)
            if 0 < p <= total]


def _fine_share(h, below: str) -> float:
    """Share of h's elements that `below` cannot hold."""
    info = jnp.finfo(below)
    return float(jnp.mean(
        h != jax.lax.reduce_precision(h, info.nexp, info.nmant)))


def _probe(env, driver, page_specs) -> dict:
    from megatronapp_tpu.inference.engine import SamplingParams
    engine, config = driver.engine, env["config"]
    total = min(PROBE_TOKENS, config["serve"]["max_seq_len"] // 2)
    # serve_closed freed the page pools for its reference pass.
    engine.pool.pages = tuple(jnp.zeros(s, d) for s, d in page_specs)
    rng = np.random.default_rng([env["seed"], total])
    subs = [driver.submit(
        rng.integers(0, config["vocab_size"], p).astype(np.int32), n,
        SamplingParams(greedy=True))
        for p, n in _probe_shapes(engine.prefill_chunk, total)]
    for _, done in subs:
        if not done.wait(timeout=600):
            raise SystemExit("perfbench: a state probe did not finish")
    below = BELOW[config["serve"]["state_dtype"]]
    held = jnp.stack([engine.pool.state[0][:, engine.requests[rid].slot]
                      for rid, _ in subs], axis=1)
    return {"probes": len(subs), "below": below,
            "fine": _fine_share(held, below)}


def run_cell(env) -> dict:
    from megatronapp_tpu.inference import server
    kept = []

    class Driver(server.DynamicBatchingDriver):
        def __init__(self, engine, *a, **kw):
            super().__init__(engine, *a, **kw)
            kept.append((self, [(p.shape, p.dtype)
                                for p in engine.pool.pages]))

    real, server.DynamicBatchingDriver = server.DynamicBatchingDriver, Driver
    _closed.REF_BATCH = 1 << 30     # every request to one call, grouped there
    _closed._reference_gaps = _reference_gaps
    try:
        run = _closed.run_cell(env)
    finally:
        server.DynamicBatchingDriver = real
    if env["trace_dir"]:    # as serve_closed_rows: the spans' attributes
        run["xplane_stats"] = xplane_stats.load(env["trace_dir"])
    config = env["config"]
    stated = env["model"].state_bytes_per_slot(
        config, config["serve"]["state_dtype"])
    state = (run.get("engine_stats") or {}).get("state") or {}
    held = state.get("bytes_per_slot")
    if held != stated:
        run["problems"].append(
            f"a slot's recurrent state takes {held} B where "
            f"{config['serve']['state_dtype']} takes {stated}")
    probe = {}
    if getattr(kept[0][0].engine.pool, "state", None):
        with jax.default_device(env["devices"][0]):
            probe = _probe(env, *kept[0])
        env["say"](
            f"perfbench: {probe['probes']} probes' states read back: "
            f"{probe['fine']:.5f} of their elements are finer than "
            f"{probe['below']} (limit {FINE_SHARE})")
        if not probe["fine"] > FINE_SHARE:
            run["problems"].append(
                f"a slot's recurrent state is held no finer than "
                f"{probe['below']}: {probe['fine']:.5f} of its elements "
                f"(<= {FINE_SHARE})")
    run["correct"] = not run["problems"]
    run["notes"].update(
        state_bytes_per_slot=held, state_resets=state.get("resets"),
        state_dropped=state.get("dropped"),
        prefill_scans=state.get("prefill_scans"),
        state_fine_share=probe.get("fine"))
    return run
