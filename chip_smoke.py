"""The quickest proof that the system still starts on the chip.

    python chip_smoke.py             # one chip: trainer twice, the server,
                                     # then a tiny hybrid state-space engine,
                                     # a tiny EVA-attention engine and a
                                     # tiny double-layer expert-share engine
                                     # and a tiny short-convolution MoE one
                                     # and a tiny sliding-window MoE one
                                     # and a tiny Mamba-2 expert-share one
    python chip_smoke.py --chips 4   # four chips: one device vs tp2 x dp2
                                     # (and tp2 x pp2), nothing else

GPT-2 125M at full width and depth (12 layers, H 768, 12 heads, S 1024,
vocab 50304), random weights from the entry points' own seeds:

- trainer: ``pretrain_gpt.py``'s ``main`` with the README Quick start's
  arguments on synthetic data, once with ``--attention-impl auto`` (on the
  chip the flash kernels, chosen from the shapes at 4 x 1024, 12 heads of
  64) and once with ``--attention-impl reference`` (XLA's dense attention:
  the other side of the A/B); under ``--tiny`` ``auto`` is the dense side
  (S 64, or no TPU) and the other run forces ``pallas``;
- server: ``tools/run_text_generation_server.py --preset gpt2-125m
  --engine dynamic`` answering real ``PUT /api`` requests;
- hybrid: a tiny model with state-space layers through the paged engine (no
  preset of that kind is small): the compiled decode step holds one
  ``ssm_update`` a scanned run of such layers and aliases the state pools.

- eva: a tiny model with EVA attention (a window of 256 exact rows, one
  pooled row for every 16 older ones) through the paged engine: requests
  that close windows, one ``eva_summary`` a layer loop in the compiled decode
  step, a slot's blocks bounded by its rows and not its length.

- share: a tiny shortcut-connected double-layer model (two latent-attention
  sublayers with a query latent a layer, a router over 8 computing and 4
  zero-compute experts of which 4 are held) through the paged engine: two
  latent kernels a layer loop over pools of 2 planes a layer, every pick
  counted as held, absent or zero-compute.

- conv: a tiny hybrid of gated short convolutions and grouped-query
  attention whose feed-forwards are experts behind a leading dense layer
  (sigmoid scores, a selection bias) through the paged engine: one tail
  pool beside the page pools, aliased; the experts' stacks read in place;
  every pick counted.

- window: a tiny sliding-window stack (full attention layers of 6 query
  heads beside window layers of 8 over 2 key/value heads, a per-head gate,
  two rotary tables, experts behind a leading dense layer) through the paged
  engine: requests longer than the window, two pairs of page pools, both
  aliased, the window planes' blocks given back, both families of paged
  kernel in the compiled decode step; then one loss and gradient of the
  same stack over packed sequences, through the flash kernels' window term
  (``flash_window_fwd``, ``_bwd_dq``, ``_bwd_dkv`` in the compiled text).

- ssd: a tiny hybrid of Mamba-2 mixers (4 heads of 64 columns, a [64, 128]
  state a head, chunks of 32) and one grouped-query attention layer a
  period without a positional term, 4 of 8 experts held beside a shared one,
  Granite's four multipliers, through the paged engine: prompts longer than
  a chunk and than a prefill call, one ``ssm_update`` a scanned run of
  layers over a pool whose planes are tiled along E, every pick counted as
  held or absent.

A chip belongs to one process at a time, so this parent imports no JAX and
runs each phase as a child, one after the other; it learns the device from
the ``device: {...}`` line every entry point prints at start-up. The LAST
line of standard output is ``{"ok": ..., "device": {...}}``; the exit code
is 0 only when every phase passed on a TPU. ``--tiny`` shrinks the sizes
for a rehearsal of the control flow on the CPU: the phases then run, and
the verdict still refuses because the platform is not a TPU.
"""

import argparse
import json
import os
import re
import signal
import socket
import statistics
import subprocess
import sys
import threading
import time
import urllib.request

ROOT = os.path.dirname(os.path.abspath(__file__))
LOG_DIR = os.path.join(ROOT, "chiprun_out")
DEVICE_LINE_PREFIX = "device: "      # megatronapp_tpu/utils/platform.py
RESULT_PREFIX = "smoke-result: "

# README Quick start, first command; --train-iters cut to a handful and
# every step logged so each loss and step time is on the output.
MODEL_ARGS = ["--num-layers", "12", "--hidden-size", "768",
              "--num-attention-heads", "12", "--seq-length", "1024"]
# Tiny: a large init and learning rate, so that the loss visibly falls on
# random tokens in 20 steps of 256 tokens.
TINY_MODEL_ARGS = ["--num-layers", "2", "--hidden-size", "64",
                   "--num-attention-heads", "4", "--seq-length", "64",
                   "--vocab-size", "512", "--init-method-std", "0.2",
                   "--lr", "0.01"]
BATCH_ARGS = ["--micro-batch-size", "4", "--global-batch-size", "4",
              "--log-interval", "1"]
# Four-chip legs: the same global batch (8 x 1024 tokens) splits into
# 4 / 2 / 4 microbatches of 2 on 1 device / dp2 / pp2.
MULTI_BATCH_ARGS = ["--micro-batch-size", "2", "--global-batch-size", "8",
                    "--train-iters", "6", "--log-interval", "1"]


def _train_args(tiny):
    return ((TINY_MODEL_ARGS if tiny else MODEL_ARGS) + BATCH_ARGS
            + ["--train-iters", "20" if tiny else "30"])


def _multi_args(tiny):
    return (TINY_MODEL_ARGS if tiny else MODEL_ARGS) + MULTI_BATCH_ARGS


# auto vs pallas differ only in how attention is computed (dense softmax
# vs blockwise online softmax, both fp32 inside, bf16 matmul inputs), on
# the same weights and the same first batch. The loss is ~10.98 (ln 50304
# plus the initial logit variance), so 1e-2 is 0.1% of it: far above bf16
# rounding through 12 layers (6.5e-5 on the chip, PR 23), far below what a
# wrong mask or scale does.
FIRST_LOSS_TOL = 1e-2
# tp2 x dp2 / tp2 x pp2 vs one device: same seed, same global batch; what
# differs is the order of bf16 partial sums (contractions split over tp,
# gradients summed over dp, microbatches regrouped). Allowed to drift
# 1e-2 (0.1% of the loss) at any of the six steps; 3.5e-4 on the chip
# (PR 23), 4e-3 at --tiny size on the CPU.
MULTI_LOSS_TOL = 1e-2

_ITER_RE = re.compile(
    r"iter\s+(\d+)/\s*(\d+) \| loss ([-\d.naninf]+) .*\| ([\d.]+) ms/step")


def _say(msg=""):
    print(msg, flush=True)


class _Transcript:
    """Child output: echoed with a tag, kept for parsing, written whole
    under chiprun_out/ (the chip tool returns only the end of stdout)."""

    def __init__(self, tag):
        self.tag = tag
        self.lines = []
        os.makedirs(LOG_DIR, exist_ok=True)
        self._file = open(os.path.join(LOG_DIR, f"chip_smoke_{tag}.log"),
                          "w")

    def note(self, line):
        self.lines.append(line)
        self._file.write(line + "\n")
        self._file.flush()
        # Tracebacks and warnings stay in the file; the echo keeps to
        # what a reader of the last 24 kB needs.
        if not line.startswith(("  ", "\t")) and len(line) < 400:
            _say(f"[{self.tag}] {line}")

    def pump(self, stream):
        for raw in stream:
            self.note(raw.rstrip("\n"))

    def close(self):
        self._file.close()


def _tagged(lines, prefix):
    """The JSON payloads of the lines that start with `prefix`."""
    return [json.loads(ln[len(prefix):]) for ln in lines
            if ln.startswith(prefix)]


def _kernel_mode(dev, tiny):
    """What a Pallas kernel must say of itself: compiled, except in a
    --tiny rehearsal off the TPU."""
    return ("(interpreted)" if tiny and dev and dev[0]["platform"] != "tpu"
            else "(compiled)")


def _spawn(tr, cmd):
    """Start a child whose output is pumped into the transcript."""
    proc = subprocess.Popen(
        cmd, stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        env=dict(os.environ, PYTHONUNBUFFERED="1"), cwd=ROOT)
    pump = threading.Thread(target=tr.pump, args=(proc.stdout,),
                            daemon=True)
    pump.start()
    return proc, pump


def _run_child(tag, child_args, timeout_s):
    """Run `python chip_smoke.py --child ...` to its end; return
    (returncode, transcript)."""
    tr = _Transcript(tag)
    proc, pump = _spawn(tr, [sys.executable,
                             os.path.join(ROOT, "chip_smoke.py"),
                             *child_args])
    try:
        rc = proc.wait(timeout=timeout_s)
    except subprocess.TimeoutExpired:
        proc.kill()
        rc = proc.wait()
        tr.note(f"killed after {timeout_s}s")
    pump.join(timeout=10)
    tr.close()
    return rc, tr


# ---------------------------------------------------------------------------
# Phase: trainer
# ---------------------------------------------------------------------------

def check_train(rc, lines, impl, tiny=False):
    """Pass/fail of one trainer run from its output lines alone (pure, so
    the CPU tests can feed it recorded lines)."""
    out = {"phase": f"train-{impl}", "ok": False, "problems": []}
    bad = out["problems"].append
    dev = _tagged(lines, DEVICE_LINE_PREFIX)
    out["device"] = dev[0] if dev else None
    res = _tagged(lines, RESULT_PREFIX)
    iters = [m.groups() for m in map(_ITER_RE.search, lines) if m]
    if rc != 0:
        bad(f"child exited {rc}")
    if not dev:
        bad("no device line")
    if not res or not iters:
        bad("no result line / no iteration lines")
        return out
    res = res[0]
    losses = res["losses"]
    step_ms = [float(g[3]) for g in iters]
    out.update(
        steps=len(losses), losses=losses,
        first_step_s=round(step_ms[0] / 1e3, 3),
        main_wall_s=res["wall_s"], compile_s=res["compile_s"],
        cache_hits=res["cache_hits"], cache_misses=res["cache_misses"],
        step_s_median_after_warmup=(
            statistics.median(step_ms[2:]) / 1e3 if len(step_ms) > 2
            else None),
        peak_bytes_in_use=res["peak_bytes_in_use"],
        index_builders=res["index_builders"])
    if len(losses) != int(iters[0][1]):
        bad(f"{len(losses)} losses for {iters[0][1]} iterations")
    if not all(isinstance(x, float) and x == x and abs(x) < 1e30
               for x in losses):
        bad("a loss is not finite")
    else:
        q = max(len(losses) // 4, 1)
        head, tail = sum(losses[:q]) / q, sum(losses[-q:]) / q
        out["loss_first_quarter"], out["loss_last_quarter"] = head, tail
        if not tail < head:
            bad(f"loss did not fall: {head:.4f} -> {tail:.4f}")
    att = [ln for ln in lines if ln.startswith("attention: self-attention")]
    out["attention"] = att
    # What `choose_attention` announces: at the real size on a TPU `auto`
    # takes the flash kernels; a --tiny S 64, or a CPU, keeps it dense.
    flash = impl == "pallas" or (impl == "auto" and not tiny)
    want = (("pallas flash kernel", _kernel_mode(dev, tiny)) if flash
            else (f"xla dense ({impl}",))
    if not any(all(w in ln for w in want) for ln in att):
        bad(f"expected the trainer to say it ran {' ... '.join(want)!r}; "
            f"it said {att}")
    out["ok"] = not out["problems"]
    return out


def phase_train(impl, tiny):
    rc, tr = _run_child(f"train-{impl}",
                        ["--child", "train", "--impl", impl]
                        + (["--tiny"] if tiny else []), timeout_s=420)
    return check_train(rc, tr.lines, impl, tiny)


def child_train(impl, tiny):
    """In the child: pretrain_gpt.py's main, as a command line would run
    it, plus what only the process itself can report."""
    import jax

    _refuse_unless_tpu(jax, tiny)
    meter = _CompileMeter(jax)
    import pretrain_gpt         # this script's directory is sys.path[0]

    argv = _train_args(tiny) + ["--attention-impl", impl]
    _say("argv: pretrain_gpt.py " + " ".join(argv))
    t0 = time.perf_counter()
    result = pretrain_gpt.main(argv)
    jax.block_until_ready(result.state)
    wall = time.perf_counter() - t0
    from megatronapp_tpu.data import helpers
    builders = ("native" if helpers._LIB is not None else
                "numpy" if helpers._LOAD_FAILED else
                "not used (synthetic data)")
    stats = jax.devices()[0].memory_stats() or {}
    _say(RESULT_PREFIX + json.dumps({
        "losses": [float(x) for x in result.losses],
        "wall_s": round(wall, 3), **meter.report(),
        "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
        "index_builders": builders}))


def _refuse_unless_tpu(jax, tiny):
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not tiny:
        raise SystemExit(f"chip_smoke: JAX found {dev.platform!r}, not a "
                         "TPU; refusing to run the real size there")


class _CompileMeter:
    """Seconds JAX spent in the backend compiler (or fetching from the
    persistent cache instead) and the cache's hit/miss counts."""

    def __init__(self, jax):
        self.seconds = 0.0
        self.hits = self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._evt)

    def _dur(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs

    def _evt(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def report(self):
        return {"compile_s": round(self.seconds, 3),
                "cache_hits": self.hits, "cache_misses": self.misses}


# ---------------------------------------------------------------------------
# Phase: server
# ---------------------------------------------------------------------------

def _http(port, path, body=None, timeout=600):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}",
        data=None if body is None else json.dumps(body).encode(),
        method="GET" if body is None else "PUT",
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return json.loads(resp.read())


def _generate(port, prompt_ids, n):
    """One greedy PUT /api; returns (generated token ids, seconds)."""
    t0 = time.perf_counter()
    out = _http(port, "/api", {
        "prompts": [" ".join(map(str, prompt_ids))],
        "tokens_to_generate": n, "greedy": True})
    return ([int(t) for t in out["segments"][0].split()],
            time.perf_counter() - t0)


def _prompt(length, salt):
    return [(salt * 7919 + i * 104729) % 50000 + 1 for i in range(length)]


def drive_server(port, tiny):
    """The requests of the server phase, against a server already up.
    Returns (facts, problems)."""
    facts, problems = {}, []
    n_new = 8 if tiny else 32
    lengths = (5, 17, 40, 70) if not tiny else (3, 9, 20, 33)

    stats0 = _http(port, "/stats")
    facts["pool_blocks"] = stats0["pool"]["num_blocks"]
    if stats0["pool"]["blocks_in_use"] != 0:
        problems.append("pool in use before any request")

    # 1. One request alone: its time includes every compile it triggers.
    alone = _prompt(24 if not tiny else 12, salt=1)
    toks_a, t_first = _generate(port, alone, n_new)
    facts["first_answer_s_including_compiles"] = round(t_first, 3)
    # 2. The same prompt again, greedy: same tokens.
    toks_b, t_again = _generate(port, alone, n_new)
    facts["same_prompt_again_s"] = round(t_again, 3)
    if len(toks_a) != n_new or len(toks_b) != n_new:
        problems.append(f"asked {n_new} tokens, got {len(toks_a)} and "
                        f"{len(toks_b)}")
    if toks_a != toks_b:
        problems.append("same greedy prompt gave different tokens")

    # 3. Several at once, different lengths, while /stats is sampled.
    results, errors, seen = {}, [], []
    done = threading.Event()

    def ask(i, length):
        try:
            results[i] = _generate(port, _prompt(length, salt=10 + i),
                                   n_new)
        except Exception as e:  # noqa: BLE001 — reported as a problem
            errors.append(f"request {i}: {type(e).__name__}: {e}")

    def watch():
        while not done.is_set():
            try:
                s = _http(port, "/stats", timeout=60)
                seen.append((s["active"], s["pool"]["blocks_in_use"]))
            except Exception as e:  # noqa: BLE001
                errors.append(f"/stats: {type(e).__name__}: {e}")
                return
            time.sleep(0.01)

    threads = [threading.Thread(target=ask, args=(i, ln))
               for i, ln in enumerate(lengths)]
    watcher = threading.Thread(target=watch)
    t0 = time.perf_counter()
    watcher.start()
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=900)
    facts["concurrent_batch_s"] = round(time.perf_counter() - t0, 3)
    done.set()
    watcher.join(timeout=120)
    problems.extend(errors)
    for i in range(len(lengths)):
        got = len(results.get(i, ([], 0))[0])
        if got != n_new:
            problems.append(f"concurrent request {i} (prompt "
                            f"{lengths[i]}): asked {n_new}, got {got}")
    facts["max_active_seen"] = max((a for a, _ in seen), default=0)
    facts["max_blocks_in_use_seen"] = max((b for _, b in seen), default=0)

    # 4. Steady state: everything is compiled now.
    n_long = 16 if tiny else 128
    toks_l, t_long = _generate(port, _prompt(lengths[1], salt=99), n_long)
    if len(toks_l) != n_long:
        problems.append(f"asked {n_long} tokens, got {len(toks_l)}")
    facts["warm_single_stream_s_per_token_http_prefill_decode"] = round(
        t_long / n_long, 5)

    stats1 = _http(port, "/stats")
    health = _http(port, "/healthz")
    facts["driver_max_active"] = stats1.get("driver_max_active")
    facts["blocks_in_use_after"] = stats1["pool"]["blocks_in_use"]
    facts["prefill_tokens"] = stats1["pool"].get("prefill_tokens")
    facts["decode_traces"] = stats1["decode_traces"]
    facts["multiquery_traces"] = stats1["multiquery_traces"]
    disp = stats1.get("decode_dispatch") or {}
    facts["decode_pallas_calls_per_step"] = disp.get("kernels")
    facts["expert_stack_slices"] = disp.get("expert_stack_slices")
    facts["healthz"] = {"status": health.get("status"),
                        "stepper": health.get("stepper")}
    if facts["max_blocks_in_use_seen"] <= 0:
        problems.append("/stats never showed the paged pool in use")
    if facts["blocks_in_use_after"] != 0:
        problems.append("paged pool not released after the requests")
    if (facts["driver_max_active"] or 0) < 2:
        problems.append("the stepper never batched two requests")
    if not disp.get("kernels"):
        problems.append("no pallas_call in the traced decode step")
    if health.get("status") != "ok":
        problems.append(f"/healthz status {health.get('status')!r}")
    return facts, problems


def check_server(facts, problems, lines, tiny=False):
    out = {"phase": "server", "ok": False, "problems": list(problems),
           **facts}
    dev = _tagged(lines, DEVICE_LINE_PREFIX)
    out["device"] = dev[0] if dev else None
    if not dev:
        out["problems"].append("no device line")
    att = [ln for ln in lines if ln.startswith("attention: paged")]
    out["attention"] = att
    mode = _kernel_mode(dev, tiny)
    for site in ("paged decode", "paged multi-query"):
        if not any(site in ln and mode in ln for ln in att):
            out["problems"].append(
                f"server did not say it ran the {site} kernel {mode}")
    out["ok"] = not out["problems"]
    return out


def phase_server(tiny):
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    cmd = [sys.executable,
           os.path.join(ROOT, "tools", "run_text_generation_server.py"),
           "--preset", "gpt2-125m", "--engine", "dynamic",
           "--host", "127.0.0.1", "--port", str(port)]
    if tiny:
        cmd += ["--max-seq-len", "128"]
    tr = _Transcript("server")
    tr.note("argv: " + " ".join(cmd[1:]))
    proc, pump = _spawn(tr, cmd)
    facts, problems = {}, []
    try:
        t0 = time.perf_counter()
        while True:
            if proc.poll() is not None:
                problems.append(f"server exited {proc.returncode} before "
                                "it answered")
                break
            try:
                _http(port, "/healthz", timeout=5)
                break
            except OSError:
                if time.perf_counter() - t0 > 300:
                    problems.append("server not up after 300 s")
                    break
                time.sleep(0.5)
        facts["server_up_s"] = round(time.perf_counter() - t0, 3)
        dev = _tagged(tr.lines, DEVICE_LINE_PREFIX)
        if not problems and dev and dev[0]["platform"] != "tpu" \
                and not tiny:
            problems.append(f"server is on {dev[0]['platform']!r}; not "
                            "driving the real size there")
        if not problems:
            try:
                facts2, problems = drive_server(port, tiny)
                facts.update(facts2)
            except Exception as e:  # noqa: BLE001 — a failed phase
                problems.append(f"{type(e).__name__}: {e}")
    finally:
        if proc.poll() is None:
            proc.send_signal(signal.SIGINT)
            try:
                proc.wait(timeout=30)
            except subprocess.TimeoutExpired:
                proc.kill()
                proc.wait()
        pump.join(timeout=10)
        tr.close()
    return check_server(facts, problems, tr.lines, tiny)


# ---------------------------------------------------------------------------
# Phase: a hybrid state-space stack through the paged engine, tiny widths
# ---------------------------------------------------------------------------

HYBRID = dict(num_layers=8, attn_layer_period=4, attn_layer_offset=1)


def phase_hybrid(tiny):
    rc, tr = _run_child("hybrid", ["--child", "hybrid"]
                        + (["--tiny"] if tiny else []), timeout_s=420)
    return check_hybrid(rc, tr.lines, tiny)


def child_hybrid(tiny):
    """In the child: a model with state-space layers (8 layers of which 1
    and 5 attend with one key/value head, E 256, state 16) serves three
    requests through DynamicInferenceEngine on the device, and
    the compiled decode step says what it holds."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    _refuse_unless_tpu(jax, tiny)
    from megatronapp_tpu.config.transformer_config import (
        ActivationKind, NormKind, PositionEmbeddingKind, TransformerConfig,
    )
    from megatronapp_tpu.inference.dynamic_engine import (
        DynamicInferenceEngine,
    )
    from megatronapp_tpu.inference.engine import SamplingParams
    from megatronapp_tpu.models.gpt import init_gpt_params
    from megatronapp_tpu.utils.platform import (
        device_line, enable_compile_cache,
    )
    enable_compile_cache()
    _say(device_line())
    cfg = TransformerConfig(
        hidden_size=128, num_attention_heads=2, num_query_groups=1,
        ffn_hidden_size=256, vocab_size=512, max_position_embeddings=128,
        normalization=NormKind.rmsnorm, activation=ActivationKind.swiglu,
        add_bias_linear=False,
        position_embedding=PositionEmbeddingKind.none, ssm_inner_norms=True,
        params_dtype=jnp.bfloat16, **HYBRID)
    params = init_gpt_params(jax.random.PRNGKey(0), cfg)[0]
    eng = DynamicInferenceEngine(params, cfg, max_batch=8, max_seq_len=128)
    _say(eng.startup_line())
    rng = np.random.default_rng(0)
    for n in (40, 9, 70):
        eng.add_request(rng.integers(0, 512, n).astype(np.int32), 12,
                        SamplingParams(greedy=True))
    out = eng.run_to_completion()
    b, mb = eng.max_batch, eng.pool.page_table.shape[1]
    compiled = eng._decode.lower(
        eng.params, jnp.zeros((b, 1), jnp.int32), eng._pools(), None,
        jnp.zeros((b, mb), jnp.int32), jnp.zeros((b,), jnp.int32),
        jnp.ones((b,), bool), None).compile()
    text = compiled.as_text()
    pools = eng._pools()
    _say(RESULT_PREFIX + json.dumps({
        "tokens": sum(len(v) for v in out.values()),
        "in_vocab": bool(all(0 <= t < 512 for v in out.values()
                             for t in v)),
        "state": eng.stats_snapshot()["state"],
        "ssm_update_calls": sum(
            1 for ln in text.splitlines()
            if "custom-call(" in ln and " %ssm_update" in ln.split("=")[0]),
        "pool_shapes": [list(p.shape) for p in pools],
        "pool_bytes": sum(p.size * p.dtype.itemsize for p in pools),
        "alias_bytes": compiled.memory_analysis().alias_size_in_bytes}))


def check_hybrid(rc, lines, tiny=False):
    out = {"phase": "hybrid", "ok": False, "problems": []}
    dev = _tagged(lines, DEVICE_LINE_PREFIX)
    out["device"] = dev[0] if dev else None
    res = _tagged(lines, RESULT_PREFIX)
    if rc != 0 or not res:
        out["problems"].append(f"child exited {rc} with "
                               f"{len(res)} result lines")
        return out
    out.update(res[0])
    interpreted = tiny and dev and dev[0]["platform"] != "tpu"
    # One ssm_update a scanned run of state-space layers, not one a layer:
    # a period of 4 with the attention layer second is a run of one layer
    # and a run of two under the outer scan over the two periods. (The
    # interpreter inlines a kernel into plain HLO: nothing to count.)
    if not interpreted and out["ssm_update_calls"] != 2:
        out["problems"].append(
            f"{out['ssm_update_calls']} ssm_update custom calls in the "
            "compiled decode step, not one a layer loop (2)")
    if out["alias_bytes"] < out["pool_bytes"]:
        out["problems"].append(
            f"the decode step aliases {out['alias_bytes']} B of "
            f"{out['pool_bytes']} B of pools: a pool is copied")
    if out["tokens"] != 40 + 9 + 70 + 3 * 12 or not out["in_vocab"]:
        out["problems"].append(f"{out['tokens']} tokens came back, or one "
                               "outside the vocabulary")
    state = out["state"] or {}
    if (state.get("layers"), state.get("resets")) != (6, 3):
        out["problems"].append(f"state counters {state}")
    if not any("paged decode" in ln and _kernel_mode(dev, tiny) in ln
               for ln in lines):
        out["problems"].append("the engine did not say it ran the paged "
                               f"decode kernel {_kernel_mode(dev, tiny)}")
    out["ok"] = not out["problems"]
    return out


# ---------------------------------------------------------------------------
# Phase: Mamba-2 mixers, held experts and Granite's multipliers, tiny widths
# ---------------------------------------------------------------------------

SSD = dict(num_layers=8, attn_layer_period=4, attn_layer_offset=1,
           ssm_heads=4, ssm_head_dim=64, ssm_state_dim=128,
           ssm_chunk_size=32, num_moe_experts=8, moe_experts_held=(0, 4),
           moe_router_topk=3, moe_ffn_hidden_size=64,
           moe_shared_expert_intermediate_size=128)
SSD_REQUESTS = ((70, 12), (9, 12), (40, 12))


def phase_ssd(tiny):
    rc, tr = _run_child("ssd", ["--child", "ssd"]
                        + (["--tiny"] if tiny else []), timeout_s=420)
    return check_ssd(rc, tr.lines, tiny)


def child_ssd(tiny):
    """In the child: 8 layers of H 128 of which 1 and 5 attend, the others
    Mamba-2 mixers whose plane [128, 256] float32 the decode kernel takes in
    two tiles of E (BLOCK_BYTES set to half a plane: at the published
    [128, 8192] the tiles are the kernel's own), serve three requests
    through DynamicInferenceEngine on the device."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    _refuse_unless_tpu(jax, tiny)
    from megatronapp_tpu.config.transformer_config import (
        ActivationKind, NormKind, PositionEmbeddingKind, TransformerConfig,
    )
    from megatronapp_tpu.inference.dynamic_engine import (
        DynamicInferenceEngine,
    )
    from megatronapp_tpu.inference.engine import SamplingParams
    from megatronapp_tpu.models.gpt import init_gpt_params
    from megatronapp_tpu.ops.pallas import ssm_update
    from megatronapp_tpu.utils.platform import (
        device_line, enable_compile_cache,
    )
    enable_compile_cache()
    _say(device_line())
    ssm_update.BLOCK_BYTES = 128 * 128 * 4
    cfg = TransformerConfig(
        hidden_size=128, num_attention_heads=2, num_query_groups=1,
        ffn_hidden_size=128, vocab_size=512, max_position_embeddings=128,
        normalization=NormKind.rmsnorm, activation=ActivationKind.swiglu,
        add_bias_linear=False, position_embedding=PositionEmbeddingKind.none,
        embedding_multiplier=12.0, attention_multiplier=1 / 64,
        residual_multiplier=0.22, logits_scaling=16.0,
        params_dtype=jnp.bfloat16, **SSD)
    params = init_gpt_params(jax.random.PRNGKey(0), cfg)[0]
    eng = DynamicInferenceEngine(params, cfg, max_batch=8, max_seq_len=128,
                                 prefill_chunk=64)
    _say(eng.startup_line())
    rng = np.random.default_rng(0)
    for n, new in SSD_REQUESTS:
        eng.add_request(rng.integers(0, 512, n).astype(np.int32), new,
                        SamplingParams(greedy=True))
    out = eng.run_to_completion()
    b, mb = eng.max_batch, eng.pool.page_table.shape[1]
    compiled = eng._decode.lower(
        eng.params, jnp.zeros((b, 1), jnp.int32), eng._pools(), None,
        jnp.zeros((b, mb), jnp.int32), jnp.zeros((b,), jnp.int32),
        jnp.ones((b,), bool), None).compile()
    text = compiled.as_text()
    pools = eng._pools()
    stats = eng.stats_snapshot()
    _say(RESULT_PREFIX + json.dumps({
        "tokens": sum(len(v) for v in out.values()),
        "in_vocab": bool(all(0 <= t < 512 for v in out.values()
                             for t in v)),
        "state": stats["state"], "moe": stats["moe"],
        "ssm_update_calls": sum(
            1 for ln in text.splitlines()
            if "custom-call(" in ln and " %ssm_update" in ln.split("=")[0]),
        "e_tiles": 256 // ssm_update._tile(128, 256),
        "pool_shapes": [list(p.shape) for p in pools],
        "pool_bytes": sum(p.size * p.dtype.itemsize for p in pools),
        "alias_bytes": compiled.memory_analysis().alias_size_in_bytes}))


def check_ssd(rc, lines, tiny=False):
    out = {"phase": "ssd", "ok": False, "problems": []}
    dev = _tagged(lines, DEVICE_LINE_PREFIX)
    out["device"] = dev[0] if dev else None
    res = _tagged(lines, RESULT_PREFIX)
    if rc != 0 or not res:
        out["problems"].append(f"child exited {rc} with "
                               f"{len(res)} result lines")
        return out
    out.update(res[0])
    interpreted = tiny and dev and dev[0]["platform"] != "tpu"
    if not interpreted and out["ssm_update_calls"] != 2:
        out["problems"].append(
            f"{out['ssm_update_calls']} ssm_update custom calls in the "
            "compiled decode step, not one a layer loop (2)")
    if out["e_tiles"] != 2:
        out["problems"].append(f"a plane in {out['e_tiles']} tiles of E, "
                               "not 2")
    if out["alias_bytes"] < out["pool_bytes"]:
        out["problems"].append(
            f"the decode step aliases {out['alias_bytes']} B of "
            f"{out['pool_bytes']} B of pools: a pool is copied")
    want = sum(n + new for n, new in SSD_REQUESTS)
    if out["tokens"] != want or not out["in_vocab"]:
        out["problems"].append(f"{out['tokens']} tokens came back (not "
                               f"{want}), or one outside the vocabulary")
    state = out["state"] or {}
    if (state.get("mixer"), state.get("layers"), state.get("resets"),
            state.get("conv_channels")) != ("mamba2", 6, 3, 512):
        out["problems"].append(f"state counters {state}")
    moe = out["moe"] or {}
    picks = moe.get("tokens", 0) * 3 * 8
    if (not picks or moe.get("assignments") != picks
            or moe.get("assignments_here", 0)
            + moe.get("assignments_absent", 0) != picks
            or not moe.get("assignments_here")
            or not moe.get("assignments_absent")):
        out["problems"].append(f"the router's picks do not add up: {moe}")
    out["ok"] = not out["problems"]
    return out


# ---------------------------------------------------------------------------
# Phase: EVA attention through the paged engine, tiny widths
# ---------------------------------------------------------------------------

EVA = dict(eva_window_size=256, eva_chunk_size=16)
EVA_WINDOW_BLOCKS = 16      # blocks of 16 rows a window of 256 fills


def _eva_requests(tiny):
    """(prompt, new tokens) of the phase's three requests: windows close in
    prefill (the first and third) and, at the real size, in decode (the
    second); the interpreter's rounds are too slow for that one."""
    return ((270, 6), (20, 6), (520, 6)) if tiny else (
        (300, 40), (9, 270), (600, 30))


def phase_eva(tiny):
    rc, tr = _run_child("eva", ["--child", "eva"]
                        + (["--tiny"] if tiny else []), timeout_s=420)
    return check_eva(rc, tr.lines, tiny)


def child_eva(tiny):
    """In the child: a model with EVA attention (4 layers, 2 heads of 128,
    an 8-column byte head) serves three requests that close one, one and
    two windows through DynamicInferenceEngine on the device,
    and the compiled decode step says what it holds."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    _refuse_unless_tpu(jax, tiny)
    from megatronapp_tpu.config.transformer_config import (
        ActivationKind, NormKind, TransformerConfig,
    )
    from megatronapp_tpu.inference.dynamic_engine import (
        DynamicInferenceEngine,
    )
    from megatronapp_tpu.inference.engine import SamplingParams
    from megatronapp_tpu.models.gpt import init_gpt_params
    from megatronapp_tpu.utils.platform import (
        device_line, enable_compile_cache,
    )
    enable_compile_cache()
    _say(device_line())
    cfg = TransformerConfig(
        num_layers=4, hidden_size=256, num_attention_heads=2,
        ffn_hidden_size=512, vocab_size=320, true_vocab_size=320,
        max_position_embeddings=1024, rotary_base=1e5,
        normalization=NormKind.rmsnorm, norm_unit_offset=True,
        activation=ActivationKind.swiglu, add_bias_linear=False,
        untie_embeddings_and_output_weights=True, num_pred_heads=8,
        params_dtype=jnp.bfloat16, **EVA)
    params = init_gpt_params(jax.random.PRNGKey(0), cfg)[0]
    eng = DynamicInferenceEngine(params, cfg, max_batch=4, max_seq_len=1024)
    _say(eng.startup_line())
    rng = np.random.default_rng(0)
    for n, new in _eva_requests(tiny):
        eng.add_request(rng.integers(0, 320, n).astype(np.int32), new,
                        SamplingParams(greedy=True))
    out = eng.run_to_completion()
    b, mb = eng.max_batch, eng.pool.page_table.shape[1]
    compiled = eng._decode.lower(
        eng.params, jnp.zeros((b, 1), jnp.int32), eng._pools(), None,
        jnp.zeros((b, mb), jnp.int32), jnp.zeros((b,), jnp.int32),
        jnp.ones((b,), bool), None).compile()
    text = compiled.as_text()
    pools = eng._pools()
    _say(RESULT_PREFIX + json.dumps({
        "tokens": sum(len(v) for v in out.values()),
        "in_vocab": bool(all(0 <= t < 320 for v in out.values()
                             for t in v)),
        "eva": eng.stats_snapshot()["eva"],
        "table_blocks": mb,
        "blocks_in_use_after": eng.pool.blocks_in_use(),
        "eva_summary_calls": sum(
            1 for ln in text.splitlines()
            if "custom-call(" in ln and " %eva_summary" in ln.split("=")[0]),
        "pool_bytes": sum(p.size * p.dtype.itemsize for p in pools),
        "alias_bytes": compiled.memory_analysis().alias_size_in_bytes}))


def check_eva(rc, lines, tiny=False):
    out = {"phase": "eva", "ok": False, "problems": []}
    dev = _tagged(lines, DEVICE_LINE_PREFIX)
    out["device"] = dev[0] if dev else None
    res = _tagged(lines, RESULT_PREFIX)
    if rc != 0 or not res:
        out["problems"].append(f"child exited {rc} with "
                               f"{len(res)} result lines")
        return out
    out.update(res[0])
    interpreted = tiny and dev and dev[0]["platform"] != "tpu"
    # The layers are one scanned stack: one summariser in the loop's body.
    # (The interpreter inlines a kernel into plain HLO: nothing to count.)
    if not interpreted and out["eva_summary_calls"] != 1:
        out["problems"].append(
            f"{out['eva_summary_calls']} eva_summary custom calls in the "
            "compiled decode step, not one a layer loop (1)")
    if out["alias_bytes"] < out["pool_bytes"]:
        out["problems"].append(
            f"the decode step aliases {out['alias_bytes']} B of "
            f"{out['pool_bytes']} B of pools: a pool is copied")
    requests = _eva_requests(tiny)
    want = sum(n + new for n, new in requests)
    if out["tokens"] != want or not out["in_vocab"]:
        out["problems"].append(f"{out['tokens']} tokens came back, not "
                               f"{want}, or one outside the vocabulary")
    eva = out["eva"] or {}
    # a request caches all its tokens but the last: 339, 278 and 629 rows
    # at the real size close 1 + 1 + 2 windows
    closed = sum((n + new - 2) // EVA["eva_window_size"]
                 for n, new in requests)
    if (eva.get("windows_closed"), eva.get("blocks_freed")) != (
            closed, closed * EVA_WINDOW_BLOCKS):
        out["problems"].append(f"eva counters {eva}, not {closed} windows "
                               "closed and 16 blocks freed by each")
    # 16 blocks of window + one of summaries a window of the 1024 positions
    if not 0 < eva.get("max_blocks_slot", 0) <= out["table_blocks"] == 20:
        out["problems"].append(
            f"a slot held {eva.get('max_blocks_slot')} blocks of a table "
            f"of {out['table_blocks']} (20: 16 + 1 a window)")
    if out["blocks_in_use_after"]:
        out["problems"].append(f"{out['blocks_in_use_after']} blocks "
                               "still held after the last request")
    if not any("paged decode" in ln and _kernel_mode(dev, tiny) in ln
               for ln in lines):
        out["problems"].append("the engine did not say it ran the paged "
                               f"decode kernel {_kernel_mode(dev, tiny)}")
    out["ok"] = not out["problems"]
    return out


# ---------------------------------------------------------------------------
# Phase: a double layer with a share of its experts, tiny widths
# ---------------------------------------------------------------------------

SHARE = dict(num_layers=2, num_moe_experts=8, moe_zero_experts=4,
             moe_router_topk=3, moe_experts_held=(0, 4))
SHARE_REQUESTS = ((40, 12), (9, 12), (70, 12))      # (prompt, new tokens)


def phase_share(tiny):
    rc, tr = _run_child("share", ["--child", "share"]
                        + (["--tiny"] if tiny else []), timeout_s=420)
    return check_share(rc, tr.lines, tiny)


def child_share(tiny):
    """In the child: a shortcut-connected double-layer model (latent
    attention at the published column widths 512 + 64 and 128-wide heads,
    4 heads, a query latent of 128, both scale corrections; 8 + 4 experts
    top-3 with a selection bias, 4 held) serves three requests through
    DynamicInferenceEngine on the device, and the compiled
    decode step says what it holds."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    _refuse_unless_tpu(jax, tiny)
    from megatronapp_tpu.config.transformer_config import (
        ActivationKind, NormKind, TransformerConfig,
    )
    from megatronapp_tpu.inference.dynamic_engine import (
        DynamicInferenceEngine,
    )
    from megatronapp_tpu.inference.engine import SamplingParams
    from megatronapp_tpu.models.gpt import init_gpt_params
    from megatronapp_tpu.utils.platform import (
        device_line, enable_compile_cache,
    )
    enable_compile_cache()
    _say(device_line())
    cfg = TransformerConfig(
        hidden_size=256, num_attention_heads=4, ffn_hidden_size=512,
        vocab_size=512, max_position_embeddings=128,
        normalization=NormKind.rmsnorm, activation=ActivationKind.swiglu,
        add_bias_linear=False, untie_embeddings_and_output_weights=True,
        multi_latent_attention=True, q_lora_rank=128, kv_lora_rank=512,
        mla_scale_q_lora=True, mla_scale_kv_lora=True,
        moe_ffn_hidden_size=128, moe_router_norm_topk_prob=False,
        moe_routed_scaling_factor=6.0, moe_router_selection_bias=True,
        moe_shortcut_double_layer=True, params_dtype=jnp.bfloat16, **SHARE)
    params = init_gpt_params(jax.random.PRNGKey(0), cfg)[0]
    eng = DynamicInferenceEngine(params, cfg, max_batch=8, max_seq_len=128)
    _say(eng.startup_line())
    rng = np.random.default_rng(0)
    for n, new in SHARE_REQUESTS:
        eng.add_request(rng.integers(0, 512, n).astype(np.int32), new,
                        SamplingParams(greedy=True))
    out = eng.run_to_completion()
    b, mb = eng.max_batch, eng.pool.page_table.shape[1]
    compiled = eng._decode.lower(
        eng.params, jnp.zeros((b, 1), jnp.int32), eng._pools(), None,
        jnp.zeros((b, mb), jnp.int32), jnp.zeros((b,), jnp.int32),
        jnp.ones((b,), bool), None).compile()
    text = compiled.as_text()
    pools = eng._pools()
    _say(RESULT_PREFIX + json.dumps({
        "tokens": sum(len(v) for v in out.values()),
        "in_vocab": bool(all(0 <= t < 512 for v in out.values()
                             for t in v)),
        "moe": eng.stats_snapshot()["moe"],
        "latent_kernel_calls": sum(
            1 for ln in text.splitlines() if "custom-call(" in ln
            and " %paged_decode_latent" in ln.split("=")[0]),
        "pool_shapes": [list(p.shape) for p in pools],
        "pool_bytes": sum(p.size * p.dtype.itemsize for p in pools),
        "alias_bytes": compiled.memory_analysis().alias_size_in_bytes}))


def check_share(rc, lines, tiny=False):
    out = {"phase": "share", "ok": False, "problems": []}
    dev = _tagged(lines, DEVICE_LINE_PREFIX)
    out["device"] = dev[0] if dev else None
    res = _tagged(lines, RESULT_PREFIX)
    if rc != 0 or not res:
        out["problems"].append(f"child exited {rc} with "
                               f"{len(res)} result lines")
        return out
    out.update(res[0])
    interpreted = tiny and dev and dev[0]["platform"] != "tpu"
    # The layers are one scanned stack of double layers: the loop's body
    # holds one latent kernel a sublayer. (The interpreter inlines a kernel
    # into plain HLO: nothing to count.)
    if not interpreted and out["latent_kernel_calls"] != 2:
        out["problems"].append(
            f"{out['latent_kernel_calls']} paged_decode_latent custom "
            "calls in the compiled decode step, not two a layer loop")
    if [sh[0] for sh in out["pool_shapes"]] != [4, 4]:
        out["problems"].append(f"pools {out['pool_shapes']}: not two "
                               "planes a layer of 2 layers")
    if out["alias_bytes"] < out["pool_bytes"]:
        out["problems"].append(
            f"the decode step aliases {out['alias_bytes']} B of "
            f"{out['pool_bytes']} B of pools: a pool is copied")
    want = sum(n + new for n, new in SHARE_REQUESTS)
    if out["tokens"] != want or not out["in_vocab"]:
        out["problems"].append(f"{out['tokens']} tokens came back, not "
                               f"{want}, or one outside the vocabulary")
    moe = out["moe"] or {}
    picks = moe.get("tokens", 0) * SHARE["moe_router_topk"] \
        * SHARE["num_layers"]
    parts = [moe.get(k, 0) for k in ("assignments_zero", "assignments_here",
                                     "assignments_absent")]
    if not picks or sum(parts) != picks or not all(parts) \
            or moe.get("experts_here") != SHARE["moe_experts_held"][1]:
        out["problems"].append(
            f"moe counters {moe}: the picks are not held + absent + "
            f"zero-compute = {picks}")
    out["ok"] = not out["problems"]
    return out


# ---------------------------------------------------------------------------
# Phase: gated short convolutions with MoE feed-forwards, tiny widths
# ---------------------------------------------------------------------------

CONV = dict(num_layers=5, attn_layer_period=4, attn_layer_offset=1,
            shortconv_kernel=3, num_moe_experts=8, moe_router_topk=2,
            moe_first_k_dense=1)
CONV_REQUESTS = ((40, 12), (9, 12), (70, 12))       # (prompt, new tokens)


def phase_conv(tiny):
    rc, tr = _run_child("conv", ["--child", "conv"]
                        + (["--tiny"] if tiny else []), timeout_s=420)
    return check_conv(rc, tr.lines, tiny)


def child_conv(tiny):
    """In the child: a hybrid of gated short convolutions and grouped-query
    attention (5 layers of which 1 attends with 4 query heads on 2 key/value
    heads of 64 and per-head norms; a leading dense layer, then 8 experts
    top-2 by sigmoid scores + a selection bias) serves three requests
    through DynamicInferenceEngine on the device, and the
    compiled decode step says what it holds."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    _refuse_unless_tpu(jax, tiny)
    from megatronapp_tpu.config.transformer_config import (
        ActivationKind, NormKind, TransformerConfig,
    )
    from megatronapp_tpu.inference.dynamic_engine import (
        DynamicInferenceEngine,
    )
    from megatronapp_tpu.inference.engine import SamplingParams
    from megatronapp_tpu.models.gpt import init_gpt_params
    from megatronapp_tpu.utils.platform import (
        device_line, enable_compile_cache,
    )
    enable_compile_cache()
    _say(device_line())
    cfg = TransformerConfig(
        hidden_size=256, num_attention_heads=4, num_query_groups=2,
        ffn_hidden_size=512, vocab_size=512, max_position_embeddings=128,
        normalization=NormKind.rmsnorm, activation=ActivationKind.swiglu,
        add_bias_linear=False, qk_layernorm=True, rotary_base=1e6,
        moe_ffn_hidden_size=128, moe_router_score="sigmoid",
        moe_router_selection_bias=True, params_dtype=jnp.bfloat16, **CONV)
    params = init_gpt_params(jax.random.PRNGKey(0), cfg)[0]
    eng = DynamicInferenceEngine(params, cfg, max_batch=8, max_seq_len=128)
    _say(eng.startup_line())
    rng = np.random.default_rng(0)
    for n, new in CONV_REQUESTS:
        eng.add_request(rng.integers(0, 512, n).astype(np.int32), new,
                        SamplingParams(greedy=True))
    out = eng.run_to_completion()
    b, mb = eng.max_batch, eng.pool.page_table.shape[1]
    compiled = eng._decode.lower(
        eng.params, jnp.zeros((b, 1), jnp.int32), eng._pools(), None,
        jnp.zeros((b, mb), jnp.int32), jnp.zeros((b,), jnp.int32),
        jnp.ones((b,), bool), None).compile()
    pools = eng._pools()
    stats = eng.stats_snapshot(include_dispatch=True)
    _say(RESULT_PREFIX + json.dumps({
        "tokens": sum(len(v) for v in out.values()),
        "in_vocab": bool(all(0 <= t < 512 for v in out.values()
                             for t in v)),
        "moe": stats["moe"], "state": stats["state"],
        "expert_stack_slices": stats["decode_dispatch"].get(
            "expert_stack_slices"),
        "pool_shapes": [list(p.shape) for p in pools],
        "pool_bytes": sum(p.size * p.dtype.itemsize for p in pools),
        "alias_bytes": compiled.memory_analysis().alias_size_in_bytes}))


def check_conv(rc, lines, tiny=False):
    out = {"phase": "conv", "ok": False, "problems": []}
    dev = _tagged(lines, DEVICE_LINE_PREFIX)
    out["device"] = dev[0] if dev else None
    res = _tagged(lines, RESULT_PREFIX)
    if rc != 0 or not res:
        out["problems"].append(f"child exited {rc} with "
                               f"{len(res)} result lines")
        return out
    out.update(res[0])
    # K and V of the one attention layer's plane, then the convolution
    # layers' tails alone: [4, slots, 2 columns x 256], no h pool.
    shapes = out["pool_shapes"]
    if len(shapes) != 3 or shapes[0][0] != 1 or shapes[2] != [4, 8, 512]:
        out["problems"].append(f"pools {shapes}: not one attention plane "
                               "and one pool of 4 layers' two columns")
    if out["alias_bytes"] < out["pool_bytes"]:
        out["problems"].append(
            f"the decode step aliases {out['alias_bytes']} B of "
            f"{out['pool_bytes']} B of pools: a pool is copied")
    if out["expert_stack_slices"] != 0:
        out["problems"].append(
            f"{out['expert_stack_slices']} slices of a layer's experts out "
            "of their stacks in the traced decode step, not 0")
    want = sum(n + new for n, new in CONV_REQUESTS)
    if out["tokens"] != want or not out["in_vocab"]:
        out["problems"].append(f"{out['tokens']} tokens came back, not "
                               f"{want}, or one outside the vocabulary")
    state = out["state"] or {}
    if (state.get("kind"), state.get("layers"), state.get("resets")) != (
            "conv", 4, len(CONV_REQUESTS)):
        out["problems"].append(f"state counters {state}: not 4 convolution "
                               "layers' tails, reset once a request")
    moe = out["moe"] or {}
    picks = moe.get("tokens", 0) * CONV["moe_router_topk"] * (
        CONV["num_layers"] - CONV["moe_first_k_dense"])
    if not picks or moe.get("assignments") != picks \
            or moe.get("experts_here") != CONV["num_moe_experts"]:
        out["problems"].append(
            f"moe counters {moe}: the picks are not tokens x top-k x MoE "
            f"layers = {picks} over {CONV['num_moe_experts']} held experts")
    if not any("paged decode" in ln and _kernel_mode(dev, tiny) in ln
               for ln in lines):
        out["problems"].append("the engine did not say it ran the paged "
                               f"decode kernel {_kernel_mode(dev, tiny)}")
    out["ok"] = not out["problems"]
    return out


# ---------------------------------------------------------------------------
# Phase: sliding-window layers beside full ones, MoE feed-forwards, tiny widths
# ---------------------------------------------------------------------------

WINDOW = dict(num_layers=5, attn_layer_period=4, attn_layer_offset=0,
              sliding_window=32, sliding_window_heads=8, num_moe_experts=8,
              moe_router_topk=2, moe_first_k_dense=1)
WINDOW_REQUESTS = ((90, 40), (9, 12), (70, 30))     # (prompt, new tokens)


def phase_window(tiny):
    rc, tr = _run_child("window", ["--child", "window"]
                        + (["--tiny"] if tiny else []), timeout_s=420)
    return check_window(rc, tr.lines, tiny)


def child_window(tiny):
    """In the child: a sliding-window stack (5 layers: a dense full layer,
    then sliding, sliding, sliding, full; 6 / 8 query heads on 2 key/value
    heads of 64, per-head norms and gate, YaRN on half of a full layer's
    head and plain RoPE on a sliding layer's; 8 experts top-2 by sigmoid
    scores + a selection bias beside a shared one) serves three requests
    through DynamicInferenceEngine on the device, and the compiled decode
    step says what it holds."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    _refuse_unless_tpu(jax, tiny)
    from megatronapp_tpu.config.transformer_config import (
        ActivationKind, NormKind, PositionEmbeddingKind, TransformerConfig,
    )
    from megatronapp_tpu.inference.dynamic_engine import (
        DynamicInferenceEngine,
    )
    from megatronapp_tpu.inference.engine import SamplingParams
    from megatronapp_tpu.models.gpt import init_gpt_params
    from megatronapp_tpu.utils.platform import (
        device_line, enable_compile_cache,
    )
    enable_compile_cache()
    _say(device_line())
    cfg = TransformerConfig(
        hidden_size=256, num_attention_heads=6, num_query_groups=2,
        kv_channels=64, ffn_hidden_size=512, vocab_size=512,
        max_position_embeddings=256, normalization=NormKind.rmsnorm,
        activation=ActivationKind.swiglu, add_bias_linear=False,
        untie_embeddings_and_output_weights=True, qk_layernorm=True,
        attention_output_gate=True,
        position_embedding=PositionEmbeddingKind.yarn, rotary_base=5e5,
        rotary_percent=0.5, rope_scaling_factor=64.0,
        yarn_original_max_position=64, yarn_beta_fast=64.0,
        sliding_rotary_base=1e4, moe_ffn_hidden_size=128,
        moe_shared_expert_intermediate_size=128, moe_router_score="sigmoid",
        moe_router_selection_bias=True, moe_routed_scaling_factor=2.5,
        params_dtype=jnp.bfloat16, **WINDOW)
    params = init_gpt_params(jax.random.PRNGKey(0), cfg)[0]
    eng = DynamicInferenceEngine(params, cfg, max_batch=8, max_seq_len=256,
                                 prefill_chunk=64)
    _say(eng.startup_line())
    rng = np.random.default_rng(0)
    for n, new in WINDOW_REQUESTS:
        eng.add_request(rng.integers(0, 512, n).astype(np.int32), new,
                        SamplingParams(greedy=True))
    out = eng.run_to_completion()
    eng.pool.audit()
    b, mb = eng.max_batch, eng.pool.page_table.shape[1]
    table = jnp.zeros((b, mb), jnp.int32)
    compiled = eng._decode.lower(
        eng.params, jnp.zeros((b, 1), jnp.int32), eng._pools(), None,
        (table, table), jnp.zeros((b,), jnp.int32), jnp.ones((b,), bool),
        None).compile()
    pools = eng._pools()
    stats = eng.stats_snapshot(include_dispatch=True)
    text = compiled.as_text()
    # The same stack TRAINED over whole packed sequences: its window
    # layers' loss and gradient run the flash kernels' band (a window of 32
    # in tiles of 64 over 256 positions: most tiles are in no grid).
    import dataclasses
    from megatronapp_tpu.models.gpt import gpt_loss
    tcfg = dataclasses.replace(cfg, attention_impl="pallas",
                               flash_block_q=64, flash_block_kv=64)
    tok = jnp.asarray(rng.integers(0, 512, (2, 256)), jnp.int32)
    segs = jnp.asarray(np.arange(256)[None] // 100 * np.ones((2, 1)),
                       jnp.int32)
    grad = jax.jit(jax.value_and_grad(lambda p: gpt_loss(
        p, tok, jnp.roll(tok, -1, 1), jnp.ones((2, 256), jnp.float32), tcfg,
        segment_ids=segs)[0])).lower(params).compile()
    train_loss, grads = grad(params)
    train_text = grad.as_text()
    _say(RESULT_PREFIX + json.dumps({
        "train_loss": float(train_loss),
        "train_grads_finite": bool(all(
            bool(jnp.all(jnp.isfinite(g.astype(jnp.float32))))
            for g in jax.tree.leaves(grads))),
        "train_kernels": sorted({k for k in (
            "flash_window_fwd", "flash_window_bwd_dq",
            "flash_window_bwd_dkv") if k in train_text}),
        "tokens": sum(len(v) for v in out.values()),
        "in_vocab": bool(all(0 <= t < 512 for v in out.values()
                             for t in v)),
        "moe": stats["moe"], "window": stats["window"],
        "kernels": sorted({k for k in ("paged_decode", "paged_window_decode",
                                       "grouped_gemm") if f"%{k}" in text}),
        "pool_shapes": [list(p.shape) for p in pools],
        "pool_bytes": sum(p.size * p.dtype.itemsize for p in pools),
        "alias_bytes": compiled.memory_analysis().alias_size_in_bytes}))


def check_window(rc, lines, tiny=False):
    out = {"phase": "window", "ok": False, "problems": []}
    dev = _tagged(lines, DEVICE_LINE_PREFIX)
    out["device"] = dev[0] if dev else None
    res = _tagged(lines, RESULT_PREFIX)
    if rc != 0 or not res:
        out["problems"].append(f"child exited {rc} with "
                               f"{len(res)} result lines")
        return out
    out.update(res[0])
    # K and V of the two full planes, then of the three window planes, whose
    # pool holds 8 slots' windows (32 / 16 + 2 blocks) and one call's rows.
    shapes = out["pool_shapes"]
    if (len(shapes) != 4 or shapes[0][0] != 2 or shapes[2][:2] != [
            3, 8 * 4 + 64 // 16 + 1]):
        out["problems"].append(f"pools {shapes}: not two full planes and "
                               "three window planes of 37 blocks")
    if out["alias_bytes"] < out["pool_bytes"]:
        out["problems"].append(
            f"the decode step aliases {out['alias_bytes']} B of "
            f"{out['pool_bytes']} B of pools: a pool is copied")
    # (an interpreted kernel is no instruction of the compiled step)
    if _kernel_mode(dev, tiny) == "(compiled)" and out["kernels"] != [
            "grouped_gemm", "paged_decode", "paged_window_decode"]:
        out["problems"].append(f"the compiled decode step names "
                               f"{out['kernels']}: not both paged families "
                               "and the grouped GEMM")
    want = sum(n + new for n, new in WINDOW_REQUESTS)
    if out["tokens"] != want or not out["in_vocab"]:
        out["problems"].append(f"{out['tokens']} tokens came back, not "
                               f"{want}, or one outside the vocabulary")
    # the same stack trained: a finite loss near ln(512) and finite
    # gradients, through the three window kernels where they are compiled
    if not (5.5 < out["train_loss"] < 7.5) or not out["train_grads_finite"]:
        out["problems"].append(
            f"the stack's training loss {out['train_loss']} (ln 512 = 6.24 "
            "at seeded weights) or a gradient is not finite")
    if _kernel_mode(dev, tiny) == "(compiled)" and out["train_kernels"] != [
            "flash_window_bwd_dkv", "flash_window_bwd_dq",
            "flash_window_fwd"]:
        out["problems"].append(f"the compiled loss and gradient name "
                               f"{out['train_kernels']}: not the three "
                               "flash_window kernels")
    window = out["window"] or {}
    if (not window.get("blocks_taken")
            or window.get("blocks_taken") != window.get("blocks_given_back")
            or window.get("blocks_held") != 0
            or not window.get("rows_walked", 0)
            < window.get("rows_full_walk", 0)):
        out["problems"].append(f"window counters {window}: blocks taken and "
                               "not all given back, or no row spared")
    moe = out["moe"] or {}
    picks = moe.get("tokens", 0) * WINDOW["moe_router_topk"] * (
        WINDOW["num_layers"] - WINDOW["moe_first_k_dense"])
    if not picks or moe.get("assignments") != picks \
            or moe.get("experts_here") != WINDOW["num_moe_experts"]:
        out["problems"].append(
            f"moe counters {moe}: the picks are not tokens x top-k x MoE "
            f"layers = {picks} over {WINDOW['num_moe_experts']} held experts")
    if not any("paged decode, sliding window 32" in ln
               and _kernel_mode(dev, tiny) in ln for ln in lines):
        out["problems"].append("the engine did not say it ran the window "
                               f"decode kernel {_kernel_mode(dev, tiny)}")
    out["ok"] = not out["problems"]
    return out


# ---------------------------------------------------------------------------
# Phase: four chips (only with --chips 4)
# ---------------------------------------------------------------------------

def check_multichip(rc, lines):
    dev = _tagged(lines, DEVICE_LINE_PREFIX)
    res = _tagged(lines, RESULT_PREFIX)
    problems = [f"child exited {rc}"] if rc != 0 else []
    if not res:
        problems.append("no result line")
    res = res[0] if res else {"problems": []}
    problems += res["problems"]
    return {**res, "phase": "multichip", "ok": not problems,
            "problems": problems, "device": dev[-1] if dev else None}


def phase_multichip(tiny):
    rc, tr = _run_child("multichip", ["--child", "multichip"]
                        + (["--tiny"] if tiny else []), timeout_s=1100)
    return check_multichip(rc, tr.lines)


def child_multichip(tiny):
    """One process holding all four chips: the same model, seed and
    global batch on (a) one of the four devices, (b) tp2 x dp2 and
    (c) tp2 x pp2, all through training/train.py's pretrain_gpt."""
    import jax

    _refuse_unless_tpu(jax, tiny)
    from megatronapp_tpu.config.arguments import (
        build_parser, configs_from_args, parse_args,
    )
    from megatronapp_tpu.config.parallel_config import ParallelConfig
    from megatronapp_tpu.data.mock import mock_batches
    from megatronapp_tpu.models.gpt import init_gpt_params
    from megatronapp_tpu.parallel.mesh import build_mesh
    from megatronapp_tpu.trace.profiler_collectives import (
        extract_hlo_collectives,
    )
    from megatronapp_tpu.training.optimizer import get_optimizer
    from megatronapp_tpu.training.train import (
        gpt_microbatch_loss, pretrain_gpt, reshape_global_batch,
    )
    from megatronapp_tpu.training.train_state import setup_train_state
    from megatronapp_tpu.training.train_step import make_train_step

    problems = []
    devs = jax.devices()
    if len(devs) != 4:
        raise SystemExit(f"chip_smoke --chips 4: JAX sees {len(devs)} "
                         "devices")
    base = _multi_args(tiny)

    def configs(extra):
        _say("argv: pretrain_gpt.py " + " ".join(base + extra))
        return configs_from_args(parse_args(build_parser("chip_smoke"),
                                            base + extra))

    def spread(x):
        """(devices an array lives on, distinct shard index ranges)."""
        return (len(x.sharding.device_set),
                len({str(s.index) for s in x.addressable_shards}))

    legs = {}
    # (a) one of the four devices.
    model, par, train, opt = configs([])
    t0 = time.perf_counter()
    ra = pretrain_gpt(model, ParallelConfig(), train, opt,
                      ctx=build_mesh(ParallelConfig(), devices=devs[:1]))
    legs["one_device"] = {"losses": [float(x) for x in ra.losses],
                          "wall_s": round(time.perf_counter() - t0, 2)}
    del ra

    # (b) tp2 x dp2 on the mesh pretrain_gpt builds from all four.
    model, par, train, opt = configs(["--tensor-model-parallel-size", "2"])
    t0 = time.perf_counter()
    rb = pretrain_gpt(model, par, train, opt)
    leg = {"losses": [float(x) for x in rb.losses],
           "wall_s": round(time.perf_counter() - t0, 2)}
    ctx = build_mesh(par)
    leg["mesh"] = dict(ctx.mesh.shape)
    # The step as pretrain_gpt builds it, compiled once more for its
    # text and for the sharding it takes its batch in.
    optimizer = get_optimizer(
        opt, train.train_iters,
        distributed=par.distributed_optimizer and not par.fsdp)
    state, shardings, _ = setup_train_state(
        jax.random.PRNGKey(train.seed),
        lambda k: init_gpt_params(k, model), optimizer, ctx)
    step = make_train_step(gpt_microbatch_loss(model, ctx=ctx), optimizer,
                           opt, ctx, shardings, train.train_iters,
                           check_nan=train.check_for_nan_in_loss)
    batch = reshape_global_batch(
        next(mock_batches(train.seq_length, model.vocab_size,
                          train.global_batch_size, seed=train.seed)),
        train.num_microbatches(ctx.dp * ctx.ep))
    with ctx.mesh:
        compiled = step.lower(state, batch).compile()
    text = compiled.as_text()
    tokens = jax.device_put(batch["tokens"],
                            compiled.input_shardings[0][1]["tokens"])
    w = rb.state["params"]["block"]["attention"]["q_kernel"]
    leg["q_kernel"] = {"shape": list(w.shape), "spec": str(w.sharding.spec),
                       "devices,shards": spread(w)}
    leg["batch_tokens"] = {"shape": list(tokens.shape),
                           "spec": str(tokens.sharding.spec),
                           "devices,shards": spread(tokens)}
    if spread(w) != (4, 2):
        problems.append(f"tp-sharded q_kernel on (devices, shards) "
                        f"{spread(w)}, expected (4, 2)")
    if spread(tokens) != (4, 2):
        problems.append(f"batch on (devices, shards) {spread(tokens)}, "
                        "expected (4, 2)")
    kinds = {}
    for c in extract_hlo_collectives(text, ctx.mesh).values():
        kinds[c["kind"]] = kinds.get(c["kind"], 0) + 1
    leg["collectives_in_compiled_step"] = kinds
    leg["tpu_custom_calls"] = text.count("tpu_custom_call")
    if not kinds:
        problems.append("no collective in the compiled tp2 x dp2 step")
    legs["tp2_dp2"] = leg
    del rb, state, step, compiled

    # (c) tp2 x pp2: flash attention is off inside the pipeline body
    # (transformer/attention.py: no kernel inside a manual region).
    model, par, train, opt = configs(
        ["--tensor-model-parallel-size", "2",
         "--pipeline-model-parallel-size", "2"])
    t0 = time.perf_counter()
    rc_ = pretrain_gpt(model, par, train, opt)
    legs["tp2_pp2"] = {"losses": [float(x) for x in rc_.losses],
                       "wall_s": round(time.perf_counter() - t0, 2),
                       "mesh": dict(build_mesh(par).mesh.shape)}
    del rc_

    ref = legs["one_device"]["losses"]
    for name in ("tp2_dp2", "tp2_pp2"):
        got = legs[name]["losses"]
        gap = max((abs(a - b) for a, b in zip(ref, got)),
                  default=float("inf"))
        legs[name]["max_abs_loss_gap_vs_one_device"] = gap
        if len(got) != len(ref) or not gap <= MULTI_LOSS_TOL:
            problems.append(f"{name} losses {got} vs one device {ref}: "
                            f"gap {gap} > {MULTI_LOSS_TOL}")
    _say(RESULT_PREFIX + json.dumps({"legs": legs, "problems": problems,
                                     "tolerance": MULTI_LOSS_TOL}))


# ---------------------------------------------------------------------------
# Verdict
# ---------------------------------------------------------------------------

def verdict(phases, want_count):
    """(ok, device, reasons): every phase passed, on a TPU, on the device
    count this run was asked to use."""
    reasons = []
    device = None
    for ph in phases:
        dev = ph.get("device")
        for p in ph.get("problems", []):
            reasons.append(f"{ph['phase']}: {p}")
        if not ph.get("ok") and not ph.get("problems"):
            reasons.append(f"{ph['phase']}: failed")
        if dev is None:
            continue
        device = device or {k: dev[k] for k in ("platform", "kind",
                                                "count")}
        if dev["platform"] != "tpu":
            reasons.append(f"{ph['phase']}: ran on {dev['platform']!r}, "
                           "not a TPU")
        if dev["count"] != want_count:
            reasons.append(f"{ph['phase']}: JAX saw {dev['count']} "
                           f"devices, this run is for {want_count}")
    if not phases:
        reasons.append("no phase ran")
    first = {ph["phase"]: ph["losses"][0] for ph in phases
             if ph["phase"].startswith("train-") and ph.get("losses")}
    if len(first) == 2:
        a, b = first.values()
        gap = abs(a - b)
        if not gap <= FIRST_LOSS_TOL:
            reasons.append(f"first-step losses {first} differ by {gap}, "
                           f"more than {FIRST_LOSS_TOL}")
    return not reasons, device, reasons


def run(chips, tiny):
    phases = []
    if chips == 4:
        plan = [lambda: phase_multichip(tiny)]
    else:
        # The A/B of the two attention paths: `auto` (flash on the chip
        # at the real size, dense at --tiny's) against the path it did
        # not take.
        plan = [lambda: phase_train("auto", tiny),
                lambda: phase_train("pallas" if tiny else "reference",
                                    tiny),
                lambda: phase_server(tiny),
                lambda: phase_hybrid(tiny),
                lambda: phase_eva(tiny),
                lambda: phase_share(tiny),
                lambda: phase_conv(tiny),
                lambda: phase_window(tiny),
                lambda: phase_ssd(tiny)]
    for step in plan:
        ph = step()
        phases.append(ph)
        _say("phase: " + json.dumps(ph))
        dev = ph.get("device")
        if not tiny and (dev is None or dev["platform"] != "tpu"):
            _say("no TPU under this phase; the remaining phases are not "
                 "run")
            break
    ok, device, reasons = verdict(phases, chips)
    for r in reasons:
        _say("FAILED: " + r)
    _say(json.dumps({"ok": ok, "device": device}))
    return 0 if ok else 1


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=[1, 4], default=1)
    ap.add_argument("--tiny", action="store_true",
                    help="rehearsal sizes; phases may run on the CPU, the "
                         "verdict still needs a TPU")
    ap.add_argument("--child", choices=["train", "multichip", "hybrid", "eva",
                                        "share", "conv", "window", "ssd"],
                    help=argparse.SUPPRESS)
    ap.add_argument("--impl", default="auto", help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.child == "train":
        child_train(args.impl, args.tiny)
        return 0
    if args.child == "multichip":
        child_multichip(args.tiny)
        return 0
    if args.child == "hybrid":
        child_hybrid(args.tiny)
        return 0
    if args.child == "eva":
        child_eva(args.tiny)
        return 0
    if args.child == "share":
        child_share(args.tiny)
        return 0
    if args.child == "conv":
        child_conv(args.tiny)
        return 0
    if args.child == "window":
        child_window(args.tiny)
        return 0
    if args.child == "ssd":
        child_ssd(args.tiny)
        return 0
    return run(args.chips, args.tiny)


if __name__ == "__main__":
    sys.exit(main())
