"""Cross-process fleet serving: replica RPC workers + process router
(ISSUE 18).

PR 14's `FleetRouter` proved the fleet story — KV-affinity admission,
live token-exact migration, zero-lost failover, rolling reloads —
inside ONE Python process. This module promotes the replica boundary to
a real OS process boundary, the way the reference's multi-node pieces
are actually deployed (MegaDPP's background sender/receiver processes,
MegaScan's per-rank trace collection; the MPMD pipeline work in
PAPERS.md runs every stage as its own communicating program):

- **Wire protocol**: serialized, length-prefixed frames over a TCP
  socket (stdlib only — an 8-byte big-endian length prefix + a pickle
  payload; both ends count messages AND exact frame bytes, so the
  benchmark's RPC accounting gates read off real serialized frames,
  not estimates).
- **`ReplicaServer` / worker entrypoint**: wraps an UNCHANGED
  `DynamicInferenceEngine` behind verbs — submit / step / abort / pop /
  export / import / release / evict / set_params / sessions / healthz /
  stats / audit / trace / shutdown. `python -m
  megatronapp_tpu.inference.fleet_rpc --state-dir D --idx I` builds the
  engine from the replica's spec file, binds an ephemeral port, writes
  `addr.json` (host/port/pid/incarnation), and heartbeats through
  `training/ft_integration.HeartbeatMonitor` — the SAME on-disk
  heartbeat the training supervisor story has carried since ISSUE 6,
  now read by the serving supervisor.
- **`ProcessFleetRouter`**: speaks the protocol to N worker processes.
  Same rid space (the router's counter rides in every submit),
  message-shaped admission with the in-process router's scoring
  (affinity − queue·load − pressure + SLO·attainment — affinity fed by
  prefix-insert keys riding step replies, attainment by each worker's
  interval-histogram state), and live migration that ships the EXACT
  `export_slot` bytes `PagedKVCache` already serializes — a migrated
  stream continues token-exact across processes because the sampler's
  fold_in chain (seed ∘ rid ∘ step) never references which process
  computes the step.
- **Failure domains**: a dead worker's sessions re-enter a survivor
  with prompt+generated intact (the preemption-resume path — zero
  sessions lost, greedy streams exact); a dead ROUTER recovers by
  interrogating worker `sessions` over RPC (`ProcessFleetRouter
  .attach`) and rebuilding owner + affinity tables from the live
  engine state — zero lost in both directions. The supervisor
  (inference/supervisor.py) owns detect → SIGKILL → relaunch.

Chaos site ``fleet-rpc`` fires in `ReplicaClient.call` AFTER the reply
frame is deserialized and BEFORE the router commits it — the
lost-acknowledgement window. Every router operation is exception-safe
against it: submit rolls back with an idempotent `evict` and resubmits;
migration evicts the half-imported destination copy (the session keeps
decoding on the source, both pools audit-clean); a lost step reply
resyncs the router's shadow books from the worker's authoritative
`sessions` state, so no emitted token is dropped.

The router presents the single-engine facade
(`add_request`/`step`/`abort_request`/`pop_request`/`has_work`/
`stats_snapshot`), so `DynamicBatchingDriver` and the /stats /healthz
/metrics endpoints serve a cross-process fleet unchanged; /metrics
aggregation (per-replica labels + supervisor restart counts) rides
`export_fleet_gauges`.
"""

from __future__ import annotations

import dataclasses
import itertools
import json
import logging
import os
import pickle
import socket
import struct
import subprocess
import sys
import threading
import time
from collections import OrderedDict
from typing import Dict, List, Optional, Tuple

import numpy as np

from megatronapp_tpu.utils import chaos
from megatronapp_tpu.utils import metrics as telemetry
from megatronapp_tpu.utils.metrics import Histogram

logger = logging.getLogger(__name__)

# Replica lifecycle states (shared vocabulary with inference/fleet.py).
ACTIVE = "active"
DEAD = "dead"

_LEN = struct.Struct("!Q")
MAX_FRAME = 1 << 32     # 4 GiB — far above any KV export at test scale


# ---------------------------------------------------------------------------
# Wire protocol: length-prefixed pickle frames. Pickle is the right
# trust model here — router, supervisor, and workers are ONE operator's
# co-located processes on a loopback socket (the payloads carry live
# numpy KV rows and Request objects); this is an internal fabric, not a
# public API surface.
# ---------------------------------------------------------------------------
def send_msg(sock: socket.socket, obj) -> int:
    """Serialize + frame + send; returns exact bytes put on the wire."""
    blob = pickle.dumps(obj, protocol=4)
    frame = _LEN.pack(len(blob)) + blob
    sock.sendall(frame)
    return len(frame)


def _recv_exact(sock: socket.socket, n: int) -> bytes:
    buf = bytearray()
    while len(buf) < n:
        chunk = sock.recv(n - len(buf))
        if not chunk:
            raise ConnectionError("fleet-rpc peer closed mid-frame")
        buf += chunk
    return bytes(buf)


def recv_msg(sock: socket.socket) -> Tuple[object, int]:
    """Receive one frame; returns (object, exact bytes off the wire)."""
    (n,) = _LEN.unpack(_recv_exact(sock, _LEN.size))
    if n > MAX_FRAME:
        raise ValueError(f"fleet-rpc frame of {n} bytes exceeds "
                         f"MAX_FRAME ({MAX_FRAME})")
    return pickle.loads(_recv_exact(sock, n)), _LEN.size + n


# ---------------------------------------------------------------------------
# Engine specs: a JSON-able recipe both the worker entrypoint and the
# in-process baseline build engines from, so a process fleet and an
# in-process fleet on the same spec hold BIT-IDENTICAL params (PRNG
# init is deterministic in the seed) — the foundation of every
# cross-process token-exactness gate.
# ---------------------------------------------------------------------------
def default_engine_spec(**overrides) -> dict:
    spec = {
        "preset": None,             # models/presets.py name, or dims:
        "num_layers": 2, "hidden_size": 64, "num_attention_heads": 4,
        "num_query_groups": 2, "vocab_size": 128,
        "max_position_embeddings": 64,
        "seed": 7,                  # params init PRNGKey
        "max_batch": 2, "max_seq_len": 48,
        "prefill_buckets": [16],
        "block_size": 8, "num_blocks": None,
        "kv_cache_dtype": "bf16",
        "prefill_chunk": None,      # None: the engine's choice
        # Host-RAM KV spill tier (ISSUE 20): parked sessions per worker.
        "kv_spill_host_mb": 0.0,
        "kv_spill_watermark_blocks": 0,
        "platform": "cpu",          # worker JAX_PLATFORMS
        # Multi-tenant LoRA serving (ISSUE 19): a lora_dir of .npz
        # adapters gives every worker an AdapterCache over the same
        # on-disk registry — cross-process fleets serve adapters with
        # identical banks because the npz bytes are the shared truth.
        "lora_dir": None,
        "lora_rank": 8,
        "max_resident_adapters": 8,
    }
    spec.update(overrides)
    return spec


def build_engine_from_spec(spec: dict):
    """Deterministic engine construction (worker entrypoint AND the
    benchmark's in-process parity leg — one build path, exact params)."""
    import jax
    import jax.numpy as jnp

    from megatronapp_tpu.config.transformer_config import TransformerConfig
    from megatronapp_tpu.inference.dynamic_engine import (
        DynamicInferenceEngine,
    )
    from megatronapp_tpu.models.gpt import init_gpt_params

    if spec.get("preset"):
        from megatronapp_tpu.models.presets import PRESETS
        cfg = PRESETS[spec["preset"]]()
    else:
        cfg = TransformerConfig(
            num_layers=spec["num_layers"],
            hidden_size=spec["hidden_size"],
            num_attention_heads=spec["num_attention_heads"],
            num_query_groups=spec["num_query_groups"],
            vocab_size=spec["vocab_size"],
            max_position_embeddings=spec["max_position_embeddings"],
            compute_dtype=jnp.float32, remat_policy="none")
    params, _ = init_gpt_params(
        jax.random.PRNGKey(spec.get("seed", 0)), cfg)
    adapter_cache = None
    if spec.get("lora_dir"):
        from megatronapp_tpu.inference.lora import (
            AdapterCache, AdapterRegistry,
        )
        adapter_cache = AdapterCache(
            cfg, AdapterRegistry(spec["lora_dir"]),
            max_resident=spec.get("max_resident_adapters", 8),
            rank=spec.get("lora_rank", 8))
    return DynamicInferenceEngine(
        params, cfg, max_batch=spec["max_batch"],
        max_seq_len=spec["max_seq_len"],
        prefill_buckets=tuple(spec.get("prefill_buckets") or (16,)),
        block_size=spec["block_size"],
        num_blocks=spec.get("num_blocks"),
        kv_cache_dtype=spec.get("kv_cache_dtype", "bf16"),
        prefill_chunk=spec.get("prefill_chunk"),
        adapter_cache=adapter_cache,
        spill_host_mb=spec.get("kv_spill_host_mb", 0.0) or 0.0,
        spill_watermark_blocks=(
            spec.get("kv_spill_watermark_blocks", 0) or 0))


# ---------------------------------------------------------------------------
# Fleet state directory layout (the supervisor/recovery rendezvous):
#   <state_dir>/replica-<i>/spec.json        engine recipe (router writes)
#   <state_dir>/replica-<i>/addr.json        host/port/pid/incarnation
#                                            (the WORKER writes, atomic)
#   <state_dir>/replica-<i>/heartbeat.json   HeartbeatMonitor (worker)
#   <state_dir>/supervisor.json              restart accounting
# ---------------------------------------------------------------------------
def replica_dir(state_dir: str, idx: int) -> str:
    return os.path.join(state_dir, f"replica-{idx}")


def heartbeat_dir(state_dir: str, idx: int) -> str:
    return replica_dir(state_dir, idx)


def replica_dirs(state_dir: str) -> List[int]:
    out = []
    try:
        for name in os.listdir(state_dir):
            if name.startswith("replica-"):
                try:
                    out.append(int(name.split("-", 1)[1]))
                except ValueError:
                    continue
    except OSError:
        pass
    return sorted(out)


def _write_json_atomic(path: str, payload: dict):
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(payload, f)
    os.replace(tmp, path)


def write_spec(state_dir: str, idx: int, spec: dict):
    d = replica_dir(state_dir, idx)
    os.makedirs(d, exist_ok=True)
    _write_json_atomic(os.path.join(d, "spec.json"), spec)


def read_spec(state_dir: str, idx: int) -> dict:
    with open(os.path.join(replica_dir(state_dir, idx),
                           "spec.json")) as f:
        return json.load(f)


def _host_is_local(host: str) -> bool:
    """True for loopback/any-local names — the only hosts the spawn +
    SIGKILL supervision model can actually manage."""
    if host in ("", "localhost", "0.0.0.0", "::", "::1"):
        return True
    return host.startswith("127.")


def read_addr(state_dir: str, idx: int) -> Optional[dict]:
    path = os.path.join(replica_dir(state_dir, idx), "addr.json")
    try:
        with open(path) as f:
            addr = json.load(f)
    except (OSError, ValueError):
        return None
    host = str(addr.get("host", ""))
    if not _host_is_local(host):
        # Fail LOUDLY at parse/attach time instead of silently assuming
        # loopback: worker supervision is os.kill-based (SIGKILL +
        # pid liveness) and spawn launches subprocesses on THIS machine,
        # so a remote host in addr.json can neither be supervised nor
        # respawned — the fleet would "work" until the first failure.
        raise RuntimeError(
            f"replica-{idx} addr.json lists non-local host {host!r}: "
            "multi-host spawn not yet supported (worker spawn and "
            "SIGKILL supervision assume every replica runs on this "
            "machine). Run one fleet per host behind a front-end "
            "instead.")
    return addr


def spawn_worker(state_dir: str, idx: int, incarnation: int,
                 extra_env: Optional[dict] = None) -> subprocess.Popen:
    """Launch one replica worker process (router.launch and the
    supervisor's relaunch share this — one spawn path)."""
    repo_root = os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    env = dict(os.environ)
    env["PYTHONPATH"] = repo_root + os.pathsep + env.get("PYTHONPATH", "")
    env.update(extra_env or {})
    log_path = os.path.join(replica_dir(state_dir, idx),
                            f"worker-{incarnation}.log")
    os.makedirs(replica_dir(state_dir, idx), exist_ok=True)
    log = open(log_path, "ab")
    try:
        return subprocess.Popen(
            [sys.executable, "-m",
             "megatronapp_tpu.inference.fleet_rpc",
             "--state-dir", state_dir, "--idx", str(idx),
             "--incarnation", str(incarnation)],
            stdout=log, stderr=subprocess.STDOUT, env=env)
    finally:
        log.close()      # the child holds its own descriptor


def wait_for_addr(state_dir: str, idx: int, incarnation: int,
                  timeout: float = 120.0) -> dict:
    """Block until the worker's addr file shows `incarnation` (a fresh
    worker pays the jax import + engine build before binding)."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        addr = read_addr(state_dir, idx)
        if addr is not None and addr.get("incarnation") == incarnation:
            return addr
        time.sleep(0.05)
    raise TimeoutError(
        f"replica {idx} incarnation {incarnation} never published its "
        f"address within {timeout}s (see worker-{incarnation}.log in "
        f"{replica_dir(state_dir, idx)})")


# ---------------------------------------------------------------------------
# Server side: one engine behind the verb table.
# ---------------------------------------------------------------------------
class ReplicaServer:
    """Serve one UNCHANGED engine over the fleet RPC protocol.

    Runs identically as a subprocess entrypoint (worker_main) and as an
    in-process thread (tests / the benchmark's thread-backed mode) —
    the wire frames, verb handlers, chaos window, and byte accounting
    are the same either way; only the process boundary differs."""

    def __init__(self, engine, idx: int = 0,
                 heartbeat: Optional[object] = None,
                 host: str = "127.0.0.1", port: int = 0):
        self.engine = engine
        self.idx = idx
        self.heartbeat = heartbeat
        self.steps = 0
        self.msgs_recv = 0
        self.msgs_sent = 0
        self.bytes_recv = 0
        self.bytes_sent = 0
        self._lock = threading.RLock()       # engine ops serialized
        self._stop = threading.Event()
        self._busy_since: Optional[float] = None
        # Prefix-insert events buffer: the in-process router wires pool
        # listeners directly; cross-process they ride step replies.
        self._prefix_buf: List[bytes] = []
        self._flushed = False
        pool = getattr(engine, "pool", None)
        if pool is not None:
            pool.prefix_listener = self._note_prefixes
            pool.flush_listener = self._note_flush
        self._sock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self._sock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._sock.bind((host, port))
        self._sock.listen(8)
        self.addr = self._sock.getsockname()

    def _note_prefixes(self, keys: List[bytes]):
        self._prefix_buf.extend(keys)

    def _note_flush(self):
        self._flushed = True
        self._prefix_buf.clear()

    # -- liveness ----------------------------------------------------------
    def _beat(self):
        if self.heartbeat is not None:
            self.heartbeat.beat()

    def _beat_loop(self, interval: float):
        """Background heartbeat: beats while the worker is responsive.
        A handler wedged longer than `interval*4` stops the beats —
        that wedge is exactly what the supervisor's staleness check
        must see, so the ticker refuses to mask it."""
        while not self._stop.wait(interval):
            busy = self._busy_since
            if busy is not None and time.monotonic() - busy > interval * 4:
                continue
            self._beat()

    # -- serve loops -------------------------------------------------------
    def start(self) -> "ReplicaServer":
        """Accept-loop in a daemon thread (in-process mode)."""
        threading.Thread(target=self.serve_forever,
                         name=f"replica-rpc-{self.idx}",
                         daemon=True).start()
        return self

    def stop(self):
        self._stop.set()
        try:
            self._sock.close()
        except OSError:
            pass

    def serve_forever(self, beat_interval: Optional[float] = None):
        if beat_interval and self.heartbeat is not None:
            threading.Thread(target=self._beat_loop,
                             args=(beat_interval,), daemon=True).start()
        while not self._stop.is_set():
            try:
                conn, _ = self._sock.accept()
            except OSError:
                break
            threading.Thread(target=self._serve_conn, args=(conn,),
                             daemon=True).start()

    def _serve_conn(self, conn: socket.socket):
        try:
            while not self._stop.is_set():
                msg, nbytes = recv_msg(conn)
                self.msgs_recv += 1
                self.bytes_recv += nbytes
                reply = self._dispatch(msg)
                self.bytes_sent += send_msg(conn, reply)
                self.msgs_sent += 1
                if msg.get("verb") == "shutdown":
                    self.stop()
                    break
        except (ConnectionError, EOFError, OSError):
            pass      # router went away; next connection re-accepts
        finally:
            try:
                conn.close()
            except OSError:
                pass

    def _dispatch(self, msg: dict) -> dict:
        verb = msg.get("verb")
        handler = getattr(self, f"_do_{verb}", None)
        if handler is None:
            return {"ok": False, "kind": "ValueError",
                    "error": f"unknown fleet-rpc verb {verb!r}"}
        self._busy_since = time.monotonic()
        try:
            with self._lock:
                value = handler(msg)
            return {"ok": True, "value": value}
        except Exception as e:  # noqa: BLE001 — errors cross the wire
            logger.warning("fleet-rpc verb %s failed", verb,
                           exc_info=True)
            return {"ok": False, "kind": type(e).__name__,
                    "error": str(e)}
        finally:
            self._busy_since = None
            self._beat()

    # -- verbs -------------------------------------------------------------
    def _do_ping(self, msg):
        return {"idx": self.idx, "pid": os.getpid()}

    def _do_submit(self, msg):
        """Admit a request under the ROUTER'S rid (one rid space spans
        the fleet). `generated` non-empty is the failover/resume shape:
        the request re-enters the waiting queue carrying its emitted
        tokens, exactly like the in-process router's `_requeue_on` —
        the engine re-prefills prompt+generated and the fold_in chain
        continues the stream token-exact."""
        from megatronapp_tpu.inference.dynamic_engine import Request
        from megatronapp_tpu.inference.engine import SamplingParams
        eng = self.engine
        rid = msg["rid"]
        generated = msg.get("generated") or []
        if rid in eng.requests:
            raise ValueError(f"rid {rid} already present on replica "
                             f"{self.idx}")
        if not generated:
            got = eng.add_request(
                msg["prompt"], msg["max_new_tokens"],
                msg.get("sampling"), eod_id=msg.get("eod_id"),
                priority=msg.get("priority", 0),
                deadline_s=msg.get("deadline_s"),
                request_id=rid,
                adapter_id=msg.get("adapter_id"),
                tenant=msg.get("tenant"))
            assert got == rid
            return {"rid": rid}
        now = time.monotonic()
        req = Request(
            rid, np.asarray(msg["prompt"], np.int32).reshape(-1),
            msg["max_new_tokens"],
            msg.get("sampling") or SamplingParams(),
            eod_id=msg.get("eod_id"),
            priority=msg.get("priority", 0),
            deadline_s=msg.get("deadline_s"),
            adapter_id=msg.get("adapter_id"),
            tenant=msg.get("tenant"),
            admit_t=now, queued_t=now)
        req.generated = list(generated)
        req.slot = -1
        eng.requests[rid] = req
        eng.waiting.append(req)
        return {"rid": rid, "resumed": len(generated)}

    def _do_step(self, msg):
        eng = self.engine
        if eng.has_work:
            ev = eng.step()
            self.steps += 1
        else:
            ev = {"admitted": [], "tokens": [], "finished": [],
                  "preempted": [], "expired": []}
        prefix = self._prefix_buf
        self._prefix_buf = []
        flushed = self._flushed
        self._flushed = False
        hist = getattr(eng, "interval_hist", None)
        return {
            "events": ev,
            "prefix_keys": prefix,
            "flushed": flushed,
            "waiting": len(eng.waiting),
            "active": sum(1 for s in eng.slots if s is not None),
            "free_slots": eng.free_decode_slots(),
            "pressure": (eng.pool.blocks_in_use() / eng.pool.num_blocks
                         if getattr(eng, "pool", None) is not None
                         else 0.0),
            "hist": hist.state() if hist is not None else None,
            "steps": self.steps,
            # the width of the engine's prefill call (its own choice where
            # the spec gives none): the router's chunks-avoided arithmetic
            "prefill_chunk": getattr(eng, "prefill_chunk", None),
        }

    def _do_abort(self, msg):
        return self.engine.abort_request(msg["rid"])

    def _do_pop(self, msg):
        return self.engine.pop_request(msg["rid"])

    def _do_export(self, msg):
        return self.engine.export_request(msg["rid"])

    def _do_import(self, msg):
        return self.engine.import_request(msg["payload"])

    def _do_release(self, msg):
        self.engine.release_exported(msg["rid"])
        return True

    def _do_evict(self, msg):
        """Idempotent un-admit (the router's rollback verb for a lost
        acknowledgement): drop `rid` from this replica's books and
        release any slot/pool resources it holds. Safe to call when the
        rid never landed (returns False)."""
        eng = self.engine
        inner = getattr(eng, "engine", eng)
        rid = msg["rid"]
        req = eng.requests.pop(rid, None)
        if req is None:
            return False
        try:
            eng.waiting.remove(req)
        except ValueError:
            pass
        slot = next((i for i, r in enumerate(inner.slots) if r is req),
                    None)
        if slot is not None:
            pool = getattr(eng, "pool", None)
            if pool is not None:
                try:
                    pool.release(slot, np.asarray(req.tokens),
                                 int(inner.lengths[slot]))
                except Exception:  # noqa: BLE001 — best-effort reclaim
                    logger.warning("evict pool release failed for rid "
                                   "%d", rid, exc_info=True)
            inner._free_slot(slot)
        return True

    def _do_park(self, msg):
        """Client/loadgen-requested park of a long-idle session into
        this worker's host spill tier (False when spill is off)."""
        fn = getattr(self.engine, "park_request", None)
        return bool(fn and fn(msg["rid"]))

    def _do_resume(self, msg):
        fn = getattr(self.engine, "resume_request", None)
        return bool(fn and fn(msg["rid"]))

    def _do_prefix_put(self, msg):
        """Seed one fleet-store prefix block into this worker's pool
        (rc==0 LRU entry, hittable by the next admit). `dup` tells the
        router the worker already held it — no bytes re-imported, and
        the router's chunks-avoided accounting counts it as local."""
        pool = getattr(self.engine, "pool", None)
        if pool is None:
            return {"ok": False, "dup": False}
        key = msg["key"]
        if pool.has_prefix(key):
            return {"ok": True, "dup": True}
        return {"ok": pool.import_prefix_block(key, msg["payload"]),
                "dup": False}

    def _do_prefix_get(self, msg):
        """Export one prefix block's payload for the fleet store (None
        when this pool no longer holds the key — it may have been
        LRU-evicted between the step reply and this fetch)."""
        pool = getattr(self.engine, "pool", None)
        if pool is None:
            return None
        return pool.export_prefix_block(msg["key"])

    def _do_set_params(self, msg):
        self.engine.set_params(msg["params"])
        return True

    def _do_sessions(self, msg):
        """Authoritative session table (router restart recovery + the
        router's lost-step-reply resync): every Request this replica
        holds, with its emitted tokens."""
        return dict(self.engine.requests)

    def _do_healthz(self, msg):
        eng = self.engine
        return {"ok": True, "idx": self.idx, "pid": os.getpid(),
                "steps": self.steps,
                "active": sum(1 for s in eng.slots if s is not None),
                "waiting": len(eng.waiting)}

    def _do_stats(self, msg):
        eng = self.engine
        out = eng.stats_snapshot() if hasattr(eng, "stats_snapshot") \
            else {}
        hist = getattr(eng, "interval_hist", None)
        out["hist"] = hist.state() if hist is not None else None
        out["rpc"] = {"msgs_recv": self.msgs_recv,
                      "msgs_sent": self.msgs_sent,
                      "bytes_recv": self.bytes_recv,
                      "bytes_sent": self.bytes_sent}
        out["pid"] = os.getpid()
        out["steps"] = self.steps
        if telemetry.enabled():
            out["metrics"] = telemetry.snapshot()
        return out

    def _do_audit(self, msg):
        pool = getattr(self.engine, "pool", None)
        if pool is not None:
            pool.audit()
        return True

    def _do_trace(self, msg):
        from megatronapp_tpu.trace.request_trace import get_request_tracer
        rt = get_request_tracer()
        return {"records": rt.dump(), "pid_names": dict(rt._pid_names),
                "pid": os.getpid()}

    def _do_shutdown(self, msg):
        return True


# ---------------------------------------------------------------------------
# Client side.
# ---------------------------------------------------------------------------
class ReplicaRpcError(RuntimeError):
    """A verb failed on the replica side (the error crossed the wire)."""


class ReplicaClient:
    """One socket to one replica worker, with exact frame accounting.

    The ``fleet-rpc`` chaos site fires AFTER a reply frame is received
    and deserialized, BEFORE the caller (the router) can commit it —
    the lost-acknowledgement window every router operation must be
    exception-safe against."""

    def __init__(self, host: str, port: int, timeout: float = 60.0,
                 connect_retries: int = 40):
        self.msgs_sent = 0
        self.msgs_recv = 0
        self.bytes_sent = 0
        self.bytes_recv = 0
        self._lock = threading.Lock()
        last: Optional[Exception] = None
        for _ in range(connect_retries):
            try:
                self.sock = socket.create_connection(
                    (host, port), timeout=timeout)
                break
            except OSError as e:
                last = e
                time.sleep(0.05)
        else:
            raise ConnectionError(
                f"fleet-rpc connect to {host}:{port} failed: {last}")
        self.sock.settimeout(timeout)

    def call(self, verb: str, **kw):
        with self._lock:
            self.bytes_sent += send_msg(self.sock, dict(kw, verb=verb))
            self.msgs_sent += 1
            reply, nbytes = recv_msg(self.sock)
            self.bytes_recv += nbytes
            self.msgs_recv += 1
        # The drill window: reply deserialized, router not yet
        # committed. (Outside the lock so rollback verbs can reuse
        # this client from the except handler.)
        chaos.fire("fleet-rpc")
        if not reply["ok"]:
            raise ReplicaRpcError(
                f"{verb} failed on replica: [{reply['kind']}] "
                f"{reply['error']}")
        return reply["value"]

    def close(self):
        try:
            self.sock.close()
        except OSError:
            pass


# ---------------------------------------------------------------------------
# Router-side shadow bookkeeping.
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class _Session:
    """The router's shadow of one request: enough to fail it over with
    nothing lost (prompt + emitted tokens + admission fields) and to
    serve results for a dead replica."""
    rid: int
    prompt: np.ndarray
    max_new_tokens: int
    sampling: object
    eod_id: Optional[int] = None
    priority: int = 0
    deadline_s: Optional[float] = None
    adapter_id: Optional[str] = None
    tenant: Optional[str] = None
    generated: list = dataclasses.field(default_factory=list)
    finished: bool = False
    running: bool = False


@dataclasses.dataclass
class _ProcReplica:
    """Router-side handle for one worker process."""
    idx: int
    client: Optional[ReplicaClient] = None
    proc: Optional[subprocess.Popen] = None
    incarnation: int = 0
    state: str = ACTIVE
    steps: int = 0
    waiting: int = 0
    active: int = 0
    free_slots: int = 1
    pressure: float = 0.0
    hist: Optional[Histogram] = None
    prefill_chunk: Optional[int] = None    # from its step replies

    def attainment(self, slo_ms: Optional[float]) -> float:
        if self.hist is None or slo_ms is None or not self.hist.count:
            return 1.0
        return self.hist.fraction_below(slo_ms)


class ProcessFleetRouter:
    """The in-process `FleetRouter`'s stepping surface over N replica
    WORKER PROCESSES (module docstring). Construct with `launch()` to
    spawn a fresh fleet, or `attach()` to recover a router over already
    -running workers (router restart: zero lost sessions)."""

    def __init__(self, state_dir: str, spec: Optional[dict] = None,
                 num_replicas: int = 2, policy: str = "affinity",
                 slo_ms: Optional[float] = None,
                 affinity_capacity: int = 8192,
                 supervise: Optional[str] = None,
                 stale_after: float = 15.0,
                 base_port: int = 0,
                 spawn: bool = True,
                 extra_env: Optional[dict] = None,
                 prefix_store_mb: float = 0.0):
        assert policy in ("affinity", "round_robin"), policy
        assert supervise in (None, "off", "thread", "process"), supervise
        self.state_dir = state_dir
        self.policy = policy
        self.slo_ms = slo_ms
        self.affinity_capacity = affinity_capacity
        self.stale_after = stale_after
        self.base_port = base_port
        self._extra_env = dict(extra_env or {})
        self._affinity: OrderedDict = OrderedDict()
        # Tenant/adapter→replica steering (same bounded-map machinery
        # as the in-process FleetRouter): keeping one tenant's requests
        # on the worker whose AdapterCache already holds its adapter
        # avoids a bank write per admission.
        self._tenant_affinity: OrderedDict = OrderedDict()
        self.tenant_affinity_capacity = 1024
        self._owner: Dict[int, Optional[int]] = {}
        self._sessions: Dict[int, _Session] = {}
        self._lock = threading.RLock()
        self._rr = 0
        self.pause_admission = False        # driver-facade compat
        self.tokenizer = None
        # Fleet-global prefix store (ISSUE 20): the router pulls newly
        # inserted prefix blocks off step replies (prefix_get) and
        # pushes them into an admission target that misses locally
        # (prefix_put) — the cross-process flavor of FleetRouter's
        # in-process store, same payloads, same counters.
        if prefix_store_mb:
            from megatronapp_tpu.inference.paged_cache import (
                FleetPrefixStore,
            )
            self.prefix_store = FleetPrefixStore(
                int(prefix_store_mb * (1 << 20)))
        else:
            self.prefix_store = None
        self.router_stats = {
            "admissions": 0, "affinity_admissions": 0,
            "migrations": 0, "migration_failures": 0,
            "migrated_kv_bytes": 0, "failovers": 0,
            "replica_deaths": 0, "reattaches": 0,
            "rpc_rollbacks": 0, "resyncs": 0,
            "prefix_store_admission_hits": 0,
            "prefix_store_seeded_blocks": 0,
            "prefix_store_seeded_bytes": 0,
            "prefill_chunks_avoided": 0,
        }
        self.supervisor = None
        self._supervisor_proc: Optional[subprocess.Popen] = None
        if spawn:
            assert spec is not None, "spawn=True needs an engine spec"
            self.spec = dict(spec)
            os.makedirs(state_dir, exist_ok=True)
            self._reps = []
            for i in range(num_replicas):
                s = dict(spec)
                if base_port:
                    s["port"] = base_port + i
                write_spec(state_dir, i, s)
                proc = spawn_worker(state_dir, i, 0,
                                    extra_env=self._extra_env)
                self._reps.append(_ProcReplica(idx=i, proc=proc))
            for rep in self._reps:
                addr = wait_for_addr(state_dir, rep.idx, 0)
                rep.client = ReplicaClient(addr["host"], addr["port"])
            self._ids = itertools.count()
        else:
            idxs = replica_dirs(state_dir)
            assert idxs, f"no replicas under {state_dir} to attach to"
            self.spec = read_spec(state_dir, idxs[0])
            self._reps = []
            for i in idxs:
                rep = _ProcReplica(idx=i)
                addr = read_addr(state_dir, i)
                if addr is None:
                    rep.state = DEAD
                else:
                    rep.incarnation = addr["incarnation"]
                    try:
                        rep.client = ReplicaClient(addr["host"],
                                                   addr["port"],
                                                   connect_retries=4)
                    except ConnectionError:
                        rep.state = DEAD
                self._reps.append(rep)
            self._recover_sessions()
        self.max_batch = self.spec["max_batch"] * len(self._reps)
        if supervise in ("thread", "process"):
            self.start_supervisor(mode=supervise)

    # -- construction fronts -----------------------------------------------
    @classmethod
    def launch(cls, state_dir: str, spec: dict, num_replicas: int = 2,
               **kw) -> "ProcessFleetRouter":
        return cls(state_dir, spec=spec, num_replicas=num_replicas,
                   spawn=True, **kw)

    @classmethod
    def attach(cls, state_dir: str, **kw) -> "ProcessFleetRouter":
        """Router restart recovery: connect to already-running workers
        and rebuild owner + session + affinity tables by interrogating
        replica state over RPC — zero sessions lost across a router
        death."""
        return cls(state_dir, spawn=False, **kw)

    def _recover_sessions(self):
        """Interrogate every live replica's authoritative books and
        rebuild the router's shadow: sessions/owners come back verbatim
        (Request objects carry prompt + generated + sampling), the rid
        counter resumes past the max in flight, and affinity entries
        are recomputed from each session's prompt hash chain — the same
        `prefix_block_keys` the pools hash with."""
        from megatronapp_tpu.inference.paged_cache import (
            prefix_block_keys,
        )
        max_rid = -1
        block_size = self.spec["block_size"]
        for rep in self._reps:
            if rep.state == DEAD or rep.client is None:
                continue
            sess_map = rep.client.call("sessions")
            for rid, req in sess_map.items():
                self._sessions[rid] = _Session(
                    rid=rid, prompt=np.asarray(req.prompt, np.int32),
                    max_new_tokens=req.max_new_tokens,
                    sampling=req.sampling, eod_id=req.eod_id,
                    priority=req.priority, deadline_s=req.deadline_s,
                    adapter_id=getattr(req, "adapter_id", None),
                    tenant=getattr(req, "tenant", None),
                    generated=list(req.generated),
                    finished=bool(req.finished),
                    running=req.slot >= 0)
                self._owner[rid] = rep.idx
                self._note_tenant(
                    getattr(req, "adapter_id", None)
                    or getattr(req, "tenant", None), rep.idx)
                max_rid = max(max_rid, rid)
                for key in prefix_block_keys(
                        np.asarray(req.prompt, np.int32), block_size,
                        len(req.prompt)):
                    self._note_prefix(key, rep.idx)
        self._ids = itertools.count(max_rid + 1)

    # -- supervision ---------------------------------------------------------
    def start_supervisor(self, mode: str = "thread",
                         interval: float = 0.5):
        from megatronapp_tpu.inference.supervisor import Supervisor
        if mode == "process":
            repo_root = os.path.dirname(os.path.dirname(
                os.path.dirname(os.path.abspath(__file__))))
            env = dict(os.environ)
            env["PYTHONPATH"] = (repo_root + os.pathsep
                                 + env.get("PYTHONPATH", ""))
            self._supervisor_proc = subprocess.Popen(
                [sys.executable, "-m",
                 "megatronapp_tpu.inference.supervisor",
                 "--state-dir", self.state_dir,
                 "--stale-after", str(self.stale_after),
                 "--interval", str(interval)],
                stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
                env=env)
            return self._supervisor_proc
        self.supervisor = Supervisor(
            _ProcessBackend(self), interval=interval,
            state_dir=self.state_dir).start()
        return self.supervisor

    def supervisor_restarts(self) -> Dict[int, int]:
        """Restart accounting regardless of which process supervises:
        the in-router thread supervisor's live counters, else the
        state-dir file the standalone supervisor process writes."""
        if self.supervisor is not None:
            return dict(self.supervisor.restarts)
        try:
            with open(os.path.join(self.state_dir,
                                   "supervisor.json")) as f:
                return {int(k): v for k, v in
                        json.load(f).get("restarts", {}).items()}
        except (OSError, ValueError):
            return {}

    # -- affinity -------------------------------------------------------------
    def _note_prefix(self, key: bytes, idx: int):
        self._affinity[key] = idx
        self._affinity.move_to_end(key)
        while len(self._affinity) > self.affinity_capacity:
            self._affinity.popitem(last=False)

    def _drop_affinity(self, idx: int):
        stale = [k for k, v in self._affinity.items() if v == idx]
        for k in stale:
            del self._affinity[k]

    def _note_tenant(self, key: Optional[str], idx: int):
        if key is None:
            return
        self._tenant_affinity[key] = idx
        self._tenant_affinity.move_to_end(key)
        while len(self._tenant_affinity) > self.tenant_affinity_capacity:
            self._tenant_affinity.popitem(last=False)

    def _drop_tenant_replica(self, idx: int):
        stale = [k for k, v in self._tenant_affinity.items() if v == idx]
        for k in stale:
            del self._tenant_affinity[k]

    # -- admission ------------------------------------------------------------
    def _live(self) -> List[_ProcReplica]:
        return [r for r in self._reps if r.state == ACTIVE]

    def _admit_target(self, prompt: np.ndarray,
                      affinity_key: Optional[str] = None) -> _ProcReplica:
        from megatronapp_tpu.inference.paged_cache import (
            prefix_block_keys,
        )
        live = self._live()
        if not live:
            raise RuntimeError("process fleet has no live replica to "
                               "admit into")
        if self.policy == "round_robin":
            rep = live[self._rr % len(live)]
            self._rr += 1
            return rep
        block_size = self.spec["block_size"]
        keys = prefix_block_keys(prompt, block_size, len(prompt))
        owners = [self._affinity.get(k) for k in keys]
        tenant_home = (None if affinity_key is None
                       else self._tenant_affinity.get(affinity_key))
        # The in-process router's scoring, off last-step-reply signals.
        queue_w, pressure_w, slo_w, tenant_w = (2.0 * block_size,
                                                4.0 * block_size,
                                                2.0 * block_size,
                                                8.0 * block_size)
        best = best_key = None
        best_aff = 0.0
        for rep in live:
            aff = 0.0
            for o in owners:
                if o != rep.idx:
                    break
                aff += block_size
            taff = tenant_w if tenant_home == rep.idx else 0.0
            load = rep.waiting + rep.active
            score = (aff + taff - queue_w * load
                     - pressure_w * rep.pressure
                     + slo_w * rep.attainment(self.slo_ms))
            key = (score, -load, -rep.idx)
            if best_key is None or key > best_key:
                best, best_key, best_aff = rep, key, aff
        if best_aff > 0:
            self.router_stats["affinity_admissions"] += 1
        return best

    def _submit_to(self, rep: _ProcReplica, sess: _Session):
        """One exception-safe submit: a lost acknowledgement (the
        fleet-rpc chaos window, or a worker death mid-call) rolls back
        with an idempotent evict, and the session re-enters admission —
        the rid was reserved router-side, so the retry is the SAME
        request and the stream it eventually emits is unchanged."""
        self._seed_from_store(rep, sess.prompt)
        try:
            rep.client.call(
                "submit", rid=sess.rid, prompt=sess.prompt,
                max_new_tokens=sess.max_new_tokens,
                sampling=sess.sampling, eod_id=sess.eod_id,
                priority=sess.priority, deadline_s=sess.deadline_s,
                adapter_id=sess.adapter_id, tenant=sess.tenant,
                generated=list(sess.generated) or None)
            rep.waiting += 1
            self._owner[sess.rid] = rep.idx
            self._note_tenant(sess.adapter_id or sess.tenant, rep.idx)
            return
        except chaos.ChaosFault:
            # Ack lost AFTER the worker may have committed: undo
            # (idempotent), then retry through admission.
            self.router_stats["rpc_rollbacks"] += 1
            telemetry.inc("fleet_rpc_rollbacks")
            try:
                rep.client.call("evict", rid=sess.rid)
            except Exception:  # noqa: BLE001 — replica may be dying
                self._fail_rep(rep)
        except (ConnectionError, EOFError, OSError, socket.timeout):
            self._fail_rep(rep, reassign=False)
        # Retry on the (possibly different) best live replica.
        self._submit_to(self._admit_target(
            sess.prompt, affinity_key=sess.adapter_id or sess.tenant),
            sess)

    def _seed_from_store(self, rep: _ProcReplica, prompt: np.ndarray):
        """Push this prompt's leading prefix blocks from the fleet
        store into the target worker's pool (prefix_put) before the
        submit, so its admit() hits them instead of re-prefilling.
        Best-effort and idempotent: a dup reply means the worker
        already held the block (counts as local, not seeded), any
        fault just stops the seeding — the submit path's own error
        handling owns worker death. Chunks-avoided follows the engine's
        chunked-prefill arithmetic exactly (leading cached blocks *
        block_size, capped at p_len - 1)."""
        store = self.prefix_store
        if store is None:
            return
        from megatronapp_tpu.inference.paged_cache import (
            cdiv, prefix_block_keys,
        )
        block_size = self.spec["block_size"]
        keys = prefix_block_keys(prompt, block_size, len(prompt))
        local = chain = seeded = 0
        leading_local = True
        for k in keys:
            payload = store.get(k)          # counts the hit/miss
            if payload is None:
                break                       # only a LEADING run helps
            try:
                reply = rep.client.call("prefix_put", key=k,
                                        payload=payload)
            except chaos.ChaosFault:
                break     # put may have landed (idempotent) — stop here
            except (ConnectionError, EOFError, OSError, socket.timeout):
                return    # submit's failover owns the dying worker
            if not reply["ok"]:
                break                       # worker pool full
            if reply["dup"] and leading_local:
                local += 1
            else:
                leading_local = False
                seeded += 1
                self.router_stats["prefix_store_seeded_blocks"] += 1
                self.router_stats["prefix_store_seeded_bytes"] += (
                    payload["nbytes"])
            chain += 1
            self._note_prefix(k, rep.idx)
        if not seeded:
            return
        p_len = len(prompt)
        self.router_stats["prefix_store_admission_hits"] += 1
        # the width of the replica's prefill call: the spec's, or the
        # engine's own choice as its step replies give it (a replica that
        # has not stepped yet: the chunks are not counted)
        chunk = self.spec.get("prefill_chunk") or rep.prefill_chunk
        if not chunk:
            return

        def chunks_at(blocks_cached: int) -> int:
            cached = min(blocks_cached * block_size, p_len - 1)
            return cdiv(p_len - cached, chunk)

        avoided = chunks_at(local) - chunks_at(chain)
        self.router_stats["prefill_chunks_avoided"] += avoided
        telemetry.inc("fleet_prefill_chunks_avoided", avoided)

    def add_request(self, prompt_tokens, max_new_tokens: int,
                    sampling=None, eod_id: Optional[int] = None,
                    priority: int = 0,
                    deadline_s: Optional[float] = None,
                    adapter_id: Optional[str] = None,
                    tenant: Optional[str] = None) -> int:
        prompt = np.asarray(prompt_tokens, np.int32).reshape(-1)
        with self._lock:
            rid = next(self._ids)
            sess = _Session(rid=rid, prompt=prompt,
                            max_new_tokens=max_new_tokens,
                            sampling=sampling, eod_id=eod_id,
                            priority=priority, deadline_s=deadline_s,
                            adapter_id=adapter_id, tenant=tenant)
            self._sessions[rid] = sess
            self._submit_to(
                self._admit_target(prompt,
                                   affinity_key=adapter_id or tenant),
                sess)
        self.router_stats["admissions"] += 1
        telemetry.inc("fleet_admissions")
        return rid

    # -- per-request forwarding ------------------------------------------------
    def _rep_of(self, rid: int) -> Optional[_ProcReplica]:
        idx = self._owner.get(rid)
        if idx is None:
            return None
        rep = next((r for r in self._reps if r.idx == idx), None)
        if rep is None or rep.state == DEAD or rep.client is None:
            return None
        return rep

    def abort_request(self, rid: int) -> Optional[str]:
        sess = self._sessions.get(rid)
        if sess is None or sess.finished:
            return None
        rep = self._rep_of(rid)
        if rep is None:
            sess.finished = True
            return "waiting"
        try:
            out = rep.client.call("abort", rid=rid)
        except chaos.ChaosFault:
            out = "running"   # worker marked it; finish event follows
        except (ConnectionError, EOFError, OSError, socket.timeout):
            self._fail_rep(rep)
            return self.abort_request(rid)
        if out == "waiting":
            sess.finished = True
        return out

    def pop_request(self, rid: int):
        """Remove + return the finished request. Serves from the
        worker's books when the owner is alive, and from the router's
        shadow when it is not (a finished-but-unfetched result must
        survive its replica's death — same transplant contract as the
        in-process router)."""
        from megatronapp_tpu.inference.dynamic_engine import Request
        from megatronapp_tpu.inference.engine import SamplingParams
        sess = self._sessions.pop(rid, None)
        rep = self._rep_of(rid)
        self._owner.pop(rid, None)
        if rep is not None:
            try:
                req = rep.client.call("pop", rid=rid)
                if req is not None:
                    return req
            except chaos.ChaosFault:
                pass          # worker popped; serve the shadow below
            except (ConnectionError, EOFError, OSError, socket.timeout):
                self._fail_rep(rep)
        if sess is None:
            return None
        req = Request(rid, sess.prompt, sess.max_new_tokens,
                      sess.sampling or SamplingParams(),
                      eod_id=sess.eod_id, priority=sess.priority,
                      deadline_s=sess.deadline_s,
                      adapter_id=sess.adapter_id, tenant=sess.tenant)
        req.generated = list(sess.generated)
        req.finished = sess.finished
        return req

    def park_request(self, rid: int) -> bool:
        """Forward a client park to the owning worker's spill tier
        (`park` verb). A lost ack counts as parked — the verb is
        engine-side idempotent and resume_request tolerates both
        states."""
        rep = self._rep_of(rid)
        if rep is None:
            return False
        try:
            return bool(rep.client.call("park", rid=rid))
        except chaos.ChaosFault:
            return True
        except (ConnectionError, EOFError, OSError, socket.timeout):
            self._fail_rep(rep)
            return False

    def resume_request(self, rid: int) -> bool:
        rep = self._rep_of(rid)
        if rep is None:
            return False
        try:
            return bool(rep.client.call("resume", rid=rid))
        except chaos.ChaosFault:
            return True
        except (ConnectionError, EOFError, OSError, socket.timeout):
            self._fail_rep(rep)
            return False

    # -- live migration --------------------------------------------------------
    def migrate_request(self, rid: int,
                        dst_idx: Optional[int] = None) -> bool:
        """Cross-process live migration: the EXACT `export_slot` bytes
        the source pool serializes travel the wire and scatter into the
        destination pool — `import_slot` is the same all-or-nothing
        call the in-process router uses, so the migrated stream
        continues token-exact. Exception-safe: a fault after import's
        ack is lost evicts the destination copy (idempotent) and the
        session keeps decoding on the source, both pools audit-clean."""
        with self._lock:
            src = self._rep_of(rid)
            if src is None:
                return False
            cands = [r for r in self._live() if r is not src
                     and (dst_idx is None or r.idx == dst_idx)
                     and r.free_slots > 0]
            if not cands:
                return False
            dst = min(cands, key=lambda r: (r.waiting + r.active,
                                            r.idx))
            payload = None
            try:
                payload = src.client.call("export", rid=rid)
                if payload is None:
                    return False
                if not dst.client.call("import", payload=payload):
                    self.router_stats["migration_failures"] += 1
                    return False
            except Exception as e:  # noqa: BLE001 — rollback + stay put
                self.router_stats["migration_failures"] += 1
                telemetry.inc("fleet_migration_failures")
                if payload is not None:
                    # The import MAY have landed before its ack was
                    # lost — evict the destination copy (idempotent;
                    # False when it never arrived). Export was
                    # read-only, so the source needs no rollback.
                    try:
                        dst.client.call("evict", rid=rid)
                        self.router_stats["rpc_rollbacks"] += 1
                    except Exception:  # noqa: BLE001 — dst dying
                        logger.warning("migration rollback evict "
                                       "failed", exc_info=True)
                logger.warning(
                    "cross-process migration of rid %d (replica %d -> "
                    "%d) failed — session stays on the source: %s",
                    rid, src.idx, dst.idx, e)
                return False
            try:
                src.client.call("release", rid=rid)
            except chaos.ChaosFault:
                pass          # worker released; ack lost is harmless
            except (ConnectionError, EOFError, OSError, socket.timeout):
                self._fail_rep(src, skip_rid=rid)
            self._owner[rid] = dst.idx
            self.router_stats["migrations"] += 1
            self.router_stats["migrated_kv_bytes"] += payload["nbytes"]
            telemetry.inc("fleet_migrations")
        return True

    # -- failure handling ------------------------------------------------------
    def _fail_rep(self, rep: _ProcReplica, reassign: bool = True,
                  skip_rid: Optional[int] = None):
        """A worker died under the router (socket error / supervisor
        kill): mark it DEAD, drop its affinity entries, and fail every
        session it owned over to survivors with prompt+generated intact
        (the preemption-resume shape — zero sessions lost, streams
        exact). Finished-but-unfetched results stay servable from the
        router's shadow."""
        if rep.state == DEAD:
            return
        logger.warning("fleet-rpc replica %d DIED — failing its "
                       "sessions over", rep.idx)
        rep.state = DEAD
        if rep.client is not None:
            rep.client.close()
        self._drop_affinity(rep.idx)
        self._drop_tenant_replica(rep.idx)
        self.router_stats["replica_deaths"] += 1
        telemetry.inc("fleet_replica_deaths")
        if not reassign:
            # Caller re-admits the in-flight rid itself; orphans still
            # need failover below.
            pass
        orphans = [rid for rid, o in self._owner.items()
                   if o == rep.idx and rid != skip_rid]
        for rid in sorted(orphans):
            sess = self._sessions.get(rid)
            if sess is None:
                self._owner.pop(rid, None)
                continue
            if sess.finished:
                self._owner[rid] = None    # shadow serves the result
                continue
            sess.running = False
            self._owner.pop(rid, None)
            self._submit_to(self._admit_target(
                sess.prompt,
                affinity_key=sess.adapter_id or sess.tenant), sess)
            self.router_stats["failovers"] += 1
            telemetry.inc("fleet_failovers")

    def _try_reattach(self, rep: _ProcReplica) -> bool:
        """A DEAD replica rejoins when the supervisor's relaunched
        worker publishes a NEWER incarnation. It comes back empty (its
        sessions already failed over) — reattaching restores capacity,
        not state."""
        addr = read_addr(self.state_dir, rep.idx)
        if addr is None or addr["incarnation"] <= rep.incarnation:
            return False
        try:
            client = ReplicaClient(addr["host"], addr["port"],
                                   connect_retries=2)
            client.call("ping")
        except (ConnectionError, ReplicaRpcError, OSError):
            return False
        rep.client = client
        rep.incarnation = addr["incarnation"]
        rep.state = ACTIVE
        rep.waiting = rep.active = 0
        rep.free_slots = self.spec["max_batch"]
        rep.pressure = 0.0
        rep.hist = None
        self.router_stats["reattaches"] += 1
        telemetry.inc("fleet_reattaches")
        logger.warning("fleet-rpc replica %d reattached "
                       "(incarnation %d)", rep.idx, rep.incarnation)
        return True

    def _resync(self, rep: _ProcReplica, events: Dict[str, List]):
        """A step reply was lost (chaos window): the worker stepped but
        the router never saw the events. Re-read the worker's
        authoritative session table and emit the missing tokens/finish
        transitions into this round's events — nothing is dropped."""
        self.router_stats["resyncs"] += 1
        telemetry.inc("fleet_rpc_resyncs")
        sess_map = rep.client.call("sessions")
        for rid, req in sess_map.items():
            sess = self._sessions.get(rid)
            if sess is None:
                continue
            new = list(req.generated[len(sess.generated):])
            for tok in new:
                sess.generated.append(int(tok))
                events["tokens"].append((rid, int(tok)))
            if req.finished and not sess.finished:
                sess.finished = True
                events["finished"].append(rid)

    # -- main loop --------------------------------------------------------------
    def _fan_out_steps(self, live: List[_ProcReplica]) -> List:
        """Issue the per-step RPCs to every live replica CONCURRENTLY
        (one thread per in-flight verb) and return each reply or the
        exception it raised, in replica order. N workers step in
        parallel instead of serializing behind one socket round-trip
        each — fleet step latency is max(replica step), not sum. The
        byte accounting is untouched: each `ReplicaClient.call` counts
        its own frames under the client's lock, and exactly one step
        frame per replica goes on the wire either way (pinned by
        tests/test_fleet_rpc.py). Replies are PROCESSED serially by the
        caller under the router lock, so the failure handling
        (resync / fail over) is byte-for-byte the sequential path's."""
        results: List = [None] * len(live)

        def run(i: int, rep: _ProcReplica):
            try:
                results[i] = rep.client.call("step")
            except Exception as e:  # noqa: BLE001 — re-handled serially
                results[i] = e

        if len(live) == 1:
            run(0, live[0])
            return results
        threads = [threading.Thread(target=run, args=(i, rep),
                                    daemon=True)
                   for i, rep in enumerate(live)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        return results

    def step(self) -> Dict[str, List]:
        events: Dict[str, List] = {"admitted": [], "tokens": [],
                                   "finished": [], "preempted": [],
                                   "expired": []}
        with self._lock:
            for rep in self._reps:
                if rep.state == DEAD:
                    self._try_reattach(rep)
            live = [rep for rep in self._reps
                    if rep.state != DEAD and rep.client is not None]
            replies = self._fan_out_steps(live)
            for rep, r in zip(live, replies):
                if isinstance(r, chaos.ChaosFault):
                    self._resync(rep, events)
                    continue
                if isinstance(r, (ConnectionError, EOFError, OSError,
                                  socket.timeout, ReplicaRpcError)):
                    if isinstance(r, ReplicaRpcError):
                        logger.warning("replica %d step raised: %s",
                                       rep.idx, r)
                    self._fail_rep(rep)
                    continue
                if isinstance(r, Exception):
                    raise r
                rep.steps = r["steps"]
                rep.waiting = r["waiting"]
                rep.active = r["active"]
                rep.free_slots = r["free_slots"]
                rep.pressure = r["pressure"]
                rep.prefill_chunk = r.get("prefill_chunk")
                if r["hist"] is not None:
                    rep.hist = Histogram.from_state(r["hist"])
                for key in r["prefix_keys"]:
                    self._note_prefix(key, rep.idx)
                    if (self.prefix_store is not None
                            and not self.prefix_store.has(key)):
                        # Pull each NEW block's payload once (prefix_get
                        # is read-only + idempotent, so a lost reply
                        # just refetches on the next insert event).
                        try:
                            payload = rep.client.call("prefix_get",
                                                      key=key)
                        except chaos.ChaosFault:
                            continue
                        except (ConnectionError, EOFError, OSError,
                                socket.timeout):
                            self._fail_rep(rep)
                            break
                        if payload is not None:
                            self.prefix_store.put(key, payload)
                if rep.state == DEAD:
                    continue
                if r["flushed"]:
                    self._drop_affinity(rep.idx)
                    if self.prefix_store is not None:
                        # A worker-side flush means a params swap: the
                        # store's blocks may hold KV from the OLD
                        # weights — drop everything, fleet-wide.
                        self.prefix_store.clear()
                ev = r["events"]
                for rid in ev["admitted"]:
                    sess = self._sessions.get(rid)
                    if sess is not None:
                        sess.running = True
                for rid in ev["preempted"]:
                    sess = self._sessions.get(rid)
                    if sess is not None:
                        sess.running = False
                for rid, tok in ev["tokens"]:
                    sess = self._sessions.get(rid)
                    if sess is not None:
                        sess.generated.append(int(tok))
                for rid in ev["finished"] + ev["expired"]:
                    sess = self._sessions.get(rid)
                    if sess is not None:
                        sess.finished = True
                for key in events:
                    events[key] += ev.get(key, [])
        return events

    @property
    def has_work(self) -> bool:
        return any(not s.finished for s in self._sessions.values())

    # Facade compat: shadow-derived views (the server's health snapshot
    # reads len()/occupancy off these).
    @property
    def slots(self) -> List:
        return [s.rid for s in self._sessions.values()
                if s.running and not s.finished]

    @property
    def waiting(self) -> List:
        return [s.rid for s in self._sessions.values()
                if not s.running and not s.finished]

    @property
    def requests(self) -> Dict:
        return dict(self._sessions)

    def free_decode_slots(self) -> int:
        return sum(r.free_slots for r in self._live())

    def expire_overdue(self, now=None) -> List[int]:
        return []    # deadlines are enforced worker-side (step events)

    def abort_all(self):
        for sess in list(self._sessions.values()):
            if not sess.finished:
                self.abort_request(sess.rid)

    def run_to_completion(self, token_callback=None
                          ) -> Dict[int, np.ndarray]:
        results: Dict[int, np.ndarray] = {}
        while self.has_work:
            ev = self.step()
            if token_callback is not None:
                for rid, tok in ev["tokens"]:
                    token_callback(rid, tok)
        for rid in [r for r, s in self._sessions.items() if s.finished]:
            req = self.pop_request(rid)
            if req is not None:
                results[rid] = req.tokens
        return results

    # -- server-facade compat ----------------------------------------------------
    def set_params(self, params):
        """Fan new weights out to every live worker (`set_params` verb;
        the swap is atomic per worker under its engine lock). The
        serving driver's generic reload path pauses admission, waits
        for `drained_for_reload`, then calls this."""
        for rep in self._live():
            rep.client.call("set_params", params=params)

    def drained_for_reload(self) -> bool:
        return not self.has_work

    def reset_compilation(self):
        pass    # workers own their engines; nothing is cached here

    def generate_text(self, prompts, max_new_tokens: int, sampling=None,
                      token_callback=None):
        """String-level API (mirrors FleetRouter.generate_text)."""
        assert self.tokenizer is not None, "tokenizer required"
        eod = getattr(self.tokenizer, "eod", None)
        rids = []
        for prompt in prompts:
            ids = np.asarray(self.tokenizer.tokenize(prompt), np.int32)
            rids.append(self.add_request(ids, max_new_tokens, sampling,
                                         eod_id=eod))
        cb = None
        if token_callback is not None:
            def cb(rid, tok):
                token_callback(rid, np.asarray([tok]), None)
        results = self.run_to_completion(token_callback=cb)
        texts = []
        for prompt, rid in zip(prompts, rids):
            n_prompt = len(self.tokenizer.tokenize(prompt))
            new_ids = results[rid][n_prompt:].tolist()
            if eod is not None and eod in new_ids:
                new_ids = new_ids[: new_ids.index(eod)]
            texts.append(self.tokenizer.detokenize(new_ids))
        return texts

    # -- observability -----------------------------------------------------------
    def rpc_totals(self) -> Dict[str, int]:
        out = {"msgs_sent": 0, "msgs_recv": 0,
               "bytes_sent": 0, "bytes_recv": 0}
        for rep in self._reps:
            if rep.client is None:
                continue
            out["msgs_sent"] += rep.client.msgs_sent
            out["msgs_recv"] += rep.client.msgs_recv
            out["bytes_sent"] += rep.client.bytes_sent
            out["bytes_recv"] += rep.client.bytes_recv
        return out

    def stats_snapshot(self, include_dispatch: bool = False) -> Dict:
        restarts = self.supervisor_restarts()
        live = self._live()
        replicas = []
        for rep in self._reps:
            entry = {
                "idx": rep.idx, "state": rep.state,
                "params_version": 0, "reloads": 0,
                "incarnation": rep.incarnation,
                "steps": rep.steps,
                "attainment": round(rep.attainment(self.slo_ms), 4),
                "restarts": restarts.get(rep.idx, 0),
            }
            if rep.state != DEAD:
                entry.update({"active": rep.active,
                              "waiting": rep.waiting,
                              "pressure": round(rep.pressure, 4)})
            if rep.hist is not None and rep.hist.count:
                entry["interval_p50_ms"] = round(
                    rep.hist.percentile(50), 3)
                entry["interval_p99_ms"] = round(
                    rep.hist.percentile(99), 3)
            replicas.append(entry)
        out = {
            "engine": "fleet",
            "paged": True,
            "max_batch": self.max_batch,
            "active": sum(r.get("active", 0) for r in replicas),
            "waiting": sum(r.get("waiting", 0) for r in replicas),
            "fleet": {
                "replicas": replicas,
                "num_replicas": len(self._reps),
                "live_replicas": len(live),
                "policy": self.policy,
                "migrate": True,
                "autoscale": False,
                "slo_ms": self.slo_ms,
                "params_version": 0,
                "reload_pending": False,
                "process_backed": True,
                "affinity_entries": len(self._affinity),
                "tenant_affinity_entries": len(self._tenant_affinity),
                "supervisor_restarts": sum(restarts.values()),
                "rpc": self.rpc_totals(),
                **self.router_stats,
            },
        }
        if self.prefix_store is not None:
            out["fleet"]["prefix_store"] = self.prefix_store.stats()
        return out

    def export_fleet_gauges(self, registry=telemetry):
        """Server /metrics hook: per-replica labeled gauges + the
        supervisor restart counter — one scrape covers the fleet."""
        restarts = self.supervisor_restarts()
        lab = registry.labeled
        for rep in self._reps:
            r = str(rep.idx)
            registry.set_gauge(lab("fleet_replica_up", replica=r),
                               int(rep.state != DEAD))
            registry.set_gauge(
                lab("fleet_replica_attainment", replica=r),
                round(rep.attainment(self.slo_ms), 4))
            registry.set_gauge(
                lab("fleet_replica_active_slots", replica=r),
                rep.active if rep.state != DEAD else 0)
            registry.set_gauge(
                lab("fleet_replica_waiting", replica=r),
                rep.waiting if rep.state != DEAD else 0)
            registry.set_gauge(
                lab("fleet_supervisor_restarts", replica=r),
                restarts.get(rep.idx, 0))
        registry.set_gauge("fleet_supervisor_restarts_total",
                           sum(restarts.values()))
        if self.prefix_store is not None:
            st = self.prefix_store.stats()
            registry.set_gauge("fleet_prefix_store_entries",
                               st["entries"])
            registry.set_gauge("fleet_prefix_store_bytes",
                               st["bytes_used"])
            registry.set_gauge("fleet_prefix_store_hit_total",
                               st["hits"])

    def merged_trace(self) -> dict:
        """ONE Chrome trace across every replica process + the router:
        each worker's request-trace ring is pulled over RPC and merged
        with per-process pid offsets (the MegaScan per-rank-merge
        story, applied to serving)."""
        from megatronapp_tpu.trace.request_trace import (
            get_request_tracer, merge_process_traces,
        )
        rt = get_request_tracer()
        procs = [("router", rt.dump(), dict(rt._pid_names))]
        for rep in self._reps:
            if rep.state == DEAD or rep.client is None:
                continue
            try:
                t = rep.client.call("trace")
            except Exception:  # noqa: BLE001 — trace is best-effort
                continue
            procs.append((f"replica-{rep.idx}", t["records"],
                          t["pid_names"]))
        return merge_process_traces(procs)

    def audit(self):
        """Pool audit on every live replica (drill gate)."""
        for rep in self._live():
            rep.client.call("audit")

    # -- teardown -----------------------------------------------------------------
    def shutdown(self):
        if self.supervisor is not None:
            self.supervisor.stop()
            self.supervisor = None
        if self._supervisor_proc is not None:
            self._supervisor_proc.kill()
            self._supervisor_proc.wait(timeout=10)
            self._supervisor_proc = None
        for rep in self._reps:
            if rep.client is not None:
                try:
                    rep.client.call("shutdown")
                except Exception:  # noqa: BLE001 — dying anyway
                    pass
                rep.client.close()
                rep.client = None
            if rep.proc is not None:
                try:
                    rep.proc.kill()
                    rep.proc.wait(timeout=10)
                except Exception:  # noqa: BLE001
                    pass


class _ProcessBackend:
    """Supervisor backend over a ProcessFleetRouter's worker table:
    alive = pid running AND heartbeat fresh; kill = SIGKILL + router
    failover; relaunch = respawn with a bumped incarnation (the router
    reattaches off the addr file). The in-process FleetRouter's backend
    lives in inference/fleet.py — both feed the SAME Supervisor."""

    def __init__(self, router: ProcessFleetRouter):
        self.router = router

    def indices(self) -> List[int]:
        return [r.idx for r in self.router._reps]

    def _rep(self, idx: int) -> _ProcReplica:
        return next(r for r in self.router._reps if r.idx == idx)

    def alive(self, idx: int) -> bool:
        from megatronapp_tpu.training.ft_integration import read_heartbeat
        rep = self._rep(idx)
        addr = read_addr(self.router.state_dir, idx)
        if addr is None:
            return False
        if rep.proc is not None and rep.incarnation == addr.get(
                "incarnation") and rep.proc.poll() is not None:
            return False
        try:
            os.kill(addr["pid"], 0)
        except (OSError, ProcessLookupError):
            return False
        hb = read_heartbeat(heartbeat_dir(self.router.state_dir, idx),
                            stale_after=self.router.stale_after)
        return bool(hb["alive"])

    def kill(self, idx: int):
        import signal
        addr = read_addr(self.router.state_dir, idx)
        if addr is not None:
            try:
                os.kill(addr["pid"], signal.SIGKILL)
            except (OSError, ProcessLookupError):
                pass
        with self.router._lock:
            self.router._fail_rep(self._rep(idx))

    def relaunch(self, idx: int, **hints):
        rep = self._rep(idx)
        incarnation = rep.incarnation + 1
        addr = read_addr(self.router.state_dir, idx)
        if addr is not None:
            incarnation = max(incarnation, addr["incarnation"] + 1)
        rep.proc = spawn_worker(self.router.state_dir, idx, incarnation,
                                extra_env=self.router._extra_env)
        wait_for_addr(self.router.state_dir, idx, incarnation)
        # The router's step loop reattaches on the incarnation bump.


def launch_threaded(state_dir: str, spec: dict, num_replicas: int = 2,
                    **router_kw):
    """Thread-backed fleet: the SAME wire frames, verbs, chaos window,
    and byte accounting over real loopback sockets, with the replica
    servers in daemon threads instead of OS processes — the fast tier-1
    smoke and the benchmark's cheap mode (subprocess workers each pay a
    full jax import). Returns (router, servers); callers stop the
    servers via router.shutdown()."""
    os.makedirs(state_dir, exist_ok=True)
    servers = []
    for i in range(num_replicas):
        write_spec(state_dir, i, spec)
        engine = build_engine_from_spec(spec)
        srv = ReplicaServer(engine, idx=i).start()
        _write_json_atomic(
            os.path.join(replica_dir(state_dir, i), "addr.json"),
            {"host": srv.addr[0], "port": srv.addr[1],
             "pid": os.getpid(), "incarnation": 0})
        servers.append(srv)
    router = ProcessFleetRouter.attach(state_dir, **router_kw)
    return router, servers


# ---------------------------------------------------------------------------
# Worker entrypoint.
# ---------------------------------------------------------------------------
def worker_main(argv=None) -> int:
    ap = __import__("argparse").ArgumentParser(
        description="fleet replica RPC worker (ISSUE 18)")
    ap.add_argument("--state-dir", required=True)
    ap.add_argument("--idx", type=int, required=True)
    ap.add_argument("--incarnation", type=int, default=0)
    args = ap.parse_args(argv)
    spec = read_spec(args.state_dir, args.idx)
    # Platform pin BEFORE any jax import: workers run on the CPU unless
    # the spec names a platform.
    os.environ.setdefault("JAX_PLATFORMS",
                          spec.get("platform") or "cpu")
    from megatronapp_tpu.training.ft_integration import (
        FTConfig, HeartbeatMonitor,
    )
    hb = HeartbeatMonitor(FTConfig(
        heartbeat_dir=heartbeat_dir(args.state_dir, args.idx),
        heartbeat_write_interval=0.2))
    hb.start_section("setup")
    engine = build_engine_from_spec(spec)
    hb.start_section("step")
    server = ReplicaServer(engine, idx=args.idx, heartbeat=hb,
                           port=int(spec.get("port", 0)))
    _write_json_atomic(
        os.path.join(replica_dir(args.state_dir, args.idx),
                     "addr.json"),
        {"host": server.addr[0], "port": server.addr[1],
         "pid": os.getpid(), "incarnation": args.incarnation})
    print(f"replica {args.idx} incarnation {args.incarnation} serving "
          f"on {server.addr[0]}:{server.addr[1]} (pid {os.getpid()})",
          flush=True)
    server.serve_forever(beat_interval=0.25)
    return 0


if __name__ == "__main__":
    sys.exit(worker_main())
