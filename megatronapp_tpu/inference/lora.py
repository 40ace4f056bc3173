"""Multi-tenant batched-LoRA serving: adapter registry + HBM LRU cache.

The production-scale scenario from the ROADMAP: thousands of fine-tuned
tenants served from ONE fleet — one resident (optionally int8) base
model, per-request low-rank adapters batched into every decode step as
``base(x) + B_i A_i x``. This module owns the host-side half of that
subsystem:

- :class:`LoraAdapter` — one tenant's ``{A, B}`` pair per
  RESIDENT_KERNELS target (q/kv/out/fc1/fc2), stacked over layers.
  Loads from disk (``<lora_dir>/<adapter_id>.npz``, optionally stored
  PTQ-int8 via quantization.quantize_leaf) or from an in-memory
  registry (tests, programmatic serving).
- :class:`AdapterRegistry` — the fetch source the cache misses into.
- :class:`AdapterCache` — a fixed number of HBM-resident adapter slots
  per target, stacked into per-target BANK arrays
  ``A[L, slots, din, rank]`` / ``B[L, slots, rank, dout]`` so the
  decode jit gathers per-row adapter weights by bank slot (the same
  shape discipline as the paged KV pools: fixed allocation, functional
  row updates, per-row integer indirection). Slot 0 is the permanent
  NULL adapter (all zeros) — rows without an adapter index it and get
  an exactly-zero delta. Slots 1..R are managed with the SAME
  refcount / LRU-evict / audit discipline as ``PagedKVCache`` blocks:
  an in-use adapter can never be evicted, rc==0 residents park in LRU
  order and stay hittable, ``audit()`` proves the books are an exact
  partition after every step.

The device-side half — the segmented batched-LoRA GEMM with
scalar-prefetched per-row adapter ids, its jnp oracle and the eager
fallback — lives in
ops/pallas/kernel_gen.py (``lora_delta`` and friends).

Chaos site ``lora-load`` fires between the registry fetch and the bank
commit: the drill (tests/test_resilience.py) proves a mid-load fault
leaves the cache books untouched and the engine admission rollback
requeues the request.
"""

from __future__ import annotations

import dataclasses
import logging
import os
from collections import OrderedDict, deque
from typing import Dict, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from megatronapp_tpu.inference.quantization import (
    RESIDENT_KERNELS, dequantize_leaf, is_quantized_leaf, quantize_leaf,
)
from megatronapp_tpu.utils import chaos
from megatronapp_tpu.utils import metrics as telemetry

logger = logging.getLogger(__name__)

# The serving-LoRA targets are exactly the kernels that can stay
# int8-resident: the adapters ride on top of whatever form the base
# weights are in (bf16 or resident int8), which is what makes the
# one-resident-base + many-adapters HBM math work.
LORA_TARGETS = RESIDENT_KERNELS


def lora_target_dims(cfg) -> Dict[str, Tuple[int, int]]:
    """(din, dout) per LoRA target for this config — the A factor is
    [din, rank], the B factor [rank, dout], matching the base kernels'
    [din, dout] exactly (the delta adds into the SAME matmul output,
    before bias)."""
    if getattr(cfg, "multi_latent_attention", False):
        raise ValueError(
            "LoRA serving targets the standard GQA projection kernels "
            "(q/kv/out); multi-latent attention factors attention "
            "through latent kernels with no q_kernel/kv_kernel leaves "
            "— serve MLA models without --lora-dir")
    from megatronapp_tpu.ops.activations import is_gated
    h = cfg.hidden_size
    d = cfg.head_dim
    nq, nkv = cfg.num_attention_heads, cfg.num_query_groups
    f = cfg.ffn_hidden_size
    fc1_out = 2 * f if is_gated(cfg.activation) else f
    return {
        "q_kernel": (h, nq * d),
        "kv_kernel": (h, 2 * nkv * d),
        "out_kernel": (nq * d, h),
        "fc1_kernel": (h, fc1_out),
        "fc2_kernel": (f, h),
    }


def adapter_nbytes(cfg, rank: int, num_layers: Optional[int] = None,
                   itemsize: int = 4) -> int:
    """Rank-exact HBM bytes of ONE adapter: sum over targets of
    L*(din + dout)*rank*itemsize. This is the number /stats and the
    bench gate report — what an adapter actually costs, not the bank
    allocation granularity."""
    layers = num_layers if num_layers is not None else cfg.num_layers
    total = 0
    for din, dout in lora_target_dims(cfg).values():
        total += layers * (din + dout) * rank * itemsize
    return total


@dataclasses.dataclass
class LoraAdapter:
    """One tenant's adapter: per-target A [L, din, rank] and
    B [L, rank, dout] float32 stacks (layer-stacked like the base
    params pytree, so the cache banks scan with the layer scan)."""
    adapter_id: str
    rank: int
    a: Dict[str, np.ndarray]
    b: Dict[str, np.ndarray]

    @property
    def nbytes(self) -> int:
        """Rank-exact byte footprint of this adapter's factors."""
        return int(sum(v.nbytes for v in self.a.values())
                   + sum(v.nbytes for v in self.b.values()))

    @classmethod
    def random(cls, adapter_id: str, cfg, rank: int, *, seed: int = 0,
               num_layers: Optional[int] = None, scale: float = 0.05,
               zero_b: bool = False) -> "LoraAdapter":
        """A reproducible random adapter (tests, benchmarks). A is
        scaled ~1/sqrt(din) (standard LoRA init); B is small random —
        or exactly zero with zero_b=True, which makes the adapted
        stream provably identical to the base model (the zero-B parity
        gate)."""
        rng = np.random.default_rng(seed)
        layers = num_layers if num_layers is not None else cfg.num_layers
        a, b = {}, {}
        for t, (din, dout) in lora_target_dims(cfg).items():
            a[t] = (rng.standard_normal((layers, din, rank))
                    / np.sqrt(din)).astype(np.float32)
            if zero_b:
                b[t] = np.zeros((layers, rank, dout), np.float32)
            else:
                b[t] = (rng.standard_normal((layers, rank, dout))
                        * scale).astype(np.float32)
        return cls(adapter_id, rank, a, b)

    def save(self, lora_dir: str, *, quantize: bool = False) -> str:
        """Write ``<lora_dir>/<adapter_id>.npz``. quantize=True stores
        the factors PTQ-int8 (quantization.quantize_leaf per stack —
        half the disk/transfer bytes; load() dequantizes), mirroring
        how the base model ships."""
        os.makedirs(lora_dir, exist_ok=True)
        path = os.path.join(lora_dir, f"{self.adapter_id}.npz")
        payload = {"rank": np.int32(self.rank)}
        for t in LORA_TARGETS:
            for side, stack in (("a", self.a[t]), ("b", self.b[t])):
                key = f"{t}.{side}"
                if quantize:
                    q = quantize_leaf(stack)
                    payload[key + ".q"] = q["q"]
                    payload[key + ".scale"] = q["scale"]
                else:
                    payload[key] = stack
        np.savez(path, **payload)
        return path

    @classmethod
    def load(cls, lora_dir: str, adapter_id: str) -> "LoraAdapter":
        """Read an adapter saved by save() (plain or PTQ-int8)."""
        path = os.path.join(lora_dir, f"{adapter_id}.npz")
        with np.load(path) as z:
            rank = int(z["rank"])
            a, b = {}, {}
            for t in LORA_TARGETS:
                for side, dest in (("a", a), ("b", b)):
                    key = f"{t}.{side}"
                    if key in z:
                        dest[t] = np.asarray(z[key], np.float32)
                    else:
                        entry = {"__quant__": "int8", "q": z[key + ".q"],
                                 "scale": z[key + ".scale"],
                                 "dtype": "float32"}
                        assert is_quantized_leaf(entry)
                        dest[t] = np.asarray(dequantize_leaf(entry),
                                             np.float32)
        return cls(adapter_id, rank, a, b)


class AdapterRegistry:
    """Where cache misses fetch from: in-memory adapters registered by
    tests/benchmarks, plus an optional ``lora_dir`` of .npz files
    (in-memory wins on collision). Unknown ids raise KeyError with the
    known population — that is a PERMANENT error the engine rejects at
    submit time, never a retry loop."""

    def __init__(self, lora_dir: Optional[str] = None):
        self.lora_dir = lora_dir
        self._mem: Dict[str, LoraAdapter] = {}

    def register(self, adapter: LoraAdapter) -> None:
        self._mem[adapter.adapter_id] = adapter

    def ids(self):
        known = set(self._mem)
        if self.lora_dir and os.path.isdir(self.lora_dir):
            for fn in os.listdir(self.lora_dir):
                if fn.endswith(".npz"):
                    known.add(fn[:-4])
        return sorted(known)

    def __contains__(self, adapter_id: str) -> bool:
        if adapter_id in self._mem:
            return True
        return bool(
            self.lora_dir
            and os.path.exists(os.path.join(self.lora_dir,
                                            f"{adapter_id}.npz")))

    def get(self, adapter_id: str) -> LoraAdapter:
        if adapter_id in self._mem:
            return self._mem[adapter_id]
        if self.lora_dir:
            path = os.path.join(self.lora_dir, f"{adapter_id}.npz")
            if os.path.exists(path):
                return LoraAdapter.load(self.lora_dir, adapter_id)
        raise KeyError(
            f"unknown adapter {adapter_id!r}; registry knows "
            f"{self.ids() or '[] (empty)'}")


class AdapterSlotsPinned(RuntimeError):
    """Every resident slot is refcount-pinned by in-flight requests —
    a TRANSIENT capacity condition (the admission loop waits for a
    retirement to release one), unlike KeyError (unknown adapter,
    permanent)."""


class AdapterCache:
    """HBM-resident LoRA banks with PagedKVCache's pin/evict/audit
    discipline over ``max_resident`` adapter slots.

    Banks are per-target stacked arrays A[L, slots, din, rank] /
    B[L, slots, rank, dout] where slots = max_resident + 1 and slot 0
    is the permanent all-zero NULL adapter (rows without an adapter
    gather it and add an exactly-zero delta — the decode jit's shape
    never depends on which rows have adapters). acquire() returns the
    bank slot for an adapter id, loading it on miss (free slot first,
    then LRU-evicting an unpinned resident); release() unpins. The
    invariants audit() proves after every step:

    - slots 1..R are an exact partition: free ∪ resident,
    - every rc==0 resident is LRU-parked (and only those),
    - slot 0 is never free, never tabled, never refcounted.
    """

    def __init__(self, cfg, registry: AdapterRegistry, *,
                 max_resident: int = 8, rank: int = 8,
                 num_layers: Optional[int] = None, dtype=jnp.float32):
        if max_resident < 1:
            raise ValueError(
                f"max_resident must be >= 1, got {max_resident}")
        if rank < 1:
            raise ValueError(f"rank must be >= 1, got {rank}")
        self.cfg = cfg
        self.registry = registry
        self.rank = int(rank)
        self.max_resident = int(max_resident)
        self.slots = self.max_resident + 1            # + NULL slot 0
        self.num_layers = (num_layers if num_layers is not None
                           else cfg.num_layers)
        self.dtype = dtype
        self.dims = lora_target_dims(cfg)
        self.banks: Dict[str, Tuple[jnp.ndarray, jnp.ndarray]] = {
            t: (jnp.zeros((self.num_layers, self.slots, din, self.rank),
                          dtype),
                jnp.zeros((self.num_layers, self.slots, self.rank, dout),
                          dtype))
            for t, (din, dout) in self.dims.items()
        }
        self._free: deque = deque(range(1, self.slots))
        self._table: Dict[str, int] = {}              # adapter_id -> slot
        self._slot_id: Dict[int, str] = {}            # slot -> adapter_id
        self._refcount = np.zeros((self.slots,), np.int64)
        self._lru: "OrderedDict[int, None]" = OrderedDict()
        self.stats = {"hits": 0, "misses": 0, "evictions": 0,
                      "load_faults": 0}

    # ---- byte accounting --------------------------------------------------
    @property
    def adapter_nbytes(self) -> int:
        """Rank-exact bytes of ONE resident adapter (what an adapter
        costs, independent of the bank allocation)."""
        return adapter_nbytes(self.cfg, self.rank,
                              num_layers=self.num_layers,
                              itemsize=jnp.dtype(self.dtype).itemsize)

    def resident_bytes(self) -> int:
        """Rank-exact bytes of the CURRENTLY resident adapters."""
        return len(self._table) * self.adapter_nbytes

    def bank_bytes(self) -> int:
        """Full HBM allocation of the banks (capacity, incl. slot 0)."""
        return int(sum(a.nbytes + b.nbytes
                       for a, b in self.banks.values()))

    # ---- lookup -----------------------------------------------------------
    def slot_of(self, adapter_id: str) -> Optional[int]:
        return self._table.get(adapter_id)

    def resident_ids(self):
        return sorted(self._table)

    # ---- acquire / release ------------------------------------------------
    def _validate(self, adapter: LoraAdapter) -> None:
        if adapter.rank != self.rank:
            raise ValueError(
                f"adapter {adapter.adapter_id!r} has rank "
                f"{adapter.rank} but the cache banks are sized for "
                f"rank {self.rank} (--lora-rank)")
        for t, (din, dout) in self.dims.items():
            want_a = (self.num_layers, din, self.rank)
            want_b = (self.num_layers, self.rank, dout)
            got_a = tuple(adapter.a[t].shape)
            got_b = tuple(adapter.b[t].shape)
            if got_a != want_a or got_b != want_b:
                raise ValueError(
                    f"adapter {adapter.adapter_id!r} target {t}: A/B "
                    f"shapes {got_a}/{got_b} do not match this model's "
                    f"{want_a}/{want_b}")

    def _take_free(self) -> int:
        if self._free:
            return self._free.popleft()
        if self._lru:
            slot, _ = self._lru.popitem(last=False)   # least recent
            evicted = self._slot_id.pop(slot)
            del self._table[evicted]
            self.stats["evictions"] += 1
            telemetry.inc("lora_cache_evictions")
            return slot
        raise AdapterSlotsPinned(
            f"all {self.max_resident} resident adapter slots are "
            f"pinned by in-flight requests — waiting for a retirement "
            f"(raise --max-resident-adapters to run more distinct "
            f"adapters concurrently)")

    def acquire(self, adapter_id: Optional[str]) -> int:
        """Pin an adapter resident and return its bank slot (0 for
        None). Miss path: fetch from the registry, take a slot (free
        first, else LRU-evict an unpinned resident), write the banks,
        commit the books. Exception-safe: a fault anywhere before the
        commit (the ``lora-load`` chaos site fires between fetch and
        commit) leaves every book untouched."""
        if adapter_id is None:
            return 0
        slot = self._table.get(adapter_id)
        if slot is not None:
            self.stats["hits"] += 1
            telemetry.inc("lora_cache_hits")
            self._refcount[slot] += 1
            self._lru.pop(slot, None)
            return slot
        self.stats["misses"] += 1
        telemetry.inc("lora_cache_misses")
        adapter = self.registry.get(adapter_id)       # may KeyError
        self._validate(adapter)
        try:
            # The drill window: the adapter bytes were fetched but
            # nothing is committed — a fault here must leave free/LRU/
            # refcount/table exactly as they were (no slot consumed, no
            # resident evicted for a load that never landed).
            chaos.fire("lora-load")
        except BaseException:
            self.stats["load_faults"] += 1
            raise
        slot = self._take_free()
        dt = self.dtype
        new_banks = {}
        for t, (a_bank, b_bank) in self.banks.items():
            new_banks[t] = (
                a_bank.at[:, slot].set(
                    jnp.asarray(adapter.a[t], dt)),
                b_bank.at[:, slot].set(
                    jnp.asarray(adapter.b[t], dt)),
            )
        # Commit point: banks + books move together.
        self.banks = new_banks
        self._table[adapter_id] = slot
        self._slot_id[slot] = adapter_id
        self._refcount[slot] = 1
        return slot

    def release(self, slot: int) -> None:
        """Unpin one reference to a bank slot (0 is a no-op — the NULL
        adapter is never refcounted). rc==0 residents park in the LRU
        (still hittable) rather than freeing — the next acquire of the
        same id is a hit."""
        slot = int(slot)
        if slot == 0:
            return
        assert slot in self._slot_id, f"release of untabled slot {slot}"
        self._refcount[slot] -= 1
        assert self._refcount[slot] >= 0, (
            f"negative refcount on adapter slot {slot}")
        if self._refcount[slot] == 0:
            self._lru[slot] = None

    # ---- invariants -------------------------------------------------------
    def audit(self) -> None:
        """Assert the exact-partition invariants (run after every step
        in tests — same discipline as PagedKVCache.audit)."""
        used = set(self._table.values())
        free = set(self._free)
        assert len(self._free) == len(free), "duplicate free slots"
        assert 0 not in used and 0 not in free, (
            "NULL slot 0 leaked into the managed books")
        assert not (used & free), f"slots both used and free: {used & free}"
        assert used | free == set(range(1, self.slots)), (
            f"slots 1..{self.slots - 1} are not an exact partition: "
            f"used={sorted(used)} free={sorted(free)}")
        assert used == set(self._slot_id), "table/slot_id out of sync"
        for aid, slot in self._table.items():
            assert self._slot_id[slot] == aid, (
                f"slot {slot} maps back to {self._slot_id[slot]!r}, "
                f"not {aid!r}")
        assert set(self._lru) <= used, "LRU entry for a non-resident slot"
        for slot in used:
            rc = int(self._refcount[slot])
            assert rc >= 0, f"negative refcount on slot {slot}"
            assert (slot in self._lru) == (rc == 0), (
                f"slot {slot} rc={rc} LRU-parked={slot in self._lru}")
        for slot in free:
            assert self._refcount[slot] == 0, (
                f"free slot {slot} still refcounted")
        assert self._refcount[0] == 0, "NULL slot 0 refcounted"

    def stats_snapshot(self) -> Dict:
        return {
            "rank": self.rank,
            "capacity": self.max_resident,
            "resident": len(self._table),
            "pinned": int(np.count_nonzero(self._refcount[1:])),
            "resident_ids": self.resident_ids(),
            "adapter_bytes": self.adapter_nbytes,
            "resident_bytes": self.resident_bytes(),
            "bank_bytes": self.bank_bytes(),
            **self.stats,
        }


# ---- per-tenant SLO classes ----------------------------------------------
# Composes with the PR-8 scheduler: the engine orders admission and
# preemption by (priority, request_id) — a tenant's SLO class shifts the
# priority every one of its requests carries and supplies a default
# deadline, WITHOUT a second scheduling mechanism.
SLO_CLASSES: Dict[str, Dict] = {
    "premium": {"priority_offset": -1, "deadline_s": None},
    "standard": {"priority_offset": 0, "deadline_s": None},
    "batch": {"priority_offset": 1, "deadline_s": None},
}


class TenantSLO:
    """tenant -> SLO class mapping with (priority, deadline)
    composition. Unknown tenants get ``default_class``."""

    def __init__(self, default_class: str = "standard"):
        if default_class not in SLO_CLASSES:
            raise ValueError(
                f"unknown SLO class {default_class!r}; known: "
                f"{sorted(SLO_CLASSES)}")
        self.default_class = default_class
        self._classes: Dict[str, str] = {}

    def assign(self, tenant: str, slo_class: str) -> None:
        if slo_class not in SLO_CLASSES:
            raise ValueError(
                f"unknown SLO class {slo_class!r}; known: "
                f"{sorted(SLO_CLASSES)}")
        self._classes[tenant] = slo_class

    def class_of(self, tenant: Optional[str]) -> str:
        if tenant is None:
            return self.default_class
        return self._classes.get(tenant, self.default_class)

    def compose(self, tenant: Optional[str], priority: int = 0,
                deadline_s: Optional[float] = None
                ) -> Tuple[int, Optional[float]]:
        """Effective (priority, deadline_s) for a request: the tenant
        class's priority offset ADDS to the caller's priority (lower =
        more important, so premium outranks same-priority standard in
        the (priority, rid) order), and the class deadline applies only
        when the caller set none."""
        cls = SLO_CLASSES[self.class_of(tenant)]
        eff_priority = priority + cls["priority_offset"]
        eff_deadline = deadline_s
        if eff_deadline is None and cls["deadline_s"] is not None:
            import time
            eff_deadline = time.monotonic() + cls["deadline_s"]
        return eff_priority, eff_deadline
