"""Device mesh construction and accessors.

TPU-native analogue of ``parallel_state.py`` group construction
(/root/reference/megatron/core/parallel_state.py:1272 and accessors :18-124).
Where the reference builds ~20 NCCL/Gloo process groups and stores them in
module globals, here a single ``MeshContext`` owns a ``jax.sharding.Mesh`` with
named axes (pp, dp, ep, cp, tp); "groups" are just axis names, and collectives
are either emitted by XLA from shardings or written explicitly with
``shard_map`` + ``psum``/``ppermute`` over an axis name.
"""

from __future__ import annotations

import contextlib
import dataclasses
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from megatronapp_tpu.config.parallel_config import (
    MESH_AXES, ParallelConfig, DP_AXIS, TP_AXIS, PP_AXIS, CP_AXIS, EP_AXIS,
)


@dataclasses.dataclass
class MeshContext:
    """Owns the device mesh and the parallel config that shaped it."""

    mesh: Mesh
    parallel: ParallelConfig
    # FBD half-meshes set this: shard_maps then bind the ABSTRACT mesh
    # (axis names only) and resolve devices from argument shardings, so a
    # vjp pullback traced on the forward mesh can execute on the backward
    # mesh. Default False — eager abstract-mesh shard_maps on unsharded
    # args are not supported by this XLA build.
    abstract_collectives: bool = False

    @property
    def shard_map_mesh(self):
        """The mesh object to pass to jax.shard_map."""
        return (self.mesh.abstract_mesh if self.abstract_collectives
                else self.mesh)

    # --- degree accessors (parity with parallel_state get_*_world_size) ---
    @property
    def tp(self) -> int:
        return self.mesh.shape[TP_AXIS]

    @property
    def pp(self) -> int:
        return self.mesh.shape[PP_AXIS]

    @property
    def dp(self) -> int:
        return self.mesh.shape[DP_AXIS]

    @property
    def cp(self) -> int:
        return self.mesh.shape[CP_AXIS]

    @property
    def ep(self) -> int:
        return self.mesh.shape[EP_AXIS]

    @property
    def num_devices(self) -> int:
        return int(np.prod([self.mesh.shape[a] for a in MESH_AXES]))

    # --- sharding helpers ---
    def sharding(self, *spec) -> NamedSharding:
        return NamedSharding(self.mesh, P(*spec))

    def replicated(self) -> NamedSharding:
        return NamedSharding(self.mesh, P())

    def batch_spec(self, seq_sharded: bool = True) -> P:
        """PartitionSpec for a [batch, seq, ...] activation/token array.

        Batch is sharded over dp (and ep, which subdivides the data-parallel
        world exactly as in the reference where EP ranks hold distinct data;
        parallel_state.py:43-52). Sequence is sharded over cp (context
        parallelism, §5.7 of SURVEY) when seq_sharded.
        """
        batch_axes = (DP_AXIS, EP_AXIS)
        if seq_sharded and self.cp > 1:
            return P(batch_axes, CP_AXIS)
        return P(batch_axes)

    @contextlib.contextmanager
    def use(self):
        with self.mesh:
            yield self


_distributed_initialized = False


def initialize_multi_host(coordinator_address: Optional[str] = None,
                          num_processes: Optional[int] = None,
                          process_id: Optional[int] = None) -> None:
    """Join the multi-host runtime (reference
    torch.distributed.init_process_group, training/initialize.py:330-335;
    here ``jax.distributed.initialize`` — the JAX runtime then exposes one
    global ``jax.devices()`` list spanning all hosts, and XLA routes
    inter-slice collectives over DCN).

    On TPU pods (GKE/queued resources) all three arguments auto-detect from
    the metadata server; pass them explicitly for manual launches
    (reference MASTER_ADDR/RANK/WORLD_SIZE env). Idempotent: a second call
    in the same process (repeated parse_args in tests/notebooks) is a
    no-op instead of a double-initialize error."""
    kwargs = {}
    if coordinator_address is not None:
        kwargs["coordinator_address"] = coordinator_address
    if num_processes is not None:
        kwargs["num_processes"] = num_processes
    if process_id is not None:
        kwargs["process_id"] = process_id
    global _distributed_initialized
    if _distributed_initialized:
        return
    try:
        jax.distributed.initialize(**kwargs)
        _distributed_initialized = True
    except RuntimeError as e:
        # jax.distributed exposes no public already-initialized query
        # (global_state lives under jax._src); the flag above handles
        # re-entry within this process, and the error-string match below
        # is only a fallback for initializes done outside this helper.
        if "only be called once" not in str(e):
            raise
        _distributed_initialized = True


def _dcn_slice_axis(shape: Sequence[int], n_slices: int) -> int:
    """Pick the mesh axis to split across DCN slices: the OUTERMOST of
    pp/dp/ep whose degree n_slices divides (axis order pp, dp, ep, cp, tp
    — pipeline stages or data-parallel replicas span slices; cp/tp
    collectives are latency-critical and must stay on intra-slice ICI,
    the reference's NCCL-topology preference)."""
    for i, extent in enumerate(shape[:3]):  # pp, dp, ep only
        if extent > 1 and extent % n_slices == 0:
            return i
    raise ValueError(
        f"no pp/dp/ep mesh axis in {tuple(shape)} divisible by {n_slices} "
        "DCN slices; choose pp/dp degrees that factor across slices")


def build_mesh(parallel: ParallelConfig,
               devices: Optional[Sequence[jax.Device]] = None) -> MeshContext:
    """Build the mesh with axis order pp, dp, ep, cp, tp (outer→inner).

    TP innermost keeps tensor-parallel collectives on nearest-neighbor ICI
    links; PP outermost lets pipeline stages span slices over DCN — the
    reference encodes the same locality preference via RankGenerator order
    tp-cp-ep-dp-pp (parallel_state.py).

    On real TPU the device array is laid out topology-aware: within one
    slice via ``mesh_utils.create_device_mesh`` (ICI torus assignment), and
    across slices via ``create_hybrid_device_mesh`` with the slice count on
    the outermost divisible axis (DCN traffic rides pp/dp, never tp).
    Virtual/CPU devices keep the plain deterministic reshape (tests)."""
    if devices is None:
        devices = jax.devices()
    shape = parallel.mesh_shape(len(devices))
    if getattr(devices[0], "platform", None) == "tpu":
        from jax.experimental import mesh_utils
        slice_ids = {getattr(d, "slice_index", 0) for d in devices}
        if len(slice_ids) > 1:
            # Raises (with a config suggestion) when no pp/dp/ep axis
            # factors across the slices — a misconfigured multi-slice job
            # must fail loudly, not silently put tp/cp on DCN.
            dcn = [1] * len(shape)
            dcn[_dcn_slice_axis(shape, len(slice_ids))] = len(slice_ids)
            per_slice = [s // d for s, d in zip(shape, dcn)]
            dev_array = mesh_utils.create_hybrid_device_mesh(
                per_slice, dcn, devices=devices)
        else:
            # No fallback: a shape the slice's topology cannot carry
            # raises here instead of quietly running on the enumeration
            # order.
            dev_array = mesh_utils.create_device_mesh(
                shape, devices=devices)
    else:
        dev_array = np.asarray(devices).reshape(shape)
    mesh = Mesh(dev_array, MESH_AXES)
    return MeshContext(mesh=mesh, parallel=parallel)


def single_device_mesh() -> MeshContext:
    """Trivial 1-device mesh (all axes size 1) for single-chip runs/tests."""
    return build_mesh(ParallelConfig(), devices=jax.devices()[:1])
