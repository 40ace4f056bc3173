"""megatronapp_tpu package init.

Pin ``jax_threefry_partitionable=True`` (JAX's default today, pinned so an
environment override cannot flip it): with it False, ``jax.random`` values
under jit depend on the MESH the init runs under, so the same seed
produces different params on different tp/cp/pp layouts — breaking every
cross-layout loss-parity contract (cp=1 vs cp=2 training parity, golden
loss curves, A/B benchmarks that share an init). Partitionable threefry is
sharding-invariant by construction.
"""

import jax as _jax

try:
    _jax.config.update("jax_threefry_partitionable", True)
except Exception:  # pragma: no cover — flag retired on newer jax
    pass
