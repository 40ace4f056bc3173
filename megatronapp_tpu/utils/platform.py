"""Start-up helpers shared by the entry points: where compiled programs are
cached, and the one line that says which device a run is on.

Entry points (config/arguments.py parse_args, tools/
run_text_generation_server.py, bench.py's child, chip_smoke.py's children)
call ``enable_compile_cache()`` before their first jit and print
``device_line()`` once the mesh is known.
"""

from __future__ import annotations

import contextlib
import json
import os
from typing import Optional

import jax

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

DEVICE_LINE_PREFIX = "device: "


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at ONE place and return it.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has already read it and
    nothing is set here. Otherwise the cache is ``<checkout>/.jax_cache``:
    the directory is part of what makes an entry findable again, so it is
    never derived from the working directory, a temporary name, a process
    id or the time."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(_REPO_ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


@contextlib.contextmanager
def fresh_compiles():
    """Compile what the body compiles, whatever the persistent cache holds,
    and write none of it there.

    For the jits that pin a device LAYOUT (the paged KV pool's row-major
    one, inference/paged_cache.pool_format). On the installed JAX (0.9.0)
    an executable loaded from the persistent cache hands back arrays that
    REPORT the default layout while their buffers have the layout it was
    compiled for, and every later use of such an array trusts the report:
    the next step is refused or fails on the buffer's size (my chip runs,
    PR 27; a program compiled in the same process reports right). The
    switch is the process's, not the thread's: a compile in another thread
    meanwhile just misses the cache."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def device_line(mesh: Optional[jax.sharding.Mesh] = None) -> str:
    """``device: {...}`` — platform, device_kind and device count as JAX
    reports them, plus the mesh shape the run placed itself on."""
    dev = jax.devices()[0]
    return DEVICE_LINE_PREFIX + json.dumps({
        "platform": dev.platform,
        "kind": dev.device_kind,
        "count": len(jax.devices()),
        "mesh": None if mesh is None else dict(mesh.shape),
    })
