"""Test configuration: 8 virtual CPU devices for multi-device mesh tests.

Mirrors the reference test strategy (SURVEY §4): the reference launches 8
real GPU ranks per node and reconfigures logical TP×PP×DP combos against
them (tests/unit_tests/test_utilities.py:27-80 Utils); here a single host
exposes 8 virtual CPU devices via --xla_force_host_platform_device_count and
tests build meshes of any factorization over them.
"""

import os

# Must be set before jax initializes its backends.
flags = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

# The tests run on the CPU wherever they are: pin the env var (entry points
# started as child processes inherit it) and the config of this process.
os.environ["JAX_PLATFORMS"] = "cpu"

import jax

jax.config.update("jax_platforms", "cpu")
# Entry points under test switch the persistent compilation cache to
# <checkout>/.jax_cache (utils/platform.py); in-process tests compile
# everything themselves, as they always have.
jax.config.update("jax_enable_compilation_cache", False)

import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "chaos: chaos fault-injection drills (tests/test_resilience.py) "
        "— subprocess SIGTERM/hang/exit drills and fault-site exercises")


@pytest.fixture(scope="session")
def devices8():
    devs = jax.devices()
    assert len(devs) >= 8, f"expected 8 virtual devices, got {len(devs)}"
    return devs[:8]


def pytest_collection_modifyitems(config, items):
    """Apply the 'slow' marker from tests/slow_manifest.txt (measured
    >6s tests; reference pytest.ini's internal/flaky gating). The fast
    iteration lane is `pytest -m "not slow"` (~7 min); the full suite
    remains the default so `pytest tests/` still covers everything."""
    manifest = os.path.join(os.path.dirname(__file__), "slow_manifest.txt")
    try:
        with open(manifest) as f:
            slow = {ln.strip() for ln in f
                    if ln.strip() and not ln.startswith("#")}
    except OSError:
        return
    matched = set()
    for item in items:
        nodeid = item.nodeid.replace("\\", "/")
        if not nodeid.startswith("tests/"):
            nodeid = "tests/" + nodeid
        if nodeid in slow:
            item.add_marker(pytest.mark.slow)
            matched.add(nodeid)
    stale = slow - matched
    if stale and len(items) > len(slow):
        # Renamed/re-parameterized slow tests would silently drift into
        # the fast lane; surface manifest staleness at collection time.
        import warnings
        warnings.warn(
            f"tests/slow_manifest.txt has {len(stale)} entries matching "
            f"no collected test (e.g. {sorted(stale)[0]}); regenerate "
            "with tools/update_slow_manifest.py", stacklevel=1)
