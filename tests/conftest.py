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
# What the tests hold is the program's arithmetic, not how well XLA:CPU
# optimises it: their models are tiny and their time is compile time (a
# paged engine's two steps: 10-20 s), so this process compiles at XLA's
# level 0 without LLVM's expensive passes, as JAX's own tests do. Child
# processes (the entry points, the cells' rehearsals) compile as they always
# do, and tests/test_chip_compile.py, whose subject is the compiler, turns
# it back on.
jax.config.update("jax_disable_most_optimizations", True)

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


def _as_built(eng):
    assert not eng.has_work and eng._round is None
    assert eng.pool.blocks_in_use() == 0


@pytest.fixture
def lend():
    """lend(engine) -> the engine, for a case that reads or runs an engine
    its class (or module) built once. A DynamicInferenceEngine compiles its
    steps anew for every instance, so a class builds one and its cases
    share it; it is handed on only as it was built (no request waiting,
    parked or in a slot, no round in flight, no block in use), checked here
    before the case and after it, so that a case that leaks state fails
    itself and not its neighbour. Counters run on: a case reads the
    difference over its own run."""
    lent = []

    def lend(eng):
        _as_built(eng)
        lent.append(eng)
        return eng
    yield lend
    for eng in lent:
        try:
            _as_built(eng)
        except AssertionError:
            eng.abort_all()     # the neighbour's engine, whatever this case did
            raise


def pytest_collection_modifyitems(config, items):
    """Apply the 'slow' marker from tests/slow_manifest.txt (measured
    >10s tests; reference pytest.ini's internal/flaky gating). The fast
    iteration lane is `pytest -m "not slow"` (tier 1: 2,024 cases, about
    14 min on six workers, PR 62); the full suite remains the default so
    `pytest tests/` still covers everything."""
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


def pytest_terminal_summary(terminalreporter):
    """One line on where the run's time went: the sum of the cases' time
    (set-up, call and teardown, as the junit file counts a case) and the
    five dearest files. A PR quotes it from its own log, so the run's
    length is seen before the driver's limit cuts it."""
    by_file = {}
    for reports in terminalreporter.stats.values():
        for rep in reports:
            if hasattr(rep, "when"):        # a case's phase, not a warning
                name = rep.nodeid.split("::")[0]
                by_file[name] = by_file.get(name, 0.0) + rep.duration
    if not by_file:
        return
    dearest = sorted(by_file.items(), key=lambda kv: -kv[1])[:5]
    terminalreporter.write_line(
        "case time: %.0f s in %d files; dearest: %s" % (
            sum(by_file.values()), len(by_file),
            ", ".join("%s %.0f" % (os.path.basename(f), t)
                      for f, t in dearest)))
