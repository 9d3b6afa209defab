"""Test configuration: force an 8-device virtual CPU mesh.

Tests run on the CPU, never on an attached chip: multi-device sharding tests
run on XLA's virtual host devices. Both the env var and the ``jax_platforms``
config are pinned before any backend initializes; the chip path is exercised
by ``chip_smoke.py`` and AOT-compiled in ``tests/test_tpu_compile.py``.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax

jax.config.update("jax_platforms", "cpu")

import numpy as np
import pytest


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long-running soak tests excluded from tier-1 "
        "(-m 'not slow')",
    )


@pytest.fixture
def rng():
    return np.random.default_rng(0x5EED)


@pytest.fixture(autouse=True)
def _isolate_default_observability():
    """Scope the process-wide default registry and tracer to the test.

    Every instrumented layer records into ONE module-level registry and
    tracer, so without a boundary a test inherits the previous test's
    counter values, histogram buckets, trace-exemplar refs, and — worst
    — callback gauges whose closures pin the previous test's gates and
    labs alive. Setup-time reset (autouse fixtures instantiate before
    the test's own fixtures) zeroes child values in place, drops
    callback-gauge children, and clears the tracer ring, so each test
    observes only what it recorded. Delta-style tests (before/after
    scrapes) are unaffected — they normalize their own baseline."""
    from noise_ec_tpu.obs.events import default_event_log
    from noise_ec_tpu.obs.registry import default_registry
    from noise_ec_tpu.obs.trace import default_tracer

    default_registry().reset_values()
    default_tracer().clear()
    default_event_log().clear()
    yield


@pytest.fixture
def lockgraph():
    """Opt-in lockdep/tsan-lite harness (docs/static-analysis.md):
    instruments every ``threading.Lock``/``RLock`` the test creates,
    recording lock-order edges and loop-thread blocking; teardown
    asserts zero ordering cycles and zero loop-blocking events, so the
    test run itself is the race detector. Sleep-under-lock events are
    reported but not asserted (worker-side lingers can be deliberate)."""
    from noise_ec_tpu.analysis import lockgraph as lg

    graph = lg.install()
    try:
        yield graph
    finally:
        lg.uninstall()
    report = graph.report()
    assert report["locks"], "lockgraph engaged but saw no locks created"
    assert report["cycles"] == [], (
        f"lock-order cycles over the run: {report['cycles']}"
    )
    assert report["loop_block_events"] == [], (
        "loop threads blocked during the run: "
        f"{report['loop_block_events']}"
    )


def hypothesis_stubs():
    """Stand-ins for ``(given, settings, st)`` when hypothesis is absent.

    The optional test deps (requirements-test.txt) may be missing in
    hermetic images; a module-level ``from hypothesis import ...`` then
    kills the WHOLE module at collection — dozens of non-property tests
    with it. These stubs let the module import: ``@given``-decorated
    tests are marked skipped, everything else runs. ``st`` chains any
    attribute/call (strategy expressions evaluate at decoration time).
    """

    class _Anything:
        def __call__(self, *args, **kwargs):
            return self

        def __getattr__(self, name):
            return self

    def given(*args, **kwargs):
        def deco(fn):
            return pytest.mark.skip(reason="hypothesis not installed")(fn)

        return deco

    def settings(*args, **kwargs):
        def deco(fn):
            return fn

        return deco

    return given, settings, _Anything()
