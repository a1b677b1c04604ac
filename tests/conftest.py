"""Test env: the CPU XLA backend with 8 virtual devices, so multi-chip
sharding tests run without TPU hardware (SURVEY.md §4: CPU-XLA is the
reference backend sharing the compiler with TPU).  Tier-1 runs with
``JAX_PLATFORMS=cpu``; ``force_cpu(8)`` makes the same choice through
jax.config for a bare ``pytest`` and adds the virtual devices.

The persistent XLA compilation cache (``enable_compile_cache``: where
``JAX_COMPILATION_CACHE_DIR`` says, else ``.jax_cache/`` in the checkout)
keeps repeat suite runs fast: the first run pays the compiles.
Run the quick tier with ``pytest -m "not slow"``.
"""
import os
import sys
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from mxnet_tpu.utils.platform import enable_compile_cache, force_cpu  # noqa: E402

force_cpu(8)
enable_compile_cache()


import pytest  # noqa: E402


@pytest.fixture
def mesh_devices():
    """Factory fixture: ``mesh_devices(n)`` → the first ``n`` XLA
    devices, SKIPPING the test when the process has fewer (e.g. a bare
    pytest invocation that bypassed this conftest's ``force_cpu(8)``).
    Guarding — instead of forcing the platform flag from inside the
    test — keeps the main process's jax platform state unpoisoned:
    ``--xla_force_host_platform_device_count`` is read exactly once at
    backend bring-up, so a mid-session re-force is at best a no-op.
    Multi-device tests that drive real meshes should be lean; heavy
    variants carry the ``slow`` marker."""
    from mxnet_tpu.test_utils import mesh_devices as _take

    def take(n):
        devs = _take(n)
        if devs is None:
            import jax
            pytest.skip(
                f"needs {n} XLA devices, have {len(jax.devices())} — "
                "run under tests/conftest.py (force_cpu(8)) or set "
                "XLA_FLAGS=--xla_force_host_platform_device_count "
                "before jax initializes")
        return devs

    return take


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: heavy sharded-model / long-sequence tests "
        "(deselect with -m 'not slow' for the <5-min smoke tier)")
    config.addinivalue_line(
        "markers",
        "chaos: fault-injection / preemption chaos tests (deterministic "
        "and CPU-fast; select with -m chaos)")
    config.addinivalue_line(
        "markers",
        "fleet: multi-replica router performance contracts "
        "(timing-sensitive, also marked slow; select with -m fleet)")
