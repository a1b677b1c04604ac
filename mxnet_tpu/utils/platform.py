"""Process bring-up shared by the entry points: which XLA backend a run
uses, and where its compiled programs are cached.

Tests run on the CPU backend (:func:`force_cpu`, eight virtual devices);
everything that measures or proves something about the chip calls
:func:`require_tpu`, which has no CPU mode — a missing chip is an error,
never a smaller run under the same name.
"""
from __future__ import annotations

import os

# the one in-checkout cache location (listed in .gitignore); fixed because
# the directory is part of the persistent cache's key
_REPO_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def force_cpu(n_devices: int = 8) -> None:
    """Select the CPU XLA backend with ``n_devices`` virtual devices.

    Must run BEFORE any jax device is touched: both the platform choice
    and ``--xla_force_host_platform_device_count`` are read once, at
    backend bring-up.
    """
    flags = os.environ.get("XLA_FLAGS", "")
    if "host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=%d"
            % max(n_devices, 1))

    import jax

    jax.config.update("jax_platforms", "cpu")


def require_tpu():
    """The first TPU device, or :class:`MXNetError` naming what was found
    instead.  No probe process and no fallback: the chip belongs to one
    process at a time, and a run that silently lands on the CPU reports
    numbers nobody deploys."""
    import jax

    from ..base import MXNetError

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise MXNetError(
            f"no TPU: jax.devices()[0] is {dev.platform}:{dev.device_kind} "
            f"(JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r}) — this "
            "entry point runs on the chip only; the CPU backend is for "
            "tests (tests/conftest.py)")
    return dev


def enable_compile_cache() -> str:
    """Turn on jax's persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set jax has already read it and
    no directory is set in code; otherwise the cache lives at ``.jax_cache``
    in the checkout root.
    """
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", _REPO_CACHE)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return jax.config.jax_compilation_cache_dir
