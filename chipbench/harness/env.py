"""Process bring-up for a benchmark run: the device it must find, the
compile cache, the count of compiles, peak memory."""
from __future__ import annotations

import os

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def require_chips(n: int):
    """The first ``n`` TPU devices, or :class:`NoChip`.  There is no CPU
    mode: a number from a CPU run is never a device metric."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: jax.devices()[0] is {devs[0].platform}:"
                     f"{devs[0].device_kind} (JAX_PLATFORMS="
                     f"{os.environ.get('JAX_PLATFORMS')!r})")
    if len(devs) < n:
        raise NoChip(f"the cell needs {n} chips, jax found {len(devs)}")
    return devs[:n]


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache at a fixed path: where
    ``JAX_COMPILATION_CACHE_DIR`` is set, there (jax has read it);
    otherwise ``.jax_cache`` at the root of the checkout — the same
    rule as the program's own helper, so both agree on one directory."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(ROOT, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return jax.config.jax_compilation_cache_dir


class CompileCounter:
    """Counts XLA backend compiles in this process (cache hits are not
    compiles) through ``jax.monitoring``.  One listener per process."""

    _events = None

    def __init__(self):
        cls = CompileCounter
        if cls._events is None:
            import jax.monitoring

            cls._events = []

            def on(name, *_a, **_kw):
                if name == "/jax/core/compile/backend_compile_duration":
                    cls._events.append(name)

            jax.monitoring.register_event_duration_secs_listener(on)

    def count(self) -> int:
        return len(CompileCounter._events)


class GcPauses:
    """Python's own garbage collections while it is open: ``close()``
    gives (generation, seconds) of those over 50 ms.  So that a stalled
    step can be told from a collection in this process."""

    def __init__(self):
        import gc
        import time

        self._found, self._t0 = [], 0.0

        def on(phase, info):
            if phase == "start":
                self._t0 = time.monotonic()
            elif time.monotonic() - self._t0 > 0.05:
                self._found.append((info["generation"],
                                    time.monotonic() - self._t0))

        self._on = on
        gc.callbacks.append(on)

    def close(self) -> list:
        import gc

        gc.callbacks.remove(self._on)
        return self._found


def device_record(devices) -> dict:
    """platform, kind, count and the peak bytes on the fullest chip, as
    JAX's ``memory_stats()`` reports them.  On this runtime a program's
    temporaries are not in ``peak_bytes_in_use``: they are reserved apart
    and show under ``peak_bytes_reserved`` (a 124M training step reads
    2.0 GB in use beside 7.4 GB reserved, where the compiler sizes its
    temporaries at 8.9 GB; a serving engine 11.8 GB beside 0.7 GB), so
    the chip's peak is the sum of the two."""
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0))
                     + int(stats.get("peak_bytes_reserved", 0)))
    say(memory_stats=devices[0].memory_stats())
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices),
            "memory_peak_bytes": max(peaks)}


def say(**rec):
    """One JSON line on standard output, ahead of the result line."""
    import json

    print(json.dumps(rec), flush=True)
