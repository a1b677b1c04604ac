"""Device / context model.

Parity target: ``python/mxnet/context.py`` (1.x) / ``device.py`` (2.x) —
``mx.cpu()``, ``mx.gpu(i)``, default-context scoping, ``num_gpus()``.

TPU-first design: a :class:`Context` is a thin named handle resolving to a
``jax.Device``.  ``gpu(i)`` is kept as a compatibility alias that resolves to
the i-th accelerator so existing scripts run unmodified; ``tpu(i)`` is the
native spelling.  There are no per-device streams to manage — XLA's async
dispatch replaces MXNet's stream/engine machinery (SURVEY.md §7.1).
"""
from __future__ import annotations

import threading
from typing import List, Optional

import jax

__all__ = ["Context", "Device", "cpu", "gpu", "tpu", "cpu_pinned",
           "num_gpus", "num_tpus", "current_context", "current_device"]

_state = threading.local()


class Context:
    """A device handle: ``Context('tpu', 0)``.

    Acts as a context manager setting the default context, mirroring
    ``with mx.gpu(0): ...`` semantics.
    """

    devtype2id = {"cpu": 1, "gpu": 2, "cpu_pinned": 3, "cpu_shared": 5, "tpu": 6}
    devid2type = {v: k for k, v in devtype2id.items()}

    def __init__(self, device_type: str = "cpu", device_id: int = 0):
        if device_type not in self.devtype2id:
            raise ValueError(f"unknown device type {device_type!r}")
        self.device_type = device_type
        self.device_id = int(device_id)

    # -- identity ----------------------------------------------------------
    def __eq__(self, other):
        return (
            isinstance(other, Context)
            and self.device_type == other.device_type
            and self.device_id == other.device_id
        )

    def __hash__(self):
        return hash((self.device_type, self.device_id))

    def __repr__(self):
        return f"{self.device_type}({self.device_id})"

    # -- resolution --------------------------------------------------------
    @property
    def jax_device(self) -> jax.Device:
        """Resolve to a concrete jax.Device.

        cpu→host backend; gpu/tpu→the default accelerator backend.  ``gpu`` is
        an alias kept so GluonCV-era scripts keep working on TPU.  With no
        accelerator in the process a gpu/tpu context is an error, never a
        CPU device under an accelerator's name.
        """
        if self.device_type in ("cpu", "cpu_pinned", "cpu_shared"):
            devs = _backend_devices("cpu")
        else:
            devs = accelerator_devices()
            if not devs:
                from .base import MXNetError
                raise MXNetError(
                    f"{self!r}: this process has no accelerator device "
                    f"(jax backend {jax.default_backend()!r}) — use "
                    "mx.cpu(), or run where the chip is attached")
        return devs[self.device_id % len(devs)]

    # convenience parity helpers
    def empty_cache(self):  # MXNet: ctx.empty_cache() — XLA manages HBM pools
        return None


Device = Context  # 2.x name


_DEVICE_CACHE: dict = {}


def _backend_devices(platform: str) -> List[jax.Device]:
    """PROCESS-LOCAL devices of a platform: MXNet context semantics are
    per-worker (each worker's cpu(0)/tpu(0) is its own), and in a
    multi-process job placing eager arrays on another process's device is
    both wrong and unsupported.  Cached — device enumeration sits on the
    eager dispatch hot path."""
    devs = _DEVICE_CACHE.get(platform)
    if devs is None:
        devs = _DEVICE_CACHE[platform] = list(
            jax.local_devices(backend=platform))
    return devs


_ACCEL_CACHE: Optional[List[jax.Device]] = None


def accelerator_devices() -> List[jax.Device]:
    """All non-host devices (TPU chips), else empty.

    The result — INCLUDING an empty one — is cached: this sits on the
    eager dispatch hot path (``current_context`` consults it per op on
    an empty context stack), so a CPU-only host must not re-enumerate
    devices forever."""
    global _ACCEL_CACHE
    if _ACCEL_CACHE is None:
        _ACCEL_CACHE = [d for d in jax.local_devices()
                        if d.platform != "cpu"]
    return _ACCEL_CACHE


def cpu(device_id: int = 0) -> Context:
    return Context("cpu", device_id)


def cpu_pinned(device_id: int = 0) -> Context:
    return Context("cpu_pinned", device_id)


def gpu(device_id: int = 0) -> Context:
    return Context("gpu", device_id)


def tpu(device_id: int = 0) -> Context:
    return Context("tpu", device_id)


def num_gpus() -> int:
    """Compat: reports accelerator count so ``ctx = mx.gpu() if mx.context.
    num_gpus() else mx.cpu()`` idioms pick the TPU."""
    return len(accelerator_devices())


def num_tpus() -> int:
    return len(accelerator_devices())


def current_context() -> Context:
    stack = getattr(_state, "stack", None)
    if stack:
        return stack[-1]
    return Context("tpu", 0) if accelerator_devices() else Context("cpu", 0)


current_device = current_context


def _push_context(ctx: Context):
    if not hasattr(_state, "stack"):
        _state.stack = []
    _state.stack.append(ctx)


def _pop_context():
    _state.stack.pop()


class _CtxScope:
    def __init__(self, ctx):
        self.ctx = ctx

    def __enter__(self):
        _push_context(self.ctx)
        return self.ctx

    def __exit__(self, *a):
        _pop_context()


# Attach context-manager behavior to Context itself (mx 2.x style).
Context.__enter__ = lambda self: (_push_context(self), self)[1]
Context.__exit__ = lambda self, *a: _pop_context()
