"""Runtime feature detection (parity: python/mxnet/runtime.py +
src/libinfo.cc, SURVEY.md §5.6.3).

Build flags become runtime facts on TPU: features reflect what the JAX
backend actually provides in this process (TPU present, Pallas usable,
distributed initialized, ...), so tests can gate with
``mx.runtime.Features()["TPU"].enabled`` the way MXNet tests gate on CUDA.
"""
from __future__ import annotations

from collections import OrderedDict, namedtuple

__all__ = ["Feature", "Features", "feature_list"]

Feature = namedtuple("Feature", ["name", "enabled"])


def _detect():
    import jax

    feats = OrderedDict()

    def add(name, enabled):
        feats[name] = Feature(name, bool(enabled))

    platforms = set()
    try:
        platforms = {d.platform for d in jax.devices()}
    except Exception:
        pass
    add("TPU", "tpu" in platforms)
    add("CUDA", "gpu" in platforms)
    add("CPU", True)
    add("CPU_SSE", True)   # XLA:CPU vectorizes; kept for API compat
    add("BLAS_OPEN", True)
    add("F16C", True)
    add("BF16", True)      # native on TPU
    add("INT64_TENSOR_SIZE", False)
    add("SIGNAL_HANDLER", False)
    add("PROFILER", True)  # jax.profiler bridge
    try:
        import jax.experimental.pallas  # noqa: F401
        add("PALLAS", True)
    except ImportError:
        add("PALLAS", False)
    add("DIST_KVSTORE", True)  # jax.distributed collectives
    try:
        from .utils import native
        add("NATIVE_IO", native.available())
    except Exception:
        add("NATIVE_IO", False)
    add("ONEDNN", False)
    add("TENSORRT", False)
    add("OPENCV", False)   # PIL-backed image path instead
    return feats


class Features(OrderedDict):
    """Mapping of feature name → Feature (parity: mx.runtime.Features)."""

    instance = None

    def __new__(cls):
        if cls.instance is None:
            cls.instance = super().__new__(cls)
            OrderedDict.__init__(cls.instance, _detect())
        return cls.instance

    def __init__(self):
        pass

    def __repr__(self):
        return f"[{', '.join(self.keys())}]"

    def is_enabled(self, name):
        return self[name].enabled


def feature_list():
    return list(Features().values())
