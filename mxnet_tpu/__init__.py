"""mxnet_tpu — a TPU-native deep learning framework with MXNet's capability
surface (imperative NDArray + Gluon + hybridize + KVStore data parallel),
built on JAX/XLA/Pallas/pjit.  See SURVEY.md for the blueprint.

Import style parity:  ``import mxnet_tpu as mx`` then ``mx.nd``, ``mx.gluon``,
``mx.autograd``, ``mx.context`` work as in upstream MXNet.
"""
__version__ = "0.1.0"

import os as _os

# ---- env knobs honored at import (documented in docs/env_vars.md; the
# MXNET_* runtime-knob surface of SURVEY.md §5.6.2, TPU-relevant subset) --
# Matmul precision contract: upstream f32 dot/conv is TRUE f32 on every
# backend, while the TPU MXU natively computes f32 contractions as bf16
# passes.  Default to 'highest' (6-pass f32 — bitwise-meaningful f32
# parity; bf16 inputs are unaffected, so AMP keeps full MXU speed) with
# an env knob to relax for f32-heavy speed runs.  Accepts any
# jax_default_matmul_precision value: highest|high|default|float32|
# tensorfloat32|bfloat16_3x|bfloat16.
_prec = _os.environ.get("MXNET_TPU_MATMUL_PRECISION", "highest").lower()
_PREC_VALUES = ("highest", "high", "float32", "tensorfloat32",
                "bfloat16_3x", "bfloat16")
if _prec not in ("", "default"):
    if _prec not in _PREC_VALUES:
        # a typo'd env var must not break import NOR silently drop to the
        # MXU's bf16-pass default — warn and keep the package default
        # 'highest' (the documented f32-parity contract)
        import warnings as _warnings
        _warnings.warn(
            f"MXNET_TPU_MATMUL_PRECISION={_prec!r} is not one of "
            f"{_PREC_VALUES + ('default',)}; using the package default "
            "'highest'", RuntimeWarning)
        _prec = "highest"
    import jax as _jax
    _jax.config.update("jax_default_matmul_precision", _prec)

if _os.environ.get("MXNET_ENGINE_TYPE", "").lower() == "naiveengine":
    # SURVEY.md §5.2: the fully synchronous debug engine ≡ no XLA staging
    import jax as _jax
    _jax.config.update("jax_disable_jit", True)

from . import base
from .base import MXNetError
from .context import Context, Device, cpu, gpu, tpu, num_gpus, num_tpus, \
    current_context
from . import context
from . import random
from . import ndarray
from . import ndarray as nd
from . import autograd

# Subpackages are imported lazily via __getattr__ to keep import time low.
_LAZY = {
    "gluon": ".gluon",
    "optimizer": ".optimizer",
    "kvstore": ".kvstore",
    "kv": ".kvstore",
    "metric": ".metric",
    "initializer": ".initializer",
    "init": ".initializer",
    "lr_scheduler": ".lr_scheduler",
    "parallel": ".parallel",
    "models": ".models",
    "amp": ".amp",
    "profiler": ".profiler",
    "io": ".io",
    "data": ".data",
    "image": ".image",
    "recordio": ".recordio",
    "runtime": ".runtime",
    "serving": ".serving",
    "fleet": ".fleet",
    "resilience": ".resilience",
    "observability": ".observability",
    "test_utils": ".test_utils",
    "np": ".numpy",
    "npx": ".numpy_extension",
    "sym": ".symbol",
    "symbol": ".symbol",
    "module": ".module",
    "mod": ".module",
    "callback": ".callback",
    "util": ".util",
    "contrib": ".contrib",
    "operator": ".operator",
    "onnx": ".onnx",
    "subgraph": ".subgraph",
    "viz": ".visualization",
    "visualization": ".visualization",
    "library": ".library",
    "monitor": ".monitor",
    "mon": ".monitor",
    "model": ".model",
    "engine": ".engine",
    "name": ".name",
    "attribute": ".attribute",
    "rtc": ".rtc",
    "device": ".context",   # 2.x rename: mx.device is the context module
}


def __getattr__(name):
    if name in _LAZY:
        import importlib
        mod = importlib.import_module(_LAZY[name], __name__)
        globals()[name] = mod
        return mod
    raise AttributeError(f"module 'mxnet_tpu' has no attribute {name!r}")
