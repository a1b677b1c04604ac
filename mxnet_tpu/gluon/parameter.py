"""Parameter & ParameterDict (parity: python/mxnet/gluon/parameter.py).

TPU-first: a Parameter holds ONE NDArray whose payload may be a sharded
``jax.Array`` laid out over the device mesh (replacing MXNet's per-context
copy lists).  ``data(ctx)`` / ``list_data()`` keep their signatures.
"""
from __future__ import annotations

from typing import Dict, List, Optional

import jax.numpy as jnp
import numpy as onp

from .. import base as _base
from .. import initializer as init_mod
from ..context import Context, cpu, current_context
from ..ndarray import NDArray, ndarray as _ndmod


class DeferredInitializationError(_base.MXNetError):
    pass


class Parameter:
    def __init__(self, name="weight", grad_req="write", shape=None,
                 dtype="float32", lr_mult=1.0, wd_mult=1.0, init=None,
                 allow_deferred_init=False, differentiable=True,
                 stype="default", grad_stype="default"):
        self._name = name
        self.shape = tuple(shape) if shape is not None else None
        self.dtype = _base.canonical_dtype(dtype)
        self.lr_mult = lr_mult
        self.wd_mult = wd_mult
        self.init = init
        self.allow_deferred_init = allow_deferred_init
        self.grad_req = grad_req if differentiable else "null"
        self.stype = stype
        self.grad_stype = grad_stype
        self._data: Optional[NDArray] = None
        self._deferred_init = None  # (initializer, ctx)
        self._sharding = None       # jax.sharding.Sharding once mesh-placed

    # -- naming ------------------------------------------------------------
    @property
    def name(self):
        return self._name

    def __repr__(self):
        return (f"Parameter {self._name} (shape={self.shape}, "
                f"dtype={self.dtype})")

    # -- shape -------------------------------------------------------------
    @property
    def _shape_known(self):
        return self.shape is not None and all(s > 0 for s in self.shape)

    def _set_shape(self, shape):
        shape = tuple(shape)
        if self.shape is not None and len(self.shape) == len(shape):
            for old, new in zip(self.shape, shape):
                if old > 0 and old != new:
                    raise ValueError(
                        f"Parameter {self._name}: inferred shape {shape} "
                        f"incompatible with declared {self.shape}")
        self.shape = shape
        if self._deferred_init is not None:
            self._finish_deferred_init()

    # -- init --------------------------------------------------------------
    def initialize(self, init=None, ctx=None, default_init=None,
                   force_reinit=False):
        if self._data is not None and not force_reinit:
            return
        if ctx is None:
            ctx = current_context()
        if isinstance(ctx, (list, tuple)):
            ctx = ctx[0]  # single sharded array covers all devices
        initializer = init_mod.create(
            init or self.init or default_init or init_mod.Uniform())
        if not self._shape_known:
            if not self.allow_deferred_init:
                raise ValueError(
                    f"Cannot initialize Parameter {self._name}: shape "
                    f"{self.shape} unknown and deferred init not allowed")
            self._deferred_init = (initializer, ctx)
            return
        self._init_impl(initializer, ctx)

    def _init_impl(self, initializer, ctx):
        # deferred init can fire inside an abstract settle trace
        # (ShardedTrainer's jax.eval_shape pass); under omnistaging every
        # jax op would stage to that trace and bind TRACERS as param data.
        # Parameter values are never functions of traced inputs, so force
        # concrete evaluation.
        import jax

        with jax.ensure_compile_time_eval():
            arr = _ndmod.zeros(self.shape, ctx=ctx, dtype=self.dtype)
            initializer(self._name, arr, explicit=self.init is not None)
            self._data = arr
            self._deferred_init = None
            if self.grad_req != "null":
                self._attach_grad()   # grad buffer must be concrete too

    def _finish_deferred_init(self):
        if self._deferred_init is None:
            return
        initializer, ctx = self._deferred_init
        if not self._shape_known:
            raise DeferredInitializationError(
                f"Parameter {self._name} shape still unknown")
        self._init_impl(initializer, ctx)

    def _attach_grad(self):
        if self._data is None:
            return
        self._data.attach_grad(grad_req=self.grad_req,
                               stype=self.grad_stype)

    # -- access ------------------------------------------------------------
    def data(self, ctx=None) -> NDArray:
        if self._data is None:
            if self._deferred_init is not None:
                raise DeferredInitializationError(
                    f"Parameter {self._name} pending deferred init — call "
                    "the block with data first")
            raise _base.MXNetError(
                f"Parameter {self._name} has not been initialized. Call "
                ".initialize() first")
        return self._data

    def list_data(self) -> List[NDArray]:
        return [self.data()]

    def grad(self, ctx=None) -> NDArray:
        d = self.data()
        if d.grad is None:
            if self.grad_req == "null":
                raise _base.MXNetError(
                    f"Parameter {self._name} grad_req='{self.grad_req}' — "
                    "no gradient buffer")
            # released by a trainer that owns the step: zero until an
            # eager backward writes it (the tape's leaf node stays)
            node = d._node
            self._attach_grad()
            if node is not None:
                d._node = node
        return d.grad

    def _release_grad(self) -> int:
        """Drop the attached gradient buffer and return its bytes.  The
        tape's leaf node stays, so an eager ``backward`` (or ``grad()``)
        afterwards attaches a fresh zero buffer: a released buffer reads
        as the zeros ``zero_grad`` would have left."""
        d = self._data
        if d is None or d._grad is None:
            return 0
        g, d._grad = d._grad, None
        # a row-sparse buffer holds its rows present, not (rows, dim)
        held = g._sp_data if self.grad_stype == "row_sparse" else g.jax
        return int(held.nbytes)

    def list_grad(self) -> List[NDArray]:
        return [self.grad()]

    def list_ctx(self) -> List[Context]:
        return [self.data().context] if self._data is not None else []

    def set_data(self, data):
        if not isinstance(data, NDArray):
            data = _ndmod.array(data, dtype=self.dtype)
        if self._data is None:
            self.shape = data.shape
            self._data = _ndmod.array(data, dtype=self.dtype)
            self._deferred_init = None
            if self.grad_req != "null":
                self._attach_grad()
        else:
            new = jnp.asarray(data.jax, dtype=self.dtype)
            old = self._data.jax
            if getattr(new, "_committed", False) and \
                    not getattr(old, "_committed", True):
                # the replacement payload must inherit the OLD
                # payload's placement: initialize() leaves params
                # UNCOMMITTED (default placement), and jax's jit cache
                # keys on committed-ness — a committed replacement
                # (e.g. nd.array(host_data) routed through device_put)
                # silently re-specializes EVERY executable that traced
                # over the old payload, one hidden recompile per
                # program on its next dispatch.  A serving engine
                # sharing this net then stalls on traffic after
                # warmup() with its compile counter unmoved — the
                # compile-freeze contract violated from outside.
                # reset_ctx is the API for intentional placement moves.
                new = jnp.asarray(onp.asarray(new), dtype=self.dtype)
            self._data._rebind(new)

    def zero_grad(self):
        d = self._data
        if d is not None and d.grad is not None:
            from ..ndarray.sparse import RowSparseNDArray
            if isinstance(d.grad, RowSparseNDArray):
                d.grad._set_components(
                    jnp.zeros((0,) + tuple(d.grad._sp_shape[1:]),
                              d.grad._sp_data.dtype),
                    jnp.zeros((0,), jnp.int32))
            else:
                d.grad._rebind(jnp.zeros_like(d.grad.jax))

    def reset_ctx(self, ctx):
        if self._data is not None:
            self._data = self._data.as_in_context(
                ctx[0] if isinstance(ctx, (list, tuple)) else ctx)
            if self.grad_req != "null":
                self._attach_grad()

    def cast(self, dtype):
        self.dtype = _base.canonical_dtype(dtype)
        if self._data is not None:
            had_grad = self._data.grad is not None
            self._data = self._data.astype(self.dtype)
            if had_grad:
                self._attach_grad()

    # -- serialization -----------------------------------------------------
    def _reduce(self) -> NDArray:
        return self.data()

    @property
    def var(self):  # symbol-API compat hook
        return self


class Constant(Parameter):
    """Non-trainable constant parameter (parity: gluon.Constant)."""

    def __init__(self, name, value=None):
        if value is None:  # 2.x signature Constant(value)
            value = name
            name = "const"
        if not isinstance(value, NDArray):
            value = _ndmod.array(onp.asarray(value))
        self.value = value
        super().__init__(name=name, grad_req="null", shape=value.shape,
                         dtype=value.dtype,
                         init=init_mod.Constant(0.0), differentiable=False)
        self._data = value


class ParameterDict:
    """Ordered name→Parameter mapping (parity: gluon.ParameterDict)."""

    def __init__(self, prefix="", shared=None):
        self._prefix = prefix
        self._params: Dict[str, Parameter] = {}
        self._shared = shared

    @property
    def prefix(self):
        return self._prefix

    def __getitem__(self, name) -> Parameter:
        return self._params[name]

    def __contains__(self, name):
        return name in self._params

    def __iter__(self):
        return iter(self._params)

    def __len__(self):
        return len(self._params)

    def items(self):
        return self._params.items()

    def keys(self):
        return self._params.keys()

    def values(self):
        return self._params.values()

    def get(self, name, **kwargs) -> Parameter:
        full = self._prefix + name
        if full in self._params:
            return self._params[full]
        if self._shared is not None and full in self._shared:
            self._params[full] = self._shared[full]
            return self._params[full]
        p = Parameter(name=full, **kwargs)
        self._params[full] = p
        return p

    def update(self, other):
        if isinstance(other, ParameterDict):
            other = other._params
        for k, v in other.items():
            self._params[k] = v

    def initialize(self, init=None, ctx=None, verbose=False,
                   force_reinit=False):
        for p in self._params.values():
            p.initialize(init=None, ctx=ctx, default_init=init,
                         force_reinit=force_reinit)

    def zero_grad(self):
        for p in self._params.values():
            p.zero_grad()

    def reset_ctx(self, ctx):
        for p in self._params.values():
            p.reset_ctx(ctx)

    def setattr(self, name, value):
        for p in self._params.values():
            setattr(p, name, value)

    def save(self, filename, strip_prefix=""):
        from ..utils.serialization import save
        data = {}
        for name, p in self._params.items():
            if strip_prefix and name.startswith(strip_prefix):
                name = name[len(strip_prefix):]
            data[name] = p._reduce()
        save(filename, data)

    def load(self, filename, ctx=None, allow_missing=False,
             ignore_extra=False, restore_prefix=""):
        from ..utils.serialization import load
        loaded = load(filename)
        loaded = {restore_prefix + k: v for k, v in loaded.items()}
        for name, p in self._params.items():
            if name in loaded:
                p.set_data(loaded[name])
            elif not allow_missing:
                raise _base.MXNetError(f"Parameter {name} missing in file "
                                       f"{filename}")
        if not ignore_extra:
            extra = set(loaded) - set(self._params)
            if extra:
                raise _base.MXNetError(
                    f"Extra parameters in {filename}: {sorted(extra)}")
