"""NDArray: imperative, mutable-looking tensor facade over ``jax.Array``.

Parity target: ``include/mxnet/ndarray.h`` + ``python/mxnet/ndarray/ndarray.py``
(see SURVEY.md §2.1, §7.1).  TPU-first design decisions:

- The payload is an **immutable** ``jax.Array`` (or a JAX tracer while inside
  a hybridized/jitted trace).  "In-place" mutation rebinds the payload
  (functional SSA under the hood) — this is what makes the same op code work
  both eagerly and under ``jax.jit`` tracing, replacing MXNet's
  engine-var/version machinery wholesale: XLA async dispatch already gives the
  compute/copy overlap the threaded engine existed for.
- **Views** (basic slicing) carry a reference to their base plus the index;
  reads re-slice the base lazily, writes scatter into the base and rebind it.
  This reproduces MXNet's aliasing semantics (``y = x[1:3]; y += 1`` mutates
  ``x``) without shared mutable memory.
- **Async semantics**: JAX dispatch is already asynchronous;
  ``wait_to_read()`` maps to ``jax.block_until_ready`` — same contract as the
  dependency engine's ``WaitForVar``.
- **Autograd**: when recording, every dispatched op creates a tape node (see
  ``mxnet_tpu.autograd.tape``); input values are captured as immutable jax
  arrays, so later in-place rebinds can never corrupt the backward pass (a
  class of bug MXNet guards against with version counters).
"""
from __future__ import annotations

import numbers
from typing import Any, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as onp

from .. import base as _base
from ..context import Context, current_context
from ..observability.trace import host_range as _host_range

__all__ = ["NDArray", "array", "from_jax", "zeros", "ones", "full", "empty",
           "arange", "eye", "linspace", "concatenate"]


def _is_jax_value(x) -> bool:
    return isinstance(x, (jax.Array, jax.core.Tracer))


class NDArray:
    __slots__ = ("_data", "_ctx", "_base", "_key", "_node", "_grad",
                 "_mask", "__weakref__")

    def __init__(self, data, ctx: Optional[Context] = None,
                 _base_arr: "Optional[NDArray]" = None, _key=None):
        self._base = _base_arr
        self._key = _key
        self._node = None      # autograd tape node (or None)
        self._grad = None      # NDArray gradient buffer once attach_grad'd
        self._ctx = ctx or current_context()
        if _base_arr is not None:
            self._data = None  # view: value derived from base lazily
        else:
            self._data = data

    # ------------------------------------------------------------------ value
    @property
    def jax(self):
        """The current jax.Array value (resolving views)."""
        if self._base is not None:
            return self._base.jax[self._key]
        return self._data

    def _rebind(self, new_value, node=None):
        """In-place mutation: rebind payload (or scatter into view base)."""
        if self._base is not None:
            base_new = self._base.jax.at[self._key].set(
                jnp.asarray(new_value, dtype=self._base.dtype))
            self._base._rebind(base_new, node=None)
            return
        self._data = new_value
        self._node = node

    # ---------------------------------------------------------------- basics
    @property
    def shape(self) -> Tuple[int, ...]:
        return tuple(self.jax.shape)

    @property
    def dtype(self):
        return onp.dtype(self.jax.dtype)

    @property
    def size(self) -> int:
        return int(onp.prod(self.shape)) if self.shape else 1

    @property
    def ndim(self) -> int:
        return len(self.shape)

    @property
    def context(self) -> Context:
        return self._ctx

    ctx = context
    device = context

    @property
    def stype(self) -> str:
        return "default"  # sparse storage types are handled by sparse module

    @property
    def T(self) -> "NDArray":
        from . import ops
        return ops.transpose(self)

    def __len__(self):
        if self.ndim == 0:
            raise TypeError("len() of unsized object")
        return self.shape[0]

    def __iter__(self):
        for i in range(len(self)):
            yield self[i]

    def __repr__(self):
        try:
            body = str(self.asnumpy())
        except Exception:  # tracer
            body = f"<traced {self.shape} {self.dtype}>"
        return f"\n{body}\n<NDArray {'x'.join(map(str, self.shape))} @{self._ctx}>"

    # ------------------------------------------------------------- transfers
    def asnumpy(self) -> onp.ndarray:
        """Synchronizing device→host copy (MXNet's WaitToRead + copy).

        Always returns an OWNED, writable array.  On the CPU backend
        ``np.asarray(jax_array)`` is a zero-copy read-only view of the
        device buffer — and XLA donation (``ShardedTrainer(donate=True)``,
        the serving cache) reuses that memory without regard for live
        numpy views, so a supposedly-snapshotted value would silently
        change under the caller.  The MXNet contract is a copy; pay the
        memcpy (TPU's device→host transfer already owns its buffer, so
        nothing is copied twice)."""
        v = self.jax
        if isinstance(v, jax.core.Tracer):
            raise _base.MXNetError(
                "asnumpy() called inside a hybridized/jitted trace; this "
                "graph-breaks. Use .item()/asnumpy() outside hybridize.")
        # the blocking transfer: the host waits here for every program
        # the value depends on (a wait, it launches nothing)
        with _host_range("ndarray", "readback", launches=False):
            a = onp.asarray(v)
        if a.base is not None or not a.flags.writeable:
            a = onp.array(a)
        return a

    def asscalar(self):
        if self.size != 1:
            raise ValueError("The current array is not a scalar")
        return self.asnumpy().reshape(())[()]

    def item(self):
        return self.asscalar()

    def tolist(self):
        return self.asnumpy().tolist()

    def wait_to_read(self):
        v = self.jax
        if not isinstance(v, jax.core.Tracer):
            with _host_range("ndarray", "readback", launches=False):
                jax.block_until_ready(v)

    wait_to_write = wait_to_read

    def copy(self) -> "NDArray":
        return NDArray(self.jax, ctx=self._ctx)

    def copyto(self, other) -> "NDArray":
        if isinstance(other, Context):
            return self.as_in_context(other)
        other._rebind(jnp.asarray(self.jax, dtype=other.dtype))
        return other

    def as_in_context(self, ctx: Context) -> "NDArray":
        v = self.jax
        if not isinstance(v, jax.core.Tracer):
            v = jax.device_put(v, ctx.jax_device)
        return NDArray(v, ctx=ctx)

    as_in_ctx = as_in_context
    to_device = as_in_context

    def astype(self, dtype, copy=True) -> "NDArray":
        dt = _base.canonical_dtype(dtype)
        if not copy and dt == self.dtype:
            return self
        from . import ops
        return ops.cast(self, dtype=dt)

    # ------------------------------------------------------------- autograd
    def attach_grad(self, grad_req: str = "write", stype=None):
        from ..autograd import tape
        if stype == "row_sparse":
            # compact zero: no (rows, dim) dense buffer is ever allocated
            from .sparse import RowSparseNDArray
            self._grad = RowSparseNDArray.from_components(
                jnp.zeros((0,) + self.shape[1:], self.jax.dtype),
                jnp.zeros((0,), jnp.int32), self.shape, ctx=self._ctx)
        else:
            self._grad = NDArray(jnp.zeros_like(self.jax), ctx=self._ctx)
        self._node = tape.LeafNode(self, grad_req)

    @property
    def grad(self) -> "Optional[NDArray]":
        return self._grad

    def detach(self) -> "NDArray":
        out = NDArray(self.jax, ctx=self._ctx)
        return out

    def backward(self, out_grad=None, retain_graph=False, train_mode=True):
        from .. import autograd
        autograd.backward([self], [out_grad] if out_grad is not None else None,
                          retain_graph=retain_graph, train_mode=train_mode)

    # ------------------------------------------------------------- indexing
    def _canonical_key(self, key):
        if isinstance(key, tuple):
            return self, tuple(self._index_key(k) for k in key)
        return self, self._index_key(key)

    def _index_key(self, k):
        if isinstance(k, NDArray):
            kj = k.jax
            if kj.dtype == jnp.bool_:
                return kj
            if getattr(k, "_mask", False):
                # result of a comparison op: boolean-mask semantics
                return kj.astype(bool)
            if not jnp.issubdtype(kj.dtype, jnp.integer):
                # MXNet comparisons yield float 0/1 arrays: a same-shaped
                # float key is the x[x > 5] mask idiom, else a fancy index
                if kj.ndim > 0 and tuple(kj.shape) == self.shape:
                    return kj.astype(bool)
                return kj.astype(jnp.int32)
            return kj
        return k

    @staticmethod
    def _is_basic_index(key) -> bool:
        """Basic (view-forming) index: ints/slices/ellipsis/None tuples."""
        items = key if isinstance(key, tuple) else (key,)
        return all(isinstance(k, (int, onp.integer, slice, type(Ellipsis),
                                  type(None))) for k in items)

    def __getitem__(self, key):
        _, key = self._canonical_key(key)
        from . import ops
        if self._is_basic_index(key) and not _base.is_recording():
            # aliasing view: writes through this object hit the base
            return NDArray(None, ctx=self._ctx, _base_arr=self._root_base(),
                           _key=self._compose_key(key))
        return ops._getitem(self, key)

    def _root_base(self):
        return self if self._base is None else self._base

    def _compose_key(self, key):
        if self._base is None:
            return key
        # view-of-view: compose by materializing through jnp indexing chain
        # (correctness first; deep view chains are rare in real scripts)
        return _ComposedKey(self._key, key)

    def __setitem__(self, key, value):
        _, key = self._canonical_key(key)
        if isinstance(value, NDArray):
            vj = value.jax
        elif isinstance(value, (numbers.Number, bool)):
            vj = value
        else:
            vj = jnp.asarray(value)
        if key is Ellipsis or key == slice(None):
            tgt = self.jax
            new = jnp.broadcast_to(jnp.asarray(vj, dtype=self.dtype),
                                   tgt.shape)
            if _base.is_recording() and isinstance(vj, (jax.Array, jax.core.Tracer)):
                from . import ops
                ops._setitem_full(self, value if isinstance(value, NDArray) else NDArray(new))
            else:
                self._rebind(new)
            return
        if _base.is_recording():
            from . import ops
            ops._setitem(self, key, value if isinstance(value, NDArray)
                         else NDArray(jnp.asarray(vj)))
        else:
            self._rebind(self.jax.at[key].set(
                jnp.asarray(vj, dtype=self.dtype)))

    # ---------------------------------------------------------- arithmetic
    def _binop(self, name, other, reflected=False):
        from . import ops
        fn = getattr(ops, name)
        if reflected:
            return fn(other, self)
        return fn(self, other)

    def __add__(self, o): return self._binop("add", o)
    def __radd__(self, o): return self._binop("add", o, True)
    def __sub__(self, o): return self._binop("subtract", o)
    def __rsub__(self, o): return self._binop("subtract", o, True)
    def __mul__(self, o): return self._binop("multiply", o)
    def __rmul__(self, o): return self._binop("multiply", o, True)
    def __truediv__(self, o): return self._binop("divide", o)
    def __rtruediv__(self, o): return self._binop("divide", o, True)
    def __floordiv__(self, o): return self._binop("floor_divide", o)
    def __rfloordiv__(self, o): return self._binop("floor_divide", o, True)
    def __mod__(self, o): return self._binop("mod", o)
    def __rmod__(self, o): return self._binop("mod", o, True)
    def __pow__(self, o): return self._binop("power", o)
    def __rpow__(self, o): return self._binop("power", o, True)
    def __matmul__(self, o): return self._binop("matmul", o)
    def __rmatmul__(self, o): return self._binop("matmul", o, True)
    def __neg__(self):
        from . import ops
        return ops.negative(self)
    def __abs__(self):
        from . import ops
        return ops.abs(self)

    def _inplace(self, name, other):
        res = self._binop(name, other)
        self._rebind(res.jax, node=res._node)
        return self

    def __iadd__(self, o): return self._inplace("add", o)
    def __isub__(self, o): return self._inplace("subtract", o)
    def __imul__(self, o): return self._inplace("multiply", o)
    def __itruediv__(self, o): return self._inplace("divide", o)
    def __imod__(self, o): return self._inplace("mod", o)
    def __ipow__(self, o): return self._inplace("power", o)

    def __eq__(self, o): return self._binop("equal", o)
    def __ne__(self, o): return self._binop("not_equal", o)
    def __lt__(self, o): return self._binop("lesser", o)
    def __le__(self, o): return self._binop("lesser_equal", o)
    def __gt__(self, o): return self._binop("greater", o)
    def __ge__(self, o): return self._binop("greater_equal", o)

    def __hash__(self):
        return id(self)

    def __bool__(self):
        if self.size == 1:
            return bool(self.asscalar())
        raise ValueError("The truth value of an NDArray with multiple "
                         "elements is ambiguous.")

    def __float__(self):
        return float(self.asscalar())

    def __int__(self):
        return int(self.asscalar())

    def __index__(self):
        return int(self.asscalar())

    # --------------------------------------------------- method-style ops
    def _unary(self, name, **kw):
        from . import ops
        return getattr(ops, name)(self, **kw)

    def reshape(self, *shape, **kwargs):
        from . import ops
        if len(shape) == 1 and isinstance(shape[0], (tuple, list)):
            shape = tuple(shape[0])
        if "shape" in kwargs:
            shape = kwargs["shape"]
        return ops.reshape(self, shape=shape)

    def reshape_like(self, other):
        return self.reshape(other.shape)

    def transpose(self, *axes):
        from . import ops
        if len(axes) == 1 and isinstance(axes[0], (tuple, list)):
            axes = tuple(axes[0])
        return ops.transpose(self, axes=axes if axes else None)

    def swapaxes(self, a1, a2): return self._unary("swapaxes", dim1=a1, dim2=a2)
    def flatten(self): return self._unary("flatten")
    def expand_dims(self, axis): return self._unary("expand_dims", axis=axis)
    def squeeze(self, axis=None): return self._unary("squeeze", axis=axis)
    def broadcast_to(self, shape): return self._unary("broadcast_to", shape=shape)
    def broadcast_like(self, other): return self.broadcast_to(other.shape)
    def sum(self, axis=None, keepdims=False):
        return self._unary("sum", axis=axis, keepdims=keepdims)
    def mean(self, axis=None, keepdims=False):
        return self._unary("mean", axis=axis, keepdims=keepdims)
    def max(self, axis=None, keepdims=False):
        return self._unary("max", axis=axis, keepdims=keepdims)
    def min(self, axis=None, keepdims=False):
        return self._unary("min", axis=axis, keepdims=keepdims)
    def prod(self, axis=None, keepdims=False):
        return self._unary("prod", axis=axis, keepdims=keepdims)
    def argmax(self, axis=None): return self._unary("argmax", axis=axis)
    def argmin(self, axis=None): return self._unary("argmin", axis=axis)

    def _np_method(self, name, *args, **kwargs):
        """Delegate to the mx.np function of the same name (numpy-parity
        methods whose op lives only in the np namespace)."""
        from .. import numpy as _np
        return getattr(_np, name)(self, *args, **kwargs)

    def std(self, axis=None, keepdims=False):
        return self._np_method("std", axis=axis, keepdims=keepdims)
    def var(self, axis=None, keepdims=False):
        return self._np_method("var", axis=axis, keepdims=keepdims)
    def cumsum(self, axis=None):
        return self._np_method("cumsum", axis=axis)
    # sort/argsort follow NUMPY semantics here (differentiable sort,
    # integer indices); the legacy float32-index mx.nd.argsort op keeps
    # its 1.x behavior as a free function
    def sort(self, axis=-1):
        return self._np_method("sort", axis=axis)
    def argsort(self, axis=-1):
        return self._np_method("argsort", axis=axis)
    def nonzero(self): return self._np_method("nonzero")
    def all(self, axis=None, keepdims=False):
        return self._np_method("all", axis=axis, keepdims=keepdims)
    def any(self, axis=None, keepdims=False):
        return self._np_method("any", axis=axis, keepdims=keepdims)
    def ravel(self): return self._np_method("ravel")

    @property
    def itemsize(self):
        import numpy as _onp
        return _onp.dtype(self.dtype).itemsize

    @property
    def flat(self):
        # read-only: a writable .flat would mutate only a host copy —
        # raising beats silently discarding writes
        a = self.asnumpy()
        a.flags.writeable = False
        return a.flat
    def norm(self, ord=2, axis=None, keepdims=False):
        return self._unary("norm", ord=ord, axis=axis, keepdims=keepdims)
    def clip(self, a_min=None, a_max=None):
        return self._unary("clip", a_min=a_min, a_max=a_max)
    def abs(self): return self._unary("abs")
    def exp(self): return self._unary("exp")
    def log(self): return self._unary("log")
    def sqrt(self): return self._unary("sqrt")
    def square(self): return self._unary("square")
    def sign(self): return self._unary("sign")
    def round(self): return self._unary("round")
    def floor(self): return self._unary("floor")
    def ceil(self): return self._unary("ceil")
    def sigmoid(self): return self._unary("sigmoid")
    def tanh(self): return self._unary("tanh")
    def relu(self): return self._unary("relu")
    def softmax(self, axis=-1): return self._unary("softmax", axis=axis)
    def log_softmax(self, axis=-1): return self._unary("log_softmax", axis=axis)
    def one_hot(self, depth, **kw): return self._unary("one_hot", depth=depth, **kw)
    def take(self, indices, axis=0):
        from . import ops
        return ops.take(self, indices, axis=axis)
    def dot(self, other):
        from . import ops
        return ops.dot(self, other)
    def slice_axis(self, axis, begin, end):
        from . import ops
        return ops.slice_axis(self, axis=axis, begin=begin, end=end)
    def split(self, num_outputs, axis=1, squeeze_axis=False):
        from . import ops
        return ops.split(self, num_outputs=num_outputs, axis=axis,
                         squeeze_axis=squeeze_axis)
    def tile(self, reps): return self._unary("tile", reps=reps)
    def repeat(self, repeats, axis=None):
        return self._unary("repeat", repeats=repeats, axis=axis)
    def flip(self, axis): return self._unary("flip", axis=axis)
    def pad(self, *a, **kw): return self._unary("pad", *a, **kw)
    def zeros_like(self): return self._unary("zeros_like")
    def ones_like(self): return self._unary("ones_like")
    def tostype(self, stype):
        if stype == "default":
            return self
        from .sparse import cast_storage
        return cast_storage(self, stype)

    # numpy-protocol interop
    def __array__(self, dtype=None):
        a = self.asnumpy()
        return a.astype(dtype) if dtype is not None else a

    def __dlpack__(self, **kw):
        return self.jax.__dlpack__(**kw)


class _ComposedKey:
    """Index composition for view-of-view (read path materializes)."""

    def __init__(self, outer, inner):
        self.outer = outer
        self.inner = inner


# patched __getitem__ on jax values for composed keys
_orig_jax_getitem = None


def _resolve_key(value, key):
    if isinstance(key, _ComposedKey):
        return _resolve_key(_resolve_key(value, key.outer), key.inner)
    return value[key]


# Make NDArray.jax handle composed keys.
def _jax_prop(self):
    if self._base is not None:
        return _resolve_key(self._base.jax, self._key)
    return self._data


NDArray.jax = property(_jax_prop)


def _rebind_view(self, new_value, node=None):
    if self._base is not None:
        key = self._key
        if isinstance(key, _ComposedKey):
            outer_val = _resolve_key(self._base.jax, key.outer)
            updated = outer_val.at[key.inner].set(
                jnp.asarray(new_value, dtype=outer_val.dtype))
            base_new = self._base.jax.at[key.outer].set(updated)
        else:
            base_new = self._base.jax.at[key].set(
                jnp.asarray(new_value, dtype=self._base.dtype))
        self._base._rebind(base_new, node=None)
        return
    self._data = new_value
    if node is None:
        # attach_grad leaf-ness survives non-recorded mutation (optimizer
        # updates, set_data); only a recorded op result replaces the node
        from ..autograd.tape import LeafNode
        if isinstance(self._node, LeafNode):
            return
    self._node = node


NDArray._rebind = _rebind_view


import contextlib


@contextlib.contextmanager
def swap_values(nds, values):
    """Temporarily rebind each NDArray's payload to a traced value.

    The functionalization primitive shared by CachedOp, ShardedTrainer and
    the driver entry: inside the scope each NDArray in `nds` holds the
    corresponding (usually tracer) value with no autograd node; on exit the
    original payload/node are restored.  Mutations made inside the scope are
    visible via each NDArray's current payload before exit (callers read them
    to functionalize aux-state updates such as BatchNorm running stats).
    """
    saved = [(d, d._data, d._node) for d in nds]
    for d, v in zip(nds, values):
        d._data, d._node = v, None
    try:
        yield saved
    finally:
        for d, old, node in saved:
            d._data, d._node = old, node


# ----------------------------------------------------------------- creation

def _put(value, ctx: Optional[Context]) -> jax.Array:
    ctx = ctx or current_context()
    if isinstance(value, jax.core.Tracer):
        return value
    return jax.device_put(value, ctx.jax_device)


def array(source, ctx: Optional[Context] = None, dtype=None) -> NDArray:
    if isinstance(source, NDArray):
        source = source.jax
    dt = _base.canonical_dtype(dtype) if dtype is not None else None
    if not _is_jax_value(source):
        keep_dtype = isinstance(source, onp.ndarray)
        source = onp.asarray(source, dtype=dt)
        if dt is None:
            if source.dtype == onp.float64:
                source = source.astype(onp.float32)
            elif not keep_dtype:
                # MXNet: python lists default to float32; numpy arrays keep
                # their dtype (python/mxnet/ndarray/ndarray.py array())
                source = source.astype(onp.float32)
    elif dt is not None:
        source = jnp.asarray(source, dtype=dt)
    ctx = ctx or current_context()
    return NDArray(_put(source, ctx), ctx=ctx)


def from_jax(value, ctx: Optional[Context] = None) -> NDArray:
    return NDArray(value, ctx=ctx or current_context())


def zeros(shape, ctx=None, dtype="float32") -> NDArray:
    shape = (shape,) if isinstance(shape, int) else tuple(shape)
    return array(onp.zeros(shape, dtype=_base.canonical_dtype(dtype)), ctx=ctx)


def ones(shape, ctx=None, dtype="float32") -> NDArray:
    shape = (shape,) if isinstance(shape, int) else tuple(shape)
    return array(onp.ones(shape, dtype=_base.canonical_dtype(dtype)), ctx=ctx)


def full(shape, val, ctx=None, dtype="float32") -> NDArray:
    shape = (shape,) if isinstance(shape, int) else tuple(shape)
    return array(onp.full(shape, val, dtype=_base.canonical_dtype(dtype)),
                 ctx=ctx)


def empty(shape, ctx=None, dtype="float32") -> NDArray:
    return zeros(shape, ctx=ctx, dtype=dtype)


def arange(start, stop=None, step=1.0, repeat=1, ctx=None,
           dtype="float32") -> NDArray:
    arr = onp.arange(start, stop, step, dtype=_base.canonical_dtype(dtype))
    if repeat > 1:
        arr = onp.repeat(arr, repeat)
    return array(arr, ctx=ctx)


def eye(N, M=None, k=0, ctx=None, dtype="float32") -> NDArray:
    return array(onp.eye(N, M, k, dtype=_base.canonical_dtype(dtype)), ctx=ctx)


def linspace(start, stop, num, endpoint=True, ctx=None,
             dtype="float32") -> NDArray:
    return array(onp.linspace(start, stop, num, endpoint=endpoint,
                              dtype=_base.canonical_dtype(dtype)), ctx=ctx)


def concatenate(arrays, axis=0) -> NDArray:
    from . import ops
    return ops.concat(*arrays, dim=axis)
