"""The ``nd`` operator namespace.

Parity target: the generated ``mx.nd.*`` wrappers over ``src/operator/**``
(SURVEY.md §2.3, §2.6).  TPU-first: each op is a pure JAX function dispatched
through :func:`invoke`, which (a) unwraps NDArray→jax.Array, (b) captures a
``jax.vjp`` pullback when autograd is recording, (c) wraps outputs.  Under
hybridize the same code path runs on tracers, so the whole op surface lowers
into a single XLA computation — the CachedOp role with zero extra machinery.

XLA fuses elementwise chains into matmul/conv epilogues on its own; only ops
XLA cannot express well (flash attention) get Pallas kernels (mxnet_tpu.ops).
"""
from __future__ import annotations

import builtins
import functools
import math
from typing import Any, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as onp
from jax import lax

from .. import base as _base
from .. import random as _random
from ..autograd.tape import OpNode, OutRef, node_of
from ..context import current_context
from .ndarray import NDArray, array, from_jax

__all__: list = []  # populated by _export


def _export(fn):
    __all__.append(fn.__name__)
    return fn


# ---------------------------------------------------------------- dispatcher

_amp_mod = None


def _amp_policy():
    global _amp_mod
    if _amp_mod is None:
        from .. import amp as _a
        _amp_mod = _a
    return _amp_mod.current_policy()


# optional op-observation hooks (mx.monitor.Monitor installs here); each
# is called hook(op_name, output_NDArrays) after a successful dispatch
_invoke_hooks = []


def invoke(name, pure_fn, nd_inputs, nout=1, ctx=None, differentiable=True):
    """Dispatch a pure jax function over NDArray inputs with autograd."""
    arrs = tuple(x.jax for x in nd_inputs)
    pol = _amp_policy()
    if pol is not None:
        arrs = pol.cast_args(name, arrs)
    recording = _base.is_recording() and differentiable
    in_nodes = [node_of(x) for x in nd_inputs] if recording else None
    needs_grad = recording and any(n is not None for n in in_nodes)
    ctx = ctx or (nd_inputs[0].context if nd_inputs else current_context())
    with _base.executing_on(ctx.jax_device.platform):
        if needs_grad:
            outs, vjp_fn = jax.vjp(pure_fn, *arrs)
        else:
            outs = pure_fn(*arrs)
    multi = isinstance(outs, (tuple, list))
    outs_list = list(outs) if multi else [outs]
    res = [NDArray(o, ctx=ctx) for o in outs_list]
    if needs_grad:
        node = OpNode(
            vjp_fn, in_nodes, len(res), name=name,
            out_avals=[jax.ShapeDtypeStruct(o.shape, o.dtype)
                       for o in outs_list])
        for i, r in enumerate(res):
            r._node = OutRef(node, i)
    if _invoke_hooks:
        for h in tuple(_invoke_hooks):
            h(name, res)
    return res if multi else res[0]


def _as_nd(x):
    if isinstance(x, NDArray):
        return x
    return array(x)


def _unary_op(name, jfn, differentiable=True):
    def op(data, out=None, **ignored):
        data = _as_nd(data)
        r = invoke(name, jfn, [data], differentiable=differentiable)
        if out is not None:
            out._rebind(r.jax, node=r._node)
            return out
        return r
    op.__name__ = name
    return _export(op)


def _binary_op(name, jfn, differentiable=True, is_mask=False):
    def op(lhs, rhs, out=None, **ignored):
        if isinstance(lhs, NDArray) and isinstance(rhs, NDArray):
            r = invoke(name, jfn, [lhs, rhs], differentiable=differentiable)
        elif isinstance(lhs, NDArray):
            r = invoke(name, lambda a: jfn(a, rhs), [lhs],
                       differentiable=differentiable)
        elif isinstance(rhs, NDArray):
            r = invoke(name, lambda b: jfn(lhs, b), [rhs],
                       differentiable=differentiable)
        else:
            return jfn(lhs, rhs)
        if is_mask:
            r._mask = True
        if out is not None:
            out._rebind(r.jax, node=r._node)
            return out
        return r
    op.__name__ = name
    return _export(op)


def _kw_op(name, make_fn, differentiable=True, n_in=1):
    """Op whose pure fn depends on kwargs: make_fn(**kw) -> jax fn."""
    def op(*inputs, **kw):
        nds = [_as_nd(x) for x in inputs[:n_in]]
        return invoke(name, make_fn(**kw), nds,
                      differentiable=differentiable)
    op.__name__ = name
    return _export(op)


# ------------------------------------------------------------- element-wise

add = _binary_op("add", jnp.add)
subtract = _binary_op("subtract", jnp.subtract)
multiply = _binary_op("multiply", jnp.multiply)
divide = _binary_op("divide", jnp.divide)
floor_divide = _binary_op("floor_divide", jnp.floor_divide,
                          differentiable=False)
mod = _binary_op("mod", jnp.mod)
power = _binary_op("power", jnp.power)
maximum = _binary_op("maximum", jnp.maximum)
minimum = _binary_op("minimum", jnp.minimum)
hypot = _binary_op("hypot", jnp.hypot)
arctan2 = _binary_op("arctan2", jnp.arctan2)
equal = _binary_op("equal", lambda a, b: jnp.equal(a, b).astype(jnp.result_type(a)), differentiable=False, is_mask=True)
not_equal = _binary_op("not_equal", lambda a, b: jnp.not_equal(a, b).astype(jnp.result_type(a)), differentiable=False, is_mask=True)
greater = _binary_op("greater", lambda a, b: jnp.greater(a, b).astype(jnp.result_type(a)), differentiable=False, is_mask=True)
greater_equal = _binary_op("greater_equal", lambda a, b: jnp.greater_equal(a, b).astype(jnp.result_type(a)), differentiable=False, is_mask=True)
lesser = _binary_op("lesser", lambda a, b: jnp.less(a, b).astype(jnp.result_type(a)), differentiable=False, is_mask=True)
lesser_equal = _binary_op("lesser_equal", lambda a, b: jnp.less_equal(a, b).astype(jnp.result_type(a)), differentiable=False, is_mask=True)
logical_and = _binary_op("logical_and", lambda a, b: jnp.logical_and(a, b).astype(jnp.float32), differentiable=False)
logical_or = _binary_op("logical_or", lambda a, b: jnp.logical_or(a, b).astype(jnp.float32), differentiable=False)
logical_xor = _binary_op("logical_xor", lambda a, b: jnp.logical_xor(a, b).astype(jnp.float32), differentiable=False)

# broadcast_* aliases (MXNet names)
for _nm, _f in [("broadcast_add", "add"), ("broadcast_sub", "subtract"),
                ("broadcast_mul", "multiply"), ("broadcast_div", "divide"),
                ("broadcast_power", "power"), ("broadcast_maximum", "maximum"),
                ("broadcast_minimum", "minimum"), ("broadcast_mod", "mod"),
                ("broadcast_equal", "equal"),
                ("broadcast_not_equal", "not_equal"),
                ("broadcast_greater", "greater"),
                ("broadcast_greater_equal", "greater_equal"),
                ("broadcast_lesser", "lesser"),
                ("broadcast_lesser_equal", "lesser_equal"),
                ("broadcast_logical_and", "logical_and"),
                ("broadcast_logical_or", "logical_or"),
                ("broadcast_logical_xor", "logical_xor"),
                ("elemwise_add", "add"), ("elemwise_sub", "subtract"),
                ("elemwise_mul", "multiply"), ("elemwise_div", "divide")]:
    globals()[_nm] = globals()[_f]
    __all__.append(_nm)

negative = _unary_op("negative", jnp.negative)
abs = _unary_op("abs", jnp.abs)
sign = _unary_op("sign", jnp.sign, differentiable=False)
round = _unary_op("round", jnp.round, differentiable=False)
rint = _unary_op("rint", jnp.rint, differentiable=False)
floor = _unary_op("floor", jnp.floor, differentiable=False)
ceil = _unary_op("ceil", jnp.ceil, differentiable=False)
trunc = _unary_op("trunc", jnp.trunc, differentiable=False)
fix = _unary_op("fix", jnp.trunc, differentiable=False)
exp = _unary_op("exp", jnp.exp)
expm1 = _unary_op("expm1", jnp.expm1)
log = _unary_op("log", jnp.log)
log10 = _unary_op("log10", jnp.log10)
log2 = _unary_op("log2", jnp.log2)
log1p = _unary_op("log1p", jnp.log1p)
sqrt = _unary_op("sqrt", jnp.sqrt)
rsqrt = _unary_op("rsqrt", lax.rsqrt)
cbrt = _unary_op("cbrt", jnp.cbrt)
rcbrt = _unary_op("rcbrt", lambda x: 1.0 / jnp.cbrt(x))
square = _unary_op("square", jnp.square)
reciprocal = _unary_op("reciprocal", jnp.reciprocal)
sin = _unary_op("sin", jnp.sin)
cos = _unary_op("cos", jnp.cos)
tan = _unary_op("tan", jnp.tan)
arcsin = _unary_op("arcsin", jnp.arcsin)
arccos = _unary_op("arccos", jnp.arccos)
arctan = _unary_op("arctan", jnp.arctan)
sinh = _unary_op("sinh", jnp.sinh)
cosh = _unary_op("cosh", jnp.cosh)
tanh = _unary_op("tanh", jnp.tanh)
arcsinh = _unary_op("arcsinh", jnp.arcsinh)
arccosh = _unary_op("arccosh", jnp.arccosh)
arctanh = _unary_op("arctanh", jnp.arctanh)
degrees = _unary_op("degrees", jnp.degrees)
radians = _unary_op("radians", jnp.radians)
erf = _unary_op("erf", jax.scipy.special.erf)
erfinv = _unary_op("erfinv", jax.scipy.special.erfinv)
gamma = _unary_op("gamma", lambda x: jnp.exp(jax.scipy.special.gammaln(x)))
gammaln = _unary_op("gammaln", jax.scipy.special.gammaln)
sigmoid = _unary_op("sigmoid", jax.nn.sigmoid)
softsign = _unary_op("softsign", jax.nn.soft_sign)
relu = _unary_op("relu", jax.nn.relu)
softplus = _unary_op("softplus", jax.nn.softplus)
logical_not = _unary_op("logical_not", lambda x: jnp.logical_not(x).astype(jnp.float32), differentiable=False)
isnan = _unary_op("isnan", lambda x: jnp.isnan(x).astype(jnp.float32), differentiable=False)
isinf = _unary_op("isinf", lambda x: jnp.isinf(x).astype(jnp.float32), differentiable=False)
isfinite = _unary_op("isfinite", lambda x: jnp.isfinite(x).astype(jnp.float32), differentiable=False)
zeros_like = _unary_op("zeros_like", jnp.zeros_like, differentiable=False)
ones_like = _unary_op("ones_like", jnp.ones_like, differentiable=False)
identity = _unary_op("identity", lambda x: x)


@_export
def clip(data, a_min=None, a_max=None, out=None, **kw):
    data = _as_nd(data)
    r = invoke("clip", lambda x: jnp.clip(x, a_min, a_max), [data])
    if out is not None:
        out._rebind(r.jax, node=r._node)
        return out
    return r


@_export
def cast(data, dtype, out=None):
    dt = jnp.dtype(_base.canonical_dtype(dtype))
    data = _as_nd(data)
    r = invoke("cast", lambda x: x.astype(dt), [data])
    if out is not None:
        out._rebind(r.jax, node=r._node)
        return out
    return r


Cast = cast
__all__.append("Cast")


@_export
def where(condition, x, y):
    condition, x, y = _as_nd(condition), _as_nd(x), _as_nd(y)
    return invoke("where",
                  lambda c, a, b: jnp.where(c.astype(bool), a, b),
                  [condition, x, y])


# ---------------------------------------------------------------- reductions

def _reduce_op(name, jfn, differentiable=True):
    def op(data, axis=None, keepdims=False, exclude=False, out=None, **kw):
        data = _as_nd(data)
        ax = axis
        if isinstance(ax, (list, tuple)) and len(ax) == 0:
            ax = None
        if exclude and ax is not None:
            axes = (ax,) if isinstance(ax, int) else tuple(ax)
            ax = tuple(i for i in range(data.ndim) if i not in
                       tuple(a % data.ndim for a in axes))
        r = invoke(name, lambda x: jfn(x, axis=ax, keepdims=keepdims),
                   [data], differentiable=differentiable)
        if out is not None:
            out._rebind(r.jax, node=r._node)
            return out
        return r
    op.__name__ = name
    return _export(op)


sum = _reduce_op("sum", jnp.sum)
mean = _reduce_op("mean", jnp.mean)
prod = _reduce_op("prod", jnp.prod)
max = _reduce_op("max", jnp.max)
min = _reduce_op("min", jnp.min)
nansum = _reduce_op("nansum", jnp.nansum)
nanprod = _reduce_op("nanprod", jnp.nanprod)

sum_axis = sum
__all__.append("sum_axis")


@_export
def norm(data, ord=2, axis=None, keepdims=False, out=None):
    data = _as_nd(data)
    def f(x):
        if ord == 2:
            return jnp.sqrt(jnp.sum(jnp.square(x), axis=axis,
                                    keepdims=keepdims))
        if ord == 1:
            return jnp.sum(jnp.abs(x), axis=axis, keepdims=keepdims)
        raise ValueError("norm only supports ord=1,2")
    return invoke("norm", f, [data])


@_export
def argmax(data, axis=None, keepdims=False):
    data = _as_nd(data)
    return invoke("argmax",
                  lambda x: jnp.argmax(x, axis=axis, keepdims=keepdims)
                  .astype(jnp.float32),
                  [data], differentiable=False)


@_export
def argmin(data, axis=None, keepdims=False):
    data = _as_nd(data)
    return invoke("argmin",
                  lambda x: jnp.argmin(x, axis=axis, keepdims=keepdims)
                  .astype(jnp.float32),
                  [data], differentiable=False)


@_export
def topk(data, axis=-1, k=1, ret_typ="indices", is_ascend=False, dtype="float32"):
    data = _as_nd(data)
    dt = jnp.dtype(_base.canonical_dtype(dtype))

    def f(x):
        xs = jnp.moveaxis(x, axis, -1)
        vals, idx = lax.top_k(-xs if is_ascend else xs, k)
        if is_ascend:
            vals = -vals
        vals = jnp.moveaxis(vals, -1, axis)
        idx = jnp.moveaxis(idx, -1, axis)
        if ret_typ == "indices":
            return idx.astype(dt)
        if ret_typ == "value":
            return vals
        return (vals, idx.astype(dt))

    return invoke("topk", f, [data], differentiable=False)


@_export
def sort(data, axis=-1, is_ascend=True):
    data = _as_nd(data)
    def f(x):
        s = jnp.sort(x, axis=axis)
        return s if is_ascend else jnp.flip(s, axis=axis)
    return invoke("sort", f, [data], differentiable=False)


@_export
def argsort(data, axis=-1, is_ascend=True, dtype="float32"):
    data = _as_nd(data)
    dt = jnp.dtype(_base.canonical_dtype(dtype))
    def f(x):
        s = jnp.argsort(x, axis=axis)
        if not is_ascend:
            s = jnp.flip(s, axis=axis)
        return s.astype(dt)
    return invoke("argsort", f, [data], differentiable=False)


# ------------------------------------------------------------ linear algebra

@_export
def dot(lhs, rhs, transpose_a=False, transpose_b=False):
    lhs, rhs = _as_nd(lhs), _as_nd(rhs)

    def f(a, b):
        if transpose_a:
            a = jnp.swapaxes(a, -1, -2) if a.ndim > 1 else a
        if transpose_b:
            b = jnp.swapaxes(b, -1, -2) if b.ndim > 1 else b
        # MXNet dot: contracts last axis of a with first axis of b
        return jnp.tensordot(a, b, axes=([a.ndim - 1], [0]))

    return invoke("dot", f, [lhs, rhs])


@_export
def batch_dot(lhs, rhs, transpose_a=False, transpose_b=False):
    lhs, rhs = _as_nd(lhs), _as_nd(rhs)

    def f(a, b):
        if transpose_a:
            a = jnp.swapaxes(a, -1, -2)
        if transpose_b:
            b = jnp.swapaxes(b, -1, -2)
        return jnp.matmul(a, b)

    return invoke("batch_dot", f, [lhs, rhs])


@_export
def matmul(lhs, rhs):
    lhs, rhs = _as_nd(lhs), _as_nd(rhs)
    return invoke("matmul", jnp.matmul, [lhs, rhs])


@_export
def linalg_gemm2(A, B, transpose_a=False, transpose_b=False, alpha=1.0):
    A, B = _as_nd(A), _as_nd(B)

    def f(a, b):
        if transpose_a:
            a = jnp.swapaxes(a, -1, -2)
        if transpose_b:
            b = jnp.swapaxes(b, -1, -2)
        return alpha * jnp.matmul(a, b)

    return invoke("linalg_gemm2", f, [A, B])


@_export
def linalg_potrf(A):
    return invoke("linalg_potrf", jnp.linalg.cholesky, [_as_nd(A)])


@_export
def linalg_trsm(A, B, transpose=False, rightside=False, lower=True, alpha=1.0):
    A, B = _as_nd(A), _as_nd(B)

    def f(a, b):
        return alpha * jax.lax.linalg.triangular_solve(
            a, b, left_side=not rightside, lower=lower,
            transpose_a=transpose)

    return invoke("linalg_trsm", f, [A, B])


@_export
def linalg_syrk(A, transpose=False, alpha=1.0):
    A = _as_nd(A)

    def f(a):
        at = jnp.swapaxes(a, -1, -2)
        return alpha * (jnp.matmul(at, a) if transpose else jnp.matmul(a, at))

    return invoke("linalg_syrk", f, [A])


# --------------------------------------------------------------- shape ops

@_export
def reshape(data, shape=None, reverse=False, **kw):
    data = _as_nd(data)
    tgt = _mx_reshape_shape(data.shape, tuple(shape), reverse)
    return invoke("reshape", lambda x: jnp.reshape(x, tgt), [data])


def _mx_reshape_shape(src: Tuple[int, ...], spec: Tuple[int, ...],
                      reverse: bool) -> Tuple[int, ...]:
    """Implements MXNet reshape special codes 0, -1, -2, -3, -4."""
    if reverse:
        rev = _mx_reshape_shape(tuple(reversed(src)),
                                tuple(reversed(spec)), False)
        return tuple(reversed(rev))
    out: list = []
    src_i = 0
    i = 0
    spec = tuple(spec)
    while i < len(spec):
        s = spec[i]
        if s == 0:
            out.append(src[src_i]); src_i += 1
        elif s == -1:
            out.append(-1); src_i += 1
        elif s == -2:
            out.extend(src[src_i:]); src_i = len(src)
        elif s == -3:
            out.append(src[src_i] * src[src_i + 1]); src_i += 2
        elif s == -4:
            a, b = spec[i + 1], spec[i + 2]
            dim = src[src_i]
            if a == -1:
                a = dim // b
            if b == -1:
                b = dim // a
            out.extend([a, b]); src_i += 1; i += 2
        else:
            out.append(int(s)); src_i += 1
        i += 1
    if -1 in out:
        known = 1
        for v in out:
            if v != -1:
                known *= v
        total = 1
        for v in src:
            total *= v
        out[out.index(-1)] = total // known if known else 0
    return tuple(out)


@_export
def transpose(data, axes=None):
    data = _as_nd(data)
    ax = tuple(axes) if axes else None
    return invoke("transpose", lambda x: jnp.transpose(x, ax), [data])


@_export
def swapaxes(data, dim1=0, dim2=1):
    data = _as_nd(data)
    return invoke("swapaxes", lambda x: jnp.swapaxes(x, dim1, dim2), [data])


SwapAxis = swapaxes
__all__.append("SwapAxis")


@_export
def flatten(data):
    data = _as_nd(data)
    n = data.shape[0] if data.ndim else 1
    return invoke("flatten", lambda x: jnp.reshape(x, (n, -1)), [data])


Flatten = flatten
__all__.append("Flatten")


@_export
def expand_dims(data, axis):
    data = _as_nd(data)
    return invoke("expand_dims", lambda x: jnp.expand_dims(x, axis), [data])


@_export
def squeeze(data, axis=None):
    data = _as_nd(data)
    return invoke("squeeze", lambda x: jnp.squeeze(x, axis), [data])


@_export
def broadcast_to(data, shape):
    data = _as_nd(data)
    src = data.shape
    tgt = tuple(s if t == 0 else t for s, t in zip(src, tuple(shape)))
    return invoke("broadcast_to", lambda x: jnp.broadcast_to(x, tgt), [data])


@_export
def broadcast_like(lhs, rhs):
    lhs, rhs = _as_nd(lhs), _as_nd(rhs)
    return invoke("broadcast_like",
                  lambda a, b: jnp.broadcast_to(a, b.shape), [lhs, rhs])


@_export
def reshape_like(lhs, rhs, lhs_begin=None, lhs_end=None, rhs_begin=None,
                 rhs_end=None):
    """Reshape lhs to rhs's shape (parity: reshape_like op, incl. the
    partial-range form reshaping lhs[lhs_begin:lhs_end] dims to
    rhs[rhs_begin:rhs_end] dims)."""
    lhs, rhs = _as_nd(lhs), _as_nd(rhs)

    partial = any(v is not None for v in
                  (lhs_begin, lhs_end, rhs_begin, rhs_end))

    def f(a, b):
        if not partial:
            return jnp.reshape(a, b.shape)
        lb = 0 if lhs_begin is None else lhs_begin
        le = a.ndim if lhs_end is None else lhs_end
        rb = 0 if rhs_begin is None else rhs_begin
        re_ = b.ndim if rhs_end is None else rhs_end
        new_shape = a.shape[:lb] + b.shape[rb:re_] + a.shape[le:]
        return jnp.reshape(a, new_shape)

    return invoke("reshape_like", f, [lhs, rhs])


@_export
def broadcast_axis(data, axis=(), size=()):
    data = _as_nd(data)
    axes = (axis,) if isinstance(axis, int) else tuple(axis)
    sizes = (size,) if isinstance(size, int) else tuple(size)
    tgt = list(data.shape)
    for a, s in zip(axes, sizes):
        tgt[a] = s
    return invoke("broadcast_axis",
                  lambda x: jnp.broadcast_to(x, tuple(tgt)), [data])


@_export
def concat(*data, dim=1, **kw):
    if len(data) == 1 and isinstance(data[0], (list, tuple)):
        data = tuple(data[0])
    nds = [_as_nd(d) for d in data]
    return invoke("concat", lambda *xs: jnp.concatenate(xs, axis=dim),
                  list(nds))


Concat = concat
__all__.append("Concat")


@_export
def stack(*data, axis=0, **kw):
    if len(data) == 1 and isinstance(data[0], (list, tuple)):
        data = tuple(data[0])
    nds = [_as_nd(d) for d in data]
    return invoke("stack", lambda *xs: jnp.stack(xs, axis=axis), list(nds))


@_export
def split(data, num_outputs=None, axis=1, squeeze_axis=False):
    data = _as_nd(data)

    def f(x):
        parts = jnp.split(x, num_outputs, axis=axis)
        if squeeze_axis:
            parts = [jnp.squeeze(p, axis=axis) for p in parts]
        return tuple(parts)

    return invoke("split", f, [data])


SliceChannel = split
__all__.append("SliceChannel")


@_export
def slice(data, begin, end, step=None):
    data = _as_nd(data)
    begin = tuple(begin); end = tuple(end)
    step = tuple(step) if step is not None else (1,) * len(begin)
    idx = tuple(builtins.slice(b, e, s) for b, e, s in zip(begin, end, step))
    return invoke("slice", lambda x: x[idx], [data])


@_export
def slice_axis(data, axis, begin, end):
    data = _as_nd(data)
    def f(x):
        idx = [builtins.slice(None)] * x.ndim
        e = end if end is not None else x.shape[axis]
        idx[axis] = builtins.slice(begin, e)
        return x[tuple(idx)]
    return invoke("slice_axis", f, [data])


@_export
def slice_like(data, shape_like, axes=None):
    data, shape_like = _as_nd(data), _as_nd(shape_like)
    tgt = shape_like.shape

    def f(x, y):
        idx = [builtins.slice(None)] * x.ndim
        axs = axes if axes is not None else range(len(tgt))
        for a in axs:
            idx[a] = builtins.slice(0, tgt[a])
        return x[tuple(idx)]

    return invoke("slice_like", f, [data, shape_like])


@_export
def take(a, indices, axis=0, mode="clip"):
    a, indices = _as_nd(a), _as_nd(indices)

    def f(x, idx):
        return jnp.take(x, idx.astype(jnp.int32), axis=axis,
                        mode="wrap" if mode == "wrap" else "clip")

    return invoke("take", f, [a, indices])


@_export
def pick(data, index, axis=-1, keepdims=False, mode="clip"):
    data, index = _as_nd(data), _as_nd(index)

    def f(x, idx):
        out = jnp.take_along_axis(
            x, jnp.expand_dims(idx.astype(jnp.int32), axis), axis=axis)
        return out if keepdims else jnp.squeeze(out, axis=axis)

    return invoke("pick", f, [data, index])


@_export
def gather_nd(data, indices):
    data, indices = _as_nd(data), _as_nd(indices)

    def f(x, idx):
        idx = idx.astype(jnp.int32)
        return x[tuple(idx[i] for i in range(idx.shape[0]))]

    return invoke("gather_nd", f, [data, indices])


@_export
def scatter_nd(data, indices, shape):
    data, indices = _as_nd(data), _as_nd(indices)

    def f(d, idx):
        idx = idx.astype(jnp.int32)
        z = jnp.zeros(tuple(shape), dtype=d.dtype)
        return z.at[tuple(idx[i] for i in range(idx.shape[0]))].add(d)

    return invoke("scatter_nd", f, [data, indices])


@_export
def one_hot(indices, depth, on_value=1.0, off_value=0.0, dtype="float32"):
    indices = _as_nd(indices)
    dt = jnp.dtype(_base.canonical_dtype(dtype))

    def f(idx):
        oh = jax.nn.one_hot(idx.astype(jnp.int32), depth, dtype=dt)
        return oh * (on_value - off_value) + off_value

    return invoke("one_hot", f, [indices], differentiable=False)


@_export
def tile(data, reps):
    data = _as_nd(data)
    return invoke("tile", lambda x: jnp.tile(x, reps), [data])


@_export
def repeat(data, repeats, axis=None):
    data = _as_nd(data)
    return invoke("repeat", lambda x: jnp.repeat(x, repeats, axis=axis),
                  [data])


@_export
def flip(data, axis):
    data = _as_nd(data)
    return invoke("flip", lambda x: jnp.flip(x, axis=axis), [data])


reverse = flip
__all__.append("reverse")


@_export
def pad(data, mode="constant", pad_width=None, constant_value=0.0):
    data = _as_nd(data)
    pw = tuple(pad_width)
    pairs = tuple((pw[2 * i], pw[2 * i + 1]) for i in range(len(pw) // 2))
    jmode = {"constant": "constant", "edge": "edge",
             "reflect": "reflect"}[mode]

    def f(x):
        if jmode == "constant":
            return jnp.pad(x, pairs, mode=jmode,
                           constant_values=constant_value)
        return jnp.pad(x, pairs, mode=jmode)

    return invoke("pad", f, [data])


Pad = pad
__all__.append("Pad")


@_export
def arange_like(data, start=0.0, step=1.0, axis=None):
    data = _as_nd(data)

    def f(x):
        if axis is None:
            n = x.size
            return (start + step * jnp.arange(n, dtype=x.dtype)).reshape(x.shape)
        n = x.shape[axis]
        return start + step * jnp.arange(n, dtype=x.dtype)

    return invoke("arange_like", f, [data], differentiable=False)


@_export
def shape_array(data):
    data = _as_nd(data)
    return from_jax(jnp.asarray(data.shape, dtype=jnp.int64), ctx=data.context)


@_export
def size_array(data):
    data = _as_nd(data)
    return from_jax(jnp.asarray([data.size], dtype=jnp.int64),
                    ctx=data.context)


# ----------------------------------------------------- indexing for NDArray

def _getitem(data, key):
    return invoke("getitem", lambda x: x[key], [data])


def _setitem(data, key, value):
    r = invoke("setitem",
               lambda x, v: x.at[key].set(v.astype(x.dtype)), [data, value])
    data._rebind(r.jax, node=r._node)


def _setitem_full(data, value):
    r = invoke("setitem_full",
               lambda x, v: jnp.broadcast_to(v.astype(x.dtype), x.shape),
               [data, value])
    data._rebind(r.jax, node=r._node)


# ------------------------------------------------------------ activations &
# softmax family

@_export
def softmax(data, axis=-1, length=None, temperature=None, use_length=False):
    data = _as_nd(data)
    t = temperature or 1.0
    if length is not None:
        length = _as_nd(length)

        def f(x, ln):
            # mask positions >= length along `axis` (SequenceMask'd softmax,
            # parity: src/operator/nn/softmax*.h length path)
            n = x.shape[axis]
            ar = jnp.arange(n)
            shape = [1] * x.ndim
            shape[axis] = n
            ar = ar.reshape(shape)
            ln_b = jnp.expand_dims(ln.astype(jnp.int32), axis)
            mask = ar < ln_b
            neg = jnp.finfo(x.dtype).min
            return jax.nn.softmax(jnp.where(mask, x / t, neg), axis=axis) * mask

        return invoke("softmax", f, [data, length])
    return invoke("softmax", lambda x: jax.nn.softmax(x / t, axis=axis),
                  [data])


@_export
def log_softmax(data, axis=-1, temperature=None):
    data = _as_nd(data)
    t = temperature or 1.0
    return invoke("log_softmax",
                  lambda x: jax.nn.log_softmax(x / t, axis=axis), [data])


@_export
def logsumexp(data, axis=-1, keepdims=False):
    """log(sum(exp(x))) along `axis`, computed stably in f32 (the reduction
    that lets losses avoid materializing a full log_softmax)."""
    data = _as_nd(data)

    def f(x):
        r = jax.scipy.special.logsumexp(
            x.astype(jnp.float32), axis=axis, keepdims=keepdims)
        return r

    return invoke("logsumexp", f, [data])


@_export
def softmax_cross_entropy(data, label):
    data, label = _as_nd(data), _as_nd(label)

    def f(x, y):
        ls = jax.nn.log_softmax(x, axis=-1)
        picked = jnp.take_along_axis(
            ls, y.astype(jnp.int32)[:, None], axis=-1)
        return -jnp.sum(picked)

    return invoke("softmax_cross_entropy", f, [data, label])


@jax.custom_vjp
def _gelu_erf(h):
    """Exact (erf) GELU whose backward works from the input alone, as
    mshadow_op::gelu_grad does; autodiff of ``jax.nn.gelu`` keeps erfc's
    branches and their predicate besides (PERF.md, PR 29).  The backward
    takes Phi from erf, one rational form, where erfc computes two
    branches: on the chip it runs inside a matmul's fusion and is bound
    by the vector unit, not by memory."""
    return jax.nn.gelu(h, approximate=False)


def _gelu_erf_fwd(h):
    return _gelu_erf(h), h


def _gelu_erf_bwd(h, dy):
    ct = jnp.promote_types(h.dtype, jnp.float32)
    hf = h.astype(ct)
    cdf = 0.5 + 0.5 * lax.erf(hf * math.sqrt(0.5))
    pdf = jnp.exp(-0.5 * hf * hf) * (1.0 / math.sqrt(2.0 * math.pi))
    return ((dy.astype(ct) * (cdf + hf * pdf)).astype(h.dtype),)


_gelu_erf.defvjp(_gelu_erf_fwd, _gelu_erf_bwd)


ACTIVATION_FNS = {
    "relu": jax.nn.relu, "sigmoid": jax.nn.sigmoid,
    "tanh": jnp.tanh, "softrelu": jax.nn.softplus,
    "softsign": jax.nn.soft_sign, "log_sigmoid": jax.nn.log_sigmoid,
    "mish": lambda x: x * jnp.tanh(jax.nn.softplus(x)),
    "gelu": jax.nn.gelu, "silu": jax.nn.silu}


@_export
def Activation(data, act_type="relu", **kw):
    data = _as_nd(data)
    return invoke(f"activation_{act_type}", ACTIVATION_FNS[act_type],
                  [data])


@_export
def LeakyReLU(data, gamma=None, act_type="leaky", slope=0.25,
              lower_bound=0.125, upper_bound=0.334, **kw):
    data = _as_nd(data)
    if act_type == "leaky":
        return invoke("leaky_relu",
                      lambda x: jax.nn.leaky_relu(x, negative_slope=slope),
                      [data])
    if act_type == "elu":
        return invoke("elu", lambda x: jax.nn.elu(x, alpha=slope), [data])
    if act_type == "selu":
        return invoke("selu", jax.nn.selu, [data])
    if act_type == "gelu":
        return invoke("gelu", _gelu_erf, [data])
    if act_type == "prelu":
        g = _as_nd(gamma)
        return invoke("prelu",
                      lambda x, a: jnp.where(x >= 0, x, a * x), [data, g])
    if act_type == "rrelu":
        mid = (lower_bound + upper_bound) / 2.0
        if _base.is_training():
            key = _random.next_key(data.context)
            def f(x):
                s = jax.random.uniform(key, x.shape, minval=lower_bound,
                                       maxval=upper_bound, dtype=x.dtype)
                return jnp.where(x >= 0, x, s * x)
            return invoke("rrelu", f, [data])
        return invoke("rrelu",
                      lambda x: jnp.where(x >= 0, x, mid * x), [data])
    raise ValueError(f"unknown LeakyReLU act_type {act_type}")


# ------------------------------------------------------------- neural ops

@_export
def FullyConnected(data, weight, bias=None, num_hidden=None,
                   no_bias=False, flatten=True, **kw):
    """Parity: src/operator/nn/fully_connected.cc. weight is (out, in)."""
    nds = [_as_nd(data), _as_nd(weight)]
    has_bias = bias is not None and not no_bias
    if has_bias:
        nds.append(_as_nd(bias))

    def f(x, w, *b):
        if flatten and x.ndim > 2:
            x = x.reshape(x.shape[0], -1)
        y = jnp.matmul(x, w.T)
        if b:
            y = y + b[0]
        return y

    return invoke("FullyConnected", f, nds)


@_export
def Embedding(data, weight, input_dim=None, output_dim=None,
              dtype="float32", sparse_grad=False, **kw):
    data, weight = _as_nd(data), _as_nd(weight)

    def f(idx, w):
        return jnp.take(w, idx.astype(jnp.int32), axis=0, mode="clip")

    if sparse_grad:
        r = _embedding_sparse_grad(data, weight, f)
        if r is not None:
            return r
    return invoke("Embedding", f, [data, weight])


def _embedding_sparse_grad(data, weight, f):
    """Eager sparse_grad=True lookup: the weight cotangent is emitted as a
    compact RowSparse structure (unique rows + segment-sum) instead of a
    dense scatter-add (parity: Embedding's sparse_grad path, SURVEY §2.3
    `src/operator/tensor/indexing_op.*`).  Returns None — falling back to
    the dense path — inside traces (whole-step vjp already yields dense
    grads there) or when the weight is not a gradient leaf."""
    from ..autograd.tape import LeafNode
    if not _base.is_recording():
        return None
    wnode = node_of(weight)
    if not isinstance(wnode, LeafNode):
        return None
    idx_val, w_val = data.jax, weight.jax
    if isinstance(idx_val, jax.core.Tracer) or \
            isinstance(w_val, jax.core.Tracer):
        return None
    out = f(idx_val, w_val)
    res = NDArray(out, ctx=weight.context)
    n_rows, row_shape = w_val.shape[0], w_val.shape[1:]
    flat_idx = onp.clip(onp.asarray(idx_val).astype("int64").reshape(-1),
                        0, n_rows - 1)
    uniq, inv = onp.unique(flat_idx, return_inverse=True)
    inv_j = jnp.asarray(inv, jnp.int32)
    uniq_j = jnp.asarray(uniq, jnp.int32)

    def vjp_fn(cot):
        from .sparse import _RowSparseCot
        rows = jax.ops.segment_sum(
            cot.reshape((-1,) + row_shape), inv_j, num_segments=len(uniq))
        return (None, _RowSparseCot(rows, uniq_j, w_val.shape))

    node = OpNode(
        vjp_fn, [None, wnode], 1, name="Embedding(sparse_grad)",
        out_avals=[jax.ShapeDtypeStruct(out.shape, out.dtype)])
    res._node = OutRef(node, 0)
    if _invoke_hooks:
        for h in tuple(_invoke_hooks):
            h("Embedding", [res])
    return res


_CHANNELS_LAST_LAYOUTS = ("NWC", "NHWC", "NDHWC")


def _conv_dim_numbers(ndim, layout=None):
    """MXNet layout string → lax dimension numbers.  Weights stay in the
    upstream (O, I, kH, kW) layout for BOTH data layouts so checkpoints
    are layout-portable; XLA relaids them internally."""
    if layout in (None, "NCW", "NCHW", "NCDHW"):
        if layout is not None and len(layout) != ndim:
            raise _base.MXNetError(
                f"conv layout {layout!r} expects {len(layout)}-d input, "
                f"got {ndim}-d")
        if ndim == 3:
            return ("NCH", "OIH", "NCH")
        if ndim == 4:
            return ("NCHW", "OIHW", "NCHW")
        return ("NCDHW", "OIDHW", "NCDHW")
    if layout == "NWC" and ndim == 3:
        return ("NHC", "OIH", "NHC")
    if layout == "NHWC" and ndim == 4:
        # feature dim last = TPU lane dim: the conv needs no edge
        # transposes (src/operator/nn/convolution.cc accepts NHWC too)
        return ("NHWC", "OIHW", "NHWC")
    if layout == "NDHWC" and ndim == 5:
        return ("NDHWC", "OIDHW", "NDHWC")
    raise _base.MXNetError(f"unsupported conv layout {layout!r} for "
                           f"{ndim}-d input")


@_export
def Convolution(data, weight, bias=None, kernel=None, stride=None,
                dilate=None, pad=None, num_filter=None, num_group=1,
                no_bias=False, layout=None, **kw):
    """Parity: src/operator/nn/convolution.cc — NCHW default or NHWC via
    ``layout`` (TPU-preferred: channels on the lane dim), (O,I,kH,kW)
    weights either way.  Lowers to lax.conv_general_dilated → MXU."""
    data = _as_nd(data)
    weight = _as_nd(weight)
    nds = [data, weight]
    has_bias = bias is not None and not no_bias
    if has_bias:
        nds.append(_as_nd(bias))
    nd_spatial = data.ndim - 2
    stride = tuple(stride) if stride else (1,) * nd_spatial
    dilate = tuple(dilate) if dilate else (1,) * nd_spatial
    pad_ = tuple(pad) if pad else (0,) * nd_spatial
    dn = _conv_dim_numbers(data.ndim, layout)
    channels_last = layout in _CHANNELS_LAST_LAYOUTS

    def f(x, w, *b):
        # no preferred_element_type: the MXU accumulates bf16 convs in f32
        # internally already, and lax's conv transpose-rhs rule rejects
        # mixed (bf16 operand, f32 cotangent) pairs it would produce
        y = lax.conv_general_dilated(
            x, w, window_strides=stride,
            padding=tuple((p, p) for p in pad_),
            rhs_dilation=dilate, dimension_numbers=dn,
            feature_group_count=num_group)
        if b:
            bshape = ((1,) + (1,) * nd_spatial + (-1,)) if channels_last \
                else ((1, -1) + (1,) * nd_spatial)
            y = y + b[0].reshape(bshape)
        return y

    return invoke("Convolution", f, nds)


@_export
def Deconvolution(data, weight, bias=None, kernel=None, stride=None,
                  dilate=None, pad=None, adj=None, num_filter=None,
                  num_group=1, no_bias=True, layout=None, **kw):
    data, weight = _as_nd(data), _as_nd(weight)
    _conv_dim_numbers(data.ndim, layout)   # validate the layout string
    if layout in _CHANNELS_LAST_LAYOUTS:
        raise _base.MXNetError(
            "channels-last layout is not supported for Deconvolution "
            "(runs NCHW)")
    nds = [data, weight]
    has_bias = bias is not None and not no_bias
    if has_bias:
        nds.append(_as_nd(bias))
    nd_spatial = data.ndim - 2
    stride = tuple(stride) if stride else (1,) * nd_spatial
    dilate = tuple(dilate) if dilate else (1,) * nd_spatial
    pad_ = tuple(pad) if pad else (0,) * nd_spatial
    kernel = tuple(kernel)
    dn = _conv_dim_numbers(data.ndim)

    def f(x, w, *b):
        pads = []
        for i in range(nd_spatial):
            k = (kernel[i] - 1) * dilate[i]
            pads.append((k - pad_[i], k - pad_[i]))
        # weight is (Cin, Cout/g, k...); grouped transpose-conv kernel must
        # be (Cout, Cin/g, k...): per-group swap of the io axes
        cin = w.shape[0]
        cout_g = w.shape[1]
        spatial = w.shape[2:]
        wg = w.reshape((num_group, cin // num_group, cout_g) + spatial)
        wg = jnp.swapaxes(wg, 1, 2)
        wt = wg.reshape((num_group * cout_g, cin // num_group) + spatial)
        wt = jnp.flip(wt, axis=tuple(range(2, 2 + nd_spatial)))
        y = lax.conv_general_dilated(
            x, wt,
            window_strides=(1,) * nd_spatial, padding=tuple(pads),
            lhs_dilation=stride, rhs_dilation=dilate,
            dimension_numbers=dn, feature_group_count=num_group)
        if b:
            bshape = (1, -1) + (1,) * nd_spatial
            y = y + b[0].reshape(bshape)
        return y

    return invoke("Deconvolution", f, nds)


@_export
def Pooling(data, kernel=None, pool_type="max", global_pool=False,
            stride=None, pad=None, pooling_convention="valid",
            count_include_pad=True, layout=None, **kw):
    """Parity: src/operator/nn/pooling.cc (max/avg/sum/lp); NCHW default
    or channels-last via ``layout`` (NWC/NHWC/NDHWC)."""
    data = _as_nd(data)
    nd_spatial = data.ndim - 2
    _LAYOUT_NDIM = {"NCW": 3, "NWC": 3, "NCHW": 4, "NHWC": 4,
                    "NCDHW": 5, "NDHWC": 5}
    if layout is not None:
        if layout not in _LAYOUT_NDIM:
            raise _base.MXNetError(f"unsupported pooling layout {layout!r}")
        if _LAYOUT_NDIM[layout] != data.ndim:
            raise _base.MXNetError(
                f"pooling layout {layout!r} expects "
                f"{_LAYOUT_NDIM[layout]}-d input, got {data.ndim}-d")
    channels_last = layout in _CHANNELS_LAST_LAYOUTS
    sp0 = 1 if channels_last else 2          # first spatial axis

    def f(x):
        if global_pool:
            axes = tuple(range(sp0, sp0 + nd_spatial))
            if pool_type == "max":
                return jnp.max(x, axis=axes, keepdims=True)
            return jnp.mean(x, axis=axes, keepdims=True)
        k = tuple(kernel)
        s = tuple(stride) if stride else k
        p = tuple(pad) if pad else (0,) * nd_spatial

        def lay(spatial, fill):
            sp = list(spatial)
            return ((fill, *sp, fill) if channels_last
                    else (fill, fill, *sp))

        window = lay(k, 1)
        strides = lay(s, 1)
        if pooling_convention == "full":
            # ceil-mode: pad upper side enough for a final partial window
            sp_pads = []
            for i in range(nd_spatial):
                in_sz = x.shape[sp0 + i] + 2 * p[i]
                out_sz = int(math.ceil((in_sz - k[i]) / s[i])) + 1
                need = (out_sz - 1) * s[i] + k[i] - in_sz
                sp_pads.append((p[i], p[i] + builtins.max(need, 0)))
        else:
            sp_pads = [(pi, pi) for pi in p]
        pads = tuple(lay(sp_pads, (0, 0)))
        if pool_type == "max":
            init = -jnp.inf if jnp.issubdtype(x.dtype, jnp.floating) else \
                jnp.iinfo(x.dtype).min
            return lax.reduce_window(x, init, lax.max, window, strides, pads)
        if pool_type in ("avg", "sum"):
            summed = lax.reduce_window(x, 0.0, lax.add, window, strides, pads)
            if pool_type == "sum":
                return summed
            if count_include_pad:
                denom = 1
                for ki in k:
                    denom *= ki
                return summed / denom
            ones = jnp.ones_like(x)
            counts = lax.reduce_window(ones, 0.0, lax.add, window, strides,
                                       pads)
            return summed / counts
        if pool_type == "lp":
            pval = kw.get("p_value", 2)
            summed = lax.reduce_window(jnp.abs(x) ** pval, 0.0, lax.add,
                                       window, strides, pads)
            return summed ** (1.0 / pval)
        raise ValueError(f"unknown pool_type {pool_type}")

    return invoke("Pooling", f, [data])


@_export
def BatchNorm(data, gamma, beta, moving_mean, moving_var, eps=1e-5,
              momentum=0.9, fix_gamma=False, use_global_stats=False,
              output_mean_var=False, axis=1, **kw):
    """Parity: src/operator/nn/batch_norm.cc.

    Functional: returns (out, batch_mean, batch_var); the Gluon layer updates
    the moving stats (MXNet mutates aux states inside the op; we keep the op
    pure for XLA and move the mutation to the layer).
    """
    nds = [_as_nd(x) for x in (data, gamma, beta, moving_mean, moving_var)]
    training = _base.is_training() and not use_global_stats

    def f(x, g, b, mmean, mvar):
        ax = axis % x.ndim          # canonicalize: axis=-1 (NHWC) must
        shape = [1] * x.ndim        # exclude the LAST dim from the stat
        shape[ax] = x.shape[ax]     # reduction, not match nothing
        g_ = jnp.ones_like(g) if fix_gamma else g
        if training:
            axes = tuple(i for i in range(x.ndim) if i != ax)
            mean = jnp.mean(x, axis=axes)
            var = jnp.var(x, axis=axes)
        else:
            mean, var = mmean, mvar
        inv = lax.rsqrt(var + eps).reshape(shape)
        out = (x - mean.reshape(shape)) * inv * g_.reshape(shape) \
            + b.reshape(shape)
        return out, mean, var

    out, mean, var = invoke("BatchNorm", f, nds)
    if output_mean_var:
        return out, mean, var
    return (out, mean, var) if kw.get("_internal_stats") else out


def _row_stats(x, axis, eps):
    mean = jnp.mean(x, axis=axis, keepdims=True)
    var = jnp.var(x, axis=axis, keepdims=True)
    return mean, lax.rsqrt(var + eps)


def _along(x, axis):
    """The shape that lays a vector along ``axis`` of ``x``."""
    shape = [1] * x.ndim
    shape[axis] = x.shape[axis]
    return shape


def _normalise(x, mean, rstd, g, b, axis):
    shape = _along(x, axis)
    return (x - mean) * rstd * g.reshape(shape) + b.reshape(shape)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _layer_norm(x, g, b, axis, eps):
    """LayerNorm with the backward MXNet's operator has
    (src/operator/nn/layer_norm.cc): it keeps the input and two numbers a
    row, where autodiff of the formula keeps three arrays of the input's
    size (PERF.md, PR 29)."""
    return _normalise(x, *_row_stats(x, axis, eps), g, b, axis)


def _layer_norm_fwd(x, g, b, axis, eps):
    mean, rstd = _row_stats(x, axis, eps)
    y = _normalise(x, mean, rstd, g, b, axis)
    ct = jnp.promote_types(x.dtype, jnp.float32)
    if x.dtype != ct:
        # the backward works in float32: it gets float32's statistics,
        # not the forward's rounded ones
        mean, rstd = _row_stats(x.astype(ct), axis, eps)
    return y, (x, g, b, mean, rstd)


def _layer_norm_bwd(axis, eps, res, dy):
    x, g, b, mean, rstd = res
    ct = rstd.dtype
    ax = axis % x.ndim
    others = tuple(i for i in range(x.ndim) if i != ax)
    dy = dy.astype(ct)
    xh = (x.astype(ct) - mean) * rstd
    dxh = dy * g.astype(ct).reshape(_along(x, ax))
    dx = (dxh - jnp.mean(dxh, axis=ax, keepdims=True)
          - xh * jnp.mean(dxh * xh, axis=ax, keepdims=True)) * rstd
    dg = jnp.sum(dy * xh, axis=others).reshape(g.shape)
    db = jnp.sum(dy, axis=others).reshape(b.shape)
    return dx.astype(x.dtype), dg.astype(g.dtype), db.astype(b.dtype)


_layer_norm.defvjp(_layer_norm_fwd, _layer_norm_bwd)


@_export
def LayerNorm(data, gamma, beta, axis=-1, eps=1e-5, **kw):
    nds = [_as_nd(x) for x in (data, gamma, beta)]
    return invoke("LayerNorm",
                  lambda x, g, b: _layer_norm(x, g, b, axis, eps), nds)


@_export
def RMSNorm(data, gamma, eps=1e-5, unit_offset=False, **kw):
    """Root-mean-square norm over the last axis (float32 under AMP);
    ``unit_offset``: the gain is ``1 + gamma`` (a gamma that starts at
    zero)."""
    nds = [_as_nd(x) for x in (data, gamma)]

    def f(x, g):
        ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
        return x * lax.rsqrt(ms + eps) * (1.0 + g if unit_offset else g)

    return invoke("RMSNorm", f, nds)


@_export
def GroupNorm(data, gamma, beta, num_groups=1, eps=1e-5, **kw):
    nds = [_as_nd(x) for x in (data, gamma, beta)]

    def f(x, g, b):
        n, c = x.shape[0], x.shape[1]
        spatial = x.shape[2:]
        xg = x.reshape((n, num_groups, c // num_groups) + spatial)
        axes = tuple(range(2, xg.ndim))
        mean = jnp.mean(xg, axis=axes, keepdims=True)
        var = jnp.var(xg, axis=axes, keepdims=True)
        xn = ((xg - mean) * lax.rsqrt(var + eps)).reshape(x.shape)
        shape = (1, c) + (1,) * len(spatial)
        return xn * g.reshape(shape) + b.reshape(shape)

    return invoke("GroupNorm", f, nds)


@_export
def InstanceNorm(data, gamma, beta, eps=1e-3, **kw):
    nds = [_as_nd(x) for x in (data, gamma, beta)]

    def f(x, g, b):
        axes = tuple(range(2, x.ndim))
        mean = jnp.mean(x, axis=axes, keepdims=True)
        var = jnp.var(x, axis=axes, keepdims=True)
        shape = (1, x.shape[1]) + (1,) * (x.ndim - 2)
        return (x - mean) * lax.rsqrt(var + eps) * g.reshape(shape) \
            + b.reshape(shape)

    return invoke("InstanceNorm", f, nds)


@_export
def L2Normalization(data, eps=1e-10, mode="instance"):
    data = _as_nd(data)

    def f(x):
        if mode == "instance":
            axes = tuple(range(1, x.ndim))
        elif mode == "channel":
            axes = (1,)
        else:  # spatial
            axes = tuple(range(2, x.ndim))
        nrm = jnp.sqrt(jnp.sum(jnp.square(x), axis=axes, keepdims=True) + eps)
        return x / nrm

    return invoke("L2Normalization", f, [data])


@_export
def Dropout(data, p=0.5, mode="training", axes=(), cudnn_off=False, **kw):
    data = _as_nd(data)
    if not _base.is_training() and mode != "always":
        return invoke("dropout_id", lambda x: x, [data])
    if p <= 0:
        return invoke("dropout_id", lambda x: x, [data])
    key = _random.next_key(data.context)

    def f(x):
        shape = list(x.shape)
        for a in axes:
            shape[a] = 1  # broadcast dropout over these axes
        keep = jax.random.bernoulli(key, 1.0 - p, tuple(shape))
        return jnp.where(keep, x / (1.0 - p), jnp.zeros_like(x))

    return invoke("Dropout", f, [data])


@_export
def SequenceMask(data, sequence_length=None, use_sequence_length=False,
                 value=0.0, axis=0):
    data = _as_nd(data)
    if not use_sequence_length or sequence_length is None:
        return invoke("seqmask_id", lambda x: x, [data])
    sl = _as_nd(sequence_length)

    def f(x, ln):
        n = x.shape[axis]
        ar = jnp.arange(n)
        shape = [1] * x.ndim
        shape[axis] = n
        ar = ar.reshape(shape)
        batch_axis = 1 if axis == 0 else 0
        lshape = [1] * x.ndim
        lshape[batch_axis] = x.shape[batch_axis]
        mask = ar < ln.astype(jnp.int32).reshape(lshape)
        return jnp.where(mask, x, jnp.full_like(x, value))

    return invoke("SequenceMask", f, [data, sl])


@_export
def SequenceLast(data, sequence_length=None, use_sequence_length=False,
                 axis=0):
    data = _as_nd(data)
    if not use_sequence_length or sequence_length is None:
        def f(x):
            idx = [builtins.slice(None)] * x.ndim
            idx[axis] = -1
            return x[tuple(idx)]
        return invoke("SequenceLast", f, [data])
    sl = _as_nd(sequence_length)

    def f(x, ln):
        idx = (ln.astype(jnp.int32) - 1)
        xm = jnp.moveaxis(x, axis, 0)
        return jnp.take_along_axis(
            xm, idx.reshape((1, -1) + (1,) * (xm.ndim - 2)), axis=0)[0]

    return invoke("SequenceLast", f, [data, sl])


@_export
def SequenceReverse(data, sequence_length=None, use_sequence_length=False,
                    axis=0):
    data = _as_nd(data)
    if not use_sequence_length or sequence_length is None:
        return invoke("SequenceReverse",
                      lambda x: jnp.flip(x, axis=axis), [data])
    sl = _as_nd(sequence_length)

    def f(x, ln):
        t = x.shape[axis]
        xm = jnp.moveaxis(x, axis, 0)  # (T, B, ...)
        ar = jnp.arange(t)[:, None]
        ln_i = ln.astype(jnp.int32)[None, :]
        src = jnp.where(ar < ln_i, ln_i - 1 - ar, ar)
        out = jnp.take_along_axis(
            xm, src.reshape(src.shape + (1,) * (xm.ndim - 2)), axis=0)
        return jnp.moveaxis(out, 0, axis)

    return invoke("SequenceReverse", f, [data, sl])


# ---------------------------------------------------------------- sampling

def _sample_op(name, sampler):
    def op(*shape_args, shape=None, dtype="float32", ctx=None, out=None,
           **params):
        ctx = ctx or current_context()
        dt = jnp.dtype(_base.canonical_dtype(dtype))
        if shape is None:
            shape = ()
        if isinstance(shape, int):
            shape = (shape,)
        key = _random.next_key(ctx)
        val = sampler(key, tuple(shape), dt, **params)
        r = NDArray(val, ctx=ctx)
        if out is not None:
            out._rebind(r.jax)
            return out
        return r
    op.__name__ = name
    return _export(op)


random_uniform = _sample_op(
    "random_uniform",
    lambda key, shape, dt, low=0.0, high=1.0, **kw:
    jax.random.uniform(key, shape, dtype=dt, minval=low, maxval=high))
random_normal = _sample_op(
    "random_normal",
    lambda key, shape, dt, loc=0.0, scale=1.0, **kw:
    loc + scale * jax.random.normal(key, shape, dtype=dt))
random_gamma = _sample_op(
    "random_gamma",
    lambda key, shape, dt, alpha=1.0, beta=1.0, **kw:
    beta * jax.random.gamma(key, alpha, shape, dtype=dt))
random_exponential = _sample_op(
    "random_exponential",
    lambda key, shape, dt, lam=1.0, **kw:
    jax.random.exponential(key, shape, dtype=dt) / lam)
random_poisson = _sample_op(
    "random_poisson",
    lambda key, shape, dt, lam=1.0, **kw:
    jax.random.poisson(key, lam, shape).astype(dt))
random_randint = _sample_op(
    "random_randint",
    lambda key, shape, dt, low=0, high=2, **kw:
    jax.random.randint(key, shape, low, high).astype(dt))

normal = random_normal
uniform = random_uniform
__all__ += ["normal", "uniform"]


@_export
def random_bernoulli(p=0.5, shape=(), dtype="float32", ctx=None):
    ctx = ctx or current_context()
    key = _random.next_key(ctx)
    dt = jnp.dtype(_base.canonical_dtype(dtype))
    return NDArray(jax.random.bernoulli(key, p, shape).astype(dt), ctx=ctx)


@_export
def sample_multinomial(data, shape=1, get_prob=False, dtype="int32"):
    """Parity: src/operator/random/sample_op (multinomial).  `shape` is the
    per-distribution sample shape; with get_prob=True also returns the
    log-likelihood of each draw (policy-gradient idiom)."""
    data = _as_nd(data)
    key = _random.next_key(data.context)
    sample_shape = (shape,) if isinstance(shape, int) else tuple(shape)
    n = int(onp.prod(sample_shape)) if sample_shape else 1
    scalar = shape == 1
    dt = jnp.dtype(_base.canonical_dtype(dtype))

    def f(p):
        logits = jnp.log(jnp.maximum(p, 1e-37))
        if p.ndim == 1:
            s = jax.random.categorical(key, logits, shape=(n,))
            s = s[0] if scalar else s.reshape(sample_shape)
            logp = jnp.take(jax.nn.log_softmax(logits), s)
        else:
            s = jax.random.categorical(key, logits[:, None, :], axis=-1,
                                       shape=(p.shape[0], n))
            ls = jax.nn.log_softmax(logits, axis=-1)
            logp = jnp.take_along_axis(ls, s, axis=-1)
            if scalar:
                s, logp = s[:, 0], logp[:, 0]
            else:
                s = s.reshape((p.shape[0],) + sample_shape)
                logp = logp.reshape((p.shape[0],) + sample_shape)
        if get_prob:
            return s.astype(dt), logp
        return s.astype(dt)

    return invoke("sample_multinomial", f, [data], differentiable=False)


@_export
def shuffle(data):
    data = _as_nd(data)
    key = _random.next_key(data.context)
    return invoke("shuffle", lambda x: jax.random.permutation(key, x),
                  [data], differentiable=False)


# -------------------------------------------------------------- rnn helpers

@_export
def RNN(data, parameters, state, state_cell=None, state_size=None,
        num_layers=1, mode="lstm", bidirectional=False, p=0.0,
        state_outputs=True, projection_size=None, **kw):
    """Fused multi-layer RNN (parity: src/operator/rnn.cc).

    Layout: data (T, B, C).  Parameters packed flat exactly like MXNet/cuDNN:
    per layer/direction: [W_i2h, W_h2h] then all biases [b_i2h, b_h2h].
    Implemented with lax.scan over time — XLA fuses the gate matmuls; this is
    the TPU-idiomatic fused RNN.
    """
    from ..gluon.rnn._rnn_impl import rnn_forward  # lazy: avoids cycle
    return rnn_forward(data, parameters, state, state_cell, state_size,
                       num_layers, mode, bidirectional, p, state_outputs,
                       **kw)


# ----------------------------------------------------- misc / contrib ops

@_export
def smooth_l1(data, scalar=1.0):
    data = _as_nd(data)
    s2 = scalar * scalar

    def f(x):
        return jnp.where(jnp.abs(x) < 1.0 / s2, 0.5 * s2 * jnp.square(x),
                         jnp.abs(x) - 0.5 / s2)

    return invoke("smooth_l1", f, [data])


@_export
def SoftmaxOutput(data, label=None, grad_scale=1.0, ignore_label=-1,
                  use_ignore=False, normalization="null",
                  out_grad=False, **kw):
    """Classic 1.x softmax loss head (parity: src/operator/softmax_output.cc):
    forward = softmax(data); backward IGNORES the incoming gradient and
    emits (softmax - onehot(label)) * grad_scale, normalized per
    ``normalization`` ('null' | 'batch' | 'valid')."""
    data = _as_nd(data)
    if label is None:
        return softmax(data, axis=-1)
    label = _as_nd(label)

    @jax.custom_vjp
    def _softmax_output(x, y):
        return jax.nn.softmax(x, axis=-1)

    def _fwd(x, y):
        p = jax.nn.softmax(x, axis=-1)
        return p, (p, y)

    def _bwd(res, g):
        p, y = res
        yi = y.astype(jnp.int32)
        onehot = jax.nn.one_hot(yi, p.shape[-1], dtype=p.dtype)
        dx = (p - onehot) * grad_scale
        valid = None
        if use_ignore:
            valid = (yi != ignore_label)
            dx = dx * valid[..., None].astype(p.dtype)
        if normalization == "batch":
            dx = dx / p.shape[0]
        elif normalization == "valid":
            n = jnp.sum(valid) if valid is not None else \
                jnp.asarray(float(onp.prod(y.shape)), p.dtype)
            dx = dx / jnp.maximum(n, 1)
        return dx, jnp.zeros_like(y)

    _softmax_output.defvjp(_fwd, _bwd)
    return invoke("SoftmaxOutput", _softmax_output, [data, label])


@_export
def LinearRegressionOutput(data, label=None, grad_scale=1.0, **kw):
    """1.x L2 head (parity: regression_output.cc): forward = identity;
    backward = (data - label) * grad_scale."""
    data = _as_nd(data)
    if label is None:
        return data
    label = _as_nd(label)

    @jax.custom_vjp
    def _linreg(x, y):
        return x

    def _fwd(x, y):
        return x, (x, y)

    def _bwd(res, g):
        x, y = res
        return ((x - y.reshape(x.shape)) * grad_scale,
                jnp.zeros_like(y))

    _linreg.defvjp(_fwd, _bwd)
    return invoke("LinearRegressionOutput", _linreg, [data, label])


@_export
def MakeLoss(data, grad_scale=1.0, **kw):
    data = _as_nd(data)
    return invoke("make_loss", lambda x: x * grad_scale, [data])


@_export
def BlockGrad(data):
    return _as_nd(data).detach()


stop_gradient = BlockGrad
__all__.append("stop_gradient")


@_export
def interleaved_matmul_selfatt_qk(queries_keys_values, heads):
    """Parity: src/operator/contrib/transformer.cc (GluonNLP BERT path).

    qkv: (T, B, 3*E) interleaved per head: [q h0, k h0, v h0, q h1, ...].
    Returns (B*heads, T, T) scaled scores.
    """
    qkv = _as_nd(queries_keys_values)

    def f(x):
        t, b, e3 = x.shape
        hd = e3 // (3 * heads)
        xr = x.reshape(t, b, heads, 3, hd)
        q = xr[:, :, :, 0, :]
        k = xr[:, :, :, 1, :]
        q = jnp.transpose(q, (1, 2, 0, 3)).reshape(b * heads, t, hd)
        k = jnp.transpose(k, (1, 2, 0, 3)).reshape(b * heads, t, hd)
        scale = 1.0 / jnp.sqrt(jnp.asarray(hd, dtype=x.dtype))
        return jnp.matmul(q * scale, jnp.swapaxes(k, -1, -2))

    return invoke("interleaved_matmul_selfatt_qk", f, [qkv])


@_export
def interleaved_matmul_selfatt_valatt(queries_keys_values, attention, heads):
    qkv, att = _as_nd(queries_keys_values), _as_nd(attention)

    def f(x, a):
        t, b, e3 = x.shape
        hd = e3 // (3 * heads)
        xr = x.reshape(t, b, heads, 3, hd)
        v = jnp.transpose(xr[:, :, :, 2, :], (1, 2, 0, 3)) \
            .reshape(b * heads, t, hd)
        out = jnp.matmul(a, v)  # (B*H, T, hd)
        out = out.reshape(b, heads, t, hd)
        return jnp.transpose(out, (2, 0, 1, 3)).reshape(t, b, heads * hd)

    return invoke("interleaved_matmul_selfatt_valatt", f, [qkv, att])


@_export
def div_sqrt_dim(data):
    data = _as_nd(data)
    return invoke("div_sqrt_dim",
                  lambda x: x / jnp.sqrt(jnp.asarray(x.shape[-1],
                                                     dtype=x.dtype)),
                  [data])


@_export
def choose_element_0index(data, index):
    return pick(data, index, axis=-1)


@_export
def UpSampling(data, scale=2, sample_type="nearest", **kw):
    data = _as_nd(data)

    def f(x):
        n, c, h, w = x.shape
        if sample_type == "nearest":
            return jnp.repeat(jnp.repeat(x, scale, axis=2), scale, axis=3)
        return jax.image.resize(x, (n, c, h * scale, w * scale), "bilinear")

    return invoke("UpSampling", f, [data])


@_export
def add_n(*args, **kw):
    """Sum a list of arrays (parity: elemwise_sum/add_n)."""
    if len(args) == 1 and isinstance(args[0], (list, tuple)):
        args = tuple(args[0])
    nds = [_as_nd(a) for a in args]
    return invoke("add_n", lambda *xs: functools.reduce(jnp.add, xs), nds)


@_export
def diag(data, k=0, axis1=0, axis2=1):
    """Parity: mx.nd.diag — extract diagonals (>=2-D) or build a diagonal
    matrix (1-D)."""
    data = _as_nd(data)

    def f(x):
        if x.ndim == 1:
            return jnp.diag(x, k=k)
        return jnp.diagonal(x, offset=k, axis1=axis1, axis2=axis2)

    return invoke("diag", f, [data])


@_export
def unravel_index(data, shape):
    data = _as_nd(data)
    return invoke(
        "unravel_index",
        lambda i: jnp.stack(jnp.unravel_index(i.astype(jnp.int32),
                                              tuple(shape))),
        [data], differentiable=False)


@_export
def ravel_multi_index(data, shape):
    data = _as_nd(data)

    def f(m):
        idx = tuple(m[i].astype(jnp.int32) for i in range(m.shape[0]))
        return jnp.ravel_multi_index(idx, tuple(shape), mode="clip")

    return invoke("ravel_multi_index", f, [data], differentiable=False)


@_export
def hard_sigmoid(data, alpha=0.2, beta=0.5):
    data = _as_nd(data)
    return invoke("hard_sigmoid",
                  lambda x: jnp.clip(alpha * x + beta, 0.0, 1.0), [data])


@_export
def relu6(data):
    data = _as_nd(data)
    return invoke("relu6", lambda x: jnp.clip(x, 0.0, 6.0), [data])


@_export
def selu(data):
    data = _as_nd(data)
    return invoke("selu", jax.nn.selu, [data])


@_export
def gelu(data):
    data = _as_nd(data)
    return invoke("gelu", _gelu_erf, [data])


@_export
def prelu(data, gamma):
    data, gamma = _as_nd(data), _as_nd(gamma)

    def f(x, g):
        gshape = [1] * x.ndim
        if x.ndim > 1:
            gshape[1] = -1
        return jnp.where(x >= 0, x, x * g.reshape(gshape))

    return invoke("prelu", f, [data, gamma])


random_negative_binomial = _sample_op(
    "random_negative_binomial",
    lambda key, shape, dt, k=1, p=1.0, **kw:
    jax.random.poisson(
        jax.random.fold_in(key, 1),
        jax.random.gamma(key, k, shape) * (1 - p) / builtins.max(p, 1e-12),
        shape).astype(dt))
random_generalized_negative_binomial = _sample_op(
    "random_generalized_negative_binomial",
    lambda key, shape, dt, mu=1.0, alpha=1.0, **kw:
    jax.random.poisson(
        jax.random.fold_in(key, 1),
        jax.random.gamma(key, 1.0 / builtins.max(alpha, 1e-12), shape)
        * (alpha * mu), shape).astype(dt))


def _param_sample_op(name, sampler):
    """Per-distribution sampling: parameter ARRAYS, one draw-set per row
    (parity: sample_uniform/sample_normal...)."""
    def op(*params, shape=(), dtype="float32", ctx=None, **kw):
        nds = [_as_nd(p) for p in params]
        dt = jnp.dtype(_base.canonical_dtype(dtype))
        sample_shape = (shape,) if isinstance(shape, int) else tuple(shape)
        key = _random.next_key(nds[0].context if nds else current_context())

        def f(*ps):
            full = ps[0].shape + sample_shape
            broad = [p.reshape(p.shape + (1,) * len(sample_shape))
                     for p in ps]
            return sampler(key, full, dt, *broad)

        return invoke(name, f, nds, differentiable=False)
    op.__name__ = name
    return _export(op)


sample_uniform = _param_sample_op(
    "sample_uniform",
    lambda key, full, dt, low, high:
    low + (high - low) * jax.random.uniform(key, full, dtype=dt))
sample_normal = _param_sample_op(
    "sample_normal",
    lambda key, full, dt, mu, sigma:
    mu + sigma * jax.random.normal(key, full, dtype=dt))
sample_gamma = _param_sample_op(
    "sample_gamma",
    lambda key, full, dt, alpha, beta:
    beta * jax.random.gamma(key, alpha, full, dtype=dt))
sample_exponential = _param_sample_op(
    "sample_exponential",
    lambda key, full, dt, lam:
    jax.random.exponential(key, full, dtype=dt) / lam)
sample_poisson = _param_sample_op(
    "sample_poisson",
    lambda key, full, dt, lam:
    jax.random.poisson(key, jnp.broadcast_to(lam, full), full).astype(dt))


@_export
def make_loss(data, **kw):
    return MakeLoss(data, **kw)


@_export
def ROIPooling(data, rois, pooled_size, spatial_scale, **kw):
    """Parity: src/operator/roi_pooling.cc — max-pool each ROI into a
    fixed (ph, pw) grid.  rois are (R, 5): [batch_idx, x1, y1, x2, y2]
    in image coords.  Coordinate rounding is half-away-from-zero and bin
    edges are floor/ceil of fractional boundaries (bins may overlap, and
    a narrow ROI can contribute one pixel to MANY bins).  A rectangle
    max is separable, so each ROI costs O((ph+pw)*C*H*W): ph masked row
    reductions then pw masked column reductions — exact for every
    overlap case."""
    data, rois = _as_nd(data), _as_nd(rois)
    ph, pw = pooled_size

    def f(x, r):
        n, c, h, w = x.shape
        ys = jnp.arange(h, dtype=jnp.float32)
        xs = jnp.arange(w, dtype=jnp.float32)

        def one(roi):
            b = roi[0].astype(jnp.int32)
            # C++ round: half away from zero (coords are non-negative)
            x1 = jnp.floor(roi[1] * spatial_scale + 0.5)
            y1 = jnp.floor(roi[2] * spatial_scale + 0.5)
            x2 = jnp.floor(roi[3] * spatial_scale + 0.5)
            y2 = jnp.floor(roi[4] * spatial_scale + 0.5)
            rw = jnp.maximum(x2 - x1 + 1.0, 1.0)
            rh = jnp.maximum(y2 - y1 + 1.0, 1.0)
            fm = x[b]                                  # (C, H, W)

            row_maxes = []
            for i in range(ph):
                sy = jnp.floor(y1 + i * rh / ph)
                ey = jnp.ceil(y1 + (i + 1) * rh / ph)
                m = (ys >= sy) & (ys < ey)
                row_maxes.append(
                    jnp.where(m[None, :, None], fm, -jnp.inf).max(axis=1))
            rowm = jnp.stack(row_maxes, axis=1)        # (C, ph, W)

            col_maxes = []
            for j in range(pw):
                sx = jnp.floor(x1 + j * rw / pw)
                ex = jnp.ceil(x1 + (j + 1) * rw / pw)
                m = (xs >= sx) & (xs < ex)
                col_maxes.append(
                    jnp.where(m[None, None, :], rowm, -jnp.inf).max(axis=2))
            out = jnp.stack(col_maxes, axis=2)         # (C, ph, pw)
            return jnp.where(jnp.isfinite(out), out, 0.0)

        return jax.vmap(one)(r)

    return invoke("ROIPooling", f, [data, rois])


@_export
def Crop(data, *like, offset=(0, 0), h_w=(0, 0), center_crop=False, **kw):
    """Parity: mx.nd.Crop (v1 symbol era) — crop data (N,C,H,W) to the
    spatial size of `like` (second input) or to `h_w`, at `offset` or
    centered."""
    data = _as_nd(data)
    nds = [data]
    if like:
        nds.append(_as_nd(like[0]))

    def f(x, *rest):
        th, tw = (rest[0].shape[2], rest[0].shape[3]) if rest else h_w
        if center_crop:
            y0 = (x.shape[2] - th) // 2
            x0 = (x.shape[3] - tw) // 2
        else:
            y0, x0 = offset
        return x[:, :, y0:y0 + th, x0:x0 + tw]

    return invoke("Crop", f, nds)


# ------------------------------------------------------- long-tail op sweep
# (VERDICT r2 missing #4: ops off the main model path that upstream scripts
# reach for — vision kernels, LRN-era layers, linalg, detection utilities.
# Parity: src/operator/{nn/lrn,contrib/*,tensor/la_op}*)


@_export
def LRN(data, alpha=1e-4, beta=0.75, knorm=2.0, nsize=5, **kw):
    """Local response normalization across channels (parity: mx.nd.LRN,
    src/operator/nn/lrn.cc; the AlexNet-era layer)."""
    data = _as_nd(data)

    def f(x):
        sq = x * x
        half = nsize // 2
        # sum over a channel window via padded sliding window
        pad = [(0, 0)] * x.ndim
        pad[1] = (half, half)
        sqp = jnp.pad(sq, pad)
        acc = builtins.sum(
            jax.lax.slice_in_dim(sqp, i, i + x.shape[1], axis=1)
            for i in range(nsize))
        # upstream lrn-inl.h normalizes alpha by the window size
        return x / jnp.power(knorm + (alpha / nsize) * acc, beta)

    return invoke("LRN", f, [data])


@_export
def SoftmaxActivation(data, mode="instance", **kw):
    """Deprecated-but-used softmax layer (parity: mx.nd.SoftmaxActivation):
    mode='instance' softmaxes over all non-batch dims flattened;
    mode='channel' softmaxes over axis 1."""
    data = _as_nd(data)

    def f(x):
        if mode == "channel":
            return jax.nn.softmax(x, axis=1)
        flat = x.reshape(x.shape[0], -1)
        return jax.nn.softmax(flat, axis=-1).reshape(x.shape)

    return invoke("SoftmaxActivation", f, [data])


@_export
def depth_to_space(data, block_size, **kw):
    """(N, C*b*b, H, W) → (N, C, H*b, W*b) (parity: mx.nd.depth_to_space,
    DCR order like the upstream kernel)."""
    data = _as_nd(data)
    b = int(block_size)

    def f(x):
        n, c, h, w = x.shape
        x = x.reshape(n, b, b, c // (b * b), h, w)
        x = x.transpose(0, 3, 4, 1, 5, 2)
        return x.reshape(n, c // (b * b), h * b, w * b)

    return invoke("depth_to_space", f, [data])


@_export
def space_to_depth(data, block_size, **kw):
    """(N, C, H*b, W*b) → (N, C*b*b, H, W) (parity: mx.nd.space_to_depth)."""
    data = _as_nd(data)
    b = int(block_size)

    def f(x):
        n, c, h, w = x.shape
        x = x.reshape(n, c, h // b, b, w // b, b)
        x = x.transpose(0, 3, 5, 1, 2, 4)
        return x.reshape(n, c * b * b, h // b, w // b)

    return invoke("space_to_depth", f, [data])


@_export
def batch_take(a, indices, **kw):
    """Per-row element pick: out[i] = a[i, indices[i]] (parity:
    mx.nd.batch_take)."""
    a, indices = _as_nd(a), _as_nd(indices)

    def f(x, idx):
        return jnp.take_along_axis(
            x, idx.astype(jnp.int32).reshape(-1, 1), axis=1)[:, 0]

    return invoke("batch_take", f, [a, indices])


@_export
def cumsum(a, axis=None, dtype=None, **kw):
    """Parity: mx.np.cumsum exposed on the nd namespace too."""
    a = _as_nd(a)

    def f(x):
        y = jnp.cumsum(x.ravel() if axis is None else x, axis=0 if axis is
                       None else axis)
        return y.astype(_base.canonical_dtype(dtype)) if dtype else y

    return invoke("cumsum", f, [a])


@_export
def cumprod(a, axis=None, dtype=None, **kw):
    a = _as_nd(a)

    def f(x):
        y = jnp.cumprod(x.ravel() if axis is None else x, axis=0 if axis is
                        None else axis)
        return y.astype(_base.canonical_dtype(dtype)) if dtype else y

    return invoke("cumprod", f, [a])


@_export
def moments(data, axes=None, keepdims=False, **kw):
    """(mean, variance) over `axes` (parity: mx.nd.moments)."""
    data = _as_nd(data)
    ax = tuple(axes) if isinstance(axes, (list, tuple)) else axes

    def f(x):
        mk = jnp.mean(x, axis=ax, keepdims=True)
        v = jnp.mean((x - mk) ** 2, axis=ax, keepdims=keepdims)
        m = mk if keepdims else jnp.squeeze(
            mk, axis=ax if ax is not None
            else tuple(range(x.ndim)))
        return m, v

    return invoke("moments", f, [data], nout=2)


# ---- linalg long tail (parity: src/operator/tensor/la_op.cc) ----

@_export
def linalg_det(A, **kw):
    A = _as_nd(A)
    return invoke("linalg_det", jnp.linalg.det, [A])


@_export
def linalg_slogdet(A, **kw):
    A = _as_nd(A)

    def f(a):
        sign, logabs = jnp.linalg.slogdet(a)
        return sign, logabs

    return invoke("linalg_slogdet", f, [A], nout=2)


@_export
def linalg_inverse(A, **kw):
    A = _as_nd(A)
    return invoke("linalg_inverse", jnp.linalg.inv, [A])


@_export
def linalg_extractdiag(A, offset=0, **kw):
    A = _as_nd(A)
    return invoke("linalg_extractdiag",
                  lambda a: jnp.diagonal(a, offset=offset, axis1=-2,
                                         axis2=-1), [A])


@_export
def linalg_makediag(A, offset=0, **kw):
    A = _as_nd(A)

    def f(a):
        n = a.shape[-1] + builtins.abs(offset)
        out = jnp.zeros(a.shape[:-1] + (n, n), a.dtype)
        idx = jnp.arange(a.shape[-1])
        r = idx + builtins.max(-offset, 0)
        c = idx + builtins.max(offset, 0)
        return out.at[..., r, c].set(a)

    return invoke("linalg_makediag", f, [A])


# ---- spatial sampling (parity: src/operator/bilinear_sampler.cc,
#      grid_generator, spatial_transformer, contrib/roi_align) ----

def _bilinear_sample(fm, gx, gy):
    """Sample fm (C, H, W) at normalized grid coords gx/gy in [-1, 1]
    (Ho, Wo) with zero padding outside — the BilinearSampler contract."""
    c, h, w = fm.shape
    x = (gx + 1.0) * (w - 1) / 2.0
    y = (gy + 1.0) * (h - 1) / 2.0
    x0 = jnp.floor(x)
    y0 = jnp.floor(y)
    wx = x - x0
    wy = y - y0

    def at(yy, xx):
        inside = (yy >= 0) & (yy < h) & (xx >= 0) & (xx < w)
        yc = jnp.clip(yy, 0, h - 1).astype(jnp.int32)
        xc = jnp.clip(xx, 0, w - 1).astype(jnp.int32)
        v = fm[:, yc, xc]                      # (C, Ho, Wo)
        return jnp.where(inside[None], v, 0.0)

    return (at(y0, x0) * (1 - wx) * (1 - wy)
            + at(y0, x0 + 1) * wx * (1 - wy)
            + at(y0 + 1, x0) * (1 - wx) * wy
            + at(y0 + 1, x0 + 1) * wx * wy)


@_export
def BilinearSampler(data, grid, **kw):
    """Sample (N, C, H, W) at grid (N, 2, Ho, Wo) of normalized coords
    (parity: mx.nd.BilinearSampler — the STN sampling stage)."""
    data, grid = _as_nd(data), _as_nd(grid)

    def f(x, g):
        return jax.vmap(
            lambda fm, gg: _bilinear_sample(fm, gg[0], gg[1]))(x, g)

    return invoke("BilinearSampler", f, [data, grid])


@_export
def GridGenerator(data, transform_type="affine", target_shape=None, **kw):
    """Generate a sampling grid from 6-dof affine params (N, 6) or use
    direct flow (N, 2, H, W) (parity: mx.nd.GridGenerator)."""
    data = _as_nd(data)

    def f(t):
        if transform_type == "warp":
            n, _, h, w = t.shape
            xs, ys = jnp.meshgrid(jnp.arange(w, dtype=jnp.float32),
                                  jnp.arange(h, dtype=jnp.float32))
            gx = (xs[None] + t[:, 0]) * 2.0 / (w - 1) - 1.0
            gy = (ys[None] + t[:, 1]) * 2.0 / (h - 1) - 1.0
            return jnp.stack([gx, gy], axis=1)
        h, w = target_shape
        xs = jnp.linspace(-1.0, 1.0, w)
        ys = jnp.linspace(-1.0, 1.0, h)
        gx, gy = jnp.meshgrid(xs, ys)
        ones = jnp.ones_like(gx)
        src = jnp.stack([gx, gy, ones], axis=0).reshape(3, -1)  # (3, HW)
        theta = t.reshape(-1, 2, 3)
        out = jnp.einsum("nij,jk->nik", theta, src)             # (N, 2, HW)
        return out.reshape(-1, 2, h, w)

    return invoke("GridGenerator", f, [data])


@_export
def SpatialTransformer(data, loc, target_shape=None,
                       transform_type="affine",
                       sampler_type="bilinear", **kw):
    """STN: affine grid from `loc` then bilinear sampling (parity:
    mx.nd.SpatialTransformer)."""
    grid = GridGenerator(loc, transform_type=transform_type,
                         target_shape=target_shape)
    return BilinearSampler(data, grid)


# ---- detection utilities (parity: src/operator/contrib/bounding_box.cc,
#      roi_align.cc) ----

@_export
def box_iou(lhs, rhs, format="corner", **kw):
    """Pairwise IoU of (..., N, 4) x (..., M, 4) boxes (parity:
    mx.nd.contrib.box_iou)."""
    lhs, rhs = _as_nd(lhs), _as_nd(rhs)

    def corners(b):
        if format == "center":
            cx, cy, w, h = (b[..., 0], b[..., 1], b[..., 2], b[..., 3])
            return (cx - w / 2, cy - h / 2, cx + w / 2, cy + h / 2)
        return (b[..., 0], b[..., 1], b[..., 2], b[..., 3])

    def f(a, b):
        ca = jnp.stack(corners(a), axis=-1)
        cb = jnp.stack(corners(b), axis=-1)
        return _pairwise_iou(ca, cb)

    return invoke("box_iou", f, [lhs, rhs])


@_export
def box_nms(data, overlap_thresh=0.5, valid_thresh=0.0, topk=-1,
            coord_start=2, score_index=1, id_index=-1,
            force_suppress=False, in_format="corner",
            out_format="corner", **kw):
    """Greedy non-max suppression with static shapes (parity:
    mx.nd.contrib.box_nms).  Suppressed rows become -1, preserving the
    upstream contract.  O(N^2) mask matrix + lax.scan over score order —
    static shapes keep XLA happy."""
    data = _as_nd(data)

    def f(x):
        shape = x.shape
        batched = x.ndim == 3
        xb = x if batched else x[None]

        def one(rows):
            scores = rows[:, score_index]
            boxes = rows[:, coord_start:coord_start + 4]
            if in_format == "center":
                cx, cy, w, h = (boxes[:, 0], boxes[:, 1], boxes[:, 2],
                                boxes[:, 3])
                boxes = jnp.stack([cx - w / 2, cy - h / 2, cx + w / 2,
                                   cy + h / 2], axis=1)
            valid = scores > valid_thresh
            order = jnp.argsort(-jnp.where(valid, scores, -jnp.inf))
            iou = _pairwise_iou(boxes, boxes)
            same_cls = jnp.ones_like(iou, bool) if (
                force_suppress or id_index < 0) else (
                rows[:, id_index][:, None] == rows[:, id_index][None])
            sup_pair = (iou > overlap_thresh) & same_cls

            n = rows.shape[0]
            kmax = n if topk is None or topk < 0 else builtins.min(topk, n)

            def body(suppressed, oi):
                i = order[oi]
                # upstream truncates the CANDIDATE set at score rank k
                # before NMS — ranks beyond k are discarded outright
                ok = valid[i] & ~suppressed[i] & (oi < kmax)
                suppressed = jnp.where(
                    ok, suppressed | sup_pair[i], suppressed)
                suppressed = jnp.where(
                    ok, suppressed.at[i].set(False), suppressed)
                keep = jnp.where(ok, False, True)
                return suppressed, keep

            suppressed, dropped = jax.lax.scan(
                body, jnp.zeros((n,), bool), jnp.arange(n))
            # a row survives if valid, within the top-k candidates, not
            # suppressed by a kept row, and was itself kept
            kept_mask = jnp.zeros((n,), bool).at[order].set(~dropped)
            kept_mask = kept_mask & valid & ~suppressed
            # `boxes` is corner-format here regardless of in_format;
            # rewrite the coord columns only when the encoding changes
            out_rows = rows
            if out_format != in_format:
                if out_format == "corner":
                    b4 = boxes
                else:
                    b4 = jnp.stack(
                        [(boxes[:, 0] + boxes[:, 2]) / 2,
                         (boxes[:, 1] + boxes[:, 3]) / 2,
                         boxes[:, 2] - boxes[:, 0],
                         boxes[:, 3] - boxes[:, 1]], axis=1)
                out_rows = rows.at[
                    :, coord_start:coord_start + 4].set(b4)
            return jnp.where(kept_mask[:, None], out_rows,
                             jnp.full_like(rows, -1.0))

        out = jax.vmap(one)(xb)
        return out if batched else out.reshape(shape)

    return invoke("box_nms", f, [data])


@_export
def ROIAlign(data, rois, pooled_size=None, spatial_scale=1.0,
             sample_ratio=2, position_sensitive=False, **kw):
    """ROI Align with bilinear sampling (parity:
    mx.nd.contrib.ROIAlign, src/operator/contrib/roi_align.cc).

    Deviation: upstream's ``sample_ratio=-1`` adapts the per-bin sample
    count to each ROI's size, which needs dynamic shapes; here -1 maps
    to a STATIC 2x2 sample grid per bin (the common configured value)
    with a one-time warning."""
    data, rois = _as_nd(data), _as_nd(rois)
    ph, pw = pooled_size
    if sample_ratio < 0:
        global _WARNED_ROIALIGN_ADAPTIVE
        if not _WARNED_ROIALIGN_ADAPTIVE:
            import logging
            logging.warning(
                "ROIAlign sample_ratio=-1 (adaptive) needs dynamic "
                "shapes; using a static 2x2 sample grid per bin")
            _WARNED_ROIALIGN_ADAPTIVE = True
        sample_ratio = 2
    sr = builtins.max(int(sample_ratio), 1)

    def f(x, r):
        def one(roi):
            b = roi[0].astype(jnp.int32)
            x1 = roi[1] * spatial_scale
            y1 = roi[2] * spatial_scale
            x2 = roi[3] * spatial_scale
            y2 = roi[4] * spatial_scale
            rw = jnp.maximum(x2 - x1, 1.0)
            rh = jnp.maximum(y2 - y1, 1.0)
            fm = x[b]                                     # (C, H, W)
            h, w = fm.shape[1], fm.shape[2]
            bin_h, bin_w = rh / ph, rw / pw
            # sr x sr sample points per output bin, averaged
            iy = jnp.arange(ph * sr, dtype=jnp.float32)
            ix = jnp.arange(pw * sr, dtype=jnp.float32)
            sy = y1 + (iy + 0.5) * bin_h / sr             # (ph*sr,)
            sx = x1 + (ix + 0.5) * bin_w / sr             # (pw*sr,)
            gy = sy * 2.0 / jnp.maximum(h - 1, 1) - 1.0
            gx = sx * 2.0 / jnp.maximum(w - 1, 1) - 1.0
            gyy = jnp.broadcast_to(gy[:, None], (ph * sr, pw * sr))
            gxx = jnp.broadcast_to(gx[None, :], (ph * sr, pw * sr))
            sampled = _bilinear_sample(fm, gxx, gyy)      # (C, ph*sr, pw*sr)
            c = sampled.shape[0]
            pooled = sampled.reshape(c, ph, sr, pw, sr).mean(axis=(2, 4))
            if position_sensitive:
                # PS-ROIAlign (R-FCN): bin (i, j) pools its OWN channel
                # group — C = c_out * ph * pw
                c_out = c // (ph * pw)
                g = pooled.reshape(c_out, ph, pw, ph, pw)
                ii = jnp.arange(ph)[:, None]
                jj = jnp.arange(pw)[None, :]
                return g[:, ii, jj, ii, jj]               # (c_out, ph, pw)
            return pooled

        return jax.vmap(one)(r)

    return invoke("ROIAlign", f, [data, rois])


# ---- SSD MultiBox triad (parity: src/operator/contrib/multibox_prior.cc,
#      multibox_target.cc, multibox_detection.cc — the GluonCV-era SSD ops)


@_export
def MultiBoxPrior(data, sizes=(1.0,), ratios=(1.0,), clip=False,
                  steps=(-1.0, -1.0), offsets=(0.5, 0.5), **kw):
    """Anchor boxes per feature-map pixel → (1, H*W*A, 4) corners in
    [0, 1], A = len(sizes) + len(ratios) - 1 (all sizes at ratio[0], then
    size[0] at the remaining ratios — upstream's enumeration)."""
    data = _as_nd(data)
    sizes = tuple(float(s) for s in sizes)
    ratios = tuple(float(r) for r in ratios)

    def f(x):
        h, w = x.shape[2], x.shape[3]
        step_y = steps[0] if steps[0] > 0 else 1.0 / h
        step_x = steps[1] if steps[1] > 0 else 1.0 / w
        cy = (jnp.arange(h, dtype=jnp.float32) + offsets[0]) * step_y
        cx = (jnp.arange(w, dtype=jnp.float32) + offsets[1]) * step_x
        wh = []
        for s in sizes:
            r = ratios[0]
            wh.append((s * math.sqrt(r), s / math.sqrt(r)))
        for r in ratios[1:]:
            s = sizes[0]
            wh.append((s * math.sqrt(r), s / math.sqrt(r)))
        wh_j = jnp.asarray(wh, jnp.float32)              # (A, 2)
        cyy, cxx = jnp.meshgrid(cy, cx, indexing="ij")   # (H, W)
        centers = jnp.stack([cxx, cyy], axis=-1).reshape(-1, 1, 2)
        half = wh_j[None, :, :] / 2.0                    # (1, A, 2)
        mins = centers - half                            # (HW, A, 2)
        maxs = centers + half
        out = jnp.concatenate([mins, maxs], axis=-1).reshape(1, -1, 4)
        if clip:
            out = jnp.clip(out, 0.0, 1.0)
        return out

    return invoke("MultiBoxPrior", f, [data])


_WARNED_ROIALIGN_ADAPTIVE = False


def _corner_to_center(b):
    w = b[..., 2] - b[..., 0]
    h = b[..., 3] - b[..., 1]
    return (b[..., 0] + w / 2, b[..., 1] + h / 2, w, h)


def _pairwise_iou(a, b):
    """IoU matrix of corner-format boxes a (..., N, 4) x b (..., M, 4)."""
    ax1, ay1, ax2, ay2 = (a[..., :, None, i] for i in range(4))
    bx1, by1, bx2, by2 = (b[..., None, :, i] for i in range(4))
    iw = jnp.maximum(jnp.minimum(ax2, bx2) - jnp.maximum(ax1, bx1), 0)
    ih = jnp.maximum(jnp.minimum(ay2, by2) - jnp.maximum(ay1, by1), 0)
    inter = iw * ih
    area_a = jnp.maximum(ax2 - ax1, 0) * jnp.maximum(ay2 - ay1, 0)
    area_b = jnp.maximum(bx2 - bx1, 0) * jnp.maximum(by2 - by1, 0)
    return inter / jnp.maximum(area_a + area_b - inter, 1e-12)


@_export
def MultiBoxTarget(anchor, label, cls_pred, overlap_threshold=0.5,
                   ignore_label=-1.0, negative_mining_ratio=-1.0,
                   negative_mining_thresh=0.5,
                   variances=(0.1, 0.1, 0.2, 0.2), **kw):
    """SSD training targets: match anchors to GT boxes and encode offsets
    (parity: multibox_target.cc).  label is (B, M, 5) [cls, x1, y1, x2,
    y2] with -1 padding rows.  Returns (loc_target (B, A*4), loc_mask
    (B, A*4), cls_target (B, A)) — cls_target 0 = background, k+1 = GT
    class k."""
    anchor, label, cls_pred = (_as_nd(anchor), _as_nd(label),
                               _as_nd(cls_pred))
    nds = [anchor, label, cls_pred]
    v = tuple(float(x) for x in variances)

    def f(anc, lab, cp):
        a = anc.reshape(-1, 4)                           # (A, 4)
        na = a.shape[0]

        def one(rows, cpb):
            m_gt = rows.shape[0]
            valid = rows[:, 0] >= 0                      # (M,)
            gt = rows[:, 1:5]                            # (M, 4)
            iou = _pairwise_iou(a, gt)
            iou = jnp.where(valid[None, :], iou, -1.0)   # (A, M)

            best_gt = jnp.argmax(iou, axis=1)            # per anchor
            best_iou = jnp.max(iou, axis=1)
            # force-match: each VALID GT claims its best anchor; padding
            # rows scatter into a spill slot so they cannot clobber a real
            # GT's forced match (duplicate-index .at[].set is unordered)
            best_anchor = jnp.argmax(iou, axis=0)        # (M,)
            scatter_to = jnp.where(valid, best_anchor, na)
            forced = jnp.zeros((na + 1,), bool).at[
                scatter_to].set(True)[:na]
            forced_gt = jnp.zeros((na + 1,), jnp.int32).at[
                scatter_to].set(jnp.arange(m_gt, dtype=jnp.int32))[:na]
            matched = forced | (best_iou >= overlap_threshold)
            gt_idx = jnp.where(forced, forced_gt, best_gt)

            cls_t = jnp.where(
                matched, rows[gt_idx, 0].astype(jnp.float32) + 1.0, 0.0)
            if negative_mining_ratio > 0:
                # hard negative mining (multibox_target.cc): keep only the
                # top ratio*n_pos hardest negatives as background; the
                # rest are ignore_label and drop out of the cls loss
                neg_cand = (~matched) & \
                    (best_iou < negative_mining_thresh)
                hardness = jnp.max(cpb[1:, :], axis=0)   # max fg score
                hardness = jnp.where(neg_cand, hardness, -jnp.inf)
                n_pos = jnp.sum(matched.astype(jnp.int32))
                k = jnp.minimum(
                    (negative_mining_ratio * n_pos).astype(jnp.int32),
                    jnp.sum(neg_cand.astype(jnp.int32)))
                order = jnp.argsort(-hardness)
                rank = jnp.zeros((na,), jnp.int32).at[order].set(
                    jnp.arange(na, dtype=jnp.int32))
                mined = neg_cand & (rank < k)
                cls_t = jnp.where(matched, cls_t,
                                  jnp.where(mined, 0.0,
                                            float(ignore_label)))
            acx, acy, aw, ah = _corner_to_center(a)
            m = gt[gt_idx]
            gcx, gcy, gw, gh = _corner_to_center(m)
            lt = jnp.stack([
                (gcx - acx) / jnp.maximum(aw, 1e-12) / v[0],
                (gcy - acy) / jnp.maximum(ah, 1e-12) / v[1],
                jnp.log(jnp.maximum(gw, 1e-12) /
                        jnp.maximum(aw, 1e-12)) / v[2],
                jnp.log(jnp.maximum(gh, 1e-12) /
                        jnp.maximum(ah, 1e-12)) / v[3]], axis=1)
            mask = matched.astype(jnp.float32)[:, None]
            return (lt * mask).reshape(-1), \
                jnp.broadcast_to(mask, (na, 4)).reshape(-1), cls_t

        lt, lm, ct = jax.vmap(one)(lab, cp)
        return lt, lm, ct

    return invoke("MultiBoxTarget", f, nds, nout=3, differentiable=False)


@_export
def MultiBoxDetection(cls_prob, loc_pred, anchor, clip=True,
                      threshold=0.01, nms_threshold=0.5,
                      force_suppress=False,
                      variances=(0.1, 0.1, 0.2, 0.2), nms_topk=-1, **kw):
    """Decode SSD predictions and suppress duplicates (parity:
    multibox_detection.cc).  cls_prob (B, C+1, A) with class 0 =
    background; returns (B, A, 6) rows [cls_id, score, x1, y1, x2, y2],
    suppressed rows -1."""
    cls_prob, loc_pred, anchor = (_as_nd(cls_prob), _as_nd(loc_pred),
                                  _as_nd(anchor))
    v = tuple(float(x) for x in variances)

    def f(cp, lp, anc):
        a = anc.reshape(-1, 4)
        acx, acy, aw, ah = _corner_to_center(a)

        def one(cpb, lpb):
            loc = lpb.reshape(-1, 4)
            cx = loc[:, 0] * v[0] * aw + acx
            cy = loc[:, 1] * v[1] * ah + acy
            w = jnp.exp(loc[:, 2] * v[2]) * aw
            h = jnp.exp(loc[:, 3] * v[3]) * ah
            boxes = jnp.stack([cx - w / 2, cy - h / 2,
                               cx + w / 2, cy + h / 2], axis=1)
            if clip:
                boxes = jnp.clip(boxes, 0.0, 1.0)
            scores = cpb[1:, :]                          # (C, A)
            cls_id = jnp.argmax(scores, axis=0)          # (A,)
            score = jnp.max(scores, axis=0)
            keep = score > threshold
            rows = jnp.concatenate([
                jnp.where(keep, cls_id.astype(jnp.float32), -1.0)[:, None],
                jnp.where(keep, score, -1.0)[:, None], boxes], axis=1)
            return rows

        rows = jax.vmap(one)(cp, lp)
        return rows

    decoded = invoke("MultiBoxDetection_decode", f,
                     [cls_prob, loc_pred, anchor], differentiable=False)
    return box_nms(decoded, overlap_thresh=nms_threshold,
                   valid_thresh=threshold, topk=nms_topk,
                   coord_start=2, score_index=1, id_index=0,
                   force_suppress=force_suppress)
