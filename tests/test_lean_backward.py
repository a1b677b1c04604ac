"""LayerNorm and exact GELU state their own backward (PR 29): the forward
is the plain formula bit for bit, the gradients are the plain formula's,
and ``jax.vjp`` keeps the input and two numbers a row (LayerNorm) or the
input alone (GELU) where autodiff of the formulas kept three arrays of
the input's size, erfc's branches and their predicate.

The plain formulas are written out HERE (the parent commit's code), so
that nothing under test is its own reference.
"""
import functools
import math

import jax
import jax.numpy as jnp
import numpy as onp
import pytest
from jax import lax

import mxnet_tpu as mx
from mxnet_tpu import amp, autograd, nd
from mxnet_tpu.ndarray import NDArray
from mxnet_tpu.ndarray import ops as OPS


def plain_layer_norm(x, g, b, axis=-1, eps=1e-5):
    mean = jnp.mean(x, axis=axis, keepdims=True)
    var = jnp.var(x, axis=axis, keepdims=True)
    shape = [1] * x.ndim
    shape[axis] = x.shape[axis]
    return (x - mean) * lax.rsqrt(var + eps) * g.reshape(shape) \
        + b.reshape(shape)


plain_gelu = functools.partial(jax.nn.gelu, approximate=False)


def written_out_gelu(x):
    return 0.5 * x * lax.erfc(-x * math.sqrt(0.5))


_rs = onp.random.RandomState(29)

# (shape, axis): the last axis, an inner axis, and one to three batch
# dimensions broadcast against gamma and beta
LN_CASES = [((6, 16), -1), ((2, 5, 16), -1), ((2, 16, 5), 1),
            ((3, 2, 4, 8), 2)]
DTYPES = ["float32", "bfloat16"]
# a bfloat16 has 8 bits of mantissa, and the row statistics are rounded
# to it before the gradient sees them
TOL = {"float32": 2e-5, "bfloat16": 4e-2}


def _ln_inputs(shape, axis, dtype):
    c = shape[axis]
    x = (_rs.randn(*shape) * 1.5 + 0.3).astype("float32")
    g = (1.0 + 0.3 * _rs.randn(c)).astype("float32")
    b = (0.2 * _rs.randn(c)).astype("float32")
    w = _rs.randn(*shape).astype("float32")
    return tuple(jnp.asarray(a).astype(dtype) for a in (x, g, b, w))


def _worst(got, want):
    """Largest gap over the largest wanted entry."""
    got = onp.asarray(jnp.asarray(got, jnp.float32))
    want = onp.asarray(jnp.asarray(want, jnp.float32))
    return float(onp.max(onp.abs(got - want)) / onp.max(onp.abs(want)))


def _tape_grads(op, arrays, w):
    """Gradients of sum(op(*arrays) * w) by the imperative tape."""
    nds = [NDArray(a) for a in arrays]
    for a in nds:
        a.attach_grad()
    with autograd.record():
        loss = (op(*nds) * NDArray(w)).sum()
    loss.backward()
    return [a.grad.jax for a in nds]


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("shape,axis", LN_CASES)
def test_layer_norm_gradients_match_the_plain_formula(shape, axis, dtype):
    x, g, b, w = _ln_inputs(shape, axis, dtype)
    got = _tape_grads(lambda *a: nd.LayerNorm(*a, axis=axis, eps=1e-5),
                      (x, g, b), w)
    # the reference computes in float32 from the same (rounded) inputs
    f32 = [a.astype(jnp.float32) for a in (x, g, b, w)]
    want = jax.grad(lambda x, g, b: jnp.sum(
        plain_layer_norm(x, g, b, axis) * f32[3]), argnums=(0, 1, 2))(*f32[:3])
    for gt, wt, src in zip(got, want, (x, g, b)):
        assert gt.shape == src.shape and gt.dtype == src.dtype
        assert _worst(gt, wt) < TOL[dtype]
    if dtype == "bfloat16":
        # no coarser than autodiff of the formula in bfloat16 itself
        old = jax.grad(lambda x, g, b: jnp.sum(
            (plain_layer_norm(x, g, b, axis) * w).astype(jnp.float32)),
            argnums=(0, 1, 2))(x, g, b)
        for gt, od, wt in zip(got, old, want):
            assert _worst(gt, wt) <= 1.5 * _worst(od, wt) + 1e-3


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("op", ["gelu", "LeakyReLU"])
def test_gelu_gradient_matches_the_plain_formula(op, dtype):
    call = nd.gelu if op == "gelu" else \
        functools.partial(nd.LeakyReLU, act_type="gelu")
    h = jnp.asarray(onp.concatenate(
        [_rs.randn(4, 30) * 2.0, [[-9.0, -4.0, -1e-3, 0.0, 1e-3, 4.0, 9.0]
                                  + [0.5] * 23]]).astype("float32"))
    h = h.astype(dtype)
    w = jnp.asarray(_rs.randn(*h.shape).astype("float32")).astype(dtype)
    (got,) = _tape_grads(call, (h,), w)
    want = jax.grad(lambda h: jnp.sum(written_out_gelu(h) * w.astype(
        jnp.float32)))(h.astype(jnp.float32))
    assert got.shape == h.shape and got.dtype == h.dtype
    assert bool(jnp.all(jnp.isfinite(got.astype(jnp.float32))))
    assert _worst(got, want) < TOL[dtype]


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("shape,axis", LN_CASES[1:3])
def test_layer_norm_forward_is_the_plain_formula_bit_for_bit(shape, axis,
                                                             dtype):
    x, g, b, _w = _ln_inputs(shape, axis, dtype)
    got = nd.LayerNorm(NDArray(x), NDArray(g), NDArray(b), axis=axis).jax
    want = plain_layer_norm(x, g, b, axis)
    assert got.dtype == want.dtype
    assert onp.array_equal(onp.asarray(got.astype(jnp.float32)),
                           onp.asarray(want.astype(jnp.float32)))
    # and as one compiled program, which is how every model runs it
    lean = jax.jit(lambda x, g, b: OPS._layer_norm(x, g, b, axis, 1e-5))
    plain = jax.jit(lambda x, g, b: plain_layer_norm(x, g, b, axis))
    assert onp.array_equal(
        onp.asarray(lean(x, g, b).astype(jnp.float32)),
        onp.asarray(plain(x, g, b).astype(jnp.float32)))


@pytest.mark.parametrize("dtype", DTYPES)
def test_gelu_forward_is_the_plain_formula_bit_for_bit(dtype):
    h = jnp.asarray((_rs.randn(8, 64) * 3).astype("float32")).astype(dtype)
    bits = lambda a: onp.asarray(a.astype(jnp.float32))
    want = bits(plain_gelu(h))
    for got in (nd.gelu(NDArray(h)).jax,
                nd.LeakyReLU(NDArray(h), act_type="gelu").jax):
        assert got.dtype == h.dtype
        assert onp.array_equal(bits(got), want)
    # and compiled (a compiled bfloat16 chain rounds once, at its end:
    # compiled is compared with compiled)
    assert onp.array_equal(bits(jax.jit(OPS._gelu_erf)(h)),
                           bits(jax.jit(plain_gelu)(h)))


def _second_order(fn, x, w, v):
    """d/dx of <d/dx sum(fn(x) * w), v>: reverse over reverse."""
    first = jax.grad(lambda x: jnp.sum(fn(x) * w))
    return jax.grad(lambda x: jnp.sum(first(x) * v))(x)


@pytest.mark.parametrize("which", ["layer_norm", "gelu"])
def test_grad_of_grad_runs_and_matches(which):
    x, g, b, w = _ln_inputs((3, 4, 16), -1, "float32")
    v = jnp.asarray(_rs.randn(*x.shape).astype("float32"))
    if which == "layer_norm":
        lean = lambda x: OPS._layer_norm(x, g, b, -1, 1e-5)
        plain = lambda x: plain_layer_norm(x, g, b, -1)
    else:
        lean, plain = OPS._gelu_erf, written_out_gelu
    got, want = (_second_order(f, x, w, v) for f in (lean, plain))
    assert float(jnp.max(jnp.abs(want))) > 1e-3
    assert _worst(got, want) < 1e-4


def _kept_by_vjp(monkeypatch, plain):
    """(shape, dtype) of every array ``jax.vjp`` holds for one
    TransformerBlock under bf16 AMP.  Many rows of few tokens, so that
    the activations outweigh the weights' casts and attention's (T, T)
    arrays; not causal, because the plain-XLA attention of this size
    keeps its own mask (the chip's step runs the flash kernel)."""
    from mxnet_tpu.gluon.cached_op import make_pure_fn
    from mxnet_tpu.models.transformer import TransformerBlock

    with monkeypatch.context() as patch:
        if plain:
            patch.setattr(OPS, "_layer_norm", plain_layer_norm)
            patch.setattr(OPS, "_gelu_erf", plain_gelu)
        amp.init("bfloat16")
        try:
            blk = TransformerBlock(64, 256, 2, causal=False)
            blk.initialize()
            params, pure = make_pure_fn(blk, blk, "one_block")
            vals = [p.data().jax for p in params]
            x = jnp.asarray(_rs.randn(64, 8, 64).astype("float32"))
            _out, pullback = jax.vjp(
                lambda pv, x: pure(pv, NDArray(x)).jax, vals, x)
        finally:
            amp.reset()
    return [(tuple(a.shape), jnp.dtype(a.dtype))
            for a in jax.tree_util.tree_leaves(pullback)
            if hasattr(a, "dtype")]


def test_one_block_keeps_a_third_less_and_no_mask(monkeypatch):
    size = lambda kept: sum(math.prod(s) * d.itemsize for s, d in kept)
    plain = _kept_by_vjp(monkeypatch, plain=True)
    lean = _kept_by_vjp(monkeypatch, plain=False)
    assert size(lean) <= 0.65 * size(plain), (size(lean), size(plain))
    masks = [(s, d) for s, d in lean if d in (jnp.dtype("uint8"),
                                              jnp.dtype("bool"))]
    assert not masks, masks
    hidden = (64, 8, 256)        # fc1's output and GELU's: h and gelu(h)
    count = lambda kept: sum(1 for s, _d in kept if s == hidden)
    assert count(lean) == 2 and count(plain) >= 4
    stream = ((64, 8, 64), jnp.dtype("float32"))    # one x a LayerNorm
    assert lean.count(stream) == 2 and plain.count(stream) >= 6


def test_gelu_and_leaky_relu_gelu_are_one_function(monkeypatch):
    seen = []
    real = OPS.invoke

    def spy(name, fn, inputs, *a, **kw):
        seen.append((name, fn))
        return real(name, fn, inputs, *a, **kw)

    monkeypatch.setattr(OPS, "invoke", spy)
    h = mx.nd.array(onp.linspace(-2, 2, 8, dtype="float32"))
    nd.gelu(h)
    nd.LeakyReLU(h, act_type="gelu")
    assert [n for n, _f in seen] == ["gelu", "gelu"]
    assert seen[0][1] is seen[1][1] is OPS._gelu_erf
