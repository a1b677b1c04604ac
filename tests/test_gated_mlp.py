"""The dense gated feed-forward of the hybrid families
(``models/hybrid_common.py gated_mlp``, PR 48): two products over the
halves of ONE ``w_in`` leaf, the cotangents of both rounded to the compute
type where they are made, nothing kept for that.

The plain form is written out HERE (the parent commit's code: one product,
its float32 result sliced, differentiated by JAX), so that nothing under
test is its own reference.
"""
import jax
import jax.numpy as jnp
import numpy as onp
import pytest

import mxnet_tpu  # noqa: F401  (sets the package-default matmul precision)
from mxnet_tpu import observability
from mxnet_tpu.models.hybrid_common import dense, gated_mlp

DTYPES = ["float32", "bfloat16"]
# tests/test_lean_backward.py's: a bfloat16 has 8 bits of mantissa, and the
# cotangents dg, dv are rounded to it once before the products that read them
TOL = {"float32": 2e-5, "bfloat16": 4e-2}
# (leading shape, D, F): an odd row count; F no multiple of 128, nor of 8
SHAPES = [((7,), 24, 20), ((3, 5), 32, 136), ((1, 9), 16, 130)]


def plain_gated_mlp(x, w_in, w_out, cd):
    u = dense(x, w_in, cd)
    half = u.shape[-1] // 2
    return dense(jax.nn.silu(u[..., :half]) * u[..., half:], w_out, cd)


def _inputs(lead, d, f, seed=48):
    rs = onp.random.RandomState(seed)
    x = rs.randn(*lead, d).astype("float32")
    w_in = (rs.randn(2 * f, d) / onp.sqrt(d)).astype("float32")
    w_out = (rs.randn(d, f) / onp.sqrt(f)).astype("float32")
    ct = rs.randn(*lead, d).astype("float32")
    return tuple(jnp.asarray(a) for a in (x, w_in, w_out, ct))


def _value_and_grads(fn, cd, x, w_in, w_out, ct):
    y, back = jax.vjp(lambda *a: fn(*a, cd), x, w_in, w_out)
    return (y,) + back(ct)


def _worst(got, want):
    """Largest gap over the largest wanted entry."""
    got, want = (onp.asarray(a, "float32") for a in (got, want))
    return float(onp.max(onp.abs(got - want)) / onp.max(onp.abs(want)))


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("lead,d,f", SHAPES)
def test_value_and_gradients_are_the_plain_forms(lead, d, f, dtype):
    cd = jnp.dtype(dtype)
    args = _inputs(lead, d, f)
    got = jax.jit(lambda *a: _value_and_grads(gated_mlp, cd, *a))(*args)
    want = jax.jit(lambda *a: _value_and_grads(plain_gated_mlp, cd, *a))(
        *args)
    for name, g, w in zip(("y", "dx", "dw_in", "dw_out"), got, want):
        assert g.shape == w.shape and g.dtype == w.dtype, name
        assert _worst(g, w) <= TOL[dtype], (name, _worst(g, w))
    if dtype == "bfloat16":
        # forward: the same sums over the same products, element by element
        assert onp.array_equal(onp.asarray(got[0]), onp.asarray(want[0]))


@pytest.mark.parametrize("dtype", DTYPES)
def test_gradient_of_w_in_is_one_leaf_gate_rows_first(dtype):
    """``dW_in`` has ``w_in``'s shape and type; its first F rows are the
    gradient of the GATE's product (the value ``v`` frozen) and its last
    F rows the value's (the gate frozen), each as JAX differentiates that
    product alone."""
    cd = jnp.dtype(dtype)
    x, w_in, w_out, ct = _inputs((6,), 16, 12)
    f = w_in.shape[0] // 2
    _, _, dw_in, _ = _value_and_grads(gated_mlp, cd, x, w_in, w_out, ct)
    assert dw_in.shape == w_in.shape and dw_in.dtype == w_in.dtype
    v = dense(x, w_in[f:], cd)

    def gate_only(wg):
        return jnp.sum(dense(jax.nn.silu(dense(x, wg, cd)) * v, w_out, cd)
                       * ct)

    def value_only(wv):
        g = dense(x, w_in[:f], cd)
        return jnp.sum(dense(jax.nn.silu(g) * dense(x, wv, cd), w_out, cd)
                       * ct)

    assert _worst(dw_in[:f], jax.grad(gate_only)(w_in[:f])) <= TOL[dtype]
    assert _worst(dw_in[f:], jax.grad(value_only)(w_in[f:])) <= TOL[dtype]
    # and the two halves are not each other's
    assert _worst(dw_in[:f], dw_in[f:]) > 0.1


@pytest.mark.parametrize("dtype", DTYPES)
def test_recomputed_under_checkpoint_gives_the_same_gradients(dtype):
    cd = jnp.dtype(dtype)
    x, w_in, w_out, ct = _inputs((2, 7), 24, 20)

    def grads(fn):
        return jax.jit(jax.grad(
            lambda *a: jnp.sum(fn(*a) * ct), argnums=(0, 1, 2)))(
            x, w_in, w_out)

    direct = grads(lambda *a: gated_mlp(*a, cd))
    again = grads(jax.checkpoint(lambda *a: gated_mlp(*a, cd)))
    for g, w in zip(again, direct):
        assert onp.array_equal(onp.asarray(g), onp.asarray(w))


def test_float32_compute_rounds_nothing_on_the_way_back():
    """With no AMP the identity on ``g`` and ``v`` is one: the jaxpr of
    the gradient holds no convert to a narrower type."""
    x, w_in, w_out, ct = _inputs((5,), 16, 12)
    text = str(jax.make_jaxpr(jax.grad(
        lambda *a: jnp.sum(gated_mlp(*a, jnp.float32) * ct),
        argnums=(0, 1, 2)))(x, w_in, w_out))
    assert "f16" not in text        # neither bf16 nor f16


def test_nothing_is_kept_for_the_rounding():
    """What ``jax.vjp`` keeps is what the plain products keep: the
    identity on ``g`` and ``v`` adds no residual (a ``custom_vjp`` that
    kept values took the looped decoder's temporaries up by 6 GB)."""
    cd = jnp.bfloat16
    x, w_in, w_out, _ = _inputs((3, 5), 32, 136)

    def two_products_no_identity(x, w_in, w_out):
        f = w_in.shape[0] // 2
        g, v = dense(x, w_in[:f], cd), dense(x, w_in[f:], cd)
        return dense(jax.nn.silu(g) * v, w_out, cd)

    def kept(fn):
        _, back = jax.vjp(fn, x, w_in, w_out)
        return sorted((a.shape, str(a.dtype))
                      for a in jax.tree_util.tree_leaves(back))

    assert kept(lambda *a: gated_mlp(*a, cd)) == kept(
        two_products_no_identity)


@pytest.mark.parametrize("dtype", DTYPES)
def test_plan_event_says_which_form_runs(dtype):
    cd = jnp.dtype(dtype)
    x, w_in, w_out, _ = _inputs((3, 5), 32, 136)
    tracer = observability.enable_tracing()
    try:
        for _ in range(2):      # one event a distinct shape, not a call
            jax.jit(lambda *a: gated_mlp(*a, cd))(x, w_in, w_out)
            jax.make_jaxpr(lambda *a: gated_mlp(*a, cd))(x, w_in, w_out)
        events = [s.attrs for s in tracer.spans(name="mlp.plan")]
    finally:
        observability.disable_tracing()
    assert events == [{"form": "halves", "rows": 15, "half": 136,
                       "compute_dtype": dtype,
                       "wide_bytes_a_call": 15 * 136 * 4}]
