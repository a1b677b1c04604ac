"""The Mamba-2 scan: the chunked ``jax.numpy`` form and the Pallas kernel
(interpret mode on the CPU) against the step-by-step recurrence, forward
and gradients."""
import jax
import jax.numpy as jnp
import numpy as onp
import pytest

import mxnet_tpu  # noqa: F401  (the package-default matmul precision)
from mxnet_tpu.ops import ssd


def _inputs(b=2, t=64, h=4, p=8, g=2, n=16, seed=0, decay=1.0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(ks[0], (b, t, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, t, h)))
    a = -decay * jnp.exp(jax.random.normal(ks[2], (h,)))
    bm = jax.random.normal(ks[3], (b, t, g, n))
    cm = jax.random.normal(ks[4], (b, t, g, n))
    return x, dt, a, bm, cm


@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("chunk", [8, 16, 64])
def test_chunked_scan_matches_recurrence(impl, chunk):
    args = _inputs()
    want = ssd.ssd_recurrence(*args)
    got = ssd.ssd_scan(*args, chunk=chunk, impl=impl)
    assert got.dtype == jnp.float32 and got.shape == want.shape
    onp.testing.assert_allclose(onp.asarray(got), onp.asarray(want),
                                rtol=1e-4, atol=2e-4)


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_chunked_scan_gradients_match_recurrence(impl):
    args = _inputs(seed=1)
    w = jax.random.normal(jax.random.PRNGKey(9), args[0].shape)

    def loss(fn):
        return lambda *a: jnp.sum(fn(*a) * w)

    got = jax.grad(loss(lambda *a: ssd.ssd_scan(*a, chunk=16, impl=impl)),
                   argnums=(0, 1, 2, 3, 4))(*args)
    want = jax.grad(loss(ssd.ssd_recurrence), argnums=(0, 1, 2, 3, 4))(*args)
    for g, r in zip(got, want):
        assert float(jnp.max(jnp.abs(g - r))) <= 1e-4 * float(
            jnp.max(jnp.abs(r))) + 1e-5


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_decay_that_underflows_a_naive_exp(impl):
    """A running sum of dt A near -2,000 by the sequence's end: exp of it
    is 0 in float32 and 1 / exp of it overflows; only differences inside a
    chunk may be exponentiated."""
    args = _inputs(t=128, decay=60.0, seed=2)
    cum = jnp.cumsum(args[1] * args[2], axis=1)
    assert float(jnp.min(cum)) < -1000.0
    want = ssd.ssd_recurrence(*args)
    got = ssd.ssd_scan(*args, chunk=32, impl=impl)
    assert bool(jnp.all(jnp.isfinite(got)))
    onp.testing.assert_allclose(onp.asarray(got), onp.asarray(want),
                                rtol=1e-4, atol=2e-4)
    g = jax.grad(lambda *a: jnp.sum(ssd.ssd_scan(*a, chunk=32, impl=impl)),
                 argnums=(0, 1, 2))(*args)
    assert all(bool(jnp.all(jnp.isfinite(v))) for v in g)


def test_bf16_operands_keep_float32_decays_and_states():
    x, dt, a, bm, cm = _inputs(seed=3)
    bf = jnp.bfloat16
    want = ssd.ssd_recurrence(x.astype(bf), dt, a, bm.astype(bf),
                              cm.astype(bf))
    for impl in ("xla", "pallas"):
        got = ssd.ssd_scan(x.astype(bf), dt, a, bm.astype(bf),
                           cm.astype(bf), chunk=16, impl=impl)
        assert got.dtype == jnp.float32
        assert float(jnp.max(jnp.abs(got - want))) <= 0.03 * float(
            jnp.max(jnp.abs(want)))


def test_causal_conv_is_shifted_multiply_adds():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 12, 6))
    w = jax.random.normal(jax.random.PRNGKey(1), (6, 4))
    bias = jax.random.normal(jax.random.PRNGKey(2), (6,))
    got = ssd.causal_conv1d(x, w, bias)
    want = onp.zeros((2, 12, 6), onp.float32) + onp.asarray(bias)
    xn, wn = onp.asarray(x), onp.asarray(w)
    for t in range(12):
        for k in range(4):
            if t - (3 - k) >= 0:
                want[:, t] += xn[:, t - (3 - k)] * wn[:, k]
    onp.testing.assert_allclose(onp.asarray(got), want, rtol=1e-5, atol=1e-5)


def test_plan_and_event():
    from mxnet_tpu import observability as obs

    assert ssd.ssd_plan(1, 8192, 64, 8, 128) == (128, 64, 8, 512)
    with pytest.raises(ValueError):
        ssd.ssd_plan(1, 100, 4, 2, 16)
    args = _inputs(t=32)
    obs.disable_tracing()
    ssd.ssd_scan(*args, chunk=16, impl="xla")      # says nothing while off
    tr = obs.enable_tracing()
    try:
        assert not tr.spans(name="ssd.plan")
        ssd.ssd_scan(*args, chunk=16, impl="xla")
        ssd.ssd_scan(*args, chunk=16, impl="xla")
        events = tr.spans(name="ssd.plan")
    finally:
        obs.disable_tracing()
    assert len(events) == 1
    assert events[0].attrs["chunk"] == 16 and events[0].attrs["heads"] == 4
