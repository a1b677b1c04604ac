"""The gated delta rule: the chunked ``jax.numpy`` form and the Pallas
kernel (interpret mode on the CPU) against the step-by-step recurrence,
forward and gradients."""
import jax
import jax.numpy as jnp
import numpy as onp
import pytest

import mxnet_tpu  # noqa: F401  (the package-default matmul precision)
from mxnet_tpu.ops import gdn


def _inputs(b=2, t=128, hk=2, hv=4, dk=16, dv=8, seed=0, decay=1.0,
            dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)

    def unit(x):
        return x / jnp.linalg.norm(x, axis=-1, keepdims=True)

    q = unit(jax.random.normal(ks[0], (b, t, hk, dk))) * dk ** -0.5
    k = unit(jax.random.normal(ks[1], (b, t, hk, dk)))
    v = jax.random.normal(ks[2], (b, t, hv, dv))
    g = -decay * jax.nn.softplus(jax.random.normal(ks[3], (b, t, hv)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, t, hv)))
    return q.astype(dtype), k.astype(dtype), v.astype(dtype), g, beta


@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("chunk,heads", [(8, (2, 4)), (16, (2, 2)),
                                         (64, (2, 4)), (32, (1, 3))])
def test_chunked_rule_matches_recurrence(impl, chunk, heads):
    args = _inputs(hk=heads[0], hv=heads[1])
    want = gdn.gdn_recurrence(*args)
    got = gdn.gdn_scan(*args, chunk=chunk, impl=impl)
    assert got.dtype == jnp.float32 and got.shape == want.shape
    onp.testing.assert_allclose(onp.asarray(got), onp.asarray(want),
                                rtol=1e-4, atol=2e-5)


@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("hk,hv", [(2, 4), (8, 8)])   # 8: two groups behind
def test_chunked_rule_gradients_match_recurrence(impl, hk, hv):
    args = _inputs(seed=1, t=64, hk=hk, hv=hv, decay=0.3)
    w = jax.random.normal(jax.random.PRNGKey(9), args[2].shape)

    def loss(fn):
        return lambda *a: jnp.sum(fn(*a) * w)

    got = jax.grad(loss(lambda *a: gdn.gdn_scan(*a, chunk=16, impl=impl)),
                   argnums=(0, 1, 2, 3, 4))(*args)
    want = jax.grad(loss(gdn.gdn_recurrence), argnums=(0, 1, 2, 3, 4))(*args)
    for g, r in zip(got, want):
        assert g.shape == r.shape
        assert float(jnp.max(jnp.abs(g - r))) <= 1e-4 * float(
            jnp.max(jnp.abs(r))) + 1e-5


def test_kernel_and_xla_form_agree_to_rounding():
    """The kernel computes the chunked form's own arithmetic: interpreted,
    it differs from it by float32 rounding alone."""
    args = _inputs(seed=3)
    a = gdn.gdn_scan(*args, chunk=64, impl="xla")
    b = gdn.gdn_scan(*args, chunk=64, impl="pallas")
    assert float(jnp.max(jnp.abs(a - b))) <= 1e-6


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_decays_that_underflow_give_zeros_not_nan(impl):
    """A running sum of g near -7,000 by the sequence's end: exp of it is
    0 in float32 and 1 / exp of it overflows; only differences inside a
    chunk may be exponentiated.  A state that has decayed to nothing
    leaves o_t = beta_t (q_t . k_t) v_t."""
    q, k, v, g, beta = _inputs(decay=80.0, seed=2)
    assert float(jnp.min(jnp.cumsum(g, axis=1))) < -1000.0
    want = gdn.gdn_recurrence(q, k, v, g, beta)
    got = gdn.gdn_scan(q, k, v, g, beta, chunk=32, impl=impl)
    assert bool(jnp.all(jnp.isfinite(got)))
    onp.testing.assert_allclose(onp.asarray(got), onp.asarray(want),
                                rtol=1e-4, atol=2e-5)
    grads = jax.grad(lambda *a: jnp.sum(gdn.gdn_scan(
        *a, chunk=32, impl=impl) ** 2), argnums=(0, 1, 2, 3, 4))(
            q, k, v, g, beta)
    assert all(bool(jnp.all(jnp.isfinite(x))) for x in grads)
    # a step whose decay kills the state altogether
    g = g.at[:, 5].set(-1e4)
    alone = gdn.gdn_scan(q, k, v, g, beta, chunk=32, impl=impl)[:, 5]
    qk = jnp.sum(jnp.repeat(q[:, 5] * k[:, 5], 2, axis=1), -1)
    onp.testing.assert_allclose(
        onp.asarray(alone),
        onp.asarray((beta[:, 5] * qk)[..., None] * v[:, 5]),
        rtol=1e-4, atol=1e-6)


def test_bf16_operands_keep_float32_decays_and_state():
    args = _inputs(dtype=jnp.bfloat16, seed=4)
    want = gdn.gdn_recurrence(*args)
    for impl in ("xla", "pallas"):
        got = gdn.gdn_scan(*args, chunk=32, impl=impl)
        assert got.dtype == jnp.float32
        assert float(jnp.max(jnp.abs(got - want))) <= 0.03 * float(
            jnp.max(jnp.abs(want)))


def test_a_sequence_that_is_no_whole_number_of_chunks_is_refused():
    args = _inputs(t=72)
    for impl in ("xla", "pallas"):
        with pytest.raises(ValueError, match="whole number of chunks"):
            gdn.gdn_scan(*args, chunk=16, impl=impl)
    with pytest.raises(ValueError, match="do not divide"):
        gdn.gdn_plan(1, 64, 4, 6, 16)
    with pytest.raises(ValueError, match="impl must be"):
        gdn.gdn_scan(*_inputs(), chunk=16, impl="triton")


def test_the_triangular_inverse_is_exact_for_a_nilpotent_matrix():
    c = 64
    a = jnp.tril(jax.random.normal(jax.random.PRNGKey(0), (c, c)) / 8, -1)
    eye = jnp.eye(c)
    inv = gdn._unit_lower_inverse(
        a, eye, lambda x, y: jnp.matmul(x, y, precision="highest"))
    onp.testing.assert_allclose(
        onp.asarray(jnp.matmul(inv, eye + a, precision="highest")),
        onp.asarray(eye), atol=2e-5)


def test_plan_and_event():
    from mxnet_tpu import observability as obs

    assert gdn.gdn_plan(1, 8192, 16, 32, 64) == (64, 128, 2, 2048)
    args = _inputs()
    tr = obs.enable_tracing()
    try:
        for _ in range(2):
            gdn.gdn_scan(*args, chunk=16, impl="xla")
        gdn.gdn_scan(*args, chunk=16, impl="pallas")
        events = tr.spans(name="gdn.plan")
    finally:
        obs.disable_tracing()
    assert [e.attrs["impl"] for e in events] == ["xla", "pallas"]
    assert events[0].attrs["chunk"] == 16 and events[0].attrs["chunks"] == 8
    assert events[0].attrs["heads_a_step"] == 2
    assert events[0].attrs["grid_steps"] == 2 * 2 * 8
    assert events[0].attrs["seq"] == 128 and events[0].attrs["value_heads"] == 4
