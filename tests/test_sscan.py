"""The selective scan (``ops/sscan.py``): the Pallas kernels in interpret
mode against the recurrence by ``lax.scan``, forward and the gradient of
every operand; chunks that do and do not divide the sequence; bfloat16
and float32 operands; the plan and its event; the named scopes."""
import jax
import jax.numpy as jnp
import numpy as onp
import pytest

from mxnet_tpu.ops import sscan
from mxnet_tpu.ops.sscan import selective_scan, sscan_plan, sscan_recurrence

NAMES = ("x", "dt", "a", "b", "c")


def _inputs(b=2, t=64, c=256, n=16, dtype=jnp.float32, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    return (jax.random.normal(ks[0], (b, t, c)).astype(dtype),
            jax.nn.softplus(jax.random.normal(ks[1], (b, t, c)) - 2.0),
            -jnp.exp(0.5 * jax.random.normal(ks[2], (c, n))),
            jax.random.normal(ks[3], (b, t, n)).astype(dtype),
            jax.random.normal(ks[4], (b, t, n)).astype(dtype),
            jax.random.normal(ks[5], (b, t, c)))


# (batch, steps, channels, states, chunk): the chunk divides the sequence,
# does not (40 = 2.5 chunks of 16; 48 = 1.5 of 32), is longer than it, and
# channels that are whole tiles of 128 lanes or not
SHAPES = [(2, 64, 256, 16, 32), (1, 40, 128, 16, 16), (1, 48, 24, 4, 32),
          (1, 16, 128, 8, 64), (1, 64, 640, 16, 64)]


@pytest.mark.parametrize("b,t,c,n,chunk", SHAPES)
def test_forward_is_the_recurrence(b, t, c, n, chunk):
    *args, _w = _inputs(b, t, c, n)
    got = selective_scan(*args, chunk=chunk, impl="pallas")
    assert got.shape == (b, t, c) and got.dtype == jnp.float32
    onp.testing.assert_allclose(got, sscan_recurrence(*args), rtol=2e-5,
                                atol=2e-5)


@pytest.mark.parametrize("b,t,c,n,chunk", SHAPES[:4])
@pytest.mark.parametrize("operand", range(5), ids=NAMES)
def test_gradient_of_every_operand(b, t, c, n, chunk, operand):
    *args, w = _inputs(b, t, c, n)

    def loss(f):
        return lambda *o: jnp.sum(f(*o) * w)

    got = jax.grad(loss(lambda *o: selective_scan(
        *o, chunk=chunk, impl="pallas")), argnums=operand)(*args)
    want = jax.grad(loss(sscan_recurrence), argnums=operand)(*args)
    assert got.shape == want.shape and got.dtype == args[operand].dtype
    scale = float(jnp.abs(want).max())
    onp.testing.assert_allclose(got, want, rtol=1e-4, atol=2e-5 * scale)


@pytest.mark.parametrize("chunk", [16, 32])
def test_bfloat16_operands_keep_a_float32_state(chunk):
    """x, B and C in bfloat16 (dt stays float32): the kernels upcast once
    and agree with the recurrence on the same rounded operands to float32
    accuracy; gradients come back in the operands' own types."""
    *args, w = _inputs(1, 48, 128, 16, jnp.bfloat16)
    got = selective_scan(*args, chunk=chunk, impl="pallas")
    onp.testing.assert_allclose(got, sscan_recurrence(*args), rtol=2e-5,
                                atol=2e-5)
    grads = jax.grad(lambda *o: jnp.sum(selective_scan(
        *o, chunk=chunk, impl="pallas") * w), argnums=(0, 1, 2, 3, 4))(*args)
    want = jax.grad(lambda *o: jnp.sum(sscan_recurrence(*o) * w),
                    argnums=(0, 1, 2, 3, 4))(*args)
    for g, r, a in zip(grads, want, args):
        assert g.dtype == a.dtype
        onp.testing.assert_allclose(
            g.astype(jnp.float32), r.astype(jnp.float32), rtol=2e-2,
            atol=2e-2 * float(jnp.abs(r.astype(jnp.float32)).max()))


def test_padding_steps_leave_the_state_alone():
    """A sequence cut short gives the first steps of the longer one: the
    steps of dt = 0 that fill the last chunk change nothing before them."""
    *args, _w = _inputs(1, 64, 128, 16)
    whole = selective_scan(*args, chunk=32, impl="pallas")
    x, dt, a, bm, cm = args
    short = selective_scan(x[:, :40], dt[:, :40], a, bm[:, :40], cm[:, :40],
                           chunk=32, impl="pallas")
    onp.testing.assert_allclose(short, whole[:, :40], rtol=1e-6, atol=1e-6)


def test_auto_is_the_recurrence_off_the_tpu():
    *args, _w = _inputs(1, 32, 128, 8)
    onp.testing.assert_array_equal(selective_scan(*args),
                                   sscan_recurrence(*args))
    with pytest.raises(ValueError):
        selective_scan(*args, impl="cuda")


def test_plan():
    # the benchmark's cell: 8,192 steps of 5,120 channels, 16 states
    plan = sscan_plan(1, 8192, 5120, 16)
    assert plan[:3] == (128, 512, 10 * 64)
    assert plan.vmem_bytes == sscan.step_vmem_bytes(128, 512, 16, 2)
    assert 4 << 20 < plan.vmem_bytes < 16 << 20
    assert sscan_plan(2, 100, 384, 16, chunk=32)[:3] == (32, 128, 2 * 3 * 4)
    assert sscan_plan(1, 64, 24, 4).channels == 24        # no tile divides
    with pytest.raises(ValueError):
        sscan_plan(1, 64, 128, 16, chunk=24)


def test_plan_event_and_scopes():
    """One ``sscan.plan`` event per distinct plan with a ``Tracer`` on,
    none while it is off; the lowered program's op names carry
    ``sscan_fwd`` and ``sscan_bwd``."""
    from mxnet_tpu import observability as obs

    *args, w = _inputs(1, 32, 128, 8)
    obs.disable_tracing()
    selective_scan(*args, chunk=16, impl="pallas")
    tr = obs.enable_tracing()
    try:
        assert not tr.spans(name="sscan.plan")
        selective_scan(*args, chunk=16, impl="pallas")
        selective_scan(*args, chunk=16, impl="pallas")
        selective_scan(*args, chunk=16, impl="xla")
        events = tr.spans(name="sscan.plan")
    finally:
        obs.disable_tracing()
    assert [e.attrs["impl"] for e in events] == ["pallas", "xla"]
    at = events[0].attrs
    assert (at["chunk"], at["channels"], at["grid_steps"]) == (16, 128, 2)
    assert at["vmem_bytes"] == sscan.step_vmem_bytes(16, 128, 8, 4)
    assert at["dtype"] == "float32" and at["state"] == 8
    assert events[1].attrs["vmem_bytes"] == 0
    text = jax.jit(jax.grad(lambda *o: jnp.sum(selective_scan(
        *o, chunk=16, impl="pallas") * w))).lower(*args).as_text(
            debug_info=True)
    assert "sscan_fwd" in text and "sscan_bwd" in text
