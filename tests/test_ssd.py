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


# ---------------------------------------------- one group cut into head blocks

def test_plan_cuts_a_group_that_does_not_fit():
    """One B/C group of 64 heads of 64 at state 128: a grid step owning the
    whole group would hold 14 MB (chunk 128) or 21 MB (chunk 256) of
    double-buffered blocks and walk 64 heads unrolled; the plan cuts the
    group into head blocks of at most ``HEADS_A_STEP`` heads that fit what
    it states, and the plan of a group that fits is what it was."""
    assert ssd.ssd_plan(1, 8192, 64, 8, 128) == ssd.SsdPlan(128, 64, 8, 512)
    assert ssd.ssd_plan(1, 8192, 64, 1, 128) == ssd.SsdPlan(128, 64, 8, 512)
    assert ssd.ssd_plan(1, 8192, 64, 1, 256) == ssd.SsdPlan(256, 32, 8, 256)
    assert ssd.step_vmem_bytes(128, 64, 64, 128, 2) > 14e6
    assert ssd.step_vmem_bytes(256, 64, 64, 128, 2) > 20e6
    assert ssd.step_vmem_bytes(256, 8, 64, 128, 2) < 4e6


def test_vmem_cuts_further_than_the_loop_length(monkeypatch):
    """Where eight heads' blocks do not fit, the plan halves on: the
    fewest blocks that fit, never one that does not."""
    need8 = ssd.step_vmem_bytes(256, 8, 64, 128, 2)
    need4 = ssd.step_vmem_bytes(256, 4, 64, 128, 2)
    monkeypatch.setattr(ssd, "VMEM_A_STEP", need8 - 1)
    plan = ssd.ssd_plan(1, 8192, 64, 1, 256)
    assert plan.heads_a_step == 4 and plan.grid_steps == 32 * 16
    assert need4 <= ssd.VMEM_A_STEP < need8


@pytest.mark.parametrize("b,t,h,g,chunk,p,n,itemsize", [
    (1, 8192, 64, 1, 128, 64, 128, 2), (1, 8192, 64, 1, 256, 64, 128, 4),
    (2, 4096, 128, 2, 256, 64, 128, 2), (1, 8192, 64, 8, 128, 64, 128, 2),
    (1, 1024, 32, 1, 512, 128, 256, 4), (4, 64, 4, 2, 16, 8, 16, 4)])
def test_no_plan_passes_the_stated_vmem(b, t, h, g, chunk, p, n, itemsize):
    try:
        plan = ssd.ssd_plan(b, t, h, g, chunk, head_dim=p, state=n,
                            itemsize=itemsize)
    except ValueError as e:
        assert "fits" in str(e)
        return
    assert (h // g) % plan.heads_a_step == 0
    assert plan.heads_a_step <= ssd.HEADS_A_STEP
    assert ssd.step_vmem_bytes(chunk, plan.heads_a_step, p, n,
                               itemsize) <= ssd.VMEM_A_STEP
    if plan.heads_a_step != h // g:                # a cut keeps whole lanes
        assert (plan.heads_a_step * p) % 128 == 0


def test_plan_refuses_what_cannot_fit(monkeypatch):
    monkeypatch.setattr(ssd, "VMEM_A_STEP", 1 << 16)
    with pytest.raises(ValueError, match="fits"):
        ssd.ssd_plan(1, 1024, 8, 1, 128)


def _grid_of(monkeypatch, *args, chunk):
    """The grid and block shapes ``ssd_chunk_fwd`` is launched with."""
    seen = {}
    real = ssd.pl.pallas_call

    def record(kernel, **kw):
        seen.update(grid=kw["grid"],
                    blocks=[s.block_shape for s in kw["in_specs"]],
                    out_blocks=[s.block_shape for s in kw["out_specs"]],
                    b_index=kw["in_specs"][1].index_map,
                    heads=kernel.keywords["heads"])
        return real(kernel, **kw)

    monkeypatch.setattr(ssd.pl, "pallas_call", record)
    jax.eval_shape(lambda *a: ssd.ssd_scan(*a, chunk=chunk, impl="pallas",
                                           interpret=True), *args)
    return seen


def test_a_group_that_fits_is_launched_as_it_was(monkeypatch):
    """h 64, g 8, chunk 128 (the Nemotron cell's scan): the grid and every
    block shape of the parent's kernel."""
    sds = jax.ShapeDtypeStruct
    bf = jnp.bfloat16
    seen = _grid_of(
        monkeypatch, sds((1, 8192, 64, 64), bf),
        sds((1, 8192, 64), jnp.float32), sds((64,), jnp.float32),
        sds((1, 8192, 8, 128), bf), sds((1, 8192, 8, 128), bf), chunk=128)
    assert seen["grid"] == (1, 64, 8) and seen["heads"] == 8
    assert seen["blocks"] == [(1, 128, 512), (1, 128, 128), (1, 128, 128),
                              (1, 1, 128, 8), (1, 1, 8, 128)]
    assert seen["out_blocks"] == [(1, 128, 512), (1, 1, 8, 128, 64)]
    assert seen["b_index"](0, 5, 7) == (0, 5, 7)


def test_one_group_is_launched_in_head_blocks(monkeypatch):
    sds = jax.ShapeDtypeStruct
    bf = jnp.bfloat16
    seen = _grid_of(
        monkeypatch, sds((1, 8192, 64, 64), bf),
        sds((1, 8192, 64), jnp.float32), sds((64,), jnp.float32),
        sds((1, 8192, 1, 128), bf), sds((1, 8192, 1, 128), bf), chunk=128)
    hb = ssd.ssd_plan(1, 8192, 64, 1, 128).heads_a_step
    assert seen["grid"] == (1, 64, 64 // hb) and seen["heads"] == hb
    assert seen["blocks"][0] == (1, 128, hb * 64)
    assert seen["out_blocks"] == [(1, 128, hb * 64), (1, 1, hb, 128, 64)]
    # every head block reads the one B and C
    assert {seen["b_index"](0, 3, k)
            for k in range(64 // hb)} == {(0, 3, 0)}


@pytest.mark.parametrize("g", [1, 2])
def test_head_blocks_match_recurrence(monkeypatch, g):
    """A group cut into blocks of 2 heads (the VMEM a step may take shrunk
    so that the tiny sizes cut as the real ones do), interpreted: forward
    and gradients against the recurrence."""
    monkeypatch.setattr(ssd, "VMEM_A_STEP", 130_000)
    args = _inputs(b=2, t=64, h=8, p=64, g=g, n=16, seed=4)
    plan = ssd.ssd_plan(2, 64, 8, g, 16, head_dim=64, state=16, itemsize=4)
    assert plan.heads_a_step == 2 and plan.grid_steps == 2 * 4 * 4
    want = ssd.ssd_recurrence(*args)
    got = ssd.ssd_scan(*args, chunk=16, impl="pallas")
    onp.testing.assert_allclose(onp.asarray(got), onp.asarray(want),
                                rtol=1e-4, atol=2e-4)
    w = jax.random.normal(jax.random.PRNGKey(9), args[0].shape)
    grad = lambda fn: jax.grad(lambda *a: jnp.sum(fn(*a) * w),  # noqa: E731
                               argnums=(0, 1, 2, 3, 4))(*args)
    for a, r in zip(grad(lambda *a: ssd.ssd_scan(*a, chunk=16,
                                                 impl="pallas")),
                    grad(ssd.ssd_recurrence)):
        assert float(jnp.max(jnp.abs(a - r))) <= 1e-4 * float(
            jnp.max(jnp.abs(r))) + 1e-5


def test_one_group_xla_form_matches_recurrence():
    args = _inputs(b=1, t=64, h=8, p=8, g=1, n=16, seed=5)
    want = ssd.ssd_recurrence(*args)
    onp.testing.assert_allclose(
        onp.asarray(ssd.ssd_scan(*args, chunk=16, impl="xla")),
        onp.asarray(want), rtol=1e-4, atol=2e-4)


def test_plan_event_says_how_a_group_was_cut():
    from mxnet_tpu import observability as obs

    args = _inputs(t=32, g=1, seed=6)
    tr = obs.enable_tracing()
    try:
        ssd.ssd_scan(*args, chunk=16, impl="pallas")
        attrs = tr.spans(name="ssd.plan")[-1].attrs
    finally:
        obs.disable_tracing()
    assert attrs["groups"] == 1 and attrs["blocks_a_group"] == 1
    assert attrs["heads_a_step"] == 4
    assert attrs["vmem_bytes"] == ssd.step_vmem_bytes(16, 4, 8, 16, 4)


def test_xla_form_has_no_vmem_to_fit(monkeypatch):
    """The refusal is the kernel's: where none is launched, a scan that
    no head block could fit still runs, and its event says 0 bytes."""
    from mxnet_tpu import observability as obs

    args = _inputs(t=32, g=1, seed=7)
    monkeypatch.setattr(ssd, "VMEM_A_STEP", 1 << 10)
    with pytest.raises(ValueError, match="fits"):
        ssd.ssd_scan(*args, chunk=16, impl="pallas")
    tr = obs.enable_tracing()
    try:
        got = ssd.ssd_scan(*args, chunk=16, impl="xla")
        attrs = tr.spans(name="ssd.plan")[-1].attrs
    finally:
        obs.disable_tracing()
    assert attrs["impl"] == "xla" and attrs["vmem_bytes"] == 0
    assert attrs["heads_a_step"] == 4 and attrs["blocks_a_group"] == 1
    onp.testing.assert_allclose(
        onp.asarray(got), onp.asarray(ssd.ssd_recurrence(*args)),
        rtol=1e-4, atol=2e-4)
    with pytest.raises(ValueError, match="whole number of chunks"):
        ssd.ssd_scan(*_inputs(t=24, g=1), chunk=16, impl="xla")
