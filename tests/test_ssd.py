"""The Mamba-2 scan: the chunked ``jax.numpy`` form and the Pallas kernel
(interpret mode on the CPU) against the step-by-step recurrence, forward
and gradients."""
import functools

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
    assert ssd.bwd_step_vmem_bytes(chunk, plan.heads_a_step, p, n, itemsize,
                                   h) <= ssd.BWD_VMEM_LIMIT
    if plan.heads_a_step != h // g:                # a cut keeps whole lanes
        assert (plan.heads_a_step * p) % 128 == 0


def test_plan_refuses_what_cannot_fit(monkeypatch):
    monkeypatch.setattr(ssd, "VMEM_A_STEP", 1 << 16)
    with pytest.raises(ValueError, match="fits"):
        ssd.ssd_plan(1, 1024, 8, 1, 128)


@pytest.mark.parametrize("heads,fits", [(352, True), (512, False)])
def test_plan_holds_the_backward_to_its_vmem_limit(heads, fits):
    """``ssd_chunk_bwd`` keeps the state cotangent of ALL the call's heads
    in VMEM, which no cut into head blocks shrinks: at state 256, 352
    heads of 64 just fit the limit its call states (the chip's compiler
    takes that shape: ``tests/test_chip_compile.py``), and 512 are refused
    by the plan, in its own words, though the forward's step fits."""
    sizes = (128, 8, 64, 256, 2)
    assert ssd.step_vmem_bytes(*sizes) <= ssd.VMEM_A_STEP
    need = ssd.bwd_step_vmem_bytes(*sizes, heads)
    if fits:
        assert 0.95 * ssd.BWD_VMEM_LIMIT < need <= ssd.BWD_VMEM_LIMIT
        plan = ssd.ssd_plan(1, 512, heads, 1, 128, head_dim=64, state=256)
        assert plan.heads_a_step == 8
        return
    assert need > ssd.BWD_VMEM_LIMIT
    with pytest.raises(ValueError, match=f"ssd_chunk_bwd.*all {heads} heads"):
        ssd.ssd_plan(1, 512, heads, 1, 128, head_dim=64, state=256)


def test_backward_vmem_cuts_the_head_blocks_too(monkeypatch):
    """The backward's step decides the cut where it is the one that does
    not fit: the same fewest blocks rule, one plan for both kernels."""
    need8 = ssd.bwd_step_vmem_bytes(128, 8, 64, 128, 2, 64)
    need4 = ssd.bwd_step_vmem_bytes(128, 4, 64, 128, 2, 64)
    assert ssd.ssd_plan(1, 8192, 64, 1, 128).heads_a_step == 8
    monkeypatch.setattr(ssd, "BWD_VMEM_LIMIT", need8 - 1)
    assert need4 < need8
    assert ssd.ssd_plan(1, 8192, 64, 1, 128) == ssd.SsdPlan(128, 64, 4, 1024)


def test_backward_vmem_counts_squares_and_temporaries():
    """The count holds what the body keeps between its blocks: it grows
    with the (chunk, chunk) squares, and float32 operands count more."""
    at = lambda chunk, size: ssd.bwd_step_vmem_bytes(  # noqa: E731
        chunk, 8, 64, 128, size, 64)
    assert at(256, 2) - at(128, 2) > 7 * (256 * 256 - 128 * 128) * 4
    assert at(128, 4) - at(128, 2) >= 10 * 128 * 512 * 4
    # above what the chip's compiler took at the cells' shapes (6.5 and
    # 10.75 MiB; compiler, PR 41), and under the limit
    assert 6.5 * 2 ** 20 < at(128, 2) < at(256, 2) < ssd.BWD_VMEM_LIMIT
    assert at(256, 2) > 10.75 * 2 ** 20


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
    # which backward differentiates the call, and its grid step's bytes
    assert attrs["bwd"] == "pallas"
    assert attrs["bwd_vmem_bytes"] == ssd.bwd_step_vmem_bytes(
        16, 4, 8, 16, 4, 4)
    assert attrs["bwd_vmem_bytes"] > attrs["vmem_bytes"]


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
    assert attrs["bwd"] == "xla" and attrs["bwd_vmem_bytes"] == 0
    assert attrs["heads_a_step"] == 4 and attrs["blocks_a_group"] == 1
    onp.testing.assert_allclose(
        onp.asarray(got), onp.asarray(ssd.ssd_recurrence(*args)),
        rtol=1e-4, atol=2e-4)
    with pytest.raises(ValueError, match="whole number of chunks"):
        ssd.ssd_scan(*_inputs(t=24, g=1), chunk=16, impl="xla")


# ----------------------------------------------- the backward kernel (PR 41)

# the cells' scans scaled down: several B/C groups, a grid step a group
# (Nemotron's eight groups of eight heads); one group cut into head blocks
# that add up one dB and one dC in place (Granite's one group of 64); the
# same with the whole sequence one chunk
_CELLS = {
    "groups_of_one_block": dict(b=1, t=128, h=16, p=8, g=4, n=16, chunk=32),
    "one_group_in_blocks": dict(b=2, t=64, h=16, p=16, g=1, n=16, chunk=32),
    "one_chunk": dict(b=1, t=64, h=16, p=16, g=1, n=16, chunk=64),
}
_OPERANDS = ("x", "dt", "a", "b_mat", "c_mat")


def _cell_args(cell, dtype, decay):
    sizes = dict(_CELLS[cell])
    chunk = sizes.pop("chunk")
    x, dt, a, bm, cm = _inputs(seed=11, decay=decay, **sizes)
    dtype = jnp.dtype(dtype)
    return (x.astype(dtype), 0.2 * dt, a, bm.astype(dtype),
            cm.astype(dtype)), chunk


@functools.lru_cache(maxsize=None)
def _kernel_and_comparators(cell, dtype, decay=1.0):
    """All five gradients of the kernel path (interpreted) and of the two
    comparators, both in float32 on the operands as the kernel got them
    (once a case: the two comparisons of a case share them)."""
    args, chunk = _cell_args(cell, dtype, decay)
    w = jax.random.normal(jax.random.PRNGKey(12), args[0].shape)
    wide = tuple(v.astype(jnp.float32) for v in args)

    def grads(fn, at):
        return jax.grad(lambda *v: jnp.sum(fn(*v) * w),
                        argnums=(0, 1, 2, 3, 4))(*at)

    return {
        "kernel": grads(lambda *v: ssd.ssd_scan(
            *v, chunk=chunk, impl="pallas"), args),
        "chunked": grads(lambda *v: ssd.ssd_chunked(*v, chunk=chunk), wide),
        "recurrence": grads(ssd.ssd_recurrence, wide)}


@pytest.mark.parametrize("against", ["chunked", "recurrence"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("cell", list(_CELLS))
def test_backward_kernel_gradients(cell, dtype, against):
    """``ssd_chunk_bwd`` interpreted, the gradient of every operand,
    against ``jax.grad`` of the chunked form and of the definition:
    float32 operands to rounding, bf16 operands (one MXU pass a product,
    float32 sums and decays) to bf16's."""
    sizes = dict(_CELLS[cell])
    plan = ssd.ssd_plan(sizes["b"], sizes["t"], sizes["h"], sizes["g"],
                        sizes["chunk"], head_dim=sizes["p"],
                        state=sizes["n"], itemsize=jnp.dtype(dtype).itemsize)
    blocks = sizes["h"] // sizes["g"] // plan.heads_a_step
    assert blocks == (1 if cell == "groups_of_one_block" else 2)
    assert plan.chunks == (1 if cell == "one_chunk" else
                           sizes["t"] // sizes["chunk"])
    got = _kernel_and_comparators(cell, dtype)
    rel = 1e-4 if dtype == "float32" else 2e-2
    for name, k, r in zip(_OPERANDS, got["kernel"], got[against]):
        assert k.dtype == jnp.dtype(
            dtype if name in ("x", "b_mat", "c_mat") else "float32"), name
        gap = float(jnp.max(jnp.abs(k.astype(jnp.float32) - r)))
        assert gap <= rel * float(jnp.max(jnp.abs(r))) + 1e-5, (name, gap)


@pytest.mark.parametrize("cell", ["groups_of_one_block",
                                  "one_group_in_blocks"])
def test_backward_kernel_under_a_decay_that_underflows(cell):
    """Running sums far below where exp reaches 0 inside ONE chunk: every
    exponent the backward kernel takes is a difference <= 0, so what
    underflows is an honest zero and nothing is 0 * inf."""
    args, _ = _cell_args(cell, "float32", 1000.0)
    assert float(jnp.min(jnp.cumsum(args[1] * args[2], axis=1)[:, 31])) \
        < -1000.0
    got = _kernel_and_comparators(cell, "float32", 1000.0)
    for name, k, r in zip(_OPERANDS, got["kernel"], got["recurrence"]):
        assert bool(jnp.all(jnp.isfinite(k))), name
        assert float(jnp.max(jnp.abs(k - r))) <= 1e-3 * float(
            jnp.max(jnp.abs(r))) + 1e-4, name


def _recorded_calls(monkeypatch):
    """Every ``pl.pallas_call`` of ``ops/ssd.py`` from here on, by the
    kernel's name: the keyword arguments of each."""
    calls = {}
    real = ssd.pl.pallas_call

    def record(kernel, **kw):
        calls.setdefault(kw["name"], []).append(kw)
        return real(kernel, **kw)

    monkeypatch.setattr(ssd.pl, "pallas_call", record)
    return calls


def test_layers_of_one_shape_trace_each_kernel_once(monkeypatch):
    """Scans of one shape, each recomputed under ``jax.checkpoint`` as a
    model's blocks are, differentiated under one outer ``jax.jit``: a
    kernel's body is traced once a shape and not once a layer, and the
    lowered module holds the backward once, as a private function the
    layers call.  (The forward's body may be traced twice a process: JAX
    differentiates a checkpointed block under an abstract mesh, which is
    a tracing context of its own.  A shape no other test of this process
    uses: a second trace of a shape is what must not happen.)"""
    calls = _recorded_calls(monkeypatch)
    args = _inputs(b=1, t=48, h=4, p=8, g=2, n=16, seed=8)

    @jax.checkpoint
    def layer(x, dt, a, bm, cm):
        return x + ssd.ssd_scan(x, dt, a, bm, cm, chunk=16, impl="pallas")

    def step(layers):
        def loss(x, dt, a, bm, cm):
            for _ in range(layers):
                x = layer(x, dt, a, bm, cm)
            return jnp.sum(x)
        return jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4)))

    text = step(3).lower(*args).as_text()
    traced = {k: len(v) for k, v in calls.items()}
    assert traced["ssd_chunk_bwd"] == 1 and 1 <= traced["ssd_chunk_fwd"] <= 2
    assert text.count("func.func private @_ssd_pallas_bwd") == 1
    assert text.count("call @_ssd_pallas_bwd") == 3
    # more layers, and a second trace of the step: no body is traced again
    text = step(5).lower(*args).as_text()
    assert text.count("call @_ssd_pallas_bwd") == 5
    assert {k: len(v) for k, v in calls.items()} == traced


def test_backward_call_writes_no_output_of_the_forwards_dims(monkeypatch):
    """At the Granite cell's scan (the published chunk of 256: a shape no
    other test traces): the benchmark's readers find ``ssd_chunk_fwd`` as
    the custom call with an output of dims (batch, seq, heads x head
    size), so ``ssd_chunk_bwd`` writes none; a group's head blocks follow
    one another onto one dB and one dC, and time runs back."""
    calls = _recorded_calls(monkeypatch)
    sds = jax.ShapeDtypeStruct
    bf = jnp.bfloat16
    jax.eval_shape(
        jax.grad(lambda *a: ssd.ssd_scan(*a, chunk=256, impl="pallas",
                                         interpret=True).sum(),
                 argnums=(0, 1, 2, 3, 4)),
        sds((1, 8192, 64, 64), bf), sds((1, 8192, 64), jnp.float32),
        sds((64,), jnp.float32), sds((1, 8192, 1, 128), bf),
        sds((1, 8192, 1, 128), bf))
    assert set(calls) == {"ssd_chunk_fwd", "ssd_chunk_bwd"}
    fwd, kw = calls["ssd_chunk_fwd"][0], calls["ssd_chunk_bwd"][0]
    assert tuple(fwd["out_shape"][0].shape) == (1, 8192, 4096)
    hb = ssd.ssd_plan(1, 8192, 64, 1, 256).heads_a_step
    assert kw["grid"] == fwd["grid"] == (1, 32, 64 // hb)
    shapes = [tuple(o.shape) for o in kw["out_shape"]]
    # all of them float32, d(x dt) too: no cast below the XLA form's
    assert [o.dtype for o in kw["out_shape"]] == [jnp.float32] * 5
    assert shapes[:3] == [(1, 32, 256, 4096), (1, 32, 256, 128),
                          (1, 32, 256, 128)]
    assert (1, 8192, 4096) not in shapes and all(len(s) == 4 for s in shapes)
    dx, db = kw["out_specs"][0], kw["out_specs"][1]
    assert dx.index_map(0, 0, 3) == (0, 31, 0, 3)       # the last chunk first
    assert {db.index_map(0, 5, k) for k in range(64 // hb)} == {(0, 26, 0, 0)}
    # chunks and the head blocks of a group are walked in order: the state
    # cotangent is carried from chunk to chunk, dB and dC from block to block
    assert tuple(kw["compiler_params"].dimension_semantics)[1:] == (
        "arbitrary", "arbitrary")
