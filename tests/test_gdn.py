"""The gated delta rule: the chunked ``jax.numpy`` form and the Pallas
kernel (interpret mode on the CPU) against the step-by-step recurrence,
forward and gradients; the backward the rule states for itself against
JAX's own derivative of the chunked arithmetic, which is written out HERE
(the parent commit's ``gdn_chunked``) so that nothing under test is its own
reference."""
import functools

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


# beside the first four (T 128): one, two and three value heads a key head
# at chunks of 16 and 64; 1, 3, 5 and 22 chunks; 1 to 6 key heads, so a grid
# step (at most gdn.KEY_HEADS_A_STEP = 4 key heads, a divisor of the call's)
# takes all of them, or three of six, or one of five
@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("chunk,heads,t", [
    (8, (2, 4), 128), (16, (2, 2), 128), (64, (2, 4), 128), (32, (1, 3), 128),
    (16, (3, 3), 16), (16, (6, 12), 48), (16, (1, 3), 80), (64, (5, 5), 64),
    (64, (4, 8), 192), (64, (1, 3), 320), (16, (2, 4), 352)])
def test_chunked_rule_matches_recurrence(impl, chunk, heads, t):
    args = _inputs(hk=heads[0], hv=heads[1], t=t)
    want = gdn.gdn_recurrence(*args)
    got = gdn.gdn_scan(*args, chunk=chunk, impl=impl)
    assert got.dtype == jnp.float32 and got.shape == want.shape
    onp.testing.assert_allclose(onp.asarray(got), onp.asarray(want),
                                rtol=1e-4, atol=2e-5)


@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("hk,hv", [(2, 4), (8, 8)])
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


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("ratio", [1, 2, 3])
@pytest.mark.parametrize("chunk,chunks,hk", [(16, 5, 6), (64, 3, 4),
                                             (64, 10, 5)])
def test_kernel_and_xla_form_agree_to_rounding(chunk, chunks, hk, ratio,
                                               dtype):
    """The kernel computes the chunked form's own arithmetic: interpreted,
    it differs from it by rounding alone, in what it returns and in what it
    keeps for the backward (``T`` and each chunk's first state); and the
    call that keeps them and the call that writes o alone give the same
    o."""
    args = _inputs(seed=3 + ratio, b=1, t=chunk * chunks, hk=hk,
                   hv=hk * ratio, dtype=jnp.dtype(dtype))
    plan = gdn._plan_of(args[0], args[2], chunk)
    assert plan.side_by_side == (ratio if ratio * chunk <= 128 else 1)
    assert plan.key_heads_a_step == {6: 3, 4: 4, 5: 1}[hk]
    want, (inv, states) = gdn.gdn_chunked(*args, chunk=chunk)
    got, kept = gdn._gdn_pallas(*args, chunk, True, True)
    alone, nothing = gdn._gdn_pallas(*args, chunk, True, False)
    assert nothing == () and bool(jnp.all(got == alone))
    assert [(x.shape, x.dtype) for x in kept] == [
        (x.shape, x.dtype) for x in gdn._kept_shapes(
            1, chunks, hk, hk * ratio, chunk, 16, 8)]
    # float32: rounding; bf16: a state that differs in its last float32
    # bit is cast to another bf16 now and then, one operand's last bit
    eps = 1e-6 if dtype == "float32" else 1e-2
    assert float(jnp.max(jnp.abs(kept[0] - inv))) <= 1e-6
    assert _worst(kept[1], states) <= eps
    assert _worst(got, want) <= eps
    assert float(jnp.max(jnp.abs(
        gdn.gdn_scan(*args, chunk=chunk, impl="pallas") - alone))) == 0.0


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


@pytest.mark.parametrize("chunk,heads", [(32, (2, 4)), (16, (3, 3)),
                                         (64, (2, 4)), (64, (1, 3))])
def test_bf16_operands_keep_float32_decays_and_state(chunk, heads):
    args = _inputs(dtype=jnp.bfloat16, seed=4, hk=heads[0], hv=heads[1])
    want = gdn.gdn_recurrence(*args)
    for impl in ("xla", "pallas"):
        got = gdn.gdn_scan(*args, chunk=chunk, impl=impl)
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

    # the cell's call: four key heads' chunk a grid step, the two value
    # heads of each side by side on 128 lanes: 512 grid steps where one key
    # head a step made 2,048
    cell = gdn.gdn_plan(1, 8192, 16, 32, 64)
    assert cell[:4] == (64, 128, 8, 512) and cell.grid_steps < 2048
    assert cell.key_heads_a_step == gdn.KEY_HEADS_A_STEP == 4
    assert (cell.side_by_side, cell.chains_a_step) == (2, 4)
    assert cell.vmem_bytes == gdn.step_vmem_bytes(
        64, 4, 2, 2, 128, 128, 2) == 2949120 <= gdn.VMEM_A_STEP
    # three value heads of 64 steps do not share a row of 128 lanes: each
    # is a chain of its own; of 6 key heads a step takes 3, of 5 one
    assert gdn.gdn_plan(1, 320, 3, 9, 64)[4:7] == (3, 1, 9)
    assert gdn.gdn_plan(1, 64 * 22, 6, 12, 64)[3:7] == (2 * 22, 3, 2, 3)
    assert gdn.gdn_plan(1, 64, 5, 5, 64)[3:7] == (5, 1, 1, 1)
    # states of 256 x 256 a value head, four to a key head: two key heads'
    # blocks fit; of 512 x 512: not one key head's, and one is what is
    # planned (the step every call had before PR 33)
    assert gdn.gdn_plan(1, 8192, 16, 64, 64, key_dim=256,
                        value_dim=256).key_heads_a_step == 2
    wide = gdn.gdn_plan(1, 8192, 16, 64, 64, key_dim=512, value_dim=512)
    assert wide.key_heads_a_step == 1 and wide.vmem_bytes > gdn.VMEM_A_STEP
    assert wide.grid_steps == 16 * 128 and wide.heads_a_step == 4
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
    assert events[0].attrs["heads_a_step"] == 4
    assert events[0].attrs["key_heads_a_step"] == 2
    assert events[0].attrs["side_by_side"] == 2
    assert events[0].attrs["chains_a_step"] == 2
    assert events[0].attrs["grid_steps"] == 2 * 1 * 8
    assert events[0].attrs["vmem_bytes"] == gdn.step_vmem_bytes(
        16, 2, 2, 2, 16, 8, 4)
    assert events[0].attrs["walk_handover_bytes"] == 0
    assert events[0].attrs["seq"] == 128 and events[0].attrs["value_heads"] == 4
    # the backward: written out, every key head at once at this size, and
    # what one call's forward keeps for it beside its inputs: T (float32,
    # (c, c) a chunk and value head) and a float32 state a chunk
    assert events[0].attrs["backward"] == "explicit"
    assert events[0].attrs["bwd_key_heads"] == 2
    assert events[0].attrs["residual_bytes"] == 4 * 2 * 8 * 4 * (
        16 * 16 + 16 * 8)
    assert all(e.attrs["residual_bytes"] == events[0].attrs["residual_bytes"]
               for e in events)


# ------------------------------------------------- the backward, written out

def plain_chunked(q, k, v, g, beta, *, chunk):
    """The chunked arithmetic as plain ``jax.numpy`` (``gdn_chunked`` as
    PR 30 had it, the inverse by its doublings): JAX differentiates
    THROUGH it, which is what the rule's own backward has to equal."""
    b, t, hk, dk = q.shape
    hv, dv = v.shape[2], v.shape[3]
    r, c, nc = hv // hk, chunk, t // chunk
    f32, cd = jnp.float32, v.dtype
    hi = jax.lax.Precision.HIGHEST
    pr = hi if cd == f32 else jax.lax.Precision.DEFAULT
    gs = jnp.cumsum(g.astype(f32).reshape(b, nc, c, hv), axis=2)
    gs = gs.transpose(0, 1, 3, 2)
    bt = beta.astype(f32).reshape(b, nc, c, hv).transpose(0, 1, 3, 2)
    qr = q.astype(cd).reshape(b, nc, c, hk, dk)
    kr = k.astype(cd).reshape(b, nc, c, hk, dk)
    mm = functools.partial(jnp.einsum, precision=pr,
                           preferred_element_type=f32)
    kk, qk = (jnp.repeat(mm("bcihd,bcjhd->bchij", x, kr), r, axis=2)
              for x in (kr, qr))
    rows = jnp.arange(c)
    decay = jnp.exp(jnp.where(rows[:, None] >= rows[None, :],
                              gs[..., :, None] - gs[..., None, :], -1e30))
    a = jnp.where(rows[:, None] > rows[None, :],
                  bt[..., :, None] * kk * decay, 0.0)
    inv, power, n = jnp.eye(c, dtype=f32) - a, a, 2
    while n < c:
        power = jnp.matmul(power, power, precision=hi)
        inv = inv + jnp.matmul(inv, power, precision=hi)
        n *= 2
    inv = inv.astype(cd)
    kv = jnp.repeat(kr.astype(f32), r, axis=3).transpose(0, 1, 3, 2, 4)
    qv = jnp.repeat(qr.astype(f32), r, axis=3).transpose(0, 1, 3, 2, 4)
    vv = v.astype(f32).reshape(b, nc, c, hv, dv).transpose(0, 1, 3, 2, 4)
    eg = jnp.exp(gs)[..., None]
    u0 = mm("bchij,bchjd->bchid", inv, (vv * bt[..., None]).astype(cd))
    w = mm("bchij,bchjd->bchid", inv,
           (kv * (bt[..., None] * eg)).astype(cd)).astype(cd)
    to_end = gs[..., -1:]
    xs = (u0, w, (qv * eg).astype(cd), (qk * decay).astype(cd),
          (kv * jnp.exp(to_end - gs)[..., None]).astype(cd),
          jnp.exp(to_end)[..., None])

    def step(s, xs):
        u0c, wc, qgc, ac, kc, dc = xs
        sc = s.astype(cd)
        u = (u0c - mm("bhik,bhkv->bhiv", wc, sc)).astype(cd)
        o = mm("bhik,bhkv->bhiv", qgc, sc) + mm("bhij,bhjv->bhiv", ac, u)
        return dc * s + mm("bhik,bhiv->bhkv", kc, u), o

    _, o = jax.lax.scan(step, jnp.zeros((b, hv, dk, dv), f32),
                        tuple(x.swapaxes(0, 1) for x in xs))
    return o.transpose(1, 0, 3, 2, 4).reshape(b, t, hv, dv)


def _worst(got, want):
    """Largest gap over the largest wanted entry."""
    got, want = (onp.asarray(x, onp.float32) for x in (got, want))
    return float(onp.max(onp.abs(got - want)) / onp.max(onp.abs(want)))


ALL = (0, 1, 2, 3, 4)


def _grads(fn, args, w, jit=False):
    grad = jax.grad(lambda *a: jnp.sum(fn(*a) * w), argnums=ALL)
    return (jax.jit(grad) if jit else grad)(*args)


@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("ratio", [1, 2, 3])
@pytest.mark.parametrize("chunk", [16, 64])
def test_the_written_out_backward_is_jaxs_derivative_of_the_chunked_form(
        chunk, ratio, dtype, impl):
    args = _inputs(seed=5 + chunk + ratio, t=128, hk=2, hv=2 * ratio,
                   decay=0.3, dtype=jnp.dtype(dtype))
    w = jax.random.normal(jax.random.PRNGKey(7), args[2].shape)
    # as one compiled program, which is how every model runs it
    got = _grads(lambda *a: gdn.gdn_scan(*a, chunk=chunk, impl=impl), args, w,
                 jit=True)
    want = _grads(functools.partial(plain_chunked, chunk=chunk), args, w)
    for gt, wt, src in zip(got, want, args):
        assert gt.shape == src.shape and gt.dtype == src.dtype
        assert bool(jnp.all(jnp.isfinite(gt.astype(jnp.float32))))
    if dtype == "float32":
        assert max(_worst(gt, wt) for gt, wt in zip(got, want)) < 1e-4
        return
    # bf16 operands: both round every product's operands, each in its own
    # places, so both are held against float32 arithmetic on the same
    # (rounded) inputs, and the written-out one may be no coarser
    exact = _grads(functools.partial(plain_chunked, chunk=chunk),
                   [a.astype(jnp.float32) for a in args], w)
    for gt, wt, ex in zip(got, want, exact):
        assert _worst(gt, ex) <= 1.5 * _worst(wt, ex) + 2e-3
        assert _worst(gt, ex) < 4e-2


@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("chunk", [16, 64])
def test_backward_through_decays_that_underflow(impl, chunk):
    """Running sums near -7,000 and one step whose decay kills the state:
    every gradient is finite and equals JAX's derivative of the chunked
    form, and nothing before the killing step receives a gradient from
    what comes after it (the forward there is zero)."""
    q, k, v, g, beta = _inputs(decay=80.0, seed=2)
    kill = 70
    g = g.at[:, kill].set(-1e4)
    args = (q, k, v, g, beta)
    w = jax.random.normal(jax.random.PRNGKey(8), v.shape)
    w = w.at[:, :kill].set(0.0)                 # only what comes after
    got = _grads(lambda *a: gdn.gdn_scan(*a, chunk=chunk, impl=impl), args, w)
    want = _grads(functools.partial(plain_chunked, chunk=chunk), args, w)
    for i, (gt, wt) in enumerate(zip(got, want)):
        assert bool(jnp.all(jnp.isfinite(gt)))
        assert float(jnp.max(jnp.abs(gt - wt))) <= 1e-4 * float(
            jnp.max(jnp.abs(wt))) + 1e-6
        # a decay's gradient is a running sum over its chunk, the steps
        # after the killing one included: what cancels there leaves a
        # rounding's worth, in JAX's derivative as in this one
        assert float(jnp.max(jnp.abs(gt[:, :kill]))) <= (
            1e-5 * float(jnp.max(jnp.abs(gt))) if i == 3 else 0.0)
    assert float(jnp.max(jnp.abs(got[2][:, kill:]))) > 0.0


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_key_heads_differentiated_in_groups_give_the_same_gradients(
        impl, monkeypatch):
    """At the cell's size one key head's working set is what the chip's
    fast memory holds, so the key heads are taken one at a time; here a
    budget of nothing forces that on a small call."""
    args = _inputs(seed=6, t=64, hk=4, hv=8, decay=0.3)
    w = jax.random.normal(jax.random.PRNGKey(3), args[2].shape)
    fn = lambda *a: gdn.gdn_scan(*a, chunk=16, impl=impl)  # noqa: E731
    assert gdn._bwd_key_heads(2, 64, 4, 8, 16, 16, 8) == 4
    assert gdn._bwd_key_heads(1, 8192, 16, 32, 64, 128, 128) == 1
    assert gdn._bwd_key_heads(1, 2048, 6, 6, 64, 128, 128) == 6
    assert gdn._bwd_key_heads(1, 4096, 6, 6, 64, 128, 128) == 3
    whole = _grads(fn, args, w)
    monkeypatch.setattr(gdn, "_BWD_GROUP_BYTES", 0)
    assert gdn._bwd_key_heads(2, 64, 4, 8, 16, 16, 8) == 1
    for gt, wt in zip(_grads(fn, args, w, jit=True), whole):
        assert _worst(gt, wt) < 1e-6


def _dots(jaxpr, found):
    """Every ``dot_general`` of a jaxpr and of the jaxprs inside it."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            found.append(eqn)
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else [value]:
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    _dots(inner, found)
    return found


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_the_inverse_is_differentiated_by_its_identity(impl):
    """One call's forward and backward, from the jaxpr: the float32
    `highest` products against a (c, c) right operand are the forward's ten
    doublings and the identity's two, where JAX's derivative of the
    doublings ran thirty; and what the forward keeps holds T and a state a
    chunk, no power of A."""
    c, ratio = 64, 2

    def inputs(ratio, hk=2):
        return _inputs(t=128, hk=hk, hv=hk * ratio, dk=32, dv=16,
                       dtype=jnp.bfloat16)

    # one key head of one value head: the kernel's body holds a head's
    # products once, as the batched XLA form does
    jaxpr = jax.make_jaxpr(jax.value_and_grad(
        lambda *a: jnp.sum(gdn.gdn_scan(*a, chunk=c, impl=impl)),
        argnums=ALL))(*inputs(1, hk=1))

    def square_highest(eqns):
        """(c, c) by (c, c) products, counted by the rows of the left
        operand: the kernel gives a level's two products, which share
        their right operand, as one of 2 c rows."""
        def is_highest(p):
            return p is not None and all(
                x == jax.lax.Precision.HIGHEST
                for x in (p if isinstance(p, tuple) else (p,)))
        found = [[v.aval.shape[-2:] for v in e.invars] for e in eqns
                 if is_highest(e.params["precision"])]
        assert all(right == (c, c) and left[1] == c for left, right in found)
        return sum(left[0] // c for left, _right in found)

    dots = _dots(jaxpr.jaxpr, [])
    assert square_highest(dots) == 12
    if impl == "pallas":
        assert sum(e.params["precision"] is not None and all(
            x == jax.lax.Precision.HIGHEST for x in e.params["precision"])
            for e in dots) == 6 + 2
    plain = jax.make_jaxpr(jax.value_and_grad(
        lambda *a: jnp.sum(plain_chunked(*a, chunk=c)),
        argnums=ALL))(*inputs(1))
    assert square_highest(_dots(plain.jaxpr, [])) == 30
    args = inputs(ratio)
    _o, kept = jax.eval_shape(
        lambda *a: gdn._gdn_fwd(*a, c, impl, True), *args)
    *inputs, (inv, states) = kept
    assert [x.shape for x in inputs] == [a.shape for a in args]
    b, t, hk, dk = args[0].shape
    hv, dv = args[2].shape[2:]
    assert inv.shape == (b, t // c, hk, c, ratio * c)
    assert states.shape == (b, t // c, hv, dk, dv)
    assert inv.dtype == states.dtype == jnp.float32
