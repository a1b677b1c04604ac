"""The dropless expert layer (one chip's share of an expert-parallel
deployment) against a plain loop over experts: routing as published, no
token dropped under any skew, and the shares of a deployment adding up to
the uncut layer."""
import functools
import re

import jax
import jax.numpy as jnp
import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu.models import moe

N, D, F, E, K = 96, 32, 24, 16, 3


def _weights(seed=0, skew=0.0, n=N):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(ks[0], (n, D))
    wr = 0.3 * jax.random.normal(ks[1], (E, D))
    bias = 0.05 * jax.random.normal(ks[2], (E,))
    if skew:
        # every token's best experts are 0, 1, 2: a capacity path with
        # N * K / E * 1.25 slots an expert drops most of them
        bias = bias.at[:K].add(skew)
    w_up = 0.2 * jax.random.normal(ks[3], (E, D, F))
    w_down = 0.2 * jax.random.normal(ks[4], (E, F, D))
    return x, wr, bias, w_up, w_down


def _loop(x, wr, bias, w_up, w_down, first=0, held=E, scaling=2.5, k=K):
    """The published routing and a plain loop over the experts held."""
    s = jax.nn.sigmoid(jnp.einsum("nd,ed->ne", x, wr,
                                  precision=jax.lax.Precision.HIGHEST))
    _, idx = jax.lax.top_k(s + bias, k)
    w = jnp.take_along_axis(s, idx, axis=1)
    w = w / jnp.sum(w, axis=1, keepdims=True) * scaling
    y = jnp.zeros_like(x)
    hi = jax.lax.Precision.HIGHEST
    for e in range(first, first + held):
        we = jnp.sum(jnp.where(idx == e, w, 0.0), axis=1)
        h = jnp.square(jax.nn.relu(jnp.dot(x, w_up[e], precision=hi)))
        y = y + we[:, None] * jnp.dot(h, w_down[e], precision=hi)
    return y, idx


CHUNK = 64   # of the 288 buffer rows, so a walk takes 0 to 5 turns
# what the chip holds, as (first, experts held): every pair held, a share,
# and NO pair held (the bias keeps every token off the one expert held)
LOADS = {"every_pair": (0, E), "share": (4, 4), "no_pair": (E - 1, 1)}


def _weights_at(load, **kw):
    """``_weights`` and the (first, held) of ``LOADS[load]``."""
    x, wr, bias, w_up, w_down = _weights(**kw)
    if load == "no_pair":
        bias = bias.at[E - 1].add(-50.0)
    return (x, wr, bias, w_up, w_down), LOADS[load]


@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("skew", [0.0, 5.0])
@pytest.mark.parametrize("load", ["every_pair", "no_pair"])
def test_dropless_routing_equals_the_loop(impl, skew, load, monkeypatch):
    """No token dropped at any load, by value: the gathers into sorted
    order walk the routed rows in several turns (chunks of 64 of the 288
    rows) where every pair is held, and in none where no pair is."""
    monkeypatch.setattr(moe, "_GATHER_CHUNK_ROWS", CHUNK)
    (x, wr, bias, w_up, w_down), (first, held) = _weights_at(load, skew=skew)
    # one program a side (jitted): an eager layer is a hundred small ones
    got, chosen, sizes = jax.jit(functools.partial(
        moe.dropless_ffn, top_k=K, first=first, scaling=2.5, impl=impl))(
        x, wr, bias, w_up[first:first + held], w_down[first:first + held])
    want, idx = jax.jit(functools.partial(_loop, first=first, held=held))(
        x, wr, bias, w_up, w_down)
    assert onp.array_equal(onp.sort(onp.asarray(chosen), 1),
                           onp.sort(onp.asarray(idx), 1))
    # every pair on a held expert computed
    assert int(jnp.sum(sizes)) == {"every_pair": N * K, "no_pair": 0}[load]
    onp.testing.assert_allclose(onp.asarray(got), onp.asarray(want),
                                rtol=1e-4, atol=1e-4)
    if skew and load == "every_pair":
        assert int(jnp.max(sizes)) == N            # every token on expert 0..2


def test_the_capacity_path_drops_what_the_dropless_path_keeps():
    """Under the skew the GShard path (capacity 1.25 N K / E) drops most
    token-expert pairs; the dropless buffer holds the worst case."""
    x, wr, bias, w_up, w_down = _weights(skew=5.0)
    layer = moe.MoELayer(D, F, E, top_k=K)
    assert layer.capacity(N) < N                   # it WOULD drop here
    _, _, sizes = moe.dropless_ffn(x, wr, bias, w_up, w_down, top_k=K,
                                   first=0, impl="xla")
    assert int(jnp.max(sizes)) > layer.capacity(N)
    assert int(jnp.sum(sizes)) == N * K


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_every_token_on_one_held_expert(impl):
    """Experts 4..7 held, and the bias sends every token to expert 5
    (with 0 and 1, held elsewhere): 96 rows on one expert, none dropped."""
    x, wr, bias, w_up, w_down = _weights(seed=1)
    bias = bias.at[jnp.asarray([0, 1, 5])].add(5.0)
    got, _, sizes = moe.dropless_ffn(
        x, wr, bias, w_up[4:8], w_down[4:8], top_k=K, first=4, scaling=2.5,
        impl=impl)
    want, _ = _loop(x, wr, bias, w_up, w_down, first=4, held=4)
    assert onp.asarray(sizes).tolist() == [0, N, 0, 0]
    onp.testing.assert_allclose(onp.asarray(got), onp.asarray(want),
                                rtol=1e-4, atol=1e-4)


def test_the_shares_add_up_to_the_uncut_layer():
    """16 experts in 4 shares of 4: the four partial results, the shared
    expert counted once, equal the uncut layer."""
    x, wr, bias, w_up, w_down = _weights(seed=2)
    ks = jax.random.split(jax.random.PRNGKey(7), 2)
    s_up = 0.2 * jax.random.normal(ks[0], (2 * F, D))
    s_down = 0.2 * jax.random.normal(ks[1], (D, 2 * F))
    shared = moe.relu2_mlp(x, s_up, s_down)
    whole, _ = _loop(x, wr, bias, w_up, w_down)
    whole = whole + shared
    parts = [moe.dropless_ffn(x, wr, bias, w_up[f:f + 4], w_down[f:f + 4],
                              top_k=K, first=f, scaling=2.5, impl="xla")[0]
             for f in (0, 4, 8, 12)]
    onp.testing.assert_allclose(onp.asarray(sum(parts) + shared),
                                onp.asarray(whole), rtol=1e-4, atol=1e-4)
    # and each share left out exactly what the others hold
    for f, part in zip((0, 4, 8, 12), parts):
        want, _ = _loop(x, wr, bias, w_up, w_down, first=f, held=4)
        onp.testing.assert_allclose(onp.asarray(part), onp.asarray(want),
                                    rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("load", ["share", "every_pair", "no_pair"])
def test_dropless_gradients_match_the_loop(impl, load, monkeypatch):
    """No token dropped at any load, by gradient (chunks of 64 rows: the
    backward's walk takes several turns, or none)."""
    monkeypatch.setattr(moe, "_GATHER_CHUNK_ROWS", CHUNK)
    (x, wr, bias, w_up, w_down), (first, held) = _weights_at(
        load, seed=3, skew=1.0)
    g = jax.random.normal(jax.random.PRNGKey(11), (N, D))
    here = slice(first, first + held)

    def ours(x, wr, w_up, w_down):
        return jnp.sum(moe.dropless_ffn(
            x, wr, bias, w_up[here], w_down[here], top_k=K, first=first,
            scaling=2.5, impl=impl)[0] * g)

    def loop(x, wr, w_up, w_down):
        return jnp.sum(_loop(x, wr, bias, w_up, w_down, first, held)[0] * g)

    got = jax.jit(jax.grad(ours, argnums=(0, 1, 2, 3)))(x, wr, w_up, w_down)
    want = jax.jit(jax.grad(loop, argnums=(0, 1, 2, 3)))(x, wr, w_up, w_down)
    for a, b in zip(got, want):
        assert onp.isfinite(onp.asarray(a)).all()
        onp.testing.assert_allclose(onp.asarray(a), onp.asarray(b),
                                    rtol=1e-3, atol=2e-4)
    if load == "share":
        assert float(jnp.max(jnp.abs(got[2][:4]))) == 0.0   # not held
    if load == "no_pair":
        assert all(float(jnp.max(jnp.abs(a))) == 0.0 for a in got)


@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("n,k", [(50, 5), (37, 9)])
def test_sizes_that_fill_no_tile_agree_with_the_loop(impl, n, k):
    """A token's pairs are numbered slot by slot (pair ``slot * N +
    token``), so ``N`` rows stand between a token's pairs.  Nothing in
    that needs ``N`` to be a multiple of 16 or ``top_k`` of 8 (on the
    chip such sizes are only slower): values and gradients against the
    loop, experts 4..9 held."""
    x, wr, bias, w_up, w_down = _weights(seed=4, skew=1.0, n=n)
    g = jax.random.normal(jax.random.PRNGKey(12), (n, D))

    def ours(x, wr, w_up, w_down):
        y, chosen, sizes = moe.dropless_ffn(
            x, wr, bias, w_up[4:10], w_down[4:10], top_k=k, first=4,
            scaling=2.5, impl=impl)
        return jnp.sum(y * g), (chosen, sizes)

    def loop(x, wr, w_up, w_down):
        y, idx = _loop(x, wr, bias, w_up, w_down, 4, 6, k=k)
        return jnp.sum(y * g), idx

    (got, (chosen, sizes)), d_got = jax.jit(jax.value_and_grad(
        ours, argnums=(0, 1, 2, 3), has_aux=True))(x, wr, w_up, w_down)
    (want, idx), d_want = jax.jit(jax.value_and_grad(
        loop, argnums=(0, 1, 2, 3), has_aux=True))(x, wr, w_up, w_down)
    assert chosen.shape == (n, k)
    assert onp.array_equal(onp.sort(onp.asarray(chosen), 1),
                           onp.sort(onp.asarray(idx), 1))
    assert onp.asarray(sizes).tolist() == [
        int(jnp.sum(idx == e)) for e in range(4, 10)]
    onp.testing.assert_allclose(float(got), float(want), rtol=1e-4)
    for a, b in zip(d_got, d_want):
        onp.testing.assert_allclose(onp.asarray(a), onp.asarray(b),
                                    rtol=1e-3, atol=2e-4)


@pytest.mark.parametrize("form", ["relu2", "swiglu"])
@pytest.mark.parametrize("routed", [0, 1, 2 * CHUNK, 2 * CHUNK + 1, N * K],
                         ids=["none", "one", "two_chunks", "one_over",
                              "every_pair"])
def test_sorted_order_gathers_walk_the_routed_rows(routed, form,
                                                   monkeypatch):
    """The three gathers INTO sorted order (``_rows_of_pairs`` in its
    primal and in its ``_fwd``, ``_permute``'s backward) against the
    whole-buffer gathers they replace, ``x[order % n]`` and ``g[order]``:
    equal bit for bit on the rows routed, finite past them, zero past the
    last whole chunk; in the compute type of each expert form's cell
    (``relu2`` float32 here, ``swiglu`` bfloat16)."""
    monkeypatch.setattr(moe, "_GATHER_CHUNK_ROWS", CHUNK)
    dt = {"relu2": jnp.float32, "swiglu": jnp.bfloat16}[form]
    ks = jax.random.split(jax.random.PRNGKey(routed), 3)
    x = jax.random.normal(ks[0], (N, D)).astype(dt)
    g = jax.random.normal(ks[1], (N * K, D)).astype(dt)
    order = jax.random.permutation(ks[2], N * K).astype(jnp.int32)
    inv = jnp.argsort(order).astype(jnp.int32)
    held = jnp.ones((K, N), bool)
    count = jnp.asarray(routed, jnp.int32)
    walked = -(-routed // CHUNK) * CHUNK

    @jax.jit
    def sites(x, g):
        primal = moe._rows_of_pairs(x, order, inv, held, count)
        fwd, pull = jax.vjp(
            lambda x: moe._rows_of_pairs(x, order, inv, held, count), x)
        _, back = jax.vjp(lambda y: moe._permute(y, inv, order, count), g)
        return ((primal, fwd, back(g)[0]), (x[order % N], g[order]),
                pull(g)[0])

    (primal, fwd, back), (rows, dy), dx = sites(x, g)
    for got, want in ((primal, rows), (fwd, rows), (back, dy)):
        got, want = onp.asarray(got), onp.asarray(want)
        assert got.shape == want.shape and got.dtype == want.dtype
        assert onp.array_equal(got[:routed], want[:routed])
        assert onp.isfinite(got.astype(onp.float32)).all()
        assert not got[walked:].any()
    # the derivative of the rows is the parent's: a sum over a token's pairs
    assert dx.shape == x.shape and dx.dtype == x.dtype


def _nan_past_the_routed_rows(real):
    """``grouped_matmul`` as the kernel may leave its output: every row
    past ``sum(sizes)`` NaN, in the product and in the ``lhs`` gradient."""
    def fill(out, sizes):
        routed = jnp.arange(out.shape[0]) < jnp.sum(sizes)
        return jnp.where(routed[:, None], out, jnp.nan)

    @functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
    def patched(lhs, rhs, sizes, impl):
        return fill(real(lhs, rhs, sizes, impl=impl), sizes)

    def fwd(lhs, rhs, sizes, impl):
        out, vjp = jax.vjp(lambda a, b: real(a, b, sizes, impl=impl),
                           lhs, rhs)
        return fill(out, sizes), (vjp, sizes)

    def bwd(impl, res, g):
        vjp, sizes = res
        d_lhs, d_rhs = vjp(g)
        return (fill(d_lhs, sizes), d_rhs,
                onp.zeros(sizes.shape, jax.dtypes.float0))

    patched.defvjp(fwd, bwd)
    return lambda lhs, rhs, sizes, impl="auto": patched(lhs, rhs, sizes,
                                                        impl)


@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("form", ["relu2", "swiglu"])
def test_rows_the_kernel_left_reach_no_sum(impl, form, monkeypatch):
    """The rule of ``dropless_ffn``'s buffer: rows past the routed ones
    hold whatever the kernel left, they may be gathered and may pass
    row-wise work, and they are SELECTED away before any sum that crosses
    rows or leaves the buffer.  With every such row NaN in every grouped
    product and in every gradient a product hands back, the value and
    every gradient are finite and what they are without the NaNs.
    Experts 4..7 of 16 held: most of the buffer is past the routed rows."""
    from mxnet_tpu.ops import gmm

    x, wr, bias, w_up, w_down = _weights(seed=5)
    w_gate = 0.2 * jax.random.normal(jax.random.PRNGKey(13), (E, D, F))
    g = jax.random.normal(jax.random.PRNGKey(14), (N, D))

    def loss(x, wr, w_up, w_gate, w_down):
        kw = dict(top_k=K, first=4, impl=impl)
        if form == "swiglu":
            kw.update(scoring="softmax", w_gate=w_gate[4:8])
        y, _, sizes = moe.dropless_ffn(x, wr, bias, w_up[4:8], w_down[4:8],
                                       **kw)
        return jnp.sum(y * g), (y, sizes)

    run = lambda: jax.jit(jax.value_and_grad(  # noqa: E731
        loss, argnums=(0, 1, 2, 3, 4), has_aux=True))(x, wr, w_up, w_gate,
                                                      w_down)
    (_, (want, sizes)), d_want = run()
    assert 0 < int(jnp.sum(sizes)) < N * K // 2
    monkeypatch.setattr(gmm, "grouped_matmul",
                        _nan_past_the_routed_rows(gmm.grouped_matmul))
    (_, (got, _)), d_got = run()
    assert onp.isfinite(onp.asarray(got)).all()
    assert onp.array_equal(onp.asarray(got), onp.asarray(want))
    for a, b in zip(d_got, d_want):
        assert onp.isfinite(onp.asarray(a)).all()
        assert onp.array_equal(onp.asarray(a), onp.asarray(b))


@pytest.mark.parametrize("form", ["relu2", "swiglu"])
def test_three_whole_buffer_gathers_a_layer_in_the_lowered_step(
        form, monkeypatch):
    """The differentiated, recomputed layer of a small twin, lowered for
    the TPU on this CPU (no chip, nothing compiled): of the six gathers a
    layer application had over forward, recomputed forward and backward,
    the three into PAIR order still take an index of ``N * top_k`` rows;
    the three into sorted order take a chunk's, inside a loop.  And the
    yardstick's handle: every ``moe_gmm`` call has the buffer's shape
    ``(N * top_k, .)`` among its operands and results, as before."""
    from mxnet_tpu import base

    monkeypatch.setattr(base, "resolve_exec_platform", lambda x=None: "tpu")
    n, d, f, bf = 1024, 128, 128, jnp.bfloat16
    rows, chunk = n * K, moe.gather_chunk_rows(n * K)
    assert chunk < rows

    def layer(x, w_router, bias, w_up, w_gate, w_down):
        kw = dict(top_k=K, first=4, compute_dtype=bf, impl="pallas")
        if form == "swiglu":
            kw.update(scoring="softmax", w_gate=w_gate)
        return moe.dropless_ffn(x, w_router, bias, w_up, w_down, **kw)[0]

    def loss(ct, *args):
        return jnp.sum(jax.checkpoint(layer)(*args) * ct)

    sds = jax.ShapeDtypeStruct
    text = jax.jit(jax.value_and_grad(loss, argnums=(1, 2, 4, 5, 6))).trace(
        sds((n, d), jnp.float32), sds((n, d), bf), sds((E, d), jnp.float32),
        sds((E,), jnp.float32), sds((4, d, f), bf), sds((4, d, f), bf),
        sds((4, f, d), bf)).lower(lowering_platforms=("tpu",)).as_text()
    index_rows = [int(m) for m in re.findall(
        r'"stablehlo\.gather"\(.*?: \(tensor<[^>]*>, tensor<(\d+)x1xi32>\)',
        text)]
    assert index_rows.count(rows) == 3, index_rows      # the parent: 6
    assert index_rows.count(chunk) == 3, index_rows
    assert text.count("stablehlo.while") == 3
    calls = re.findall(r"custom_call @tpu_custom_call\(.*?: (\(.*)", text)
    products = {"relu2": 2, "swiglu": 3}[form]
    assert len(calls) == 4 * products and text.count('"moe_gmm"') == len(calls)
    for types in calls:
        assert f"tensor<{rows}x" in types, types


def test_gather_counters_follow_the_rows_each_layer_routed(monkeypatch):
    """``moe.gather_rows_walked`` over ``moe.gather_rows_buffer`` is
    ``ceil(pairs_local / chunk) * chunk / pairs_total`` layer by layer on
    a seeded step: two layers that hold different shares, each with three
    gathers into sorted order (1.0 where every pair is held and the chunk
    divides the buffer; a last turn that overhangs re-reads rows, which
    are counted); and ``moe.plan`` names the chunk."""
    from mxnet_tpu import observability as obs

    chunk = 32
    monkeypatch.setattr(moe, "_GATHER_CHUNK_ROWS", chunk)
    layers = [moe.MoELayer(D, F, E, top_k=K, routing="dropless",
                           experts_held=held) for held in ((4, 4), (0, E))]
    x = mx.nd.array(onp.random.RandomState(2).randn(2, N // 2, D))
    ratios, walked, whole = [], 0.0, 0.0
    tracer = obs.enable_tracing()
    try:
        for layer in layers:
            layer.initialize()
            with mx.autograd.train_mode():
                layer(x)
            got = moe.read_routing_counters(layer)
            want = -(-got["moe.pairs_local"] // chunk) * chunk
            assert got["moe.gather_rows_walked"] == 3 * want
            assert got["moe.gather_rows_buffer"] == 3 * N * K
            ratios.append(want / got["moe.pairs_total"])
            walked += 3 * want
            whole += 3 * N * K
        plans = [e.attrs for e in tracer.spans(name="moe.plan")
                 if "gather_chunk_rows" in e.attrs]
    finally:
        obs.disable_tracing()
    assert 0 < ratios[0] < ratios[1] == 1.0
    assert plans and all(e["gather_chunk_rows"] == chunk
                         and e["buffer_rows"] == N * K for e in plans)
    # a net of both: the sums over the layers
    net = mx.gluon.nn.HybridSequential()
    net.add(*layers)
    both = moe.read_routing_counters(net)
    assert both["moe.gather_rows_walked"] == walked
    assert both["moe.gather_rows_buffer"] == whole
    monkeypatch.setattr(moe, "_GATHER_CHUNK_ROWS", CHUNK)   # 288 = 4.5 x 64
    assert moe.read_routing_counters(layers[1])[
        "moe.gather_rows_walked"] == 3 * 5 * CHUNK


def test_layer_is_told_what_it_holds_and_counts_what_it_routes():
    layer = moe.MoELayer(D, F, E, top_k=K, routing="dropless",
                         experts_held=(4, 4), shared_hidden=2 * F,
                         routed_scaling=2.5, record_choice_rows=N)
    layer.initialize()
    assert layer.w1.shape == (4, D, F) and layer.gate.shape == (E, D)
    assert layer.e_score_correction_bias.grad_req == "null"
    x = mx.nd.array(onp.random.RandomState(0).randn(2, N // 2, D))
    with mx.autograd.train_mode():
        y = layer(x)
    assert y.shape == (2, N // 2, D)
    got = moe.read_routing_counters(layer)
    chosen = layer.last_choice.data().asnumpy()
    local = int(((chosen >= 4) & (chosen < 8)).sum())
    assert got["moe.pairs_local"] == local and got["moe.pairs_total"] == N * K
    assert 0 < got["moe.load_max"] <= local
    with pytest.raises(ValueError):
        moe.MoELayer(D, F, E, routing="dropless", experts_held=(14, 4))


def test_registry_counter_counts_each_step_once_however_it_is_read():
    from mxnet_tpu.observability.registry import default_registry
    layer = moe.MoELayer(D, F, E, top_k=K, routing="dropless",
                         experts_held=(4, 4), record_choice_rows=N)
    layer.initialize()
    c = default_registry().counter("mxtpu_moe_pairs_local_total")
    start, rng, local = c.value, onp.random.RandomState(1), []

    def step():
        with mx.autograd.train_mode():
            layer(mx.nd.array(rng.randn(2, N // 2, D)))
        chosen = layer.last_choice.data().asnumpy()
        local.append(int(((chosen >= 4) & (chosen < 8)).sum()))

    step()
    first = moe.read_routing_counters(layer)
    again = moe.read_routing_counters(layer)      # two reads of one step
    assert first == again and c.value - start == local[0]
    step(), step()                                # a step no read fell on
    got = moe.read_routing_counters(layer)
    assert got["moe.pairs_local"] == local[2]
    assert c.value - start == sum(local) == got["sum_pairs_local"]
    layer.routing_stats.data()._rebind(jnp.zeros(7, jnp.float32))
    step()                                        # sums started afresh
    moe.read_routing_counters(layer)
    assert c.value - start == sum(local)


def test_capacity_path_is_what_it_was():
    layer = moe.MoELayer(D, F, 4, top_k=2)
    layer.initialize()
    assert layer.w1.shape == (4, D, F) and hasattr(layer, "b1")
    y = layer(mx.nd.array(onp.random.RandomState(1).randn(2, 8, D)))
    assert y.shape == (2, 8, D)
