"""The dropless expert layer (one chip's share of an expert-parallel
deployment) against a plain loop over experts: routing as published, no
token dropped under any skew, and the shares of a deployment adding up to
the uncut layer."""
import jax
import jax.numpy as jnp
import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu.models import moe

N, D, F, E, K = 96, 32, 24, 16, 3


def _weights(seed=0, skew=0.0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(ks[0], (N, D))
    wr = 0.3 * jax.random.normal(ks[1], (E, D))
    bias = 0.05 * jax.random.normal(ks[2], (E,))
    if skew:
        # every token's best experts are 0, 1, 2: a capacity path with
        # N * K / E * 1.25 slots an expert drops most of them
        bias = bias.at[:K].add(skew)
    w_up = 0.2 * jax.random.normal(ks[3], (E, D, F))
    w_down = 0.2 * jax.random.normal(ks[4], (E, F, D))
    return x, wr, bias, w_up, w_down


def _loop(x, wr, bias, w_up, w_down, first=0, held=E, scaling=2.5):
    """The published routing and a plain loop over the experts held."""
    s = jax.nn.sigmoid(jnp.einsum("nd,ed->ne", x, wr,
                                  precision=jax.lax.Precision.HIGHEST))
    _, idx = jax.lax.top_k(s + bias, K)
    w = jnp.take_along_axis(s, idx, axis=1)
    w = w / jnp.sum(w, axis=1, keepdims=True) * scaling
    y = jnp.zeros_like(x)
    hi = jax.lax.Precision.HIGHEST
    for e in range(first, first + held):
        we = jnp.sum(jnp.where(idx == e, w, 0.0), axis=1)
        h = jnp.square(jax.nn.relu(jnp.dot(x, w_up[e], precision=hi)))
        y = y + we[:, None] * jnp.dot(h, w_down[e], precision=hi)
    return y, idx


@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("skew", [0.0, 5.0])
def test_dropless_routing_equals_the_loop(impl, skew):
    x, wr, bias, w_up, w_down = _weights(skew=skew)
    got, chosen, sizes = moe.dropless_ffn(
        x, wr, bias, w_up, w_down, top_k=K, first=0, scaling=2.5, impl=impl)
    want, idx = _loop(x, wr, bias, w_up, w_down)
    assert onp.array_equal(onp.sort(onp.asarray(chosen), 1),
                           onp.sort(onp.asarray(idx), 1))
    assert int(jnp.sum(sizes)) == N * K            # every pair computed
    onp.testing.assert_allclose(onp.asarray(got), onp.asarray(want),
                                rtol=1e-4, atol=1e-4)
    if skew:
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
def test_dropless_gradients_match_the_loop(impl):
    x, wr, bias, w_up, w_down = _weights(seed=3, skew=1.0)
    g = jax.random.normal(jax.random.PRNGKey(11), (N, D))

    def ours(x, wr, w_up, w_down):
        return jnp.sum(moe.dropless_ffn(
            x, wr, bias, w_up[4:8], w_down[4:8], top_k=K, first=4,
            scaling=2.5, impl=impl)[0] * g)

    def loop(x, wr, w_up, w_down):
        return jnp.sum(_loop(x, wr, bias, w_up, w_down, 4, 4)[0] * g)

    got = jax.grad(ours, argnums=(0, 1, 2, 3))(x, wr, w_up, w_down)
    want = jax.grad(loop, argnums=(0, 1, 2, 3))(x, wr, w_up, w_down)
    for a, b in zip(got, want):
        onp.testing.assert_allclose(onp.asarray(a), onp.asarray(b),
                                    rtol=1e-3, atol=2e-4)
    assert float(jnp.max(jnp.abs(got[2][:4]))) == 0.0   # experts not held


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
