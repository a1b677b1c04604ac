"""Grouped matrix products over the experts held: the Pallas kernels in
interpret mode against a loop over experts, forward and gradients."""
import jax
import jax.numpy as jnp
import numpy as onp
import pytest

import mxnet_tpu  # noqa: F401
from mxnet_tpu.ops import gmm as G

M, K, N, E = 640, 96, 80, 4


def _operands(seed=0):
    key = jax.random.PRNGKey(seed)
    lhs = jax.random.normal(key, (M, K), jnp.float32)
    rhs = jax.random.normal(jax.random.fold_in(key, 1), (E, K, N),
                            jnp.float32)
    return lhs, rhs


def _loop(lhs, rhs, sizes):
    out = onp.zeros((lhs.shape[0], rhs.shape[2]), onp.float32)
    off = onp.concatenate([[0], onp.cumsum(sizes)])
    for g in range(len(sizes)):
        out[off[g]:off[g + 1]] = (onp.asarray(lhs[off[g]:off[g + 1]])
                                  @ onp.asarray(rhs[g]))
    return out


CASES = {
    "an_empty_expert": [100, 0, 300, 50],
    "every_pair_on_one_expert": [0, 0, 0, 640],
    "nothing_routed": [0, 0, 0, 0],
    "rows_not_a_multiple_of_the_tile": [257, 1, 130, 77],
    "whole_tiles": [256, 256, 128, 0],
    "a_few_rows": [1, 2, 3, 4],
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_grouped_matmul_matches_a_loop_over_experts(name):
    sizes = CASES[name]
    lhs, rhs = _operands()
    gs = jnp.asarray(sizes, jnp.int32)
    routed = sum(sizes)
    got = G.grouped_matmul(lhs, rhs, gs, impl="pallas")
    want = _loop(lhs, rhs, sizes)
    onp.testing.assert_allclose(onp.asarray(got)[:routed], want[:routed],
                                rtol=1e-4, atol=1e-3)


@pytest.mark.parametrize("name", sorted(CASES))
def test_grouped_matmul_gradients(name):
    sizes = CASES[name]
    lhs, rhs = _operands(1)
    gs = jnp.asarray(sizes, jnp.int32)
    valid = (jnp.arange(M) < sum(sizes))[:, None]
    w = jax.random.normal(jax.random.PRNGKey(5), (M, N))

    def loss(impl):
        def f(lhs, rhs):
            out = G.grouped_matmul(lhs, rhs, gs, impl=impl)
            return jnp.sum(jnp.where(valid, out, 0.0) * w)
        return f

    got = jax.grad(loss("pallas"), argnums=(0, 1))(lhs, rhs)
    want = jax.grad(loss("xla"), argnums=(0, 1))(lhs, rhs)
    d_lhs = jnp.where(valid, got[0], 0.0)     # rows past the routed: unwritten
    onp.testing.assert_allclose(onp.asarray(d_lhs), onp.asarray(want[0]),
                                rtol=1e-4, atol=2e-3)
    onp.testing.assert_allclose(onp.asarray(got[1]), onp.asarray(want[1]),
                                rtol=1e-4, atol=5e-3)
    assert bool(jnp.all(jnp.isfinite(got[1])))   # an empty expert: zeros


def test_tiles_run_follow_the_rows_routed():
    """The grid's tile axis is as long as the tiles that hold a routed
    row, plus one more visit for each group that starts inside a tile."""
    tm, m = 128, 1024
    for sizes, want in ([[0, 0, 0, 0], 0], [[128, 0, 0, 0], 1],
                        [[100, 100, 100, 100], 4 + 3],
                        [[256, 256, 256, 256], 8], [[1, 1, 1, 1], 4]):
        _meta, tiles = G.make_group_metadata(
            jnp.asarray(sizes, jnp.int32), m, tm, False)
        assert int(tiles) == want, sizes


def test_plan_is_static_and_fits():
    up = G.gmm_plan(49152, 2688, 1856)
    down = G.gmm_plan(49152, 1856, 2688)
    assert up == (256, 384, 1856) and down == (256, 1856, 384)
    for tm, tk, tn in (up, down):
        assert (2 * (tm * tk + tk * tn + tm * tn) * 2
                + max(tm, tk) * tn * 4) <= 12 * 2 ** 20
    assert G.gmm_plan(64, 32, 24) == (64, 32, 24)      # tiny: whole dims


def test_plan_event_names_buffer_and_experts_held():
    from mxnet_tpu import observability as obs

    lhs, rhs = _operands()
    tr = obs.enable_tracing()
    try:
        for _ in range(2):
            G.grouped_matmul(lhs, rhs, jnp.asarray([1, 2, 3, 4], jnp.int32),
                             impl="xla")
        events = tr.spans(name="moe.plan")
    finally:
        obs.disable_tracing()
    assert len(events) == 1
    assert events[0].attrs["buffer_rows"] == M
    assert events[0].attrs["experts_held"] == E
