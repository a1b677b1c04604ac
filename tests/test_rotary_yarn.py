"""``ops.attention.rope_frequencies`` and the table arguments of
``rotary_embedding``: the YaRN table of the benchmark's sliding-window
expert configuration against the formula in numpy float64 (``low`` 18,
``high`` 35, the ends of the ramp, the amplitude on cos and sin both), the
``theta=`` path bit for bit what it was, a factor of 1 giving the plain
table, and the benchmark's reference building the same table on its own."""
import math

import jax
import jax.numpy as jnp
import numpy as onp
import pytest

from mxnet_tpu.ops.attention import rope_frequencies, rotary_embedding

YARN = {"rope_type": "yarn", "rope_theta": 500000, "factor": 16,
        "original_max_position_embeddings": 8192, "beta_fast": 32,
        "beta_slow": 1, "attention_factor": 1.2772588722239782}
PLAIN = {"rope_type": "default", "rope_theta": 500000}


def _formula(theta, factor, length, fast, slow, dim):
    j = onp.arange(dim // 2, dtype=onp.float64)
    e = theta ** (-j / (dim // 2))
    c = lambda b: dim * math.log(length / (2 * math.pi * b)) \
        / (2 * math.log(theta))                               # noqa: E731
    low, high = math.floor(c(fast)), math.ceil(c(slow))
    r = onp.clip((j - low) / (high - low), 0, 1)
    return e / factor * r + e * (1 - r), low, high, c


def test_yarn_table_is_the_formula():
    from mxnet_tpu import observability as obs

    tr = obs.enable_tracing()
    try:
        inv, amplitude = rope_frequencies(YARN, 128)
        rope_frequencies(YARN, 128)                # the same table: no more
        plain, one = rope_frequencies(PLAIN, 128)
        events = [e.attrs for e in tr.spans(name="rope.plan")]
    finally:
        obs.disable_tracing()
    want, low, high, c = _formula(500000.0, 16.0, 8192.0, 32.0, 1.0, 128)
    assert (low, high) == (18, 35)
    assert c(32) == pytest.approx(18.08, abs=0.01)
    assert c(1) == pytest.approx(34.98, abs=0.01)
    assert inv.dtype == onp.float32 and inv.shape == (64,)
    onp.testing.assert_array_equal(inv, want.astype(onp.float32))
    # the ends of the ramp: fast dimensions turn as they did, slow ones
    # sixteen times slower, one step inside either end is a blend
    onp.testing.assert_array_equal(inv[:19], plain[:19])
    onp.testing.assert_allclose(inv[35:], plain[35:] / 16, rtol=1e-7)
    assert plain[19] / 16 < inv[19] < plain[19]
    assert plain[34] / 16 < inv[34] < plain[34]
    assert amplitude == 1.2772588722239782 == 0.1 * math.log(16) + 1
    assert one == 1.0
    onp.testing.assert_array_equal(
        plain, (500000.0 ** (-onp.arange(64) / 64.0)).astype(onp.float32))
    assert events == [
        {"kind": "yarn", "theta": 500000.0, "factor": 16.0, "low": 18,
         "high": 35, "amplitude": 1.2772588722239782, "dim": 128},
        {"kind": "default", "theta": 500000.0, "factor": 1.0, "low": 0,
         "high": 0, "amplitude": 1.0, "dim": 128}]


def test_a_factor_of_one_is_the_plain_table_and_the_default_amplitude():
    inv, amplitude = rope_frequencies(
        {k: v for k, v in dict(YARN, factor=1).items()
         if k != "attention_factor"}, 128)
    plain, _ = rope_frequencies(PLAIN, 128)
    onp.testing.assert_array_equal(inv, plain)
    assert amplitude == 1.0
    _, scaled = rope_frequencies(
        {k: v for k, v in YARN.items() if k != "attention_factor"}, 128)
    assert scaled == pytest.approx(1.2772588722239782, rel=1e-15)
    with pytest.raises(ValueError):
        rope_frequencies({"rope_type": "longrope", "rope_theta": 1e4}, 64)


def test_amplitude_multiplies_cos_and_sin_both():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 40, 3, 128))
    inv, a = rope_frequencies(YARN, 128)
    y = rotary_embedding(x, inv_freq=inv, amplitude=a)
    unit = rotary_embedding(x, inv_freq=inv)
    assert y.shape == x.shape and y.dtype == x.dtype
    onp.testing.assert_allclose(onp.asarray(y), a * onp.asarray(unit),
                                rtol=1e-6, atol=1e-6)
    # pair (j, j + 64) turned by t f_j: the cos part and the sin part
    t, j = 37, 5
    x1, x2 = onp.asarray(x[:, t, :, j]), onp.asarray(x[:, t, :, j + 64])
    ang = onp.float32(t) * inv[j]
    onp.testing.assert_allclose(
        onp.asarray(y[:, t, :, j]), a * (x1 * onp.cos(ang) - x2 * onp.sin(ang)),
        rtol=1e-5, atol=1e-5)
    onp.testing.assert_allclose(
        onp.asarray(y[:, t, :, j + 64]),
        a * (x2 * onp.cos(ang) + x1 * onp.sin(ang)), rtol=1e-5, atol=1e-5)
    # a score of two turned vectors carries the amplitude's square
    dots = lambda v: jnp.einsum("bqhd,bkhd->bhqk", v, v,      # noqa: E731
                                precision=jax.lax.Precision.HIGHEST)
    onp.testing.assert_allclose(onp.asarray(dots(y)),
                                a * a * onp.asarray(dots(unit)), rtol=1e-5,
                                atol=1e-4)
    with pytest.raises(ValueError):
        rotary_embedding(x, inv_freq=inv[:32])


def _as_before(x, positions=None, *, theta=10000.0, rotary_dim=None):
    """``rotary_embedding`` as it stood before it took a table."""
    d = x.shape[-1]
    rd = d if rotary_dim is None else int(rotary_dim)
    half = rd // 2
    if positions is None:
        positions = jnp.arange(x.shape[1])
    inv_freq = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32)
                                * 2.0 / rd))
    angle = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos = jnp.cos(angle)[None, :, None, :]
    sin = jnp.sin(angle)[None, :, None, :]
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., :half], xf[..., half:rd]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                            xf[..., rd:]], axis=-1).astype(x.dtype)


@pytest.mark.parametrize("d,rotary_dim,theta,dtype", [
    (256, 64, 1e7, "float32"),           # the gated-attention cell's call
    (256, 64, 1e7, "bfloat16"), (64, None, 1e4, "float32")])
def test_the_theta_path_is_bit_for_bit_what_it_was(d, rotary_dim, theta,
                                                   dtype):
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 96, 2, d)).astype(dtype)
    for positions in (None, jnp.arange(96) + 8000):
        got = rotary_embedding(x, positions, theta=theta,
                               rotary_dim=rotary_dim)
        want = _as_before(x, positions, theta=theta, rotary_dim=rotary_dim)
        assert got.dtype == want.dtype
        onp.testing.assert_array_equal(onp.asarray(got, onp.float32),
                                       onp.asarray(want, onp.float32))


def test_the_benchmarks_reference_builds_the_same_tables_on_its_own():
    from chipbench.reference.mellum2_ref import rope_table, rotary

    for rope in (PLAIN, YARN):
        inv, a = rope_frequencies(rope, 128)
        freq, amp = rope_table(tuple(sorted(rope.items())), 128)
        assert freq.dtype == onp.float64 and amp == a
        onp.testing.assert_array_equal(freq.astype(onp.float32), inv)
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 64, 2, 128))
    onp.testing.assert_allclose(
        onp.asarray(rotary_embedding(x, inv_freq=inv, amplitude=a)),
        onp.asarray(rotary(x, freq, amp)), rtol=1e-6, atol=1e-6)
