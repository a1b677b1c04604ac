"""Ring attention (sequence parallelism over the sp mesh axis).

Runs on the 8-virtual-device CPU mesh from conftest.  Capability add over
the reference (SURVEY.md §5.7: MXNet has no SP/CP) — the contract is
numerical agreement with single-device attention.
"""
import jax
import jax.numpy as jnp
import numpy as onp
import pytest

pytestmark = pytest.mark.slow

import mxnet_tpu as mx
from mxnet_tpu import nd, parallel as par
from mxnet_tpu.ops.attention import _attention_ref
from mxnet_tpu.ops.ring import ring_attention


def _qkv(b=4, t=64, h=4, d=16, seed=0):
    rs = onp.random.RandomState(seed)
    return tuple(jnp.asarray(rs.randn(b, t, h, d), jnp.float32)
                 for _ in range(3))


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("dp,sp,tp", [(2, 4, 1), (1, 8, 1), (2, 2, 2)])
def test_ring_matches_ref(causal, dp, sp, tp):
    mesh = par.make_mesh(dp=dp, sp=sp, tp=tp)
    q, k, v = _qkv()
    with par.use_mesh(mesh):
        out = ring_attention(q, k, v, causal=causal)
    ref = _attention_ref(q, k, v, causal=causal)
    onp.testing.assert_allclose(onp.asarray(out), onp.asarray(ref),
                                rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_grads_match_ref(causal):
    mesh = par.make_mesh(dp=1, sp=4, devices=jax.devices()[:4])
    q, k, v = _qkv(b=2, t=32, h=2, d=8, seed=1)
    with par.use_mesh(mesh):
        gf = jax.grad(
            lambda q, k, v: jnp.sum(
                ring_attention(q, k, v, causal=causal) ** 2),
            argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(
        lambda q, k, v: jnp.sum(_attention_ref(q, k, v, causal=causal) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    for a, r in zip(gf, gr):
        onp.testing.assert_allclose(onp.asarray(a), onp.asarray(r),
                                    rtol=1e-3, atol=1e-3)


def test_ring_rejects_indivisible_seq():
    mesh = par.make_mesh(dp=2, sp=4)
    q, k, v = _qkv(t=66)
    with par.use_mesh(mesh):
        with pytest.raises(ValueError):
            ring_attention(q, k, v)


def test_mha_routes_to_ring_under_sp_mesh():
    """MultiHeadAttention must produce identical results with and without
    sequence parallelism (ring vs single-device path)."""
    from mxnet_tpu.models.transformer import MultiHeadAttention
    rs = onp.random.RandomState(3)
    x = nd.array(rs.randn(2, 32, 16).astype("float32"))
    attn = MultiHeadAttention(16, 4, causal=True)
    attn.initialize()
    base = attn(x).asnumpy()
    mesh = par.make_mesh(dp=1, sp=4, devices=jax.devices()[:4])
    with par.use_mesh(mesh):
        ringed = attn(x).asnumpy()
    onp.testing.assert_allclose(ringed, base, rtol=1e-4, atol=1e-4)


def test_sharded_trainer_sp_training_step():
    """Full sharded GPT-2 training step with sp>1 goes through ring
    attention and still decreases the loss."""
    from mxnet_tpu.models import get_gpt2, gpt2_lm_loss
    mesh = par.make_mesh(dp=2, sp=2, tp=2)
    net = get_gpt2("gpt2_124m", vocab_size=128, units=32, num_layers=2,
                   num_heads=4, max_length=64, dropout=0.0)
    net.initialize()
    rs = onp.random.RandomState(0)
    toks = mx.nd.array(rs.randint(0, 128, (4, 32)), dtype="int32")
    labels = mx.nd.array(rs.randint(0, 128, (4, 32)), dtype="int32")
    with par.use_mesh(mesh):
        tr = par.ShardedTrainer(net, "adam", loss=gpt2_lm_loss,
                                optimizer_params={"learning_rate": 1e-2},
                                mesh=mesh, seq_axis=1)
        first = float(tr.step(toks, labels).asscalar())
        for _ in range(5):
            last = float(tr.step(toks, labels).asscalar())
    assert last < first, (first, last)


def _seg_ids(b, t, n_seg, seed=7):
    """Packed segment ids: sorted so each row is a run of n_seg documents."""
    rs = onp.random.RandomState(seed)
    seg = onp.sort(rs.randint(0, n_seg, (b, t)), axis=1)
    return jnp.asarray(seg, jnp.int32)


def _seg_ref(q, k, v, seg, causal):
    mask = (onp.asarray(seg)[:, None, :, None] ==
            onp.asarray(seg)[:, None, None, :])
    return _attention_ref(q, k, v, causal=causal, mask=jnp.asarray(mask))


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("dp,sp", [(2, 4), (1, 8)])
def test_ring_segment_ids_match_ref(causal, dp, sp):
    """Packed segment ids through the (unbalanced) ring: the kv-side id
    plane rotates with its K/V chunk and must reproduce single-device
    segment-masked attention."""
    mesh = par.make_mesh(dp=dp, sp=sp)
    q, k, v = _qkv(seed=11)
    seg = _seg_ids(q.shape[0], q.shape[1], 3)
    with par.use_mesh(mesh):
        out = ring_attention(q, k, v, causal=causal, segment_ids=seg,
                             balance=False)
    ref = _seg_ref(q, k, v, seg, causal)
    onp.testing.assert_allclose(onp.asarray(out), onp.asarray(ref),
                                rtol=2e-4, atol=2e-4)


def test_balanced_ring_segment_ids_match_ref():
    """Balanced (zigzag) causal ring with segment ids: ring_attention
    permutes the id plane itself, so callers pass natural order."""
    mesh = par.make_mesh(dp=2, sp=4)
    q, k, v = _qkv(seed=12)
    seg = _seg_ids(q.shape[0], q.shape[1], 4, seed=13)
    with par.use_mesh(mesh):
        out = ring_attention(q, k, v, causal=True, segment_ids=seg,
                             balance=True)
    ref = _seg_ref(q, k, v, seg, causal=True)
    onp.testing.assert_allclose(onp.asarray(out), onp.asarray(ref),
                                rtol=2e-4, atol=2e-4)


def test_ring_segment_ids_match_flash_single_device():
    """|sp|=1 fallback with segment ids agrees with the public
    dot_product_attention reference (zeros on fully-masked rows)."""
    from mxnet_tpu.ops.attention import dot_product_attention
    q, k, v = _qkv(b=2, t=32, h=2, d=16, seed=14)
    seg = _seg_ids(2, 32, 3, seed=15)
    out = ring_attention(q, k, v, causal=True, segment_ids=seg, mesh=None)
    ref = dot_product_attention(nd.array(onp.asarray(q)),
                                nd.array(onp.asarray(k)),
                                nd.array(onp.asarray(v)),
                                causal=True, segment_ids=onp.asarray(seg),
                                impl="ref").asnumpy()
    onp.testing.assert_allclose(onp.asarray(out), ref, rtol=2e-4, atol=2e-4)


def test_ring_segment_ids_shape_guard():
    mesh = par.make_mesh(dp=2, sp=4)
    q, k, v = _qkv()
    with pytest.raises(ValueError):
        ring_attention(q, k, v, causal=True, mesh=mesh,
                       segment_ids=jnp.zeros((3, 3), jnp.int32))


def test_smap_extra_specs_arity_guard():
    """len(extra) != len(extra_specs) must fail loudly at entry, not
    zip-truncate."""
    from mxnet_tpu.ops._smap import shard_mapped_qkv
    mesh = par.make_mesh(dp=2, sp=4)
    q, k, v = _qkv()
    from jax.sharding import PartitionSpec as P
    with pytest.raises(ValueError, match="extra"):
        shard_mapped_qkv(lambda q, k, v, s: q, mesh, P("dp", "sp", None, None),
                         q, k, v, jnp.zeros((4, 64), jnp.int32),
                         extra_specs=())


@pytest.mark.parametrize("dp,sp,tp", [(2, 4, 1), (1, 8, 1), (2, 2, 2)])
def test_balanced_causal_ring_matches_ref(dp, sp, tp):
    """Zigzag-balanced causal ring (2x fewer attention FLOPs: every
    computed half-block is fully live) must match single-device
    attention exactly."""
    mesh = par.make_mesh(dp=dp, sp=sp, tp=tp)
    q, k, v = _qkv(seed=5)
    out = ring_attention(q, k, v, causal=True, mesh=mesh, balance=True)
    ref = _attention_ref(q, k, v, causal=True)
    onp.testing.assert_allclose(onp.asarray(out), onp.asarray(ref),
                                rtol=2e-4, atol=2e-4)
    # plain (unbalanced) path still agrees too
    out_u = ring_attention(q, k, v, causal=True, mesh=mesh, balance=False)
    onp.testing.assert_allclose(onp.asarray(out_u), onp.asarray(ref),
                                rtol=2e-4, atol=2e-4)


def test_balanced_causal_ring_grads():
    mesh = par.make_mesh(dp=2, sp=4)
    q, k, v = _qkv(b=2, seed=6)

    def f(q, k, v):
        return jnp.sum(ring_attention(q, k, v, causal=True, mesh=mesh,
                                      balance=True) ** 2)

    def g(q, k, v):
        return jnp.sum(_attention_ref(q, k, v, causal=True) ** 2)

    for a, r in zip(jax.grad(f, (0, 1, 2))(q, k, v),
                    jax.grad(g, (0, 1, 2))(q, k, v)):
        onp.testing.assert_allclose(onp.asarray(a), onp.asarray(r),
                                    rtol=1e-3, atol=1e-3)


def test_balanced_ring_rejects_odd_split():
    mesh = par.make_mesh(dp=1, sp=8)
    q, k, v = _qkv(t=40)        # 40 % 16 != 0
    with pytest.raises(ValueError):
        ring_attention(q, k, v, causal=True, mesh=mesh, balance=True)
    # default silently falls back to the unbalanced path and still works
    out = ring_attention(q, k, v, causal=True, mesh=mesh)
    ref = _attention_ref(q, k, v, causal=True)
    onp.testing.assert_allclose(onp.asarray(out), onp.asarray(ref),
                                rtol=2e-4, atol=2e-4)
