"""Pallas flash-attention kernel vs the XLA reference attention.

Runs in interpret mode on the CPU backend (same kernel code path that
compiles on TPU).  Parity note: the reference framework has no flash
attention (SURVEY.md §5.7) — the contract here is agreement with
``_attention_ref``, the XLA attention both models and tests share.
"""
import jax
import jax.numpy as jnp
import numpy as onp
import pytest

from mxnet_tpu.ops.attention import _attention_ref, dot_product_attention
from mxnet_tpu.ops.flash import flash_attention


def _rand(shape, seed=0):
    return jnp.asarray(onp.random.RandomState(seed).randn(*shape),
                       jnp.float32)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("t,d", [(256, 64), (384, 128)])
def test_flash_forward_matches_ref(causal, t, d):
    b, h = 2, 2
    q, k, v = (_rand((b, t, h, d), s) for s in (0, 1, 2))
    out = flash_attention(q, k, v, causal=causal, interpret=True)
    ref = _attention_ref(q, k, v, causal=causal)
    assert out.shape == (b, t, h, d)
    onp.testing.assert_allclose(onp.asarray(out), onp.asarray(ref),
                                rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_grads_match_ref(causal):
    b, t, h, d = 1, 256, 2, 64
    q, k, v = (_rand((b, t, h, d), s) for s in (3, 4, 5))

    def f(q, k, v):
        return jnp.sum(
            flash_attention(q, k, v, causal=causal, interpret=True) ** 2)

    def g(q, k, v):
        return jnp.sum(_attention_ref(q, k, v, causal=causal) ** 2)

    gf = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(g, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(gf, gr):
        onp.testing.assert_allclose(onp.asarray(a), onp.asarray(b_),
                                    rtol=5e-2, atol=5e-2)


def test_flash_cross_attention_lengths():
    # non-causal tq != tk (cross attention)
    b, h, d = 1, 2, 64
    q = _rand((b, 256, h, d), 6)
    k = _rand((b, 512, h, d), 7)
    v = _rand((b, 512, h, d), 8)
    out = flash_attention(q, k, v, interpret=True)
    ref = _attention_ref(q, k, v)
    onp.testing.assert_allclose(onp.asarray(out), onp.asarray(ref),
                                rtol=2e-2, atol=2e-2)


def test_flash_bf16():
    b, t, h, d = 1, 256, 2, 64
    q, k, v = (_rand((b, t, h, d), s).astype(jnp.bfloat16)
               for s in (9, 10, 11))
    out = flash_attention(q, k, v, causal=True, interpret=True)
    ref = _attention_ref(q, k, v, causal=True)
    assert out.dtype == jnp.bfloat16
    onp.testing.assert_allclose(
        onp.asarray(out, onp.float32), onp.asarray(ref, onp.float32),
        rtol=1e-1, atol=1e-1)


def test_flash_rejects_bad_shapes():
    b, h, d = 1, 2, 64
    q = _rand((b, 200, h, d))
    with pytest.raises(ValueError):
        flash_attention(q, q, q, block_q=128, block_k=128, interpret=True)
    k = _rand((b, 512, h, d))
    with pytest.raises(ValueError):
        flash_attention(q[:, :256], k, k, causal=True, interpret=True)


def test_dot_product_attention_dispatch_ref():
    # off-TPU the public entry must route to the XLA reference and agree
    # with it exactly.
    import mxnet_tpu as mx
    b, t, h, d = 2, 64, 2, 16
    q = mx.nd.array(onp.random.RandomState(1).randn(b, t, h, d))
    out = dot_product_attention(q, q, q, causal=True)
    ref = _attention_ref(q.jax, q.jax, q.jax, causal=True)
    onp.testing.assert_allclose(out.asnumpy(), onp.asarray(ref), rtol=1e-5,
                                atol=1e-5)


def test_use_flash_rejects_cross_attention_shapes(monkeypatch):
    """Cross-attention (tq != tk) must never take the Pallas self-attention
    kernel, even when the query shape alone qualifies."""
    from mxnet_tpu.ops import attention as att

    monkeypatch.setattr(att.jax, "default_backend", lambda: "tpu")
    q = (2, 256, 4, 64)
    assert att._use_flash(q, True, None, 0.0, q)            # self: ok
    assert not att._use_flash(q, True, None, 0.0, (2, 300, 4, 64))
    assert not att._use_flash(q, False, None, 0.0, (2, 1536, 4, 64))


def test_vmem_clamp_head_dim_aware():
    """Size policy: d=64 keeps a 1024-row block with a whole head of the
    walked operand resident; big head dims and long sequences give up
    block rows (down to one that leaves room for a stretch two blocks
    long), then the rest of the resident stretch, then heads side by
    side, until the modeled working set fits the VMEM budget."""
    from mxnet_tpu.ops.flash import _VMEM_BUDGET, _clamp_blocks, _vmem_bytes

    blocks = [1024, 512]
    assert _clamp_blocks(blocks, 512, 1024, 1024, 64, 2, group=4) == \
        (1024, 1024, 1024, 1)
    # float32 operands: the head stays resident, the block halves
    assert _clamp_blocks(blocks, 512, 1024, 1024, 64, 4, group=4)[:3] == \
        (512, 1024, 1024)
    for d in (128, 256):
        for itemsize in (2, 4):
            bq, major, major_q, group = _clamp_blocks(
                blocks, 512, 8192, 8192, d, itemsize, group=4)
            assert _vmem_bytes(bq, 512, major, d, itemsize,
                               group=group) <= _VMEM_BUDGET
            assert bq in blocks and major % 512 == 0
            assert major == major_q and 8192 % major == 0
    # d=64 bf16 at T=8192: the block stays, a quarter of K and V resident
    assert _clamp_blocks(blocks, 512, 8192, 8192, 64, 2) == \
        (1024, 2048, 2048, 1)
    # d=256 f32 must NOT hold 8192 rows of q and dO, nor a 1024-row block
    bq, major, _, group = _clamp_blocks(blocks, 512, 8192, 8192, 256, 4,
                                        group=4)
    assert (bq, group) == (512, 1) and major < 8192
    # a short sequence leaves room for heads side by side
    assert _clamp_blocks([256], 256, 256, 256, 64, 2, group=4)[3] == 4
    # cross attention: each walked operand gets its own stretch
    assert _clamp_blocks([128], 128, 256, 512, 64, 2)[1:3] == (512, 256)


@pytest.mark.parametrize("d", [128, 256])
def test_flash_large_head_dim_matches_ref(d):
    b, t, h = 1, 256, 2
    q, k, v = (_rand((b, t, h, d), s) for s in (9, 10, 11))
    out = flash_attention(q, k, v, causal=True, interpret=True)
    ref = _attention_ref(q, k, v, causal=True)
    onp.testing.assert_allclose(onp.asarray(out), onp.asarray(ref),
                                rtol=2e-2, atol=2e-2)

    def f(q, k, v):
        return jnp.sum(
            flash_attention(q, k, v, causal=True, interpret=True) ** 2)

    def g(q, k, v):
        return jnp.sum(_attention_ref(q, k, v, causal=True) ** 2)

    for a, r in zip(jax.grad(f, (0, 1, 2))(q, k, v),
                    jax.grad(g, (0, 1, 2))(q, k, v)):
        onp.testing.assert_allclose(onp.asarray(a), onp.asarray(r),
                                    rtol=5e-2, atol=5e-2)


def test_segment_ids_packing_isolates_documents():
    """segment_ids packing: tokens never attend across documents packed
    in one row — each packed segment matches the same document attended
    alone."""
    import mxnet_tpu as mx
    from mxnet_tpu import nd
    from mxnet_tpu.base import MXNetError

    rs = onp.random.RandomState(0)
    b, t, h, d = 1, 8, 2, 4
    x = rs.randn(b, t, h, d).astype("f")
    q, k, v = (nd.array(x.copy()) for _ in range(3))
    seg = nd.array(onp.array([[0, 0, 0, 1, 1, 1, 1, 1]]), dtype="int32")
    packed = dot_product_attention(q, k, v, causal=True,
                                   segment_ids=seg).asnumpy()
    # each segment alone
    a0 = dot_product_attention(nd.array(x[:, :3]), nd.array(x[:, :3]),
                               nd.array(x[:, :3]), causal=True).asnumpy()
    a1 = dot_product_attention(nd.array(x[:, 3:]), nd.array(x[:, 3:]),
                               nd.array(x[:, 3:]), causal=True).asnumpy()
    onp.testing.assert_allclose(packed[:, :3], a0, rtol=1e-5, atol=1e-6)
    onp.testing.assert_allclose(packed[:, 3:], a1, rtol=1e-5, atol=1e-6)
    # impl='flash' still refuses an explicit dense mask / dropout
    with pytest.raises(MXNetError, match="mask"):
        dot_product_attention(q, k, v, causal=True, mask=q > 0,
                              impl="flash")
    # cross-attention packing via kv_segment_ids
    out_x = dot_product_attention(
        nd.array(x[:, :3]), k, v, segment_ids=nd.array(seg.asnumpy()[:, :3],
                                                       dtype="int32"),
        kv_segment_ids=seg).asnumpy()
    ref_x = dot_product_attention(
        nd.array(x[:, :3]), nd.array(x[:, :3]), nd.array(x[:, :3])).asnumpy()
    onp.testing.assert_allclose(out_x, ref_x, rtol=1e-5, atol=1e-6)
    # float 0/1 masks still compose with segment_ids
    fm = mx.nd.array(onp.ones((1, 1, t, t), "float32"))
    out_f = dot_product_attention(q, k, v, causal=True, segment_ids=seg,
                                  mask=fm).asnumpy()
    onp.testing.assert_allclose(out_f, packed, rtol=1e-5, atol=1e-6)


# ------------------------------------------------- in-kernel segment packing

@pytest.mark.parametrize("causal", [False, True])
def test_flash_kernel_segment_packing_matches_ref(causal):
    """The Pallas kernel path (VERDICT r3 item 7): per-segment parity of
    fwd AND grads against the XLA reference with the dense segment mask.
    Segment sizes straddle block boundaries (blocks forced to 128) so
    both the intra-tile mask and the block-skip predicate are exercised."""
    b, t, h, d = 2, 512, 2, 64
    q, k, v = (_rand((b, t, h, d), s) for s in (20, 21, 22))
    # doc lengths 200/312 and 512 (one doc): boundary inside a tile for
    # row 0, no boundary for row 1
    seg = jnp.asarray(
        onp.stack([[0] * 200 + [1] * 312, [0] * 512]), jnp.int32)
    seg_mask = seg[:, None, :, None] == seg[:, None, None, :]

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=causal, segment_ids=seg,
                               block_q=128, block_k=128, interpret=True)

    def ref(q, k, v):
        return _attention_ref(q, k, v, causal=causal, mask=seg_mask)

    out = flash(q, k, v)
    expect = ref(q, k, v)
    onp.testing.assert_allclose(onp.asarray(out), onp.asarray(expect),
                                rtol=2e-2, atol=2e-2)

    gf = jax.grad(lambda *a: jnp.sum(flash(*a) ** 2), (0, 1, 2))(q, k, v)
    gr = jax.grad(lambda *a: jnp.sum(ref(*a) ** 2), (0, 1, 2))(q, k, v)
    for a, r in zip(gf, gr):
        onp.testing.assert_allclose(onp.asarray(a), onp.asarray(r),
                                    rtol=5e-2, atol=5e-2)


def test_flash_kernel_segment_first_tile_fully_masked():
    """A q block whose segment begins in a LATER kv tile: the masked-safe
    exp must keep the online softmax clean (a bare exp(0)=1 per masked
    entry would corrupt l and the output)."""
    b, t, h, d = 1, 512, 1, 64
    q, k, v = (_rand((b, t, h, d), s) for s in (30, 31, 32))
    # doc 0 is exactly two 128-blocks; doc 1 starts at 256 — for doc 1's
    # rows the ki=0,1 tiles are fully masked (non-causal: visited first)
    seg = jnp.asarray([[0] * 256 + [1] * 256], jnp.int32)
    seg_mask = seg[:, None, :, None] == seg[:, None, None, :]
    out = flash_attention(q, k, v, segment_ids=seg,
                          block_q=128, block_k=128, interpret=True)
    expect = _attention_ref(q, k, v, mask=seg_mask)
    assert not onp.isnan(onp.asarray(out)).any()
    onp.testing.assert_allclose(onp.asarray(out), onp.asarray(expect),
                                rtol=2e-2, atol=2e-2)


def test_flash_kernel_cross_attention_kv_segments():
    """kv_segment_ids on the kernel path (non-causal, tq != tk)."""
    b, h, d = 1, 2, 64
    tq, tk = 128, 256
    q = _rand((b, tq, h, d), 40)
    k = _rand((b, tk, h, d), 41)
    v = _rand((b, tk, h, d), 42)
    q_seg = jnp.asarray([[0] * 128], jnp.int32)
    kv_seg = jnp.asarray([[0] * 100 + [1] * 156], jnp.int32)
    out = flash_attention(q, k, v, segment_ids=q_seg,
                          kv_segment_ids=kv_seg,
                          block_q=128, block_k=128, interpret=True)
    mask = q_seg[:, None, :, None] == kv_seg[:, None, None, :]
    expect = _attention_ref(q, k, v, mask=mask)
    onp.testing.assert_allclose(onp.asarray(out), onp.asarray(expect),
                                rtol=2e-2, atol=2e-2)


def test_dispatcher_routes_segments_to_flash(monkeypatch):
    """With segments and no dense mask the dispatcher must consider the
    kernel path (no more unconditional refusal)."""
    from mxnet_tpu.ops import attention as att

    q = (2, 512, 4, 64)
    assert att._use_flash(q, True, None, 0.0, q, platform="tpu")
    # and an explicit dense mask still forces the ref path
    assert not att._use_flash(q, True, object(), 0.0, q, platform="tpu")


def test_flash_under_a_mesh_is_shard_mapped_and_matches_ref(mesh_devices):
    """Under an ambient dp x tp mesh the kernel runs per device on its
    block of rows and heads (a Mosaic kernel cannot be GSPMD-partitioned
    on the chip — tests/test_chip_compile.py); values and gradients are
    those of the unsharded reference, packed segment ids included."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from mxnet_tpu import parallel as par
    from mxnet_tpu.ops.attention import _pallas_flash

    mesh = par.make_mesh(dp=2, tp=2, devices=mesh_devices(4))
    rs = onp.random.RandomState(3)
    sh = NamedSharding(mesh, P("dp", None, "tp", None))
    q, k, v = (jax.device_put(rs.randn(2, 256, 2, 64).astype("f"), sh)
               for _ in range(3))
    seg = jnp.asarray(onp.repeat([[0, 1], [0, 0]], 128, axis=1), jnp.int32)

    def loss(fn):
        return lambda q, k, v: (fn(q, k, v) ** 2).sum()

    for ids in (None, seg):
        mask = None if ids is None else \
            ids[:, None, :, None] == ids[:, None, None, :]
        with par.use_mesh(mesh):
            got = jax.jit(jax.value_and_grad(loss(
                lambda q, k, v: _pallas_flash(
                    q, k, v, causal=True, scale=None, q_seg=ids,
                    kv_seg=ids)), argnums=(0, 1, 2)))(q, k, v)
        want = jax.value_and_grad(loss(
            lambda q, k, v: _attention_ref(q, k, v, causal=True,
                                           mask=mask)),
            argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            onp.testing.assert_allclose(onp.asarray(a), onp.asarray(b),
                                        rtol=2e-4, atol=2e-4)


# ------------------------------------------------------- the in-kernel walk

def _fwd_and_grads(fn, *args):
    out = fn(*args)
    grads = jax.grad(lambda *a: jnp.sum(fn(*a) ** 2),
                     tuple(range(len(args))))(*args)
    return (out,) + tuple(grads)


def _assert_walk_matches_ref(q, k, v, *, causal, seg=None, kv_seg=None,
                             block_q=None, chunk=None, tol=1e-4):
    """Forward and all three gradients of the kernel (interpret mode)
    against the XLA reference with the dense mask."""
    mask = None
    if seg is not None:
        ks = seg if kv_seg is None else kv_seg
        mask = seg[:, None, :, None] == ks[:, None, None, :]
    got = _fwd_and_grads(
        lambda q, k, v: flash_attention(
            q, k, v, causal=causal, segment_ids=seg, kv_segment_ids=kv_seg,
            block_q=block_q, block_k=chunk, interpret=True), q, k, v)
    want = _fwd_and_grads(
        lambda q, k, v: _attention_ref(q, k, v, causal=causal, mask=mask),
        q, k, v)
    for name, a, r in zip(("out", "dq", "dk", "dv"), got, want):
        assert not onp.isnan(onp.asarray(a, onp.float32)).any(), name
        onp.testing.assert_allclose(
            onp.asarray(a, onp.float32), onp.asarray(r, onp.float32),
            rtol=tol, atol=tol, err_msg=name)


@pytest.mark.parametrize("block_q,chunk", [(256, 256), (512, 256),
                                           (256, 128), (1024, 512),
                                           (512, 128), (1024, 256),
                                           (None, None)])
def test_flash_causal_walk_matches_ref(block_q, chunk):
    """The cell's own sequence and head dim: the causal walk stops at the
    diagonal, masks only the diagonal's own tiles, takes each slab of
    the diagonal's square on the trapezoid of rows that sees it, and
    block and chunk need not be equal.  With nothing said the slabs are
    narrower in dq/dkv than in fwd.  Two heads: they share a grid step."""
    q, k, v = (_rand((1, 1024, 2, 64), s) for s in (50, 51, 52))
    _assert_walk_matches_ref(q, k, v, causal=True, block_q=block_q,
                             chunk=chunk)


def test_flash_noncausal_walk_matches_ref():
    """Every chunk runs, none is masked: four chunks a block."""
    q, k, v = (_rand((1, 512, 2, 64), s) for s in (53, 54, 55))
    _assert_walk_matches_ref(q, k, v, causal=False, block_q=128, chunk=128)


@pytest.mark.parametrize("docs,block_q,chunk", [
    ((200, 312), 128, 128),       # a document boundary inside a chunk
    ((256, 256), 256, 128),       # on a chunk edge
    # rows 128.. of the first 256-row block belong to document 1, whose
    # first chunk (columns 0..127, document 0) is visited for the block's
    # other rows and is fully masked for these: m stays at _MASK there
    ((128, 128, 256), 256, 128),
    ((100, 28, 384), 256, 256),   # two boundaries inside the first chunk
    ((200, 312), 512, 128),       # one block, four trapezoid slabs
    ((200, 312), None, None),     # what a call gets with nothing said
], ids=["inside", "edge", "first-chunk-masked", "two-in-one", "slabs",
        "default"])
def test_flash_causal_segment_walk_matches_ref(docs, block_q, chunk):
    q, k, v = (_rand((1, 512, 2, 64), s) for s in (56, 57, 58))
    seg = jnp.asarray([sum(([i] * n for i, n in enumerate(docs)), [])],
                      jnp.int32)
    _assert_walk_matches_ref(q, k, v, causal=True, seg=seg,
                             block_q=block_q, chunk=chunk)


@pytest.mark.parametrize("causal,tq,tk,seg", [
    (False, 256, 512, False),     # cross attention: two stretches to walk
    (True, 1024, 1024, False),    # the causal bound clipped to a stretch
    (True, 512, 512, True),
], ids=["cross", "causal", "causal-seg"])
def test_flash_walk_over_several_major_stretches(monkeypatch, causal, tq,
                                                 tk, seg):
    """A walked operand too long for VMEM is handed over in major
    stretches on the third grid axis, the accumulators carried across
    it.  Forced here by a budget that holds one 128-row chunk."""
    from mxnet_tpu.ops import flash

    monkeypatch.setattr(flash, "_VMEM_BUDGET", 900 * 1024)
    # and the fused backward's limit: one such chunk beside its whole dq
    monkeypatch.setattr(flash, "_VMEM_FUSED", flash._vmem_bytes(
        128, 128, 128, 64, 4, seg) + sum(flash._dq_vmem(1, tq, 64, 0, 4)))
    plan = flash.tile_plan(tq, tk, 64, jnp.float32, causal, seg,
                           block_q=128, chunk=128)
    assert plan.major < tk and plan.major_q < tq
    assert plan.backward == "fused"
    q = _rand((1, tq, 2, 64), 60)
    k, v = (_rand((1, tk, 2, 64), s) for s in (61, 62))
    ids = jnp.asarray([[0] * 200 + [1] * (tq - 200)], jnp.int32) \
        if seg else None
    _assert_walk_matches_ref(q, k, v, causal=causal, seg=ids, block_q=128,
                             chunk=128)


@pytest.mark.parametrize("block_q,chunk", [(256, 128), (None, None)])
def test_flash_walk_bf16(block_q, chunk):
    q, k, v = (_rand((1, 512, 4, 64), s).astype(jnp.bfloat16)
               for s in (63, 64, 65))
    _assert_walk_matches_ref(q, k, v, causal=True, block_q=block_q,
                             chunk=chunk, tol=1e-1)


def test_tile_plan_counts_what_the_causal_walk_runs():
    from mxnet_tpu.ops.flash import tile_plan

    plan = tile_plan(1024, 1024, 64, jnp.bfloat16, True, block_q=256,
                     chunk=256)
    assert (plan.block_q, plan.chunk, plan.major) == (256, 256, 1024)
    assert (plan.slab, plan.slab_bwd) == (256, 256)
    assert (plan.tiles_run, plan.tiles_full, plan.tiles_masked) == \
        (10, 16, 4)
    fine = tile_plan(1024, 1024, 64, jnp.bfloat16, True, block_q=128,
                     chunk=128)
    assert (fine.tiles_run, fine.tiles_full, fine.tiles_masked) == \
        (36, 64, 8)
    # one 1024-row block whose square is cut into trapezoid slabs counts
    # the same 10 of 16 tiles, 4 of them masked
    slabs = tile_plan(1024, 1024, 64, jnp.bfloat16, True, block_q=1024,
                      chunk=256)
    assert (slabs.block_q, slabs.tiles_run, slabs.tiles_full,
            slabs.tiles_masked) == (1024, 10, 16, 4)
    # what a call gets with nothing said: that block with the whole head
    # resident, slabs of 512 in fwd (3 of 4 tiles) and of 128 in dq/dkv
    # (36 of 64): the skip engages at the length the chip trains at
    default = tile_plan(1024, 1024, 64, jnp.bfloat16, True, heads=12)
    assert (default.block_q, default.chunk, default.major,
            default.group) == (1024, 256, 1024, 1)
    assert (default.slab, default.slab_bwd) == (512, 128)
    assert (default.tiles_run, default.tiles_full,
            default.tiles_masked) == (3, 4, 2)
    assert (default.tiles_run_bwd, default.tiles_full_bwd) == (36, 64)
    # over several stretches the chunks the diagonal crosses take every
    # row: the skip falls back to the block's granularity
    long = tile_plan(8192, 8192, 64, jnp.bfloat16, True, heads=12)
    assert long.major < 8192 and long.block_q == 1024
    assert long.tiles_run == sum(2 * 2 * (i + 1) for i in range(8))
    # non-causal runs every tile and masks none; segments mask every one
    # and want block and chunk fine
    flat = tile_plan(512, 512, 64, jnp.bfloat16, False)
    assert flat.tiles_run == flat.tiles_full and flat.tiles_masked == 0
    packed = tile_plan(1024, 1024, 64, jnp.bfloat16, True, True, heads=12)
    assert (packed.block_q, packed.chunk, packed.group) == (256, 256, 4)
    assert (packed.tiles_run, packed.tiles_masked) == (10, 10)
    # sizes that do not divide the sequence give way to ones that do
    odd = tile_plan(768, 768, 64, jnp.bfloat16, True, block_q=512,
                    chunk=512)
    assert 768 % odd.block_q == 0 and 768 % odd.chunk == 0
    assert odd.major == 768
    assert tile_plan(1152, 1152, 64, jnp.bfloat16, True).block_q == 384
    assert tile_plan(768, 768, 64, jnp.bfloat16, True).chunk == 256
    # a grid step takes heads side by side only where the count divides
    assert tile_plan(256, 256, 64, jnp.bfloat16, True, heads=25).group == 1
    assert tile_plan(256, 256, 64, jnp.bfloat16, True, heads=20).group == 4


@pytest.mark.parametrize("d", [64, 128, 256])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
def test_tile_plan_fits_vmem(d, dtype):
    from mxnet_tpu.ops.flash import (_VMEM_BUDGET, _VMEM_FUSED, _dq_vmem,
                                     _vmem_bytes, tile_plan)

    itemsize = jnp.dtype(dtype).itemsize
    for t in (256, 384, 512, 768, 1024, 2048, 4096, 8192):
        for causal, has_seg in ((True, False), (True, True), (False, False)):
            plan = tile_plan(t, t, d, dtype, causal, has_seg, heads=12)
            assert _vmem_bytes(plan.block_q, plan.chunk, plan.major, d,
                               itemsize, has_seg,
                               plan.group) <= _VMEM_BUDGET, (t, plan)
            # the one backward call: its stretch beside its whole dq,
            # under the limit it may ask Mosaic for
            assert plan.backward == "fused" and t % plan.major_q == 0
            assert plan.major_q >= plan.major
            assert _vmem_bytes(plan.block_q, plan.chunk, plan.major_q, d,
                               itemsize, has_seg, plan.group) + sum(
                _dq_vmem(plan.group, t, d, 0, itemsize)) <= _VMEM_FUSED
            assert t % plan.major == 0 and plan.major % plan.chunk == 0
            assert t % plan.block_q == 0 and plan.block_q % plan.chunk == 0
            assert plan.block_q % plan.slab == 0 == plan.slab % plan.slab_bwd
            assert plan.tiles_run <= plan.tiles_full
            assert plan.tiles_run_bwd <= plan.tiles_full_bwd


# ------------------------------------------------- grouped queries (PR 26)

@pytest.mark.parametrize("h,h_kv,causal", [(8, 2, True), (4, 1, True),
                                           (4, 2, False)])
def test_flash_grouped_queries_match_repeated_kv(h, h_kv, causal):
    """Fewer key/value than query heads: the kernels read K/V head
    ``head // share`` through their index maps; equal to K/V repeated."""
    b, t, d = 2, 256, 64
    q = _rand((b, t, h, d), 0)
    k, v = _rand((b, t, h_kv, d), 1), _rand((b, t, h_kv, d), 2)
    w = _rand((b, t, h, d), 3)
    share = h // h_kv

    def ours(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=causal, block_q=128,
                                       block_k=128, interpret=True) * w)

    def repeated(q, k, v):
        return jnp.sum(_attention_ref(q, jnp.repeat(k, share, axis=2),
                                      jnp.repeat(v, share, axis=2),
                                      causal=causal) * w)

    out = flash_attention(q, k, v, causal=causal, block_q=128, block_k=128,
                          interpret=True)
    onp.testing.assert_allclose(
        onp.asarray(out), onp.asarray(_attention_ref(q, k, v, causal=causal)),
        rtol=2e-3, atol=2e-3)
    got = jax.grad(ours, argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(repeated, argnums=(0, 1, 2))(q, k, v)
    for a, r in zip(got, want):
        assert a.shape == r.shape            # dK, dV: (B, T, H_kv, D), summed
        onp.testing.assert_allclose(onp.asarray(a), onp.asarray(r),
                                    rtol=2e-3, atol=2e-3)


def test_grouped_queries_go_to_the_kernel_and_plan_one_head_a_step():
    from mxnet_tpu.ops.attention import _use_flash
    from mxnet_tpu.ops.flash import tile_plan

    # the hybrid cell's call: T 8,192, D 128, 32 heads over 2
    assert _use_flash((1, 8192, 32, 128), True, None, 0.0,
                      (1, 8192, 2, 128), platform="tpu")
    # another length or head dim is still not the kernel's
    assert not _use_flash((1, 8192, 32, 128), True, None, 0.0,
                          (1, 4096, 2, 128), platform="tpu")
    assert not _use_flash((1, 8192, 32, 128), True, None, 0.0,
                          (1, 8192, 3, 128), platform="tpu")
    plan = tile_plan(8192, 8192, 128, jnp.bfloat16, True, heads=32,
                     kv_heads=2)
    assert plan.group == 1 and plan.block_q == 1024 and plan.major == 4096
    with pytest.raises(ValueError):
        tile_plan(1024, 1024, 64, jnp.bfloat16, True, heads=12, kv_heads=5)


def test_tile_plan_of_the_gpt2_cell_is_pinned():
    """``train_124m_seq1024``'s flash calls (12 equal heads of 64, T 1,024,
    bf16, causal) take the plan they took before grouped queries."""
    from mxnet_tpu.ops.flash import TilePlan, tile_plan

    want = TilePlan(block_q=1024, chunk=256, slab=512, slab_bwd=128,
                    major=1024, major_q=1024, group=1, tiles_run=3,
                    tiles_full=4, tiles_masked=2, tiles_run_bwd=36,
                    tiles_full_bwd=64, backward="fused",
                    dq_bytes=4 * 1024 * 128, bwd_vmem=0)
    assert tile_plan(1024, 1024, 64, jnp.bfloat16, True, heads=12) == want
    assert tile_plan(1024, 1024, 64, jnp.bfloat16, True, heads=12,
                     kv_heads=12) == want


# ------------------------------------------ the one backward call (PR 50)
# name: query heads, key heads, value heads, d, dv, then what the call
# says (window, a second pair's width and heads, documents) and what the
# plan is made to say (block, chunk, limits on VMEM that cut the walked
# operand into stretches of so many rows, a fused limit nothing fits)
_BACKWARD = {
    "plain": dict(heads=(2, 2, 2), block=(256, 128)),
    "plain-one-block-of-slabs": dict(heads=(2, 2, 2), block=(512, 128)),
    "plain-not-causal": dict(heads=(2, 2, 2), block=(128, 128),
                             causal=False),
    "the-plans-own-sizes": dict(heads=(4, 4, 4), t=1024),
    "shared-kv-heads": dict(heads=(8, 2, 2), block=(128, 128)),
    "one-kv-head-at-128": dict(heads=(4, 1, 1), d=128, dv=128,
                               block=(256, 128)),
    "window-narrower-than-a-block": dict(heads=(4, 2, 2), window=96,
                                         block=(256, 128)),
    "window-wider-than-a-block": dict(heads=(2, 2, 2), window=300,
                                      block=(128, 128)),
    "values-wider-than-keys": dict(heads=(4, 2, 1), dv=128,
                                   block=(256, 128)),
    "values-wider-under-a-window": dict(heads=(4, 2, 1), dv=128, window=160,
                                        block=(256, 128)),
    "two-operands-one-rotary-key": dict(heads=(4, 4, 4), d=128, dv=128,
                                        two=(64, 1), block=(128, 128)),
    "two-operands-a-key-a-head": dict(heads=(4, 4, 4), d=128, dv=128,
                                      two=(64, 4), block=(256, 128)),
    "segments": dict(heads=(2, 2, 2), docs=(200, 312), block=(256, 128)),
    "segments-not-causal": dict(heads=(2, 2, 2), docs=(128, 128, 256),
                                block=(128, 128), causal=False),
    "several-stretches": dict(heads=(2, 2, 2), t=1024, block=(128, 128),
                              stretch=128),
    "several-stretches-under-a-window": dict(
        heads=(2, 2, 2), t=1024, window=300, block=(128, 128), stretch=256),
    "several-stretches-two-operands": dict(
        heads=(2, 2, 2), d=128, dv=128, two=(64, 1), t=1024,
        block=(128, 128), stretch=256),
    "sent-to-the-split": dict(heads=(4, 2, 2), block=(256, 128), fused=0),
}


@pytest.mark.parametrize("case", sorted(_BACKWARD))
def test_one_backward_call_against_ref_and_against_the_two(monkeypatch,
                                                           case):
    """``flash_bwd`` takes dq, dk and dv (dq2, dk2) from one pass over the
    score tiles: each against ``_attention_ref``'s gradient, and against
    what ``flash_bwd_dq`` + ``flash_bwd_dkv`` give on the same inputs,
    which differs only in the order of dq's float32 additions."""
    from mxnet_tpu.ops import flash

    c = dict(_BACKWARD[case])
    h, hk, hv = c["heads"]
    t, d, dv = c.get("t", 512), c.get("d", 64), c.get("dv", 64)
    causal, window = c.get("causal", True), c.get("window")
    block_q, chunk = c.get("block") or (None, None)
    ops = [_rand((1, t, h, d), 70), _rand((1, t, hk, d), 71),
           _rand((1, t, hv, dv), 72)]
    second = ()
    if "two" in c:
        d2, h2 = c["two"]
        second = ("q2", "k2")
        ops += [_rand((1, t, h, d2), 73), _rand((1, t, h2, d2), 74)]
    ct = _rand((1, t, h, dv), 75)
    seg = mask = None
    if "docs" in c:
        seg = jnp.asarray([sum(([i] * n for i, n in enumerate(c["docs"])),
                               [])], jnp.int32)
        mask = seg[:, None, :, None] == seg[:, None, None, :]
    fits = 48 * 2 ** 20
    if "stretch" in c:          # what holds such a stretch, one head a
        d2 = c.get("two", (0,))[0]                  # step; fused: beside
        held = flash._vmem_bytes(block_q, chunk, c["stretch"], d, 4,
                                 d2=d2)             # its whole dq
        fits = held + sum(flash._dq_vmem(1, t, d, d2, 4))
        monkeypatch.setattr(flash, "_VMEM_BUDGET", held)
        monkeypatch.setattr(flash, "_VMEM_FUSED", fits)

    def grads(f):
        return jax.grad(lambda *a: jnp.sum(f(*a) * ct),
                        argnums=tuple(range(len(ops))))(*ops)

    def kernel(*a):
        return flash_attention(
            *a[:3], causal=causal, window=window, segment_ids=seg,
            block_q=block_q, block_k=chunk, interpret=True,
            **dict(zip(second, a[3:])))

    def calls():
        found = set()

        def visit(jaxpr):
            for eqn in jaxpr.eqns:
                if eqn.primitive.name == "pallas_call":
                    found.add(eqn.params["name"])
                for sub in jax.core.jaxprs_in_params(eqn.params):
                    visit(sub)
        visit(jax.make_jaxpr(lambda: grads(kernel))().jaxpr)
        return found

    want = grads(lambda *a: _attention_ref(
        *a[:3], causal=causal, window=window, mask=mask,
        **dict(zip(second, a[3:]))))
    if "fused" in c:            # a limit the plan can see nothing fit
        monkeypatch.setattr(flash, "_VMEM_FUSED", c["fused"])
    plan = flash.tile_plan(t, t, d, jnp.float32, causal, seg is not None,
                           heads=h, kv_heads=hk, block_q=block_q,
                           chunk=chunk, window=window, dv=dv, v_heads=hv,
                           d2=c.get("two", (0, None))[0],
                           k2_heads=c.get("two", (0, None))[1])
    if "stretch" in c:
        assert plan.major_q == c["stretch"]
    first = "split" if "fused" in c else "fused"
    assert plan.backward == first
    assert (plan.dq_bytes > 0) == (first == "fused")
    one, two = {"flash_fwd", "flash_bwd"}, \
        {"flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"}
    got = grads(kernel)
    assert calls() == (one if first == "fused" else two)
    # the other form on the same inputs
    monkeypatch.setattr(flash, "_VMEM_FUSED", 0 if first == "fused" else fits)
    other = grads(kernel)
    assert calls() == (two if first == "fused" else one)
    names = ("dq", "dk", "dv", "dq2", "dk2")
    for name, g, o, w in zip(names, got, other, want):
        assert g.shape == o.shape == w.shape and g.dtype == o.dtype, name
        assert not onp.isnan(onp.asarray(g)).any(), name
        onp.testing.assert_allclose(g, w, rtol=2e-4, atol=5e-5,
                                    err_msg=name)
        top = float(jnp.abs(w).max())
        assert float(jnp.abs(g - o).max()) <= (
            2e-6 * top if name in ("dq", "dq2") else 0.0), name


# the eight cells' backward call sites and two longer sequences: what
# tile_plan says of the backward, from shapes alone
_BACKWARD_PLANS = {
    "gpt2": (dict(tq=1024, d=64, heads=12), "fused", 1024 * 128, False),
    "ouro": (dict(tq=8192, d=128, heads=16), "fused", 8192 * 128, True),
    "nemotron": (dict(tq=8192, d=128, heads=32, kv_heads=2), "fused",
                 8192 * 128, True),
    "qwen3_next": (dict(tq=8192, d=256, heads=16, kv_heads=2), "fused",
                   8192 * 256, True),
    "granite": (dict(tq=8192, d=64, heads=32, kv_heads=8), "fused",
                8192 * 128, True),
    "mellum2_window": (dict(tq=8192, d=128, heads=32, kv_heads=4,
                            window=1024), "fused", 8192 * 128, True),
    "phi4flash_window": (dict(tq=8192, d=64, heads=40, kv_heads=20, dv=128,
                              v_heads=10, window=512), "fused", 8192 * 128,
                         True),
    "moonlight": (dict(tq=8192, d=128, heads=16, kv_heads=16, dv=128,
                       v_heads=16, d2=64, k2_heads=1), "fused",
                  8192 * (128 + 128), True),
    "t32768": (dict(tq=32768, d=128, heads=16), "fused", 32768 * 128, True),
    "t65536": (dict(tq=65536, d=128, heads=16), "split", 0, False),
}


@pytest.mark.parametrize("site", sorted(_BACKWARD_PLANS))
def test_tile_plan_says_which_backward_a_call_gets(site):
    """Fused wherever the float32 dq of the group's whole query sequence
    fits beside a step's working set; the plan asks for more scoped VMEM
    than a call has unasked exactly where that needs it, never for more
    than its limit, and keeps the two calls past it."""
    from mxnet_tpu.ops.flash import (_VMEM_BUDGET, _VMEM_FUSED, _vmem_bytes,
                                     tile_plan)

    call, backward, rows_by_lanes, asks = _BACKWARD_PLANS[site]
    call = dict(call)
    tq, d = call.pop("tq"), call.pop("d")
    plan = tile_plan(tq, tq, d, jnp.bfloat16, True, **call)
    assert plan.backward == backward
    assert plan.dq_bytes == 4 * plan.group * rows_by_lanes
    assert (plan.bwd_vmem > 0) == asks
    if asks:
        held = plan.dq_bytes + plan.dq_bytes      # and twice in q's type
        step = _vmem_bytes(plan.block_q, plan.chunk, plan.major_q,
                           max(d, call.get("dv", d)), 2, False, plan.group,
                           call.get("d2", 0))
        assert _VMEM_BUDGET < step + held <= _VMEM_FUSED
        assert step + held < plan.bwd_vmem <= _VMEM_FUSED + _VMEM_BUDGET
        assert plan.major_q == min(tq, 8192)
