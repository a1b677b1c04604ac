"""``ops/flash.py``'s causal window and its values wider than its keys:
the kernels in interpret mode against the masked XLA path, forward and
gradients, at windows smaller than, equal to and larger than a chunk,
over one stretch and several, with grouped queries and with a
differential head's layout (values half as many heads, twice as wide);
a windowed block walks its band by the two edges (trapezoid slabs on the
diagonal and on the trailing edge, the plain loop between), so windows
that are no multiple of anything, wider than a block, as wide as the
sequence, of one key, and bands that straddle two stretches; the tiles a
windowed call runs stay close to the pairs its mask lets through
(``tile_plan``'s counts); a call without either plans bit for bit as it
did."""
import jax
import jax.numpy as jnp
import numpy as onp
import pytest

from mxnet_tpu.ops import flash
from mxnet_tpu.ops.attention import _attention_ref, flash_attention as entry
from mxnet_tpu.ops.flash import flash_attention, tile_plan


def _qkv(t, h, hk, hv, d, dv, b=1, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(ks[0], (b, t, h, d)),
            jax.random.normal(ks[1], (b, t, hk, d)),
            jax.random.normal(ks[2], (b, t, hv, dv)),
            jax.random.normal(ks[3], (b, t, h, dv)))


def _both(q, k, v, w, **kw):
    """(out, grads) of the kernel and of the masked XLA path."""
    window = kw.get("window")

    def run(f):
        out = f(q, k, v)
        grads = jax.grad(lambda *o: jnp.sum(f(*o) * w),
                         argnums=(0, 1, 2))(q, k, v)
        return out, grads

    return (run(lambda *o: flash_attention(*o, causal=True, interpret=True,
                                           **kw)),
            run(lambda *o: _attention_ref(*o, causal=True, window=window)))


def _close(got, want):
    (o1, g1), (o2, g2) = got, want
    onp.testing.assert_allclose(o1, o2, rtol=2e-5, atol=2e-5)
    for a, b in zip(g1, g2):
        assert a.shape == b.shape
        onp.testing.assert_allclose(a, b, rtol=2e-4, atol=5e-5)


# t, heads, key heads, value heads, d, dv, window, block_q, chunk
CASES = [
    (512, 4, 4, 4, 64, 64, 96, 256, 128),       # smaller than a chunk
    (512, 4, 4, 4, 64, 64, 128, 256, 128),      # a chunk
    (512, 4, 4, 4, 64, 64, 300, 128, 128),      # larger, no multiple
    (512, 2, 2, 2, 64, 64, 1000, 256, 128),     # wider than the sequence
    (512, 4, 2, 2, 64, 64, 128, 128, 128),      # grouped queries
    (512, 4, 2, 1, 64, 128, None, 256, 128),    # values two key heads wide
    (512, 4, 2, 1, 64, 128, 160, 256, 128),     # both
    (1024, 2, 2, 2, 64, 64, 512, None, None),   # the plan's own sizes
    (512, 8, 4, 2, 128, 256, 256, 128, 128),    # head size 128
    # head size 128, eight query heads to a key head: the window smaller
    # than, equal to and larger than a block
    (512, 8, 1, 1, 128, 128, 96, 128, 128),
    (512, 8, 1, 1, 128, 128, 128, 128, 128),
    (512, 8, 1, 1, 128, 128, 300, 128, 128),
    # what the two-edged walk can get wrong.  The plan's own sizes under
    # windows that are no multiple of the chunk: blocks of 256 with two
    # slabs of trailing edge, and blocks of 512 whose backward has a
    # plain chunk between the edges
    (1024, 2, 2, 2, 64, 64, 300, None, None),
    (1024, 1, 1, 1, 64, 64, 1000, None, None),
    (512, 2, 2, 2, 64, 64, 512, 128, 128),      # the sequence: three plain
    (512, 2, 1, 1, 64, 64, 384, 128, 128),      # a whole number of blocks
    (256, 2, 2, 2, 64, 64, 1, 128, 128),        # one key
    (256, 2, 2, 2, 64, 64, 128, 256, 128),      # one block: no older key
    (256, 2, 2, 2, 64, 64, 40, 256, 128),       # both edges in every slab
    (512, 40, 20, 10, 64, 128, 160, 128, 128),  # SambaY's heads and widths
]


@pytest.mark.parametrize("t,h,hk,hv,d,dv,window,block_q,chunk", CASES)
def test_against_the_masked_xla_path(t, h, hk, hv, d, dv, window, block_q,
                                     chunk):
    q, k, v, w = _qkv(t, h, hk, hv, d, dv)
    _close(*_both(q, k, v, w, window=window, block_q=block_q,
                  block_k=chunk))


@pytest.mark.parametrize("t,h,hk,hv,d,dv,window,budget,major", [
    (1024, 4, 2, 1, 64, 128, 200, 1_300_000, 128),
    (1024, 2, 2, 2, 64, 64, 384, 1_300_000, 128),
    (1024, 4, 2, 1, 64, 128, None, 1_300_000, 128),
    # a window of one block: every block's trailing edge lies in the
    # stretch before its own square, where its last row sees nothing
    (1024, 2, 2, 2, 64, 64, 128, 1_300_000, 128),
    # stretches of two and of four blocks: a band lies in its block's own
    # stretch or straddles the one before; the plain loop crosses three
    (1024, 2, 2, 2, 64, 64, 300, 1_500_000, 256),
    (1024, 2, 2, 2, 64, 64, 1000, 2_500_000, 512),
    (1024, 4, 2, 1, 64, 128, 200, 2_500_000, 512)])
def test_over_several_stretches(monkeypatch, t, h, hk, hv, d, dv, window,
                                budget, major):
    """A VMEM budget that holds a chunk or a few of the walked operand:
    the third grid axis walks the stretches, the window's clamps name the
    ones a block needs."""
    monkeypatch.setattr(flash, "_VMEM_BUDGET", budget)
    # and the fused backward's limit: that budget beside its whole dq
    monkeypatch.setattr(flash, "_VMEM_FUSED", flash._vmem_bytes(
        128, 128, major, max(d, dv), 4) + sum(flash._dq_vmem(1, t, d, 0, 4)))
    plan = tile_plan(t, t, d, jnp.float32, True, heads=h, kv_heads=hk,
                     block_q=128, chunk=128, window=window, dv=dv,
                     v_heads=hv)
    assert plan.major == major and plan.major_q == major
    assert plan.backward == "fused"
    q, k, v, w = _qkv(t, h, hk, hv, d, dv)
    _close(*_both(q, k, v, w, window=window, block_q=128, block_k=128))


@pytest.mark.parametrize("window", [300, 640])
def test_a_stretch_that_is_no_whole_number_of_blocks(window):
    """1,536 keys in stretches of 384 under blocks of 256: a block's own
    square can straddle two stretches, so the slabs run in sections of
    128, each in one stretch whole."""
    t, heads = 1536, 2
    q, k, v, w = _qkv(t, heads, heads, heads, 64, 64)
    plan = tile_plan(t, t, 64, jnp.float32, True, heads=heads, block_q=256,
                     chunk=128, window=window)._replace(major=384,
                                                        major_q=384)
    assert (plan.block_q, plan.slab, plan.slab_bwd) == (256, 128, 128)

    def flat(x):
        return x.transpose(0, 2, 1, 3).reshape(heads, t, 64)

    def kernel(q, k, v):
        out = flash._flash(flat(q), flat(k), flat(v), None, None, heads,
                           True, 0.125, plan, True, window)
        return out.reshape(1, heads, t, 64).transpose(0, 2, 1, 3)

    def run(f):
        return f(q, k, v), jax.grad(lambda *o: jnp.sum(f(*o) * w),
                                    argnums=(0, 1, 2))(q, k, v)

    _close(run(kernel), run(lambda *o: _attention_ref(
        *o, causal=True, window=window)))


def test_window_of_one_key_is_the_value_itself():
    q, k, v, _w = _qkv(256, 2, 2, 2, 64, 64)
    out = flash_attention(q, k, v, causal=True, window=1, interpret=True)
    onp.testing.assert_allclose(out, v, rtol=1e-6, atol=1e-6)


def test_the_chunks_outside_the_window_are_not_visited():
    """At the benchmark's size (8,192 tokens, 40 heads of 64 over 20, a
    value of 128) a 512-key window runs 31 of the 256 (512, 512) tiles a
    head where the causal mask alone runs 144, in blocks no taller than
    the window; backward, in (128, 128) tiles, the two edges' trapezoids
    run 310 where the band's bounding boxes held 496."""
    kw = dict(heads=40, kv_heads=20, dv=128, v_heads=10)
    full = tile_plan(8192, 8192, 64, jnp.bfloat16, True, **kw)
    win = tile_plan(8192, 8192, 64, jnp.bfloat16, True, window=512, **kw)
    assert (full.block_q, full.tiles_run, full.tiles_full) == (1024, 144, 256)
    assert (win.block_q, win.chunk, win.slab) == (512, 256, 512)
    assert (win.tiles_run, win.tiles_full) == (31, 256)
    # a block as wide as its forward slab: each edge is one tile
    assert win.tiles_masked == win.tiles_run
    assert (win.tiles_run_bwd, win.tiles_full_bwd) == (310, 4096)
    # a window as wide as the sequence skips what the mask alone skips
    assert tile_plan(1024, 1024, 64, jnp.bfloat16, True, window=1024) \
        == tile_plan(1024, 1024, 64, jnp.bfloat16, True)


# heads, key heads, value heads, d, dv, window, and how many times the
# pairs the mask lets through the tiles may hold, forward and backward:
# the two windowed cells.  SambaY's block is one forward slab wide, so
# each of its forward edges is a whole (512, 512) tile (the chip ran two
# 256-wide slabs an edge slower, flash.py's table), and four 128-wide
# backward slabs cut a 512-key edge more coarsely than eight a 1,024-key
# one
@pytest.mark.parametrize("h,hk,hv,d,dv,window,fwd,bwd", [
    (32, 4, 4, 128, 128, 1024, 1.6, 1.2),
    (40, 20, 10, 64, 128, 512, 2.0, 1.25)])
def test_a_windowed_call_runs_little_more_than_its_mask_lets_through(
        h, hk, hv, d, dv, window, fwd, bwd):
    """From ``tile_plan`` alone: the (query, key) pairs of the tiles an
    8,192-token call runs against the pairs ``0 <= q - k < window`` holds
    (the band's bounding boxes, which the walk before this one ran, held
    2.0 times forward and backward at Mellum's sizes and 2.0 at
    SambaY's)."""
    t = 8192
    kw = dict(heads=h, kv_heads=hk, v_heads=hv, dv=dv)
    plan = tile_plan(t, t, d, jnp.bfloat16, True, window=window, **kw)
    pairs = sum(min(i + 1, window) for i in range(t))
    assert pairs <= plan.tiles_run * plan.slab ** 2 <= fwd * pairs
    assert pairs <= plan.tiles_run_bwd * plan.slab_bwd ** 2 <= bwd * pairs
    assert plan.tiles_masked <= plan.tiles_run
    full = tile_plan(t, t, d, jnp.bfloat16, True, **kw)
    assert plan.tiles_run_bwd < 0.27 * full.tiles_run_bwd


def test_mellums_windowed_call_by_the_new_schedule():
    """32 heads of 128 over 4 under a 1,024-key window at T 8,192 (the
    call of three layers in four of ``train_mellum2_seq8192``): 45 of 256
    forward tiles where the bounding boxes ran 60, of which the 30 on an
    edge build a mask; the same call without the window as before."""
    kw = dict(heads=32, kv_heads=4)
    win = tile_plan(8192, 8192, 128, jnp.bfloat16, True, window=1024, **kw)
    assert (win.block_q, win.chunk, win.slab, win.slab_bwd, win.major) \
        == (1024, 256, 512, 128, 4096)
    assert (win.tiles_run, win.tiles_masked, win.tiles_run_bwd) \
        == (45, 30, 540)
    full = tile_plan(8192, 8192, 128, jnp.bfloat16, True, **kw)
    # the one backward call holds Q and dO whole, so a block's own square
    # runs by its slabs' trapezoids, 36 of 64 tiles (2,304 in all when it
    # was walked in two stretches, each block's square then whole)
    assert (full.tiles_run, full.tiles_run_bwd, full.tiles_masked) \
        == (144, 2080, 32)
    assert win.major_q == full.major_q == 8192


def test_a_window_needs_a_causal_unpacked_call():
    q, k, v, _w = _qkv(256, 2, 2, 2, 64, 64)
    with pytest.raises(ValueError):
        flash_attention(q, k, v, causal=False, window=64, interpret=True)
    with pytest.raises(ValueError):
        tile_plan(256, 256, 64, jnp.float32, True, True, window=64)
    with pytest.raises(ValueError):
        tile_plan(256, 256, 64, jnp.float32, True, window=0)
    with pytest.raises(ValueError):              # 4 heads over 3 value heads
        tile_plan(256, 256, 64, jnp.float32, True, heads=4, kv_heads=2,
                  v_heads=3, dv=64)


# what ``tile_plan`` gave these calls before it knew a window or a wider
# value (PR 38's tree): the four cells' own calls among them.  Since PR 50
# the backward's stretch (``major_q``) is cut by the fused call's own
# limit: whole at T 8,192 in bf16, so each block's own square runs as
# slabs there too and ``tiles_run_bwd`` fell from 2,304 / 2,176 to 2,080
AS_BEFORE = [
    ((1024, 1024, 64, "bfloat16", True), dict(heads=12),
     (1024, 256, 512, 128, 1024, 1024, 1, 3, 4, 2, 36, 64)),
    ((8192, 8192, 128, "bfloat16", True), dict(heads=32, kv_heads=2),
     (1024, 256, 512, 128, 4096, 8192, 1, 144, 256, 32, 2080, 4096)),
    ((8192, 8192, 256, "bfloat16", True), dict(heads=16, kv_heads=2),
     (512, 256, 512, 128, 2048, 8192, 1, 136, 256, 16, 2080, 4096)),
    ((8192, 8192, 64, "bfloat16", True), dict(heads=32, kv_heads=8),
     (1024, 256, 512, 128, 4096, 8192, 1, 144, 256, 32, 2080, 4096)),
    ((512, 512, 64, "bfloat16", False), dict(heads=16),
     (512, 512, 512, 128, 512, 512, 2, 1, 1, 0, 16, 16)),
    ((1024, 1024, 64, "float32", True, True), dict(heads=4),
     (256, 256, 256, 256, 1024, 1024, 2, 10, 16, 10, 10, 16)),
    ((4096, 4096, 64, "bfloat16", True), dict(heads=12),
     (1024, 256, 512, 128, 4096, 4096, 1, 36, 64, 8, 528, 1024)),
    ((8192, 8192, 256, "float32", True), dict(heads=2),
     (512, 256, 512, 128, 1024, 4096, 1, 136, 256, 16, 2176, 4096)),
]


@pytest.mark.parametrize("args,kw,want", AS_BEFORE)
def test_a_call_without_either_plans_as_before(args, kw, want):
    plan = tile_plan(*args, **kw)
    assert plan[:12] == want and plan.backward == "fused"
    # and stating the keys' own width and head count changes nothing
    hk = kw.get("kv_heads", kw["heads"])
    assert tile_plan(*args, **kw, dv=args[2], v_heads=hk) == plan


def test_plan_event_says_window_and_dv_only_where_they_are():
    from mxnet_tpu import observability as obs

    q, k, v, _w = _qkv(512, 4, 2, 1, 64, 128)
    tr = obs.enable_tracing()
    try:
        flash_attention(q, k, k, causal=True, interpret=True)
        flash_attention(q, k, v, causal=True, window=128, block_q=128,
                        interpret=True)
        plain, wide = tr.spans(name="flash.plan")
    finally:
        obs.disable_tracing()
    assert "window" not in plain.attrs and "dv" not in plain.attrs
    assert wide.attrs["window"] == 128 and wide.attrs["dv"] == 128
    assert wide.attrs["d"] == 64
    assert wide.attrs["tiles_run"] < wide.attrs["tiles_full"]


def test_the_entry_takes_the_window_to_the_xla_path_off_the_tpu():
    q, k, v, _w = _qkv(256, 4, 2, 1, 64, 128)
    onp.testing.assert_array_equal(
        entry(q, k, v, causal=True, window=64),
        _attention_ref(q, k, v, causal=True, window=64))
    full = entry(q, k, v, causal=True)
    assert full.shape == (1, 256, 4, 128)
    assert not onp.allclose(full, entry(q, k, v, causal=True, window=64))
