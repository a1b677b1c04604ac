"""``ops/flash.py``'s causal window and its values wider than its keys:
the kernels in interpret mode against the masked XLA path, forward and
gradients, at windows smaller than, equal to and larger than a chunk,
over one stretch and several, with grouped queries and with a
differential head's layout (values half as many heads, twice as wide);
the chunks the window hides are not visited (``tile_plan``'s counts); a
call without either plans bit for bit as it did."""
import jax
import jax.numpy as jnp
import numpy as onp
import pytest

from mxnet_tpu.ops import flash
from mxnet_tpu.ops.attention import _attention_ref, flash_attention as entry
from mxnet_tpu.ops.flash import TilePlan, flash_attention, tile_plan


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
]


@pytest.mark.parametrize("t,h,hk,hv,d,dv,window,block_q,chunk", CASES)
def test_against_the_masked_xla_path(t, h, hk, hv, d, dv, window, block_q,
                                     chunk):
    q, k, v, w = _qkv(t, h, hk, hv, d, dv)
    _close(*_both(q, k, v, w, window=window, block_q=block_q,
                  block_k=chunk))


@pytest.mark.parametrize("t,h,hk,hv,d,dv,window", [
    (1024, 4, 2, 1, 64, 128, 200), (1024, 2, 2, 2, 64, 64, 384),
    (1024, 4, 2, 1, 64, 128, None)])
def test_over_several_stretches(monkeypatch, t, h, hk, hv, d, dv, window):
    """A VMEM budget that holds one chunk of the walked operand: the third
    grid axis walks eight stretches, the window's clamps name the ones a
    block needs."""
    monkeypatch.setattr(flash, "_VMEM_BUDGET", 1_300_000)
    plan = tile_plan(t, t, d, jnp.float32, True, heads=h, kv_heads=hk,
                     block_q=128, chunk=128, window=window, dv=dv,
                     v_heads=hv)
    assert plan.major == 128 and plan.major_q == 128
    q, k, v, w = _qkv(t, h, hk, hv, d, dv)
    _close(*_both(q, k, v, w, window=window, block_q=128, block_k=128))


def test_window_of_one_key_is_the_value_itself():
    q, k, v, _w = _qkv(256, 2, 2, 2, 64, 64)
    out = flash_attention(q, k, v, causal=True, window=1, interpret=True)
    onp.testing.assert_allclose(out, v, rtol=1e-6, atol=1e-6)


def test_the_chunks_outside_the_window_are_not_visited():
    """At the benchmark's size (8,192 tokens, 40 heads of 64 over 20, a
    value of 128) a 512-key window runs 31 of the 256 (512, 512) tiles a
    head where the causal mask alone runs 144, in blocks no taller than
    the window."""
    kw = dict(heads=40, kv_heads=20, dv=128, v_heads=10)
    full = tile_plan(8192, 8192, 64, jnp.bfloat16, True, **kw)
    win = tile_plan(8192, 8192, 64, jnp.bfloat16, True, window=512, **kw)
    assert (full.block_q, full.tiles_run, full.tiles_full) == (1024, 144, 256)
    assert (win.block_q, win.chunk) == (512, 256)
    assert (win.tiles_run, win.tiles_full) == (31, 256)
    assert win.tiles_masked == win.tiles_run
    assert win.tiles_run_bwd * 16 == win.tiles_run * 256   # (128, 128) tiles
    # a window as wide as the sequence skips nothing more than the mask
    wide = tile_plan(1024, 1024, 64, jnp.bfloat16, True, window=1024)
    assert wide.tiles_run == wide.tiles_full


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
# value (PR 38's tree): the four cells' own calls among them
AS_BEFORE = [
    ((1024, 1024, 64, "bfloat16", True), dict(heads=12),
     (1024, 256, 512, 128, 1024, 1024, 1, 3, 4, 2, 36, 64)),
    ((8192, 8192, 128, "bfloat16", True), dict(heads=32, kv_heads=2),
     (1024, 256, 512, 128, 4096, 4096, 1, 144, 256, 32, 2304, 4096)),
    ((8192, 8192, 256, "bfloat16", True), dict(heads=16, kv_heads=2),
     (512, 256, 512, 128, 2048, 2048, 1, 136, 256, 16, 2176, 4096)),
    ((8192, 8192, 64, "bfloat16", True), dict(heads=32, kv_heads=8),
     (1024, 256, 512, 128, 4096, 4096, 1, 144, 256, 32, 2304, 4096)),
    ((512, 512, 64, "bfloat16", False), dict(heads=16),
     (512, 512, 512, 128, 512, 512, 2, 1, 1, 0, 16, 16)),
    ((1024, 1024, 64, "float32", True, True), dict(heads=4),
     (256, 256, 256, 256, 1024, 1024, 2, 10, 16, 10, 10, 16)),
    ((4096, 4096, 64, "bfloat16", True), dict(heads=12),
     (1024, 256, 512, 128, 4096, 4096, 1, 36, 64, 8, 528, 1024)),
    ((8192, 8192, 256, "float32", True), dict(heads=2),
     (512, 256, 512, 128, 1024, 1024, 1, 136, 256, 16, 2176, 4096)),
]


@pytest.mark.parametrize("args,kw,want", AS_BEFORE)
def test_a_call_without_either_plans_as_before(args, kw, want):
    plan = tile_plan(*args, **kw)
    assert plan == TilePlan(*want)
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
