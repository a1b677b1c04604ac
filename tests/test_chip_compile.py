"""The Pallas kernels of the main path, compiled by the chip's own compiler
for a DESCRIBED TPU v5e (no chip attached): what interpret mode cannot show
— a precision Mosaic refuses, a tile it cannot lay out, too much VMEM.

These are compiles, not chip runs: they say nothing about results or
times.  They run under the package-default matmul precision ('highest'),
the setting every user process has.  Real widths of GPT-2 124M: 12 heads
of 64.  No whole-model compile at a real size here (tier-1's time budget):
the whole steps at the end are tiny twins, read for what their text holds.
"""
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest

import mxnet_tpu  # noqa: F401  (sets the package-default matmul precision)
from mxnet_tpu.ops.flash import flash_attention
from mxnet_tpu.ops.paged import paged_attention


@pytest.fixture(scope="module")
def chips():
    """The four described devices of a v5e 2x2 host; skips where this
    installation cannot describe the topology."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu, or one that cannot describe v5e
        pytest.skip(f"cannot describe a v5e topology here: {e}")
    # such a compile is written to the persistent cache but cannot be read
    # back without a chip: the next run would warn and compile again
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield list(topo.devices)
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()


@pytest.fixture
def chip(chips):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(chips[0])


def _kernels(compiled) -> int:
    return compiled.as_text().count("tpu_custom_call")


@pytest.mark.parametrize("shape,dtype,causal", [
    ((16, 1024, 12, 64), jnp.bfloat16, True),   # GPT-2 124M training
    ((4, 4096, 12, 64), jnp.bfloat16, True),    # long-sequence training
    ((2, 1024, 4, 128), jnp.float32, True),     # f32: true-f32 contract
    ((8, 1024, 12, 64), jnp.bfloat16, True),    # the benchmark's cell
    ((8, 512, 16, 64), jnp.bfloat16, False),    # BERT-large training
    # q and dO of one head do not fit VMEM: the walk's third grid axis
    ((1, 8192, 2, 256), jnp.float32, True),
])
def test_flash_fwd_bwd_compiles_for_v5e(chip, shape, dtype, causal):
    assert jax.config.jax_default_matmul_precision == "highest"

    def loss(q, k, v):
        out = flash_attention(q, k, v, causal=causal, interpret=False)
        return out.astype(jnp.float32).sum()

    x = jax.ShapeDtypeStruct(shape, dtype, sharding=chip)
    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        x, x, x).compile()
    assert _kernels(compiled) == 2          # flash_fwd, flash_bwd


def test_flash_with_segments_compiles_for_v5e(chip):
    """Packed sequences: the walked operand's segment ids are sliced per
    chunk along lanes, the block's own broadcast down the tile."""
    def loss(q, k, v, seg):
        out = flash_attention(q, k, v, causal=True, segment_ids=seg,
                              interpret=False)
        return out.astype(jnp.float32).sum()

    x = jax.ShapeDtypeStruct((8, 1024, 12, 64), jnp.bfloat16, sharding=chip)
    seg = jax.ShapeDtypeStruct((8, 1024), jnp.int32, sharding=chip)
    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        x, x, x, seg).compile()
    assert _kernels(compiled) == 2


@pytest.mark.parametrize("tq", [1, 64])
@pytest.mark.parametrize("quant", [False, True], ids=["bf16", "int8"])
def test_paged_attention_compiles_for_v5e(chip, tq, quant):
    slots, heads, d, ps, pages, table = 4, 12, 64, 16, 256, 64

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    q = sds((slots, tq, heads, d), jnp.bfloat16)
    page = sds((pages + 1, ps, heads, d),
               jnp.int8 if quant else jnp.bfloat16)
    scale = sds((pages + 1, ps, heads, 1), jnp.float32)
    tbl = sds((slots, table), jnp.int32)
    qpos = sds((slots, tq), jnp.int32)
    if quant:
        fn = lambda q, k, v, t, p, ks, vs: paged_attention(  # noqa: E731
            q, k, v, t, p, k_scale=ks, v_scale=vs, interpret=False)
        args = (q, page, page, tbl, qpos, scale, scale)
    else:
        fn = lambda q, k, v, t, p: paged_attention(  # noqa: E731
            q, k, v, t, p, interpret=False)
        args = (q, page, page, tbl, qpos)
    assert _kernels(jax.jit(fn).lower(*args).compile()) == 1


def test_flash_under_a_mesh_runs_per_device(chips, monkeypatch):
    """GSPMD cannot partition a Mosaic kernel (jax refuses to lower one
    into a multi-device program): under dp=2 x tp=2 the dispatcher must
    shard_map it, each device on its 4 rows x 6 heads."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from mxnet_tpu import base, parallel as par
    from mxnet_tpu.ops import attention

    # a CPU process: steer the dispatch onto its TPU branch here
    monkeypatch.setattr(base, "resolve_exec_platform", lambda x=None: "tpu")
    mesh = par.make_mesh(dp=2, tp=2, devices=chips)
    x = jax.ShapeDtypeStruct(
        (8, 1024, 12, 64), jnp.bfloat16,
        sharding=NamedSharding(mesh, P("dp", None, "tp", None)))

    def loss(q, k, v):
        out = attention.flash_attention(q, k, v, causal=True)
        return out.astype(jnp.float32).sum()

    with par.use_mesh(mesh):
        text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
            x, x, x).compile().as_text()
    assert text.count("tpu_custom_call") == 2
    assert "bf16[24,1024,64]" in text       # 4 rows x 6 heads, flattened


# ---------------------------------------- the hybrid stack's kernels (PR 26)

@pytest.mark.parametrize("groups,chunk", [
    (8, 128),       # nemotron_tt_30b_a3b_ep16
    (1, 128),       # granite_4h_micro_p10 as its cell runs it (scan_chunk)
    (1, 256),       # ... at the published mamba_chunk_size
])
def test_ssd_chunk_kernel_compiles_for_v5e(chip, groups, chunk):
    """One Mamba-2 layer's scan at the two cells' sizes: 64 heads of 64,
    state 128, 8,192 steps, in 8 B/C groups or in one (cut into head
    blocks); forward by ``ssd_chunk_fwd``, backward by ``ssd_chunk_bwd``,
    which keeps every (chunk, chunk) square in VMEM and writes no output
    of the forward's dims (the benchmark's readers find the forward
    kernel by them)."""
    from mxnet_tpu.ops.ssd import ssd_scan

    def loss(x, dt, a, bm, cm):
        return ssd_scan(x, dt, a, bm, cm, chunk=chunk, impl="pallas",
                        interpret=False).sum()

    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=chip)
    args = (sds((1, 8192, 64, 64), jnp.bfloat16),
            sds((1, 8192, 64), jnp.float32), sds((64,), jnp.float32),
            sds((1, 8192, groups, 128), jnp.bfloat16),
            sds((1, 8192, groups, 128), jnp.bfloat16))
    # the value keeps the forward alive: a sum's gradient needs no output
    compiled = jax.jit(jax.value_and_grad(
        loss, argnums=(0, 1, 2, 3, 4))).lower(*args).compile()
    assert _kernels(compiled) == 2          # ssd_chunk_fwd, ssd_chunk_bwd
    results = [line.split(" custom-call(")[0]
               for line in compiled.as_text().splitlines()
               if " custom-call(" in line and "tpu_custom_call" in line]
    assert len(results) == 2
    assert sum("f32[1,8192,4096]" in r for r in results) == 1
    # nothing lays the heads' squares out in HBM any more
    squares = 64 * chunk * chunk * (8192 // chunk)
    arrays = _entry_arrays(compiled)
    assert ("f32", (1, 8192, 4096)) in arrays   # the parser sees the arrays
    for ty, shape in arrays:
        assert not (ty == "f32" and math.prod(shape) >= squares), (ty, shape)


def test_ssd_backward_at_its_vmem_limit_compiles_for_v5e(chip):
    """The widest call ``ssd_plan`` lets through at state 256 (352 heads
    of 64: ``ssd_chunk_bwd``'s step counts 31.6 of its 32 MiB, most of it
    the state cotangent of all the heads): the chip's compiler takes what
    the plan takes, so a shape is refused by the plan's own error or not
    at all."""
    from mxnet_tpu.ops.ssd import (BWD_VMEM_LIMIT, bwd_step_vmem_bytes,
                                   ssd_scan)
    assert bwd_step_vmem_bytes(128, 8, 64, 256, 2, 352) > 0.95 * BWD_VMEM_LIMIT

    def loss(x, dt, a, bm, cm):
        return ssd_scan(x, dt, a, bm, cm, chunk=128, impl="pallas",
                        interpret=False).sum()

    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=chip)
    args = (sds((1, 512, 352, 64), jnp.bfloat16),
            sds((1, 512, 352), jnp.float32), sds((352,), jnp.float32),
            sds((1, 512, 1, 256), jnp.bfloat16),
            sds((1, 512, 1, 256), jnp.bfloat16))
    compiled = jax.jit(jax.value_and_grad(
        loss, argnums=(0, 1, 2, 3, 4))).lower(*args).compile()
    assert _kernels(compiled) == 2


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_moe_gmm_compiles_for_v5e(chip, dtype):
    """up -> relu^2 -> down over the worst-case buffer of 6 x 8,192 rows
    with 8 experts held, published widths: two products forward, four in
    the backward pass, the grid's tile axis dynamic."""
    from mxnet_tpu.ops.gmm import grouped_matmul

    m, u, f, held = 49152, 2688, 1856, 8

    def loss(rows, w_up, w_down, sizes):
        valid = (jnp.arange(m) < jnp.sum(sizes))[:, None]
        h = grouped_matmul(rows, w_up, sizes, impl="pallas",
                           interpret=False)
        h = jnp.square(jax.nn.relu(jnp.where(valid, h, 0)))
        y = grouped_matmul(h, w_down, sizes, impl="pallas", interpret=False)
        return jnp.where(valid, y, 0).astype(jnp.float32).sum()

    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=chip)
    compiled = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2))).lower(
        sds((m, u), dtype), sds((held, u, f), dtype),
        sds((held, f, u), dtype), sds((held,), jnp.int32)).compile()
    assert _kernels(compiled) == 6


def test_flash_grouped_queries_compile_for_v5e(chip):
    """32 query heads over 2 key/value heads of 128 at T 8,192."""
    def loss(q, k, v):
        out = flash_attention(q, k, v, causal=True, interpret=False)
        return out.astype(jnp.float32).sum()

    q = jax.ShapeDtypeStruct((1, 8192, 32, 128), jnp.bfloat16, sharding=chip)
    kv = jax.ShapeDtypeStruct((1, 8192, 2, 128), jnp.bfloat16, sharding=chip)
    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        q, kv, kv).compile()
    assert _kernels(compiled) == 2
    assert "bf16[2,8192,128]" in compiled.as_text()   # K/V never repeated


# --------------------------- the Gated DeltaNet stack's kernels (PR 30)

@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_gdn_chunk_kernel_compiles_for_v5e(chip, dtype):
    """One Gated DeltaNet layer's rule at the cell's size: 32 value heads
    over 16 key heads of 128, chunks of 64 over 8,192 steps, the state in
    VMEM across a head's chunks; forward by the kernel (float32 `highest`
    products in the triangular inverse whatever the operands are), the
    written-out backward in XLA, a key head at a time.  The temporaries
    of the two together (403,621,376 B in bf16, 470,245,888 in float32)
    are below what JAX's derivative of the recomputed chunked form took
    four key heads at a time (the parent of PR 31, same compile:
    951,072,256 and 1,340,029,952 B)."""
    from mxnet_tpu.ops.gdn import gdn_scan

    def loss(q, k, v, g, beta):
        return gdn_scan(q, k, v, g, beta, chunk=64, impl="pallas",
                        interpret=False).sum()

    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=chip)
    args = (sds((1, 8192, 16, 128), dtype), sds((1, 8192, 16, 128), dtype),
            sds((1, 8192, 32, 128), dtype), sds((1, 8192, 32), jnp.float32),
            sds((1, 8192, 32), jnp.float32))
    # the value keeps the forward alive: a sum's gradient needs no output
    compiled = jax.jit(jax.value_and_grad(
        loss, argnums=(0, 1, 2, 3, 4))).lower(*args).compile()
    assert _kernels(compiled) == 1          # gdn_chunk_fwd
    assert "gdn_chunk_bwd" in compiled.as_text()
    before = {jnp.bfloat16: 951_072_256, jnp.float32: 1_340_029_952}[dtype]
    assert compiled.memory_analysis().temp_size_in_bytes < before


def test_moe_gmm_gated_experts_compile_for_v5e(chip):
    """up, gate -> silu * -> down over the worst-case buffer of 10 x 8,192
    rows with 32 experts of 2,048 x 512 held: three products forward, six
    in the backward pass."""
    from mxnet_tpu.ops.gmm import grouped_matmul

    m, u, f, held = 81920, 2048, 512, 32

    def loss(rows, w_up, w_gate, w_down, sizes):
        valid = (jnp.arange(m) < jnp.sum(sizes))[:, None]
        product = lambda a, b: jnp.where(valid, grouped_matmul(  # noqa: E731
            a, b, sizes, impl="pallas", interpret=False), 0)
        h = (jax.nn.silu(product(rows, w_gate).astype(jnp.float32))
             * product(rows, w_up)).astype(rows.dtype)
        return product(h, w_down).astype(jnp.float32).sum()

    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=chip)
    bf = jnp.bfloat16
    compiled = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2, 3))).lower(
        sds((m, u), bf), sds((held, u, f), bf), sds((held, u, f), bf),
        sds((held, f, u), bf), sds((held,), jnp.int32)).compile()
    assert _kernels(compiled) == 9


def test_flash_head_size_256_grouped_queries_compile_for_v5e(chip):
    """16 query heads over 2 key/value heads of 256 at T 8,192."""
    def loss(q, k, v):
        out = flash_attention(q, k, v, causal=True, interpret=False)
        return out.astype(jnp.float32).sum()

    q = jax.ShapeDtypeStruct((1, 8192, 16, 256), jnp.bfloat16, sharding=chip)
    kv = jax.ShapeDtypeStruct((1, 8192, 2, 256), jnp.bfloat16, sharding=chip)
    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        q, kv, kv).compile()
    assert _kernels(compiled) == 2


# -------------------- the expert layer around its grouped products (PR 38)

def _entry_arrays(compiled):
    """(type, shape) of every array an instruction of the entry
    computation produces, the members of a tuple each."""
    text = compiled.as_text()
    found = []
    for line in text[text.index("ENTRY"):].splitlines():
        m = re.match(r"\s*(?:ROOT )?%\S+ = (\(.*?\)|\S+) [a-z][a-z\-]*\(",
                     line)
        if m:
            found += [(ty, tuple(int(s) for s in dims.split(",") if s))
                      for ty, dims in re.findall(r"(\w+)\[([\d,]*)\]",
                                                 m.group(1))]
    return found


@pytest.mark.parametrize("top_k,d,f,held,experts,form", [
    (10, 2048, 512, 32, 512, "swiglu"),     # qwen3_next_80b_a3b_share
    (6, 2688, 1856, 8, 128, "relu2"),       # nemotron_tt_30b_a3b_ep16
])
def test_the_expert_layer_lays_its_buffer_once_for_v5e(
        chip, monkeypatch, top_k, d, f, held, experts, form):
    """One routed expert layer recomputed under ``jax.checkpoint`` with
    its gradient, real widths, 2,048 tokens: a token's pairs are numbered
    slot by slot, so the buffer in pair order IS its ``top_k`` slots of
    (N, D) and is never re-laid as (N, top_k, D) (``top_k`` in the tiled
    second-minor place: a copy padded to 16), and the weighted sum, its
    backward and the sum of a token's row gradients read the bf16 buffer
    and write (N, D): no float32 array of the buffer's size exists."""
    from mxnet_tpu import base
    from mxnet_tpu.models import moe

    n, bf = 2048, jnp.bfloat16
    # the layer and its grouped products choose by platform
    monkeypatch.setattr(base, "resolve_exec_platform", lambda x=None: "tpu")

    def layer(x, w_router, bias, w_up, w_gate, w_down):
        kw = dict(top_k=top_k, first=0, compute_dtype=bf, impl="pallas")
        if form == "swiglu":
            kw.update(scoring="softmax", w_gate=w_gate)
        return moe.dropless_ffn(x, w_router, bias, w_up, w_down, **kw)[0]

    def loss(ct, *args):
        return jnp.sum(jax.checkpoint(layer)(*args) * ct)

    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=chip)
    compiled = jax.jit(jax.value_and_grad(
        loss, argnums=(1, 2, 4, 5, 6))).lower(
        sds((n, d), jnp.float32), sds((n, d), bf),
        sds((experts, d), jnp.float32), sds((experts,), jnp.float32),
        sds((held, d, f), bf), sds((held, d, f), bf),
        sds((held, f, d), bf)).compile()
    products = {"relu2": 2, "swiglu": 3}[form]
    assert _kernels(compiled) == 4 * products   # forward twice, backward x 2
    arrays = _entry_arrays(compiled)
    assert ("bf16", (n * top_k, d)) in arrays   # the parser sees the buffer
    for ty, shape in arrays:
        assert shape != (n, top_k, d), (ty, shape)
        assert not (ty == "f32" and math.prod(shape) >= n * top_k * d), \
            (ty, shape)


# --------------------------------- the SambaY stage's kernels (PR 39)

@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_selective_scan_compiles_for_v5e(chip, dtype):
    """One Mamba-1 layer's scan at the published width: 5,120 channels of
    16 states, chunks of 128 steps; forward and its own backward, the
    backward holding a chunk's (128, 16, 512) states in VMEM."""
    from mxnet_tpu import base
    from mxnet_tpu import observability as obs
    from mxnet_tpu.ops.sscan import selective_scan

    def loss(x, dt, a, bm, cm):
        return selective_scan(x, dt, a, bm, cm).sum()

    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=chip)
    args = (sds((1, 2048, 5120), dtype), sds((1, 2048, 5120), jnp.float32),
            sds((5120, 16), jnp.float32), sds((1, 2048, 16), dtype),
            sds((1, 2048, 16), dtype))
    tr = obs.enable_tracing()
    try:
        # as on the chip: nothing says which path, the platform decides
        with base.executing_on("tpu"):
            compiled = jax.jit(jax.value_and_grad(
                loss, argnums=(0, 1, 2, 3, 4))).lower(*args).compile()
        plans = tr.spans(name="sscan.plan")
    finally:
        obs.disable_tracing()
    assert [e.attrs["impl"] for e in plans] == ["pallas"]
    assert _kernels(compiled) == 2          # sscan_fwd, sscan_bwd


@pytest.mark.parametrize("window", [None, 512])
def test_differential_flash_compiles_for_v5e(chip, window):
    """40 query heads of 64 over 20 key heads, the values 10 heads of 128
    (a differential head's two key heads side by side), T 8,192, under the
    512 window and without: the walk's third grid axis, its clamps at
    both ends."""
    def loss(q, k, v):
        out = flash_attention(q, k, v, causal=True, window=window,
                              interpret=False)
        return out.astype(jnp.float32).sum()

    sds = lambda shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16,  # noqa: E731
                                             sharding=chip)
    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        sds((1, 8192, 40, 64)), sds((1, 8192, 20, 64)),
        sds((1, 8192, 10, 128))).compile()
    assert _kernels(compiled) == 2


# ------------- the sliding-window expert cell's kernels at its sizes (PR 42)

@pytest.mark.parametrize("window", [1024, None])
def test_flash_share_of_eight_compiles_for_v5e(chip, window):
    """32 query heads over 4 key/value heads of 128 at T 8,192, under the
    1,024 window (three layers of four) and without: blocks no taller than
    the window, the backward's ``dk`` / ``dv`` summed over a share of 8."""
    from mxnet_tpu.ops.flash import tile_plan

    plan = tile_plan(8192, 8192, 128, jnp.bfloat16, True, heads=32,
                     kv_heads=4, window=window)
    assert plan.block_q == 1024 and plan.group == 1
    assert plan.tiles_run == (45 if window else 144)

    def loss(q, k, v):
        out = flash_attention(q, k, v, causal=True, window=window,
                              interpret=False)
        return out.astype(jnp.float32).sum()

    q = jax.ShapeDtypeStruct((1, 8192, 32, 128), jnp.bfloat16, sharding=chip)
    kv = jax.ShapeDtypeStruct((1, 8192, 4, 128), jnp.bfloat16, sharding=chip)
    from mxnet_tpu import observability as obs
    tr = obs.enable_tracing()
    try:
        compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
            q, kv, kv).compile()
        (event,) = tr.spans(name="flash.plan")
    finally:
        obs.disable_tracing()
    assert _kernels(compiled) == 2
    # the plan tells a windowed call from a full one by `window` alone
    assert event.attrs.get("window") == window
    assert (event.attrs["d"], event.attrs["tiles_run"]) == (128, plan.tiles_run)


def test_moe_gmm_sixteen_small_experts_compile_for_v5e(chip):
    """up, gate -> silu * -> down over the worst-case buffer of 8 x 8,192
    rows with 16 experts of 2,304 x 896 held: a contraction of 2,304 is no
    multiple of the plan's 512 and tiles by 384."""
    from mxnet_tpu.ops.gmm import gmm_plan, grouped_matmul

    m, u, f, held = 65536, 2304, 896, 16
    assert gmm_plan(m, u, f) == (256, 384, 896)
    assert gmm_plan(m, f, u) == (256, 128, 768)

    def loss(rows, w_up, w_gate, w_down, sizes):
        valid = (jnp.arange(m) < jnp.sum(sizes))[:, None]
        product = lambda a, b: jnp.where(valid, grouped_matmul(  # noqa: E731
            a, b, sizes, impl="pallas", interpret=False), 0)
        h = (jax.nn.silu(product(rows, w_gate).astype(jnp.float32))
             * product(rows, w_up)).astype(rows.dtype)
        return product(h, w_down).astype(jnp.float32).sum()

    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=chip)  # noqa: E731,E501
    bf = jnp.bfloat16
    compiled = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2, 3))).lower(
        sds((m, u), bf), sds((held, u, f), bf), sds((held, u, f), bf),
        sds((held, f, u), bf), sds((held,), jnp.int32)).compile()
    assert _kernels(compiled) == 9


# --------------- the looped decoder cell's attention at its sizes (PR 46)

def test_flash_share_of_one_at_head_size_128_compiles_for_v5e(chip):
    """Plain multi-head attention, 16 query heads over 16 key/value heads
    of 128 at T 8,192: a share of ONE.  ``tile_plan`` gives it the plan of
    the grouped-query calls at this head size (blocks of 1,024 rows,
    chunks of 256, one head a grid step, 144 tiles of 256 run), each
    query head reading a key/value head of its own."""
    from mxnet_tpu.ops.flash import tile_plan

    plan = tile_plan(8192, 8192, 128, jnp.bfloat16, True, heads=16,
                     kv_heads=16)
    shared = tile_plan(8192, 8192, 128, jnp.bfloat16, True, heads=32,
                       kv_heads=2)
    assert plan == shared
    assert (plan.block_q, plan.chunk, plan.group, plan.tiles_run) == \
        (1024, 256, 1, 144)

    def loss(q, k, v):
        out = flash_attention(q, k, v, causal=True, interpret=False)
        return out.astype(jnp.float32).sum()

    x = jax.ShapeDtypeStruct((1, 8192, 16, 128), jnp.bfloat16, sharding=chip)
    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        x, x, x).compile()
    assert _kernels(compiled) == 2


def test_flash_latent_two_operand_score_compiles_for_v5e(chip):
    """Latent attention at its published sizes: 16 heads, a score that is
    a 128-wide head product plus a 64-wide rotary product on ONE shared
    key head, values of 128, T 8,192, bf16: forward, and the one backward
    call (dq with dq2; dk2 a query head, summed after)."""
    from mxnet_tpu.ops.flash import tile_plan

    plan = tile_plan(8192, 8192, 128, jnp.bfloat16, True, heads=16,
                     kv_heads=16, dv=128, v_heads=16, d2=64, k2_heads=1)
    assert (plan.block_q, plan.chunk, plan.major, plan.group) == \
        (1024, 256, 2048, 1)

    def loss(q, k, v, q2, k2):
        out = flash_attention(q, k, v, q2=q2, k2=k2, causal=True,
                              scale=192 ** -0.5, interpret=False)
        return out.astype(jnp.float32).sum()

    def x(heads, d):
        return jax.ShapeDtypeStruct((1, 8192, heads, d), jnp.bfloat16,
                                    sharding=chip)

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
        x(16, 128), x(16, 128), x(16, 128), x(16, 64), x(1, 64)).compile()
    assert _kernels(compiled) == 2


# ------- the dense gated feed-forward inside a whole step's text (PR 48)

_T, _UNITS, _HALF = 64, 32, 48
_LATENT = dict(
    vocab_size=512, vocab_held=64, units=_UNITS, num_heads=4, qk_nope_dim=16,
    qk_rope_dim=8, v_head_dim=16, kv_lora_rank=24, mlp_hidden=_HALF,
    num_experts=16, top_k=3, expert_hidden=24, shared_hidden=40,
    experts_held=(8, 4))
_DENSE_TWINS = {        # the tiny sizes of tests/test_decoder_shell.py
    "granite": ("get_granite_hybrid", dict(
        layer_types=("mamba", "attention"), vocab_size=256, vocab_held=32,
        units=_UNITS, num_heads=4, num_kv_heads=2, head_dim=8, mamba_heads=4,
        mamba_head_dim=16, state_size=8, chunk_size=16, mlp_hidden=_HALF)),
    "sambay": ("get_phi4_flash", dict(
        num_layers=4, vocab_size=64, units=_UNITS, num_heads=8,
        num_kv_heads=4, head_dim=4, window=8, mlp_hidden=_HALF, d_inner=64,
        state_size=4, conv_kernel=4, dt_rank=2)),
    "ouro": ("get_ouro", dict(
        num_layers=2, vocab_size=512, vocab_held=128, units=_UNITS,
        num_heads=2, num_kv_heads=2, head_dim=16, mlp_hidden=_HALF)),
    # the latent-attention family's leading dense layers alone (its expert
    # layers take ``ragged_dot`` off the TPU, whose float32 cotangent
    # against bf16 weights the chip's compiler refuses: the expert twins
    # are lowered, never compiled)
    "deepseek_v3": ("get_deepseek_v3", dict(_LATENT, num_layers=2,
                                            first_k_dense=2)),
}
_EXPERT_TWIN = ("get_qwen3_next", dict(
    num_layers=4, vocab_size=512, vocab_held=64, units=_UNITS, num_heads=4,
    num_kv_heads=2, head_dim=16, linear_key_heads=2, linear_value_heads=4,
    linear_key_dim=8, linear_value_dim=8, chunk_size=16, num_experts=16,
    top_k=3, expert_hidden=24, shared_hidden=_HALF, experts_held=(8, 4)))


def _lowered_twin_step(chips, monkeypatch, factory, kwargs):
    """``(lowered, mlp.plan events)`` of one bf16 Adam step of a family's
    tiny twin, recomputed block by block, lowered for ONE described chip
    as ``chipbench/rehearse_hybrid.py`` lowers a cell's: shapes on the
    described device in the arrays' place, nothing placed there.  The
    program still takes its CPU branches (interpreted kernels): what is
    read is what the chip's compiler makes of the products around them."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    import mxnet_tpu as mx
    from mxnet_tpu import amp, models, observability, parallel as par
    from mxnet_tpu.models.hybrid_common import lm_loss
    from mxnet_tpu.parallel import sharding, trainer as trainer_mod

    keep = lambda value, sh: value
    monkeypatch.setattr(sharding, "mesh_device_put", keep)
    monkeypatch.setattr(trainer_mod, "_mesh_device_put", keep)
    amp.init("bfloat16")
    tracer = observability.enable_tracing()
    try:
        net = getattr(models, factory)(remat=True, **kwargs)
        net.initialize()
        mesh = par.make_mesh(devices=chips[:1])
        tok = mx.nd.array(jnp.zeros((1, _T), jnp.int32), dtype="int32")
        # a looped net takes the labels itself and returns its objective
        own_loss = getattr(net, "passes", 1) > 1
        with par.use_mesh(mesh):
            t = par.ShardedTrainer(
                net, "adam", loss=None if own_loss else lm_loss,
                optimizer_params={"learning_rate": 1e-3}, mesh=mesh)
            batch = ((tok, tok), ()) if own_loss else ((tok,), (tok,))
            t.build(*batch)
            params, aux, states, batch_v = t._device_args(*batch)
            repl = NamedSharding(mesh, P())

            def sds(vals, shs):
                return tuple(jax.ShapeDtypeStruct(v.shape, v.dtype,
                                                  sharding=s)
                             for v, s in zip(vals, shs))

            of = lambda ps: tuple(sharding.param_sharding(p, mesh, t.rules)
                                  for _n, p in ps)
            scalar = lambda dt: jax.ShapeDtypeStruct((), dt, sharding=repl)
            lowered = t._step_fn.lower(
                sds(params, of(t._trainable)), sds(aux, of(t._aux)),
                sds(states, t._state_shardings),
                sds(batch_v, t.batch_shardings),
                jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=repl),
                scalar(jnp.float32), scalar(jnp.int32))
        plans = [s.attrs for s in tracer.spans(name="mlp.plan")]
    finally:
        observability.disable_tracing()
        amp.reset()
    return lowered, plans


@pytest.mark.parametrize("family", sorted(_DENSE_TWINS))
def test_gated_mlp_leaves_no_wide_float32_in_a_step_for_v5e(
        chips, monkeypatch, family):
    """``gated_mlp`` inside a whole recomputed bf16 step of each family
    that runs it: the chip's compiler is handed two products over the
    halves of ``w_in``, so no float32 value of (T, 2F) is in the step's
    text (the parent wrote ``f32[1,64,96]`` here, 537 MB a call at the
    Granite cell's size), and ``dg``, ``dv`` leave the product that makes
    them rounded, never as a float32 pair."""
    lowered, plans = _lowered_twin_step(chips, monkeypatch,
                                        *_DENSE_TWINS[family])
    assert plans == [{"form": "halves", "rows": _T, "half": _HALF,
                      "compute_dtype": "bfloat16",
                      "wide_bytes_a_call": _T * _HALF * 4}]
    text = lowered.compile().as_text()
    # the parser sees the step: the weight's optimizer state is there
    assert f"f32[{2 * _HALF},{_UNITS}]" in text
    wide = re.findall(rf"f32\[(?:1,)?{_T},{2 * _HALF}\]", text)
    assert not wide, sorted(set(wide))
    f32_half = rf"f32\[(?:1,)?{_T},{_HALF}\](?:\{{[^}}]*\}})?"
    pairs = re.findall(rf"\({f32_half}, {f32_half}\)", text)
    assert not pairs, sorted(set(pairs))


def test_an_expert_family_never_reaches_gated_mlp(chips, monkeypatch):
    """The bypass, held statically: an expert family's shared expert is
    ``models/moe.py``'s own, so lowering its whole step traces no
    ``gated_mlp`` (which would leave an ``mlp.plan`` event under the
    tracer) and its text is what it was before PR 48."""
    _lowered, plans = _lowered_twin_step(chips, monkeypatch, *_EXPERT_TWIN)
    assert plans == []


def test_a_dense_first_expert_stack_lowers_with_its_scopes(chips,
                                                           monkeypatch):
    """The latent-attention family as its cell runs it, a dense layer and
    then expert layers: ONE ``mlp.plan`` event (the dense layer's; the
    shared experts are ``models/moe.py``'s own) and the mixer's three
    scopes in the lowered step's locations."""
    lowered, plans = _lowered_twin_step(
        chips, monkeypatch, "get_deepseek_v3", dict(_LATENT, num_layers=3))
    assert [p["half"] for p in plans] == [_HALF]
    text = lowered.as_text(debug_info=True)
    for scope in ("mla_latent", "mla_expand", "mla_scores"):
        assert scope in text, scope

