"""Pallas TPU paged attention: reads K/V pages in place through the
page table, with int8 dequantization fused into the tile loads.

Capability add over PR 11's paged layout (docs/serving.md "Paged KV
cache"): the original paged forward gathered every slot's pages into a
dense ``(B, Tmax, H, D)`` row (``models/transformer.py:_paged_rows``)
before attending — a full re-densification of the KV working set per
layer per step.  This kernel never materializes that row: the grid's
innermost dimension walks the page table itself, the BlockSpec index
map turns each ``table[slot, j]`` entry into the DMA source block, and
the online softmax (same structure as :mod:`.flash`) accumulates across
pages in VMEM scratch.  Pages past a slot's maximum query position are
predicated out with ``pl.when`` — a decode step over a 4-page prompt in
a 64-page-table engine touches 4 page tiles of compute, not 64 dense
rows.

Quantized pages (``kv_quant='int8'``) ride the same grid: the int8
page tile and its ``(ps, H, 1)`` fp32 scale tile stream together and
the dequantize (``tile.astype(f32) * scale``) fuses into the load, so
quantization halves-of-halves the HBM traffic without a separate
dequant pass.  The unassigned-slot zero page (pool ``scratch``) reads
as zeros under any scale — masked lanes stay finite, the engine's
NaN-guard contract (docs/resilience.md) is untouched.

Interpret-mode fallback mirrors :mod:`.flash`: off-TPU the kernel runs
under the Pallas interpreter, so the CPU test suite exercises the SAME
kernel body that TPU compiles.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_MASK = -1e30
_LANES = 128

__all__ = ["paged_attention", "kv_quantize", "kv_dequantize"]


def _default_interpret(x) -> bool:
    from ..base import resolve_exec_platform
    return resolve_exec_platform(x) != "tpu"


# ------------------------------------------------------------ quantization

def kv_quantize(x, scale_dtype=jnp.float32):
    """Symmetric per-position-per-head int8 quantization of a K/V
    activation: ``scale = max(|x|, axis=-1) / 127`` over the head_dim
    lanes, ``q = round(x / scale)``.  Returns ``(int8 values, scale)``
    with ``scale`` shaped like ``x`` but with a trailing dim of 1, so
    it scatters/gathers/shards exactly like a cache leaf.

    The scale floor keeps all-zero inputs (padding rows, the zero page)
    exactly representable: ``q = 0, scale = tiny`` dequantizes to 0.0,
    never 0/0."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1, keepdims=True)
    scale = jnp.maximum(amax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -127.0, 127.0)
    return q.astype(jnp.int8), scale.astype(scale_dtype)


def kv_dequantize(q, scale):
    """Inverse of :func:`kv_quantize` for the XLA (non-kernel) paths:
    broadcast-multiply the int8 values by their per-(position, head)
    scale.  Used by the dense-row gather arm and the draft window."""
    return q.astype(jnp.float32) * scale.astype(jnp.float32)


# ----------------------------------------------------------------- kernel

def _paged_kernel(table_ref, qmax_ref, *refs, scale, ps, nheads, npages,
                  quant):
    """One (slot, page) grid step.  ``table_ref``/``qmax_ref`` are the
    scalar-prefetched page table row block and per-slot max query
    position; page/scale tiles arrive already DMA'd by the index maps
    below.  The online softmax is flash.py's, with heads unrolled in
    Python: each head's (Tq, ps) score tile is tiny, and unrolling
    keeps every dot a plain 2D MXU contraction."""
    if quant:
        (q_ref, pos_ref, k_ref, v_ref, ks_ref, vs_ref,
         o_ref, acc_ref, m_ref, l_ref) = refs
    else:
        q_ref, pos_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref = refs
    s_id = pl.program_id(0)
    j = pl.program_id(1)
    tq = q_ref.shape[1]

    @pl.when(j == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, _MASK)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    def _tile():
        kf = k_ref[0].astype(jnp.float32)              # (ps, H, D)
        vf = v_ref[0].astype(jnp.float32)
        if quant:
            # dequant fused into the tile load: the int8 page and its
            # (ps, H, 1) scale stream together, nothing re-densifies
            kf = kf * ks_ref[0]
            vf = vf * vs_ref[0]
        qpos = pos_ref[0, 0, :]                        # (Tq,) int32
        keys = j * ps + jax.lax.broadcasted_iota(
            jnp.int32, (tq, ps), 1)                    # (Tq, ps)
        keep = keys <= qpos[:, None]
        qf = q_ref[0].astype(jnp.float32)              # (Tq, H, D)
        for h in range(nheads):
            s = jax.lax.dot_general(
                qf[:, h, :], kf[:, h, :], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale  # (Tq, ps)
            s = jnp.where(keep, s, _MASK)
            m_prev = m_ref[h][:, :1]                   # (Tq, 1)
            l_prev = l_ref[h][:, :1]
            m_cur = jnp.max(s, axis=1, keepdims=True)
            m_next = jnp.maximum(m_prev, m_cur)
            # masked-safe exp (flash.py): a page fully beyond some
            # row's qpos has m_next == _MASK there, and bare
            # exp(s - m_next) would add exp(0)=1 per masked lane
            p = jnp.where(s <= _MASK * 0.5, 0.0, jnp.exp(s - m_next))
            corr = jnp.exp(m_prev - m_next)            # (Tq, 1)
            l_next = corr * l_prev + jnp.sum(p, axis=1, keepdims=True)
            pv = jax.lax.dot_general(
                p, vf[:, h, :], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)    # (Tq, D)
            acc_ref[h] = acc_ref[h] * corr + pv
            m_ref[h] = jnp.broadcast_to(m_next, m_ref.shape[1:])
            l_ref[h] = jnp.broadcast_to(l_next, l_ref.shape[1:])

    # page-skip predicate — the win over the dense gather: a page whose
    # FIRST key already exceeds the slot's max query position is fully
    # masked, so its tile never touches the MXU
    @pl.when(j * ps <= qmax_ref[s_id])
    def _():
        _tile()

    @pl.when(j == npages - 1)
    def _finish():
        out = []
        for h in range(nheads):
            l = l_ref[h][:, :1]
            # same degenerate-row guard as flash: zeros out, never inf
            empty = l <= 0.0
            out.append(jnp.where(
                empty, 0.0, acc_ref[h] / jnp.where(empty, 1.0, l)))
        o_ref[0] = jnp.stack(out, axis=1).astype(o_ref.dtype)


def paged_attention(q, k_pages, v_pages, table_rows, qpos, *,
                    k_scale=None, v_scale=None,
                    scale: Optional[float] = None,
                    interpret: Optional[bool] = None):
    """Attention over paged K/V, read in place through the page table.

    Args:
      q: ``(B, Tq, H, D)`` queries — ``Tq=1`` for decode, the chunk
        width for chunked prefill / the spec-decode verify window.
      k_pages, v_pages: ``(N+1, ps, H, D)`` per-layer page arrays
        (float, or int8 when quantized); the LAST page is the engine's
        never-written zero page.
      table_rows: ``(B, P)`` int32 — each slot's page-table row;
        unassigned entries point at the zero page.
      qpos: ``(B, Tq)`` int32 absolute query positions; key position
        ``k`` is attended iff ``k <= qpos`` (inclusive causal mask,
        matching ``_attention_chunk``/``_attention_step_slots``).
      k_scale, v_scale: ``(N+1, ps, H, 1)`` fp32 per-position-per-head
        scales — required iff the pages are int8.

    Returns ``(B, Tq, H, D)`` in ``q``'s dtype.  The output for rows
    whose table maps entirely to the zero page (parked slots) is
    finite garbage, exactly like the gather arm — callers discard it.
    """
    b, tq, h, d = q.shape
    npages_total, ps = k_pages.shape[0], k_pages.shape[1]
    p = table_rows.shape[1]
    quant = jnp.issubdtype(k_pages.dtype, jnp.integer)
    if quant and (k_scale is None or v_scale is None):
        raise ValueError("int8 pages require k_scale/v_scale")
    scale = float(scale) if scale is not None else 1.0 / (d ** 0.5)
    if interpret is None:
        interpret = _default_interpret(q)

    table_rows = table_rows.astype(jnp.int32)
    qpos = jnp.asarray(qpos, jnp.int32)
    qmax = jnp.max(qpos, axis=1)                       # (B,)
    pos3 = qpos[:, None, :]                            # (B, 1, Tq)

    kernel = functools.partial(
        _paged_kernel, scale=scale, ps=ps, nheads=h, npages=p,
        quant=bool(quant))
    page_spec = pl.BlockSpec(
        (1, ps, h, d), lambda s, j, tbl, qm: (tbl[s, j], 0, 0, 0))
    in_specs = [
        pl.BlockSpec((1, tq, h, d), lambda s, j, tbl, qm: (s, 0, 0, 0)),
        pl.BlockSpec((1, 1, tq), lambda s, j, tbl, qm: (s, 0, 0)),
        page_spec,
        page_spec,
    ]
    args = [q, pos3, k_pages, v_pages]
    if quant:
        scale_spec = pl.BlockSpec(
            (1, ps, h, 1), lambda s, j, tbl, qm: (tbl[s, j], 0, 0, 0))
        in_specs += [scale_spec, scale_spec]
        args += [k_scale.astype(jnp.float32), v_scale.astype(jnp.float32)]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, p),
        in_specs=in_specs,
        out_specs=pl.BlockSpec(
            (1, tq, h, d), lambda s, j, tbl, qm: (s, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((h, tq, d), jnp.float32),
            pltpu.VMEM((h, tq, _LANES), jnp.float32),
            pltpu.VMEM((h, tq, _LANES), jnp.float32),
        ],
    )
    itemsize = jnp.dtype(k_pages.dtype).itemsize
    out = pl.pallas_call(
        kernel,
        name="paged_decode",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, tq, h, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=4 * b * tq * p * ps * h * d,
            transcendentals=b * tq * p * ps * h,
            bytes_accessed=(2 * b * p * ps * h * d * itemsize
                            + 2 * q.size * q.dtype.itemsize)),
        interpret=bool(interpret),
    )(table_rows, qmax, *args)
    return out
