"""Grouped matrix products over the experts a chip holds (Pallas TPU).

``lhs`` is a buffer of rows sorted by group (expert), ``group_sizes`` says
how many rows each group has, and row ``r`` of group ``g`` is multiplied by
``rhs[g]``.  The buffer has a static size — the worst case, so that no row
is ever dropped — but the groups may fill only its head: the grid's tile
axis has a *dynamic* length, the number of row tiles that hold a routed
row, so time follows the rows routed and not the buffer.  Rows past the
last group's end are left UNWRITTEN (whatever the buffer held); callers
mask them on every read.

Adapted from JAX's ``jax.experimental.pallas.ops.tpu.megablox`` (Apache
2.0), which this repository now owns in this form: the sharding offsets
and the accumulate-into-existing-output path are gone, the tile sizes come
from :func:`gmm_plan`, the three calls are named ``moe_gmm`` for the trace,
and the product states its own precision.

* :func:`gmm`  — ``out[rows of g] = lhs[rows of g] @ rhs[g]`` (or
  ``rhs[g]^T`` with ``transpose_rhs``); forward, and backward for ``lhs``.
* :func:`tgmm` — ``out[g] = lhs[rows of g]^T @ rhs[rows of g]``; backward
  for the weights.  A group with no row gets zeros.
* :func:`grouped_matmul` — :func:`gmm` with its ``custom_vjp``.

Off the TPU the kernels run interpreted (tests); ``impl="xla"`` is
``jax.lax.ragged_dot``, the path the models take off the TPU.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash import _default_interpret, matmul_precision, plan_event

__all__ = ["gmm", "tgmm", "grouped_matmul", "gmm_plan", "GmmPlan"]

# rows a tile: an expert holding 1/16 of a pod's experts sees a few
# hundred rows of one 8,192-token sequence, and every group boundary
# costs a tile visited twice, so tiles stay at 256 rows; k and n tiles
# keep the weights' tile near 1.5 MB, double-buffered
DEFAULT_TM = 256
DEFAULT_TK = 512
DEFAULT_TN = 1024
_VMEM_BUDGET = 12 * 2 ** 20


class GmmPlan(NamedTuple):
    """Tile sizes of one grouped product: rows, contraction, columns."""
    tm: int
    tk: int
    tn: int


def _fit(dim: int, want: int, unit: int) -> int:
    """The largest tile of at most ``want`` that is the whole ``dim`` or a
    multiple of ``unit`` dividing it; a whole ``dim`` when none divides."""
    if dim <= want:
        return dim
    t = want - want % unit
    while t >= unit:
        if dim % t == 0:
            return t
        t -= unit
    return dim


def gmm_plan(m: int, k: int, n: int, itemsize: int = 2) -> GmmPlan:
    """Tile sizes from the problem's sizes alone: pure and static."""
    tm = _fit(m, DEFAULT_TM, 8)
    tk = _fit(k, DEFAULT_TK, 128)
    tn = _fit(n, DEFAULT_TN, 128)

    def vmem(tk, tn):
        return (2 * (tm * tk + tk * tn + tm * tn) * itemsize
                + max(tm, tk) * tn * 4)

    def smaller(dim, tile):
        if tile <= 128:
            return tile
        t = _fit(dim, tile - 128, 128)
        return t if t < tile else tile

    while vmem(tk, tn) > _VMEM_BUDGET:
        sk, sn = smaller(k, tk), smaller(n, tn)
        if (sk, sn) == (tk, tn):
            break
        if sn < tn and (tn >= tk or sk == tk):
            tn = sn
        else:
            tk = sk
    return GmmPlan(tm, tk, tn)


def _report_plan(plan: GmmPlan, m, k, n, groups, dtype):
    """One ``moe.plan`` event per distinct plan (as ``flash.plan``)."""
    plan_event("moe.plan", **plan._asdict(), buffer_rows=m, k=k, n=n,
               experts_held=groups, dtype=jnp.dtype(dtype).name)


def _dot(a, b, dims):
    return lax.dot_general(a, b, (dims, ((), ())),
                           precision=matmul_precision(a.dtype),
                           preferred_element_type=jnp.float32)


def make_group_metadata(group_sizes, m: int, tm: int, visit_empty: bool):
    """(group_offsets, group_ids, m_tile_ids), tiles to run.  A row tile is
    visited once by each group that has a row in it; with ``visit_empty``
    an empty group also gets one visit (``tgmm`` has to zero its output).
    Tiles past the last group's last row are not visited."""
    num_groups = group_sizes.shape[0]
    tiles_m = m // tm
    ends = jnp.cumsum(group_sizes)
    offsets = jnp.concatenate([jnp.zeros(1, jnp.int32), ends])
    starts = offsets[:-1]
    rounded = ((ends + tm - 1) // tm * tm - starts // tm * tm)
    group_tiles = jnp.where(group_sizes == 0, 0, rounded // tm)
    if visit_empty:
        group_tiles = jnp.where(group_sizes == 0, 1, group_tiles)
    length = tiles_m + num_groups - 1
    group_ids = jnp.repeat(jnp.arange(num_groups, dtype=jnp.int32),
                           group_tiles, total_repeat_length=length)
    # a tile is visited once by the group that owns its first row and once
    # more by each group that starts inside it
    aligned = jnp.logical_or(starts % tm == 0, group_sizes == 0)
    if visit_empty:
        aligned = jnp.where(group_sizes == 0, False, aligned)
    partial_ids = jnp.where(aligned, tiles_m, starts // tm)
    visits = jnp.zeros(tiles_m + 1, jnp.int32).at[partial_ids].add(1)
    visits = visits[:tiles_m] + 1
    m_tile_ids = jnp.repeat(jnp.arange(tiles_m, dtype=jnp.int32), visits,
                            total_repeat_length=length)
    return (offsets, group_ids, m_tile_ids), group_tiles.sum()


def _row_mask(meta, grid_id, tm: int, width: int):
    """Rows of the current tile that belong to the current group."""
    offsets, group_ids, m_tile_ids = meta
    gid = group_ids[grid_id]
    rows = (lax.broadcasted_iota(jnp.int32, (tm, width), 0)
            + m_tile_ids[grid_id] * tm)
    return jnp.logical_and(rows >= offsets[gid], rows < offsets[gid + 1])


def _pad_rows(x, tm):
    m = x.shape[0]
    pad = -m % tm
    return (jnp.pad(x, ((0, pad), (0, 0))) if pad else x), m


_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "arbitrary", "arbitrary"))


def gmm(lhs, rhs, group_sizes, *, transpose_rhs: bool = False,
        out_dtype=None, plan: Optional[GmmPlan] = None,
        interpret: Optional[bool] = None):
    """``lhs`` (m, k) rows sorted by group; ``rhs`` (G, k, n), or (G, n, k)
    with ``transpose_rhs``; ``group_sizes`` (G,) int32 summing to at most
    m.  Returns (m, n); rows past the last group are unwritten."""
    out_dtype = jnp.dtype(out_dtype or lhs.dtype)
    k = lhs.shape[1]
    n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    plan = plan or gmm_plan(lhs.shape[0], k, n, lhs.dtype.itemsize)
    tm, tk, tn = plan
    lhs, m_true = _pad_rows(lhs, tm)
    m = lhs.shape[0]
    if interpret is None:
        interpret = _default_interpret(lhs)
    tiles_k, tiles_n = k // tk, n // tn
    meta, num_tiles = make_group_metadata(group_sizes, m, tm, False)
    cdt = jnp.promote_types(lhs.dtype, rhs.dtype)

    def kernel(meta, lhs_ref, rhs_ref, out_ref, acc):
        grid_id, k_i = pl.program_id(1), pl.program_id(2)

        @pl.when(k_i == 0)
        def _zero():
            acc[...] = jnp.zeros_like(acc)

        def accumulate(last):
            a, b = lhs_ref[...].astype(cdt), rhs_ref[...].astype(cdt)
            acc[...] += _dot(a, b, ((1,), (1 if transpose_rhs else 0,)))
            if last:
                keep = _row_mask(meta, grid_id, tm, tn)
                out_ref[...] = lax.select(
                    keep, acc[...],
                    out_ref[...].astype(jnp.float32)).astype(out_dtype)

        lax.cond(k_i == tiles_k - 1, functools.partial(accumulate, True),
                 functools.partial(accumulate, False))

    def lhs_index(n_i, grid_id, k_i, meta):
        return meta[2][grid_id], k_i

    def rhs_index(n_i, grid_id, k_i, meta):
        if transpose_rhs:
            return meta[1][grid_id], n_i, k_i
        return meta[1][grid_id], k_i, n_i

    def out_index(n_i, grid_id, k_i, meta):
        return meta[2][grid_id], n_i

    rhs_block = (None, tn, tk) if transpose_rhs else (None, tk, tn)
    out = pl.pallas_call(
        kernel,
        name="moe_gmm",
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            in_specs=[pl.BlockSpec((tm, tk), lhs_index),
                      pl.BlockSpec(rhs_block, rhs_index)],
            out_specs=pl.BlockSpec((tm, tn), out_index),
            grid=(tiles_n, num_tiles, tiles_k),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)]),
        compiler_params=_PARAMS,
        cost_estimate=pl.CostEstimate(
            flops=2 * m * k * n, transcendentals=0,
            bytes_accessed=(lhs.size * tiles_n * lhs.dtype.itemsize
                            + k * n * rhs.dtype.itemsize * meta[1].size
                            + m * n * out_dtype.itemsize)),
        interpret=interpret,
    )(meta, lhs, rhs)
    return out[:m_true] if m_true != m else out


def tgmm(lhs, rhs, group_sizes, *, out_dtype=None,
         plan: Optional[GmmPlan] = None, interpret: Optional[bool] = None):
    """``out[g] = lhs[rows of g]^T @ rhs[rows of g]``: ``lhs`` (m, k),
    ``rhs`` (m, n) -> (G, k, n).  Rows outside every group are never
    read unmasked."""
    out_dtype = jnp.dtype(out_dtype or lhs.dtype)
    k, n = lhs.shape[1], rhs.shape[1]
    plan = plan or gmm_plan(lhs.shape[0], k, n, lhs.dtype.itemsize)
    tm, tk, tn = plan
    lhs, _ = _pad_rows(lhs, tm)
    rhs, _ = _pad_rows(rhs, tm)
    m = lhs.shape[0]
    if interpret is None:
        interpret = _default_interpret(lhs)
    num_groups = group_sizes.shape[0]
    tiles_k, tiles_n = k // tk, n // tn
    meta, num_tiles = make_group_metadata(group_sizes, m, tm, True)
    cdt = jnp.promote_types(lhs.dtype, rhs.dtype)

    def kernel(meta, lhs_ref, rhs_ref, out_ref, acc):
        offsets, group_ids, _tiles = meta
        grid_id = pl.program_id(2)
        last = pl.num_programs(2) - 1
        group = group_ids[grid_id]
        prev = group_ids[jnp.maximum(grid_id - 1, 0)]
        nxt = group_ids[jnp.minimum(grid_id + 1, last)]

        @pl.when(jnp.logical_or(grid_id == 0, prev != group))
        def _zero():
            acc[...] = jnp.zeros_like(acc)

        @pl.when(offsets[group + 1] > offsets[group])
        def _do():
            a = lax.select(_row_mask(meta, grid_id, tm, tk), lhs_ref[...],
                           jnp.zeros_like(lhs_ref)).astype(cdt)
            b = lax.select(_row_mask(meta, grid_id, tm, tn), rhs_ref[...],
                           jnp.zeros_like(rhs_ref)).astype(cdt)
            acc[...] += _dot(a, b, ((0,), (0,)))

        @pl.when(jnp.logical_or(grid_id == last, nxt != group))
        def _store():
            out_ref[...] = acc[...].astype(out_dtype)

    def lhs_index(n_i, k_i, grid_id, meta):
        return meta[2][grid_id], k_i

    def rhs_index(n_i, k_i, grid_id, meta):
        return meta[2][grid_id], n_i

    def out_index(n_i, k_i, grid_id, meta):
        return meta[1][grid_id], k_i, n_i

    return pl.pallas_call(
        kernel,
        name="moe_gmm",
        out_shape=jax.ShapeDtypeStruct((num_groups, k, n), out_dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            in_specs=[pl.BlockSpec((tm, tk), lhs_index),
                      pl.BlockSpec((tm, tn), rhs_index)],
            out_specs=pl.BlockSpec((None, tk, tn), out_index),
            grid=(tiles_n, tiles_k, num_tiles),
            scratch_shapes=[pltpu.VMEM((tk, tn), jnp.float32)]),
        compiler_params=_PARAMS,
        cost_estimate=pl.CostEstimate(
            flops=2 * m * k * n, transcendentals=0,
            bytes_accessed=(lhs.size * tiles_n * lhs.dtype.itemsize
                            + rhs.size * tiles_k * rhs.dtype.itemsize
                            + num_groups * k * n * out_dtype.itemsize)),
        interpret=interpret,
    )(meta, lhs, rhs)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _gmm_vjp(lhs, rhs, group_sizes, interpret):
    return gmm(lhs, rhs, group_sizes, interpret=interpret)


def _gmm_fwd(lhs, rhs, group_sizes, interpret):
    return (gmm(lhs, rhs, group_sizes, interpret=interpret),
            (lhs, rhs, group_sizes))


def _gmm_bwd(interpret, res, g):
    import numpy as np

    lhs, rhs, group_sizes = res
    g = g.astype(lhs.dtype)
    d_lhs = gmm(g, rhs, group_sizes, transpose_rhs=True,
                interpret=interpret)
    d_rhs = tgmm(lhs, g, group_sizes, out_dtype=rhs.dtype,
                 interpret=interpret)
    return d_lhs, d_rhs, np.zeros(group_sizes.shape, jax.dtypes.float0)


_gmm_vjp.defvjp(_gmm_fwd, _gmm_bwd)


def grouped_matmul(lhs, rhs, group_sizes, *, impl: str = "auto",
                   interpret: Optional[bool] = None):
    """``lhs[rows of g] @ rhs[g]`` for rows sorted by group, with
    gradients for ``lhs`` and ``rhs``.  Rows past the last group's end
    hold nothing a caller may read unmasked (the kernel leaves them
    unwritten; the XLA path happens to zero them).  ``impl``: "pallas",
    "xla" (``jax.lax.ragged_dot``) or "auto" (the kernel on the TPU)."""
    off_tpu = _default_interpret(lhs)
    if impl == "auto":
        impl = "xla" if off_tpu else "pallas"
    m, k = lhs.shape
    _report_plan(gmm_plan(m, k, rhs.shape[2], lhs.dtype.itemsize), m, k,
                 rhs.shape[2], rhs.shape[0], lhs.dtype)
    if impl == "xla":
        return lax.ragged_dot(lhs, rhs.astype(lhs.dtype), group_sizes,
                              preferred_element_type=jnp.float32
                              ).astype(lhs.dtype)
    if impl != "pallas":
        raise ValueError(f"impl must be auto, pallas or xla, got {impl!r}")
    if interpret is None:
        interpret = off_tpu
    return _gmm_vjp(lhs, rhs.astype(lhs.dtype), group_sizes,
                    bool(interpret))
