"""Pallas TPU flash attention: O(T) memory, MXU-tiled, fwd + custom bwd.

Capability add over the reference (SURVEY.md §5.7: MXNet ships NO
flash/ring attention; its fused BERT matmuls in
src/operator/contrib/transformer.cc materialize the full (T, T) score
matrix).  This kernel never materializes scores: the softmax is computed
online per (block_q, block_k) tile held in VMEM, accumulating into an
f32 VMEM scratch, so long sequences are bounded by HBM for Q/K/V only.

Layout: public entry takes (B, T, H, D) and flattens to (B*H, T, D);
grid = (batch*heads, q_blocks, kv_blocks) with the kv dimension innermost
("arbitrary" semantics — it carries the online-softmax accumulator) and
the first two parallel.  Causal blocks above the diagonal are predicated
out with ``pl.when`` so the MXU never sees them.

The backward pass is the standard flash-attention-2 split: a ``dq``
kernel (grid over q blocks, reducing across kv) and a ``dkv`` kernel
(grid over kv blocks, reducing across q), both re-computing the tile of
probabilities from the saved per-row logsumexp.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Measured on TPU v5e (B16 T1024 H12 D64, causal): 128x128 blocks run the
# fwd kernel at 16.7 ms vs 1.6 ms at 1024x1024 — big tiles keep the MXU fed
# (d=64 contractions are half-width already) and amortize grid/DMA overhead.
# 2048x2048 exceeds VMEM (the (bq, bk) f32 score tile alone is 16 MB).
DEFAULT_BLOCK_Q = 1024
DEFAULT_BLOCK_K = 1024
_MASK = -1e30
_LANES = 128

# ~16 MiB VMEM per v4/v5e core; budget leaves headroom for compiler
# temporaries/semaphores so the clamp errs safe rather than tight.
_VMEM_BUDGET = 12 * 2 ** 20


def _vmem_bytes(bq: int, bk: int, d: int, itemsize: int,
                has_seg: bool = False) -> int:
    """Working-set model of one grid step, sized for the WORST of the
    three kernels (the bwd dq/dkv kernels stream four tiles — q, k, v,
    do — where fwd streams three): two live (bq, bk) f32 score-tile
    temporaries (s→p and dp→ds are reused in place), double-buffered
    input tiles, double-buffered output tile(s), and the larger of the
    fwd/dkv f32 accumulator scratch sets.  The segment path adds one
    more (bq, bk)-sized temporary (the q==k equality mask materialized
    by the ``jnp.where``) plus the double-buffered int32 seg-id tiles."""
    score = 2 * 4 * bq * bk
    tiles = 2 * itemsize * d * 2 * (bq + bk)      # dq/dkv stream 4 tiles
    outs = 2 * itemsize * bq * d
    scratch = 4 * max(bq * d + 2 * bq * _LANES,   # fwd: acc + m + l
                      2 * bk * d)                 # dkv: dk_acc + dv_acc
    seg = (4 * bq * bk + 2 * 4 * (bq + bk)) if has_seg else 0
    return score + tiles + outs + scratch + seg


def _clamp_blocks(bq: int, bk: int, d: int, itemsize: int,
                  has_seg: bool = False):
    """Shrink (block_q, block_k) until the working set fits the VMEM
    budget — head-dim/dtype aware, so d=64 bf16 keeps the measured-fast
    1024x1024 while d=256 f32 lands on a safe smaller tile."""
    while _vmem_bytes(bq, bk, d, itemsize, has_seg) > _VMEM_BUDGET and \
            (bq > 128 or bk > 128):
        if bk >= bq and bk > 128:
            bk //= 2
        else:
            bq //= 2
    return bq, bk


def _dot(a, b, ca: int, cb: int):
    """In-kernel 2D contraction of ``a`` dim ``ca`` with ``b`` dim ``cb``,
    accumulated in f32.  The precision is stated HERE, per operand type,
    so the process-wide ``jax_default_matmul_precision`` (the package sets
    'highest') never reaches Mosaic — which refuses an fp32-precision
    ``tpu.matmul`` on bf16 operands.  bf16 operands take the MXU's single
    pass (exact products, f32 accumulation); float32 operands keep the
    package's true-f32 contract (``mxnet_tpu/__init__.py``)."""
    prec = (jax.lax.Precision.HIGHEST if a.dtype == jnp.float32
            else jax.lax.Precision.DEFAULT)
    return jax.lax.dot_general(
        a, b, (((ca,), (cb,)), ((), ())), precision=prec,
        preferred_element_type=jnp.float32)


def _default_interpret(x) -> bool:
    from ..base import resolve_exec_platform
    return resolve_exec_platform(x) != "tpu"


# --------------------------------------------------------------------- fwd

def _seg_mask(qseg_ref, kseg_ref, s):
    """Mask score tile entries whose q/k tokens belong to different packed
    segments.  The tile-skip predicate lives separately in
    :func:`_run_pred` (shared by all three kernels) so the min/max
    reductions are computed once per grid step."""
    qs = qseg_ref[0, 0, :]                             # (bq,) int32
    ks = kseg_ref[0, 0, :]                             # (bk,) int32
    return jnp.where(qs[:, None] == ks[None, :], s, _MASK)


def _fwd_kernel(*refs, scale, causal, has_seg, block_q, block_k, nk):
    if has_seg:
        (q_ref, k_ref, v_ref, qseg_ref, kseg_ref,
         o_ref, lse_ref, acc_ref, m_ref, l_ref) = refs
    else:
        q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref = refs
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, _MASK)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    def _tile():
        q = q_ref[0]                                   # (bq, d)
        k = k_ref[0]                                   # (bk, d)
        s = _dot(q, k, 1, 1) * scale                   # (bq, bk)
        if causal:
            row = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            col = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(col <= row, s, _MASK)
        if has_seg:
            s = _seg_mask(qseg_ref, kseg_ref, s)
        m_prev = m_ref[:, :1]                          # (bq, 1)
        l_prev = l_ref[:, :1]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_next = jnp.maximum(m_prev, m_cur)
        # masked-safe exp: a tile whose every entry is _MASK for some row
        # (the row's segment starts in a LATER tile) has m_next == _MASK
        # there, and bare exp(s - m_next) would contribute exp(0)=1 per
        # masked entry.  Zero masked entries explicitly instead.
        p = jnp.where(s <= _MASK * 0.5, 0.0, jnp.exp(s - m_next))
        corr = jnp.exp(m_prev - m_next)                # (bq, 1)
        l_next = corr * l_prev + jnp.sum(p, axis=1, keepdims=True)
        pv = _dot(p.astype(v_ref.dtype), v_ref[0], 1, 0)   # (bq, d)
        acc_ref[:] = acc_ref[:] * corr + pv
        m_ref[:] = jnp.broadcast_to(m_next, m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_next, l_ref.shape)

    run = _run_pred(causal, has_seg, qi, ki, block_q, block_k,
                    qseg_ref if has_seg else None,
                    kseg_ref if has_seg else None)
    if run is not None:
        @pl.when(run)
        def _():
            _tile()
    else:
        _tile()

    @pl.when(ki == nk - 1)
    def _finish():
        l = l_ref[:, :1]
        # rows with NO matching key anywhere (possible only in degenerate
        # cross-segment cases) get zeros out and a finite lse of _MASK so
        # the backward recompute exp(s - lse) stays 0, never inf
        empty = l <= 0.0
        o_ref[0] = jnp.where(
            empty, 0.0, acc_ref[:] / jnp.where(empty, 1.0, l)
        ).astype(o_ref.dtype)
        lse_ref[0, 0, :] = jnp.where(
            empty[:, 0], _MASK, m_ref[:, 0] + jnp.log(
                jnp.where(empty[:, 0], 1.0, l_ref[:, 0])))


def _seg_specs(nheads, block_q, block_k):
    """BlockSpecs for (B, 1, T) segment-id planes: the grid's flattened
    batch*heads coordinate maps back to the batch row with b // nheads."""
    return [
        pl.BlockSpec((1, 1, block_q),
                     lambda b, i, j: (b // nheads, 0, i)),
        pl.BlockSpec((1, 1, block_k),
                     lambda b, i, j: (b // nheads, 0, j)),
    ]


def _dkv_seg_specs(nheads, block_q, block_k):
    """Same as _seg_specs for the dkv grid, whose (b, j, i) coords carry
    the kv block index second."""
    return [
        pl.BlockSpec((1, 1, block_q),
                     lambda b, j, i: (b // nheads, 0, i)),
        pl.BlockSpec((1, 1, block_k),
                     lambda b, j, i: (b // nheads, 0, j)),
    ]


def _fwd(q, k, v, q_seg, kv_seg, nheads, causal, scale, block_q, block_k,
         interpret):
    bh, tq, d = q.shape
    tk = k.shape[1]
    nq = pl.cdiv(tq, block_q)
    nk = pl.cdiv(tk, block_k)
    has_seg = q_seg is not None
    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, has_seg=has_seg,
        block_q=block_q, block_k=block_k, nk=nk)
    in_specs = [
        pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
        pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
    ]
    args = [q, k, v]
    if has_seg:
        in_specs += _seg_specs(nheads, block_q, block_k)
        args += [q_seg, kv_seg]
    out, lse = pl.pallas_call(
        kernel,
        name="flash_fwd",
        grid=(bh, nq, nk),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            # lse is (bh, 1, tq) so each qi owns its own (1, 1, block_q)
            # tile — TPU block rules demand last-two dims divisible by
            # (8, 128) or equal to the array dims, and a shared full-row
            # block would race across megacore's parallel qi partitions.
            pl.BlockSpec((1, 1, block_q), lambda b, i, j: (b, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, tq, d), q.dtype),
            jax.ShapeDtypeStruct((bh, 1, tq), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=4 * bh * tq * tk * d, transcendentals=bh * tq * tk,
            bytes_accessed=2 * (q.size + k.size + v.size) * q.dtype.itemsize),
        interpret=interpret,
    )(*args)
    return out, lse


# --------------------------------------------------------------------- bwd

def _run_pred(causal, has_seg, qi, ki, block_q, block_k,
              qseg_ref, kseg_ref):
    """Tile-skip predicate shared by all three kernels: the causal
    above-diagonal test plus a range-disjointness test on the tile's
    segment ids — exact for the packed layout (ids non-decreasing along
    the row) and conservative (never skips a tile that could match) for
    arbitrary ids."""
    run = None
    if causal:
        run = ki * block_k < (qi + 1) * block_q
    if has_seg:
        qs = qseg_ref[0, 0, :]
        ks = kseg_ref[0, 0, :]
        overlap = jnp.logical_and(jnp.min(ks) <= jnp.max(qs),
                                  jnp.max(ks) >= jnp.min(qs))
        run = overlap if run is None else jnp.logical_and(run, overlap)
    return run


def _dq_kernel(*refs, scale, causal, has_seg, block_q, block_k, nk):
    if has_seg:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         qseg_ref, kseg_ref, dq_ref, acc_ref) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         dq_ref, acc_ref) = refs
        qseg_ref = kseg_ref = None
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    def _tile():
        q = q_ref[0]
        k = k_ref[0]
        s = _dot(q, k, 1, 1) * scale
        if causal:
            row = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            col = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(col <= row, s, _MASK)
        if has_seg:
            s = _seg_mask(qseg_ref, kseg_ref, s)
        lse = lse_ref[0, 0, :]
        delta = delta_ref[0, 0, :]
        p = jnp.where(s <= _MASK * 0.5, 0.0, jnp.exp(s - lse[:, None]))
        dp = _dot(do_ref[0], v_ref[0], 1, 1)           # (bq, bk)
        ds = p * (dp - delta[:, None]) * scale
        acc_ref[:] += _dot(ds.astype(k.dtype), k, 1, 0)
    run = _run_pred(causal, has_seg, qi, ki, block_q, block_k,
                        qseg_ref, kseg_ref)
    if run is not None:
        @pl.when(run)
        def _():
            _tile()
    else:
        _tile()

    @pl.when(ki == nk - 1)
    def _finish():
        dq_ref[0] = acc_ref[:].astype(dq_ref.dtype)


def _dkv_kernel(*refs, scale, causal, has_seg, block_q, block_k, nq):
    if has_seg:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         qseg_ref, kseg_ref, dk_ref, dv_ref, dk_acc, dv_acc) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         dk_ref, dv_ref, dk_acc, dv_acc) = refs
        qseg_ref = kseg_ref = None
    ki = pl.program_id(1)
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    def _tile():
        q = q_ref[0]                                   # (bq, d)
        k = k_ref[0]                                   # (bk, d)
        do = do_ref[0]                                 # (bq, d)
        s = _dot(q, k, 1, 1) * scale                   # (bq, bk)
        if causal:
            row = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            col = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(col <= row, s, _MASK)
        if has_seg:
            s = _seg_mask(qseg_ref, kseg_ref, s)
        lse = lse_ref[0, 0, :]
        delta = delta_ref[0, 0, :]
        p = jnp.where(s <= _MASK * 0.5, 0.0, jnp.exp(s - lse[:, None]))
        # dV += P^T @ dO
        dv_acc[:] += _dot(p.astype(do.dtype), do, 0, 0)   # (bk, d)
        dp = _dot(do, v_ref[0], 1, 1)                  # (bq, bk)
        ds = p * (dp - delta[:, None]) * scale
        # dK += dS^T @ Q
        dk_acc[:] += _dot(ds.astype(q.dtype), q, 0, 0)
    run = _run_pred(causal, has_seg, qi, ki, block_q, block_k,
                        qseg_ref, kseg_ref)
    if run is not None:
        @pl.when(run)
        def _():
            _tile()
    else:
        _tile()

    @pl.when(qi == nq - 1)
    def _finish():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _bwd_impl(q, k, v, q_seg, kv_seg, out, lse, do, nheads, causal, scale,
              block_q, block_k, interpret):
    bh, tq, d = q.shape
    tk = k.shape[1]
    nq = pl.cdiv(tq, block_q)
    nk = pl.cdiv(tk, block_k)
    has_seg = q_seg is not None
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)[:, None, :]               # (bh, 1, tq)

    dq_in_specs = [
        pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
        pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
        pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, 1, block_q), lambda b, i, j: (b, 0, i)),
        pl.BlockSpec((1, 1, block_q), lambda b, i, j: (b, 0, i)),
    ]
    args = [q, k, v, do, lse, delta]
    if has_seg:
        dq_in_specs += _seg_specs(nheads, block_q, block_k)
        args += [q_seg, kv_seg]
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, causal=causal,
                          has_seg=has_seg,
                          block_q=block_q, block_k=block_k, nk=nk),
        name="flash_bwd_dq",
        grid=(bh, nq, nk),
        in_specs=dq_in_specs,
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, tq, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(*args)

    dkv_in_specs = [
        pl.BlockSpec((1, block_q, d), lambda b, j, i: (b, i, 0)),
        pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
        pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
        pl.BlockSpec((1, block_q, d), lambda b, j, i: (b, i, 0)),
        pl.BlockSpec((1, 1, block_q), lambda b, j, i: (b, 0, i)),
        pl.BlockSpec((1, 1, block_q), lambda b, j, i: (b, 0, i)),
    ]
    if has_seg:
        dkv_in_specs += _dkv_seg_specs(nheads, block_q, block_k)
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, causal=causal,
                          has_seg=has_seg,
                          block_q=block_q, block_k=block_k, nq=nq),
        name="flash_bwd_dkv",
        grid=(bh, nk, nq),
        in_specs=dkv_in_specs,
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, tk, d), k.dtype),
            jax.ShapeDtypeStruct((bh, tk, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(*args)
    return dq, dk, dv


# ----------------------------------------------------------- custom_vjp glue
# Segment ids travel as primal args (they are data, not static config) and
# return symbolic-zero cotangents of dtype float0, the JAX contract for
# integer primal inputs.

def _int_zero_cotangent(x):
    if x is None:
        return None
    import numpy as _np

    from jax import dtypes as _dtypes
    return _np.zeros(x.shape, _dtypes.float0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10))
def _flash(q, k, v, q_seg, kv_seg, nheads, causal, scale, block_q, block_k,
           interpret):
    out, _ = _fwd(q, k, v, q_seg, kv_seg, nheads, causal, scale,
                  block_q, block_k, interpret)
    return out


def _flash_fwd(q, k, v, q_seg, kv_seg, nheads, causal, scale, block_q,
               block_k, interpret):
    out, lse = _fwd(q, k, v, q_seg, kv_seg, nheads, causal, scale,
                    block_q, block_k, interpret)
    return out, (q, k, v, q_seg, kv_seg, out, lse)


def _flash_bwd(nheads, causal, scale, block_q, block_k, interpret, res, do):
    q, k, v, q_seg, kv_seg, out, lse = res
    dq, dk, dv = _bwd_impl(q, k, v, q_seg, kv_seg, out, lse, do, nheads,
                           causal, scale, block_q, block_k, interpret)
    return (dq, dk, dv,
            _int_zero_cotangent(q_seg), _int_zero_cotangent(kv_seg))


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q, k, v, *, causal: bool = False,
                    scale: Optional[float] = None,
                    segment_ids=None, kv_segment_ids=None,
                    block_q: int = DEFAULT_BLOCK_Q,
                    block_k: int = DEFAULT_BLOCK_K,
                    interpret: Optional[bool] = None):
    """Flash attention on (B, T, H, D) inputs → (B, T, H, D).

    T must be a multiple of the block sizes and D one of 64/128/256 (the
    dispatcher in :mod:`mxnet_tpu.ops.attention` guarantees this before
    routing here).  ``interpret`` defaults to True off-TPU so the same
    kernel is unit-testable on the CPU backend.

    ``segment_ids`` (B, Tq) int enables SEQUENCE PACKING in-kernel:
    tokens attend only within their own segment; tiles whose q/k segment
    ranges cannot overlap are skipped at block level (exact skip for the
    packed non-decreasing layout), so packed long-context training keeps
    the O(T) memory AND the sub-quadratic compute of the kernel.
    ``kv_segment_ids`` defaults to ``segment_ids``.  Degenerate rows with
    no matching key anywhere output zeros — as does the XLA reference
    path (``attention.py:_attention_ref`` zeroes fully-masked rows), so
    the two paths are comparable row-for-row.
    """
    b, tq, h, d = q.shape
    tk = k.shape[1]
    if causal and tq != tk:
        raise ValueError("causal flash attention requires tq == tk "
                         f"(got {tq} vs {tk})")
    scale = float(scale) if scale is not None else 1.0 / (d ** 0.5)
    block_q = min(block_q, tq)
    block_k = min(block_k, tk)
    block_q, block_k = _clamp_blocks(block_q, block_k, d,
                                     jnp.dtype(q.dtype).itemsize,
                                     has_seg=segment_ids is not None)
    # halve until the block divides the sequence (any T that is a multiple
    # of 128 lands on a legal block by 128 at the latest)
    while block_q > 128 and tq % block_q:
        block_q //= 2
    while block_k > 128 and tk % block_k:
        block_k //= 2
    if tq % block_q or tk % block_k:
        raise ValueError(
            f"seq lens ({tq}, {tk}) must divide by blocks "
            f"({block_q}, {block_k})")
    if interpret is None:
        interpret = _default_interpret(q)

    q_seg = kv_seg = None
    if segment_ids is not None:
        q_seg = jnp.asarray(segment_ids, jnp.int32)[:, None, :]  # (B,1,Tq)
        kv_seg = (jnp.asarray(kv_segment_ids, jnp.int32)[:, None, :]
                  if kv_segment_ids is not None else q_seg)
        if q_seg.shape != (b, 1, tq) or kv_seg.shape != (b, 1, tk):
            raise ValueError(
                f"segment_ids must be (B, Tq)=({b}, {tq}) / "
                f"(B, Tk)=({b}, {tk}); got {segment_ids.shape}"
                + (f" / {kv_segment_ids.shape}"
                   if kv_segment_ids is not None else ""))
    elif kv_segment_ids is not None:
        raise ValueError("kv_segment_ids requires segment_ids")

    def flat(x, t):
        return x.transpose(0, 2, 1, 3).reshape(b * h, t, d)

    out = _flash(flat(q, tq), flat(k, tk), flat(v, tk), q_seg, kv_seg,
                 h, causal, scale, block_q, block_k, bool(interpret))
    return out.reshape(b, h, tq, d).transpose(0, 2, 1, 3)
