"""Mamba-2's state-space scan in chunks (the SSD form), forward and backward.

Capability add over the reference (MXNet has no recurrent-state layer
beyond cuDNN RNNs).  One head's recurrence is

    h_t = exp(dt_t A) h_{t-1} + dt_t B_t x_t^T        (an (N, P) state)
    y_t = C_t h_t

with ``A < 0`` a scalar per head, ``dt_t > 0`` per head and step, and
``B_t``, ``C_t`` shared by the heads of a group.  A sequential scan over T
steps leaves the MXU idle and a (T, T) matrix does not fit, so the
sequence is cut into chunks of ``chunk`` steps:

* **inside a chunk** the output is the masked quadratic form
  ``y_i += sum_{j<=i} exp(cum_i - cum_j) (C_i . B_j) dt_j x_j`` — two
  (chunk, chunk) matmuls a head — and the chunk's own contribution to the
  state at its end is ``sum_j exp(cum_last - cum_j) dt_j B_j x_j^T``.
  ``cum`` is the running sum of ``dt A`` inside the chunk; every exponent
  is a difference ``cum_i - cum_j <= 0``, so no decay overflows and one
  that underflows is an honest zero.  On the TPU this part is the Pallas
  kernel ``ssd_chunk_fwd``: one grid step takes one chunk of one group,
  computes ``C B^T`` once and walks the group's heads.
* **between chunks** the (heads, N, P) states follow a T / chunk step
  recurrence, and each chunk reads the state it starts from:
  ``y_i += exp(cum_i) C_i S_prev``.  Both stay in XLA.

Decays, running sums and states are float32 whatever the operands are.

The backward pass is the same chunked form written in ``jax.numpy``
(:func:`ssd_chunked`), differentiated by JAX under a ``custom_vjp``: the
kernel has no backward kernel of its own yet (``ssd_chunk_bwd`` is the
next step, PERF.md section 7).  Off the TPU the chunked XLA form is the
forward too; ``impl="pallas"`` forces the kernel (interpreted off the
TPU, for tests).
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash import (_default_interpret, _dot, matmul_precision as _prec,
                    plan_event)

__all__ = ["ssd_scan", "ssd_chunked", "ssd_recurrence", "ssd_plan",
           "causal_conv1d"]

_MASK = -1e30


class SsdPlan(NamedTuple):
    """Sizes of one scan call: steps a chunk, chunks, heads a grid step
    (one group's), grid steps of ``ssd_chunk_fwd``."""
    chunk: int
    chunks: int
    heads_a_step: int
    grid_steps: int


def ssd_plan(b: int, t: int, h: int, g: int, chunk: int) -> SsdPlan:
    if t % chunk:
        raise ValueError(f"sequence {t} is not a whole number of chunks "
                         f"of {chunk}")
    if h % g:
        raise ValueError(f"{h} heads do not divide into {g} groups")
    return SsdPlan(chunk, t // chunk, h // g, b * (t // chunk) * g)


def _report_plan(plan: SsdPlan, shape, n, dtype, impl):
    """One ``ssd.plan`` event per distinct plan (as ``flash.plan``)."""
    b, t, h, p = shape
    plan_event("ssd.plan", **plan._asdict(), batch=b, seq=t, heads=h,
               head_dim=p, state=n, dtype=jnp.dtype(dtype).name, impl=impl)


def causal_conv1d(x, w, bias):
    """Depthwise causal convolution along time as shifted multiply-adds:
    ``y_t = bias + sum_k w[:, k] x_{t - (K - 1 - k)}``.  ``x`` (B, T, C),
    ``w`` (C, K), ``bias`` (C,) or None."""
    k = w.shape[1]
    t = x.shape[1]
    xp = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    y = None if bias is None else bias.astype(x.dtype)
    for i in range(k):
        tap = xp[:, i:i + t, :] * w[:, i].astype(x.dtype)
        y = tap if y is None else y + tap
    return y


# --------------------------------------------------------- the chunked form

def _prep(x, dt, a, chunk):
    b, t, h, _p = x.shape
    nc = t // chunk
    da = dt.astype(jnp.float32) * a.astype(jnp.float32)       # (b, t, h)
    cum = jnp.cumsum(da.reshape(b, nc, chunk, h), axis=2)     # (b,nc,q,h)
    xdt = (x.astype(jnp.float32)
           * dt.astype(jnp.float32)[..., None]).astype(x.dtype)
    return cum, xdt


def _between_chunks(states, cum, c_mat, heads_per_group):
    """The recurrence over chunk states and what each chunk reads from
    the state it starts with.  ``states`` (b, nc, h, n, p) float32 are
    the chunks' own contributions; ``cum`` (b, nc, q, h); ``c_mat``
    (b, nc, q, g, n).  Returns (b, nc, q, h, p) float32."""
    b, nc, q, h = cum.shape
    chunk_decay = jnp.exp(cum[:, :, -1, :])                   # (b, nc, h)

    def step(s_prev, xs):
        dec, own = xs
        return dec[..., None, None] * s_prev + own, s_prev

    s0 = jnp.zeros(states.shape[:1] + states.shape[2:], jnp.float32)
    _, s_prev = jax.lax.scan(
        step, s0, (chunk_decay.swapaxes(0, 1), states.swapaxes(0, 1)))
    s_prev = s_prev.swapaxes(0, 1)                            # (b,nc,h,n,p)
    g = c_mat.shape[3]
    sp = s_prev.reshape(b, nc, g, heads_per_group, *s_prev.shape[3:])
    y = jnp.einsum("bcqgn,bcgjnp->bcqgjp", c_mat,
                   sp.astype(c_mat.dtype), precision=_prec(c_mat.dtype),
                   preferred_element_type=jnp.float32)
    y = y.reshape(b, nc, q, h, -1)
    return y * jnp.exp(cum)[..., None]


def ssd_chunked(x, dt, a, b_mat, c_mat, *, chunk=128):
    """The chunked scan in ``jax.numpy``: x (B, T, H, P), dt (B, T, H)
    already positive, a (H,) negative, b_mat / c_mat (B, T, G, N).
    Returns y (B, T, H, P) float32.  Matmul operands keep ``x.dtype``;
    decays and states are float32."""
    b, t, h, p = x.shape
    g, n = b_mat.shape[2], b_mat.shape[3]
    nc, q, hpg = t // chunk, chunk, h // g
    cum, xdt = _prep(x, dt, a, chunk)
    xr = xdt.reshape(b, nc, q, g, hpg, p)
    br = b_mat.reshape(b, nc, q, g, n)
    cr = c_mat.reshape(b, nc, q, g, n)
    cb = jnp.einsum("bcqgn,bcsgn->bcgqs", cr, br, precision=_prec(x.dtype),
                    preferred_element_type=jnp.float32)
    # heads ahead of the (chunk, chunk) square, so that the square is
    # what the device tiles
    cg = cum.reshape(b, nc, q, g, hpg).transpose(0, 1, 3, 4, 2)  # (b,nc,g,j,q)
    seg = cg[..., :, None] - cg[..., None, :]                 # (b,nc,g,j,i,s)
    lower = jnp.tril(jnp.ones((q, q), bool))
    decay = jnp.exp(jnp.where(lower, seg, _MASK))
    m = (cb[:, :, :, None] * decay).astype(x.dtype)
    y = jnp.einsum("bcgjqs,bcsgjp->bcqgjp", m, xr, precision=_prec(x.dtype),
                   preferred_element_type=jnp.float32)
    to_end = jnp.exp(cg[..., -1:] - cg)                       # (b,nc,g,j,q)
    xw = (xr.astype(jnp.float32)
          * to_end.transpose(0, 1, 4, 2, 3)[..., None]).astype(x.dtype)
    states = jnp.einsum("bcsgn,bcsgjp->bcgjnp", br, xw,
                        precision=_prec(x.dtype),
                        preferred_element_type=jnp.float32)
    states = states.reshape(b, nc, h, n, p)
    y = y.reshape(b, nc, q, h, p) + _between_chunks(states, cum, cr, hpg)
    return y.reshape(b, t, h, p)


def ssd_recurrence(x, dt, a, b_mat, c_mat):
    """The definition, one step at a time in float32 (tests and the
    reference's anchor; never on a training path)."""
    b, t, h, p = x.shape
    g, n = b_mat.shape[2], b_mat.shape[3]
    hpg = h // g
    f32 = jnp.float32
    x, dt, b_mat, c_mat = (v.astype(f32) for v in (x, dt, b_mat, c_mat))
    bh = jnp.repeat(b_mat, hpg, axis=2)                       # (b,t,h,n)
    ch = jnp.repeat(c_mat, hpg, axis=2)

    def step(s, xs):
        xt, dtt, bt, ct = xs
        s = (jnp.exp(dtt * a.astype(f32))[..., None, None] * s
             + (dtt[..., None] * bt)[..., :, None] * xt[..., None, :])
        return s, jnp.einsum("bhn,bhnp->bhp", ct, s,
                             precision=jax.lax.Precision.HIGHEST)

    s0 = jnp.zeros((b, h, n, p), f32)
    _, y = jax.lax.scan(step, s0, tuple(v.swapaxes(0, 1)
                                        for v in (x, dt, bh, ch)))
    return y.swapaxes(0, 1)


# ------------------------------------------------------------- the kernel

def _chunk_kernel(x_ref, b_ref, c_ref, cc_ref, cr_ref, y_ref, s_ref, *,
                  hpg, p):
    bm, cm = b_ref[0], c_ref[0]                               # (q, n)
    cb = _dot(cm, bm, 1, 1)                                   # (q, q) f32
    q = cb.shape[0]
    lower = (jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
             >= jax.lax.broadcasted_iota(jnp.int32, (q, q), 1))
    for j in range(hpg):
        col = cc_ref[0, 0, :, j:j + 1]                        # (q, 1)
        row = cr_ref[0, 0, j:j + 1, :]                        # (1, q)
        decay = jnp.exp(jnp.where(lower, col - row, _MASK))
        xh = x_ref[0, :, j * p:(j + 1) * p]                   # (q, p)
        y_ref[0, :, j * p:(j + 1) * p] = _dot(
            (cb * decay).astype(xh.dtype), xh, 1, 0)
        to_end = jnp.exp(col[q - 1:q, :] - col)               # (q, 1)
        xw = (xh.astype(jnp.float32) * to_end).astype(xh.dtype)
        s_ref[0, 0, j] = _dot(bm, xw, 0, 0)


def _chunks_pallas(xdt, b_mat, c_mat, cum, interpret):
    """``ssd_chunk_fwd``: per chunk and group, the masked quadratic form
    and the chunk's own state.  Returns y (b, t, h, p) float32 and states
    (b, nc, h, n, p) float32."""
    b, t, h, p = xdt.shape
    g, n = b_mat.shape[2], b_mat.shape[3]
    nc, q = cum.shape[1], cum.shape[2]
    hpg = h // g
    cg = cum.reshape(b, t, g, hpg)
    cum_col = cg.transpose(0, 2, 1, 3)                        # (b,g,t,hpg)
    cum_row = cg.transpose(0, 2, 3, 1)                        # (b,g,hpg,t)
    flops = b * nc * g * (2 * q * q * n + hpg * 4 * q * q * p)
    y, states = pl.pallas_call(
        functools.partial(_chunk_kernel, hpg=hpg, p=p),
        name="ssd_chunk_fwd",
        grid=(b, nc, g),
        in_specs=[
            pl.BlockSpec((1, q, hpg * p), lambda i, c, k: (i, c, k)),
            pl.BlockSpec((1, q, n), lambda i, c, k: (i, c, k)),
            pl.BlockSpec((1, q, n), lambda i, c, k: (i, c, k)),
            pl.BlockSpec((1, 1, q, hpg), lambda i, c, k: (i, k, c, 0)),
            pl.BlockSpec((1, 1, hpg, q), lambda i, c, k: (i, k, 0, c)),
        ],
        out_specs=[
            pl.BlockSpec((1, q, hpg * p), lambda i, c, k: (i, c, k)),
            pl.BlockSpec((1, 1, hpg, n, p), lambda i, c, k: (i, c, k, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, t, h * p), jnp.float32),
            jax.ShapeDtypeStruct((b, nc, h, n, p), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel")),
        cost_estimate=pl.CostEstimate(
            flops=flops, transcendentals=2 * b * t * h * q,
            bytes_accessed=(xdt.size + b_mat.size + c_mat.size)
            * xdt.dtype.itemsize + 4 * (b * t * h * p + b * nc * h * n * p)),
        interpret=interpret,
    )(xdt.reshape(b, t, h * p), b_mat.reshape(b, t, g * n),
      c_mat.reshape(b, t, g * n), cum_col, cum_row)
    return y.reshape(b, t, h, p), states


def _ssd_pallas(x, dt, a, b_mat, c_mat, chunk, interpret):
    b, t, h, p = x.shape
    g = b_mat.shape[2]
    cum, xdt = _prep(x, dt, a, chunk)
    y, states = _chunks_pallas(xdt, b_mat, c_mat, cum, interpret)
    cr = c_mat.reshape(b, t // chunk, chunk, g, -1)
    inter = _between_chunks(states, cum, cr, h // g)
    return y + inter.reshape(b, t, h, p)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _ssd(x, dt, a, b_mat, c_mat, chunk, interpret):
    return _ssd_pallas(x, dt, a, b_mat, c_mat, chunk, interpret)


def _ssd_fwd(x, dt, a, b_mat, c_mat, chunk, interpret):
    return (_ssd_pallas(x, dt, a, b_mat, c_mat, chunk, interpret),
            (x, dt, a, b_mat, c_mat))


def _ssd_bwd(chunk, interpret, res, g):
    # the chunked XLA form recomputed and differentiated by JAX
    with jax.named_scope("ssd_chunk_bwd"):
        _, vjp = jax.vjp(functools.partial(ssd_chunked, chunk=chunk), *res)
        return vjp(g)


_ssd.defvjp(_ssd_fwd, _ssd_bwd)


def ssd_scan(x, dt, a, b_mat, c_mat, *, chunk: int = 128,
             impl: str = "auto", interpret: Optional[bool] = None):
    """y (B, T, H, P), float32, of the scan over x (B, T, H, P) with steps
    dt (B, T, H) > 0, decay rates a (H,) < 0 and b_mat / c_mat
    (B, T, G, N).  ``impl``: "pallas" (the kernel; interpreted off the
    TPU), "xla" (the chunked ``jax.numpy`` form) or "auto" (the kernel
    on the TPU, the XLA form elsewhere)."""
    b, t, h, p = x.shape
    plan = ssd_plan(b, t, h, b_mat.shape[2], chunk)
    off_tpu = _default_interpret(x)
    if impl == "auto":
        impl = "xla" if off_tpu else "pallas"
    _report_plan(plan, x.shape, b_mat.shape[3], x.dtype, impl)
    if impl == "xla":
        return ssd_chunked(x, dt, a, b_mat, c_mat, chunk=chunk)
    if impl != "pallas":
        raise ValueError(f"impl must be auto, pallas or xla, got {impl!r}")
    if interpret is None:
        interpret = off_tpu
    return _ssd(x, dt, a, b_mat, c_mat, chunk, bool(interpret))
