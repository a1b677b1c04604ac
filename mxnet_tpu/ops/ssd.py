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
  computes ``C B^T`` once and walks the group's heads; a group too wide
  for a step's VMEM (one group of 64 heads) is cut into head blocks,
  each a grid step of its own (:func:`ssd_plan`).
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
    (a whole group's, or one of the equal head blocks a group is cut
    into), grid steps of ``ssd_chunk_fwd``."""
    chunk: int
    chunks: int
    heads_a_step: int
    grid_steps: int


# what one grid step's blocks, double-buffered, may take of the 16 MiB of
# scoped VMEM a kernel gets by default: the rest is for the (chunk, chunk)
# square, its decay and the products' results (one group of 64 heads of 64
# at chunk 256, 20.6 MB by this count, is refused by the chip's compiler;
# the same at chunk 128, 14.3 MB, compiles)
VMEM_A_STEP = 10 << 20
# the most heads a grid step walks: the kernel's loop over a step's heads
# is unrolled, and at 64 heads of 64 in one group the step was fastest at
# 8 heads and slower with every doubling (1.60 / 1.76 / 1.79 / 1.79 ms at
# 8 / 16 / 32 / 64 heads, chunk 128; my chip run, PR 32), whatever fitted
HEADS_A_STEP = 8


def step_vmem_bytes(chunk: int, heads: int, p: int, n: int,
                    itemsize: int) -> int:
    """Bytes of one ``ssd_chunk_fwd`` grid step's blocks, each twice (the
    pipeline's double buffer): x, B, C in the operand type, both decay
    operands, y and the heads' states in float32.  A block's last two
    dimensions are counted padded to float32's (8, 128) tile."""
    def tile(rows, cols, size):
        return -(-rows // 8) * 8 * -(-cols // 128) * 128 * size

    blocks = (tile(chunk, heads * p, itemsize) + 2 * tile(chunk, n, itemsize)
              + tile(chunk, heads, 4) + tile(heads, chunk, 4)
              + tile(chunk, heads * p, 4) + heads * tile(n, p, 4))
    return 2 * blocks


def _whole_chunks_and_groups(t: int, h: int, g: int, chunk: int) -> None:
    if t % chunk:
        raise ValueError(f"sequence {t} is not a whole number of chunks "
                         f"of {chunk}")
    if h % g:
        raise ValueError(f"{h} heads do not divide into {g} groups")


def ssd_plan(b: int, t: int, h: int, g: int, chunk: int, *,
             head_dim: int = 64, state: int = 128,
             itemsize: int = 2) -> SsdPlan:
    """A grid step takes one chunk of one group when the group has at
    most ``HEADS_A_STEP`` heads and its blocks fit ``VMEM_A_STEP``; else
    the group is cut into the fewest equal head blocks that do (each reads
    the group's B and C; a block's lanes are then whole tiles of 128)."""
    _whole_chunks_and_groups(t, h, g, chunk)
    hpg = h // g
    for heads in range(min(hpg, HEADS_A_STEP), 0, -1):
        if hpg % heads or (heads != hpg and (heads * head_dim) % 128):
            continue
        if step_vmem_bytes(chunk, heads, head_dim, state,
                           itemsize) <= VMEM_A_STEP:
            return SsdPlan(chunk, t // chunk, heads,
                           b * (t // chunk) * g * (hpg // heads))
    raise ValueError(
        f"no head block of a group of {hpg} heads of {head_dim} at chunk "
        f"{chunk}, state {state} fits {VMEM_A_STEP} B of VMEM a grid step")


def _report_plan(plan: SsdPlan, shape, g, n, dtype, impl):
    """One ``ssd.plan`` event per distinct plan (as ``flash.plan``);
    ``vmem_bytes`` is 0 where no kernel is launched."""
    b, t, h, p = shape
    vmem = 0 if impl == "xla" else step_vmem_bytes(
        plan.chunk, plan.heads_a_step, p, n, jnp.dtype(dtype).itemsize)
    plan_event("ssd.plan", **plan._asdict(), batch=b, seq=t, heads=h,
               head_dim=p, state=n, dtype=jnp.dtype(dtype).name, impl=impl,
               groups=g, blocks_a_group=h // g // plan.heads_a_step,
               vmem_bytes=vmem)


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
                  heads, p):
    bm, cm = b_ref[0], c_ref[0]                               # (q, n)
    cb = _dot(cm, bm, 1, 1)                                   # (q, q) f32
    q = cb.shape[0]
    lower = (jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
             >= jax.lax.broadcasted_iota(jnp.int32, (q, q), 1))
    for j in range(heads):
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
    """``ssd_chunk_fwd``: per chunk and head block (a group, or one of the
    blocks :func:`ssd_plan` cut it into), the masked quadratic form and
    the chunk's own state.  Returns y (b, t, h, p) float32 and states
    (b, nc, h, n, p) float32."""
    b, t, h, p = xdt.shape
    g, n = b_mat.shape[2], b_mat.shape[3]
    nc, q = cum.shape[1], cum.shape[2]
    hb = ssd_plan(b, t, h, g, q, head_dim=p, state=n,
                  itemsize=xdt.dtype.itemsize).heads_a_step
    per_group = h // g // hb          # head blocks reading one B and C
    blocks = g * per_group
    cg = cum.reshape(b, t, blocks, hb)
    cum_col = cg.transpose(0, 2, 1, 3)                        # (b,G,t,hb)
    cum_row = cg.transpose(0, 2, 3, 1)                        # (b,G,hb,t)
    flops = b * nc * blocks * (2 * q * q * n + hb * 4 * q * q * p)
    of_group = lambda i, c, k: (i, c, k // per_group)         # noqa: E731
    y, states = pl.pallas_call(
        functools.partial(_chunk_kernel, heads=hb, p=p),
        name="ssd_chunk_fwd",
        grid=(b, nc, blocks),
        in_specs=[
            pl.BlockSpec((1, q, hb * p), lambda i, c, k: (i, c, k)),
            pl.BlockSpec((1, q, n), of_group),
            pl.BlockSpec((1, q, n), of_group),
            pl.BlockSpec((1, 1, q, hb), lambda i, c, k: (i, k, c, 0)),
            pl.BlockSpec((1, 1, hb, q), lambda i, c, k: (i, k, 0, c)),
        ],
        out_specs=[
            pl.BlockSpec((1, q, hb * p), lambda i, c, k: (i, c, k)),
            pl.BlockSpec((1, 1, hb, n, p), lambda i, c, k: (i, c, k, 0, 0)),
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
    g, n = b_mat.shape[2], b_mat.shape[3]
    off_tpu = _default_interpret(x)
    if impl == "auto":
        impl = "xla" if off_tpu else "pallas"
    if impl == "xla":
        # no kernel, so no VMEM to fit: a group's heads go together
        _whole_chunks_and_groups(t, h, g, chunk)
        plan = SsdPlan(chunk, t // chunk, h // g, b * (t // chunk) * g)
        _report_plan(plan, x.shape, g, n, x.dtype, impl)
        return ssd_chunked(x, dt, a, b_mat, c_mat, chunk=chunk)
    if impl != "pallas":
        raise ValueError(f"impl must be auto, pallas or xla, got {impl!r}")
    _report_plan(ssd_plan(b, t, h, g, chunk, head_dim=p, state=n,
                          itemsize=x.dtype.itemsize),
                 x.shape, g, n, x.dtype, impl)
    if interpret is None:
        interpret = off_tpu
    return _ssd(x, dt, a, b_mat, c_mat, chunk, bool(interpret))
