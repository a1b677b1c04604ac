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

The backward pass of the kernel path is a kernel too, ``ssd_chunk_bwd``:
a grid step is the forward's (one chunk of one group or head block), the
chunks walked from the last to the first.  It makes ``C B^T``, the decay,
their product and the cotangents of all three again in VMEM, and it
carries the cotangent of every head's state from chunk to chunk in VMEM,
so the recurrence between chunks is differentiated there as well: no
``(.., heads, chunk, chunk)`` array and no state cotangent reaches HBM,
which sees ``d(x dt)``, dB, dC and the running sum's cotangent once.  The
forward leaves the running sum and the state each chunk starts from as
its residuals beside the inputs.  The step from ``x``, ``dt``, ``a`` to
``x dt`` and the running sum keeps JAX's derivative.  Products take their
operands in ``x.dtype`` as the forward's do (a float32 cotangent is cast
like the derived backward's single MXU pass casts it); sums, decays, the
carried state cotangent and all four outputs are float32.  Both kernels
sit under one ``jax.jit`` each, so a program traces and lowers each body
once a shape, however many layers call it.

A differentiated call keeps more than the XLA path does: beside the five
inputs, the running sum (b, T, H) and the state every chunk starts from,
(b, T / chunk, H, N, P) float32: 134 MB a call at 64 heads of 64, state
128, 8,192 steps in chunks of 128.  Under ``jax.checkpoint`` a layer (as
the models' blocks are) holds that only while its own backward runs;
without it every scan of a step holds its own until its turn.

:func:`ssd_chunked`, the same chunked form in ``jax.numpy`` differentiated
by JAX, is ``impl="xla"``: the path off the TPU and the tests' comparator.
``impl="pallas"`` forces the kernels (interpreted off the TPU, for tests).
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


# what ``ssd_chunk_bwd``'s call states as its VMEM limit, and what a grid
# step of it may take by :func:`bwd_step_vmem_bytes`.  The cells' steps fit
# the default 16 MiB (the chip's compiler accepts 6.5 MiB at chunk 128 and
# 10.75 at 256, 64 heads of 64, state 128, bf16); float32 operands at chunk
# 256 (19.5 MiB) and bf16 at chunk 512 (23.5) do not (compiler, PR 41)
BWD_VMEM_LIMIT = 32 << 20


def _tile(rows: int, cols: int, size: int) -> int:
    """Bytes of a block whose last two dimensions are padded to float32's
    (8, 128) tile."""
    return -(-rows // 8) * 8 * -(-cols // 128) * 128 * size


def step_vmem_bytes(chunk: int, heads: int, p: int, n: int,
                    itemsize: int) -> int:
    """Bytes of one ``ssd_chunk_fwd`` grid step's blocks, each twice (the
    pipeline's double buffer): x, B, C in the operand type, both decay
    operands, y and the heads' states in float32.  A block's last two
    dimensions are counted padded to float32's (8, 128) tile."""
    blocks = (_tile(chunk, heads * p, itemsize)
              + 2 * _tile(chunk, n, itemsize)
              + _tile(chunk, heads, 4) + _tile(heads, chunk, 4)
              + _tile(chunk, heads * p, 4) + heads * _tile(n, p, 4))
    return 2 * blocks


def bwd_step_vmem_bytes(chunk: int, heads: int, p: int, n: int,
                        itemsize: int, all_heads: int) -> int:
    """Bytes of one ``ssd_chunk_bwd`` grid step, counted as
    :func:`step_vmem_bytes` counts the forward's: its blocks, each twice
    (x dt, B and C in the operand type; both decay operands, dy, the
    heads' starting states, d(x dt), dB, dC and the running sum's
    cotangent by columns and by rows in float32); its scratch once (the
    state cotangent of ALL the call's heads, carried from chunk to chunk,
    and by the step's lanes dy in the operand type, the starting states,
    the running sum, what a row owes it, the last row's due and d(x dt)
    before the state's share in float32); and the values the body holds
    between them: seven (chunk, chunk) squares, two (state, lanes) arrays
    and ten (chunk, lanes) ones in float32, twenty where the operands are
    float32 (each goes to the MXU as three bf16 parts the compiler keeps
    beside it).  An upper count: over 14 shapes the chip's compiler
    accepted a limit of 0.57 to 0.91 of it (compiler, PR 41)."""
    width = heads * p
    blocks = (_tile(chunk, width, itemsize)
              + 2 * _tile(chunk, n, itemsize)
              + 2 * (_tile(chunk, heads, 4) + _tile(heads, chunk, 4))
              + 2 * _tile(chunk, width, 4) + heads * _tile(n, p, 4)
              + 2 * _tile(chunk, n, 4))
    scratch = ((all_heads // heads + 1) * _tile(n, width, 4)
               + _tile(chunk, width, itemsize) + 3 * _tile(chunk, width, 4)
               + _tile(1, width, 4))
    values = (7 * _tile(chunk, chunk, 4) + 2 * _tile(n, width, 4)
              + (10 if itemsize < 4 else 20) * _tile(chunk, width, 4))
    return 2 * blocks + scratch + values


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
    most ``HEADS_A_STEP`` heads, its blocks fit ``VMEM_A_STEP`` and the
    backward's step (the same cut) fits ``BWD_VMEM_LIMIT``; else the group
    is cut into the fewest equal head blocks that do (each reads the
    group's B and C; a block's lanes are then whole tiles of 128)."""
    _whole_chunks_and_groups(t, h, g, chunk)
    hpg = h // g
    for heads in range(min(hpg, HEADS_A_STEP), 0, -1):
        if hpg % heads or (heads != hpg and (heads * head_dim) % 128):
            continue
        sizes = (chunk, heads, head_dim, state, itemsize)
        if (step_vmem_bytes(*sizes) <= VMEM_A_STEP
                and bwd_step_vmem_bytes(*sizes, h) <= BWD_VMEM_LIMIT):
            return SsdPlan(chunk, t // chunk, heads,
                           b * (t // chunk) * g * (hpg // heads))
    raise ValueError(
        f"no head block of a group of {hpg} heads of {head_dim} at chunk "
        f"{chunk}, state {state} fits {VMEM_A_STEP} B of VMEM a grid step "
        f"of ssd_chunk_fwd and {BWD_VMEM_LIMIT} B a grid step of "
        f"ssd_chunk_bwd, which holds the state cotangent of all {h} heads")


def _report_plan(plan: SsdPlan, shape, g, n, dtype, impl):
    """One ``ssd.plan`` event per distinct plan (as ``flash.plan``);
    ``vmem_bytes`` and ``bwd_vmem_bytes`` are 0 where no kernel is
    launched.  ``bwd`` says what differentiates the call: ``"pallas"``
    (``ssd_chunk_bwd``) or ``"xla"`` (JAX's derivative of the chunked
    form)."""
    b, t, h, p = shape
    sizes = (plan.chunk, plan.heads_a_step, p, n, jnp.dtype(dtype).itemsize)
    vmem, bwd_vmem = (0, 0) if impl == "xla" else (
        step_vmem_bytes(*sizes), bwd_step_vmem_bytes(*sizes, h))
    plan_event("ssd.plan", **plan._asdict(), batch=b, seq=t, heads=h,
               head_dim=p, state=n, dtype=jnp.dtype(dtype).name, impl=impl,
               groups=g, blocks_a_group=h // g // plan.heads_a_step,
               vmem_bytes=vmem, bwd=impl, bwd_vmem_bytes=bwd_vmem)


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

def _prep(x, dt, a, chunk, dtype=None):
    """The running sum of ``dt a`` inside each chunk, (b, nc, q, h)
    float32, and ``x dt`` in ``dtype`` (the operands' own when None)."""
    b, t, h, _p = x.shape
    nc = t // chunk
    da = dt.astype(jnp.float32) * a.astype(jnp.float32)       # (b, t, h)
    cum = jnp.cumsum(da.reshape(b, nc, chunk, h), axis=2)     # (b,nc,q,h)
    xdt = (x.astype(jnp.float32)
           * dt.astype(jnp.float32)[..., None]).astype(dtype or x.dtype)
    return cum, xdt


def _states_before(states, cum):
    """The recurrence over chunk states: the state each chunk starts from,
    (b, nc, h, n, p) float32, of the chunks' own contributions ``states``
    (the same shape) and ``cum`` (b, nc, q, h)."""
    chunk_decay = jnp.exp(cum[:, :, -1, :])                   # (b, nc, h)

    def step(s_prev, xs):
        dec, own = xs
        return dec[..., None, None] * s_prev + own, s_prev

    s0 = jnp.zeros(states.shape[:1] + states.shape[2:], jnp.float32)
    _, s_prev = jax.lax.scan(
        step, s0, (chunk_decay.swapaxes(0, 1), states.swapaxes(0, 1)))
    return s_prev.swapaxes(0, 1)


def _read_states(s_prev, cum, c_mat, heads_per_group):
    """What each chunk reads from the state it starts with: ``s_prev``
    (b, nc, h, n, p) float32, ``cum`` (b, nc, q, h), ``c_mat``
    (b, nc, q, g, n).  Returns (b, nc, q, h, p) float32."""
    b, nc, q, h = cum.shape
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
    y = y.reshape(b, nc, q, h, p) + _read_states(
        _states_before(states, cum), cum, cr, hpg)
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


def _head_blocks(cum, plan: SsdPlan, g: int):
    """``cum`` (b, nc, q, h) as the kernels read it, by columns
    (b, blocks, t, heads a step) and by rows (b, blocks, heads a step, t),
    and the head blocks that read one group's B and C."""
    b, nc, q, h = cum.shape
    hb = plan.heads_a_step
    cg = cum.reshape(b, nc * q, h // hb, hb)
    return cg.transpose(0, 2, 1, 3), cg.transpose(0, 2, 3, 1), h // g // hb


def _chunks_pallas(xdt, b_mat, c_mat, cum, plan, interpret):
    """``ssd_chunk_fwd``: per chunk and head block (a group, or one of the
    blocks :func:`ssd_plan` cut it into), the masked quadratic form and
    the chunk's own state.  Returns y (b, t, h, p) float32 and states
    (b, nc, h, n, p) float32."""
    b, t, h, p = xdt.shape
    g, n = b_mat.shape[2], b_mat.shape[3]
    nc, q = cum.shape[1], cum.shape[2]
    hb = plan.heads_a_step
    cum_col, cum_row, per_group = _head_blocks(cum, plan, g)
    blocks = g * per_group
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


def _chunk_bwd_kernel(x_ref, b_ref, c_ref, cc_ref, cr_ref, dy_ref, s_ref,
                      dx_ref, db_ref, dc_ref, dcc_ref, dcr_ref,
                      ds_ref, dyb_ref, start_ref, cum_ref, owed_ref, end_ref,
                      dxi_ref, *, heads, p, per_group):
    """One chunk of one head block, the chunks walked from the LAST: the
    cotangent of the state a chunk hands on, ``ds_ref``, is carried back
    from chunk to chunk in VMEM (a row a head block), so the recurrence
    over states is differentiated here too and no state cotangent reaches
    HBM.  With ``dS`` what ``ds_ref`` holds on entry (the cotangent of the
    state at the chunk's END, and so of the chunk's own contribution) and
    ``S`` the state the chunk STARTS from, the step writes the cotangents
    of what :func:`_chunk_kernel` and :func:`_read_states` compute there
    and leaves ``keep dS + C^T (dy grow)`` for the chunk before, ``grow =
    exp(cum)`` and ``keep`` its last row.

    Only what touches a head's own (q, q) square runs head by head, and
    the square is held TRANSPOSED, ``[s, i]``: its two products
    (``dM^T = X dy^T`` and ``M^T dy``) then contract it as it lies, and
    only dC's product, once a step over the heads' summed ``d(C B^T)``,
    turns one.  Everything else runs once over the step's ``heads * p``
    lanes, a head's running sum spread over its lanes first (a (q, 1)
    column fills one lane of a register and p = 64 lanes half of one):
    ``B dS``, ``C S``, ``C^T (dy grow)`` are one product each, and the
    shares of dB and dC that are sums over the heads one product each
    that contracts all the lanes.  The running sum's cotangent leaves as
    a column (what row ``s`` owes: minus the square's sum over ``i``,
    minus the weight it gave its ``x dt`` in the state, plus what it read
    of ``S``; on the last row also what all rows gave and what the end
    kept of the start) and as a row (the square's sum over ``s``); XLA
    adds the two."""
    f32, dtype = jnp.float32, x_ref.dtype
    k = pl.program_id(2)

    @pl.when(pl.program_id(1) == 0)
    def _last_chunk():
        ds_ref[k] = jnp.zeros(ds_ref.shape[1:], f32)

    bm, cm = b_ref[0], c_ref[0]                               # (q, n)
    q = bm.shape[0]
    for j in range(heads):
        at = slice(j * p, (j + 1) * p)
        start_ref[:, at] = s_ref[0, 0, j]
        cum_ref[:, at] = jnp.broadcast_to(cc_ref[0, 0, :, j:j + 1], (q, p))
    cum = cum_ref[:]                                          # (q, width)
    grow = jnp.exp(cum)
    keep = grow[q - 1:q, :]                                   # (1, width)
    to_end = jnp.exp(cum[q - 1:q, :] - cum)
    start, ds = start_ref[:], ds_ref[k]                       # (n, width)
    startb, dsb = start.astype(dtype), ds.astype(dtype)
    dyf, xf = dy_ref[0], x_ref[0].astype(f32)                 # (q, width)
    dyb_ref[:] = dyf.astype(dtype)
    dye = (dyf * grow).astype(dtype)
    dxw = _dot(bm, dsb, 1, 0) * to_end            # the state's share of dX
    gave = dxw * xf
    # a row's debt but for its square's: what it read of S less the weight
    # it gave its x dt in the state, to be summed over each head's lanes
    owed_ref[:] = grow * dyf * _dot(cm, startb, 1, 0) - gave
    # the last row's due: what all rows gave, and what the end kept of S
    end_ref[:] = (jnp.sum(gave, axis=0, keepdims=True)
                  + keep * jnp.sum(ds * start, axis=0, keepdims=True))
    db = _dot((xf * to_end).astype(dtype), dsb, 1, 1)         # (q, n)
    dc = _dot(dye, startb, 1, 1)
    ds_ref[k] = keep * ds + _dot(cm, dye, 0, 0)

    cbt = _dot(bm, cm, 1, 1)                                  # (C B^T)^T
    upper = (jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
             >= jax.lax.broadcasted_iota(jnp.int32, (q, q), 0))
    last = jax.lax.broadcasted_iota(jnp.int32, (q, 1), 0) == q - 1
    dcbt = jnp.zeros((q, q), f32)
    for j in range(heads):
        at = slice(j * p, (j + 1) * p)
        col = cc_ref[0, 0, :, j:j + 1]                        # cum_s (q, 1)
        row = cr_ref[0, 0, j:j + 1, :]                        # cum_i (1, q)
        decay = jnp.exp(jnp.where(upper, row - col, _MASK))   # [s, i]
        dyh = dyb_ref[:, at]                                  # (q, p)
        dcb = _dot(x_ref[0, :, at], dyh, 1, 1) * decay        # d(C B^T)^T
        dcbt = dcbt + dcb
        wt = dcb * cbt                                        # d seg^T
        dxi_ref[:, at] = _dot((cbt * decay).astype(dtype), dyh, 1, 0)
        at_end = jnp.sum(end_ref[:, at], axis=1, keepdims=True)   # (1, 1)
        dcc_ref[0, 0, :, j:j + 1] = (
            jnp.where(last, at_end, 0.0)
            + jnp.sum(owed_ref[:, at], axis=1, keepdims=True)
            - jnp.sum(wt, axis=1, keepdims=True))
        dcr_ref[0, 0, j:j + 1, :] = jnp.sum(wt, axis=0, keepdims=True)
    dx_ref[0, 0] = dxi_ref[:] + dxw
    dcbt = dcbt.astype(dtype)
    dc = dc + _dot(dcbt, bm, 0, 0)
    db = db + _dot(dcbt, cm, 1, 0)
    # a group's head blocks follow one another: one dB, one dC
    first = k % per_group == 0

    @pl.when(first)
    def _set():
        db_ref[0, 0], dc_ref[0, 0] = db, dc

    @pl.when(jnp.logical_not(first))
    def _add():
        db_ref[0, 0] += db
        dc_ref[0, 0] += dc


def _chunks_bwd_pallas(xdt, b_mat, c_mat, cum, s_prev, dy, plan, interpret):
    """``ssd_chunk_bwd``: per chunk (from the last) and head block, the
    cotangents of y = :func:`_chunks_pallas`'s + :func:`_read_states`'s
    over the recurrence of :func:`_states_before`: d(x dt) (b, t, h, p),
    dB and dC (b, t, g, n) and the running sum's (b, nc, q, h), all
    float32.  No output has the forward's ``(b, t, h * p)`` (the
    benchmark's readers find ``ssd_chunk_fwd`` by it): rows go out by
    chunks."""
    b, t, h, p = xdt.shape
    g, n = b_mat.shape[2], b_mat.shape[3]
    nc, q = cum.shape[1], cum.shape[2]
    hb = plan.heads_a_step
    cum_col, cum_row, per_group = _head_blocks(cum, plan, g)
    blocks = g * per_group
    f32, size, back = jnp.float32, xdt.dtype.itemsize, nc - 1
    rows = pl.BlockSpec((1, q, hb * p), lambda i, c, k: (i, back - c, k))
    of_group = pl.BlockSpec((1, q, n),
                            lambda i, c, k: (i, back - c, k // per_group))
    cols = pl.BlockSpec((1, 1, q, hb), lambda i, c, k: (i, k, back - c, 0))
    lines = pl.BlockSpec((1, 1, hb, q), lambda i, c, k: (i, k, 0, back - c))
    grads = pl.BlockSpec((1, 1, q, n),
                         lambda i, c, k: (i, back - c, 0, k // per_group))

    def by_lanes(rows_, dtype):
        return pltpu.VMEM((rows_, hb * p), dtype)

    dxdt, db, dc, dcum_col, dcum_row = pl.pallas_call(
        functools.partial(_chunk_bwd_kernel, heads=hb, p=p,
                          per_group=per_group),
        name="ssd_chunk_bwd",
        grid=(b, nc, blocks),
        in_specs=[
            rows, of_group, of_group, cols, lines, rows,
            pl.BlockSpec((1, 1, hb, n, p),
                         lambda i, c, k: (i, back - c, k, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, q, hb * p),
                         lambda i, c, k: (i, back - c, 0, k)),
            grads, grads, cols, lines,
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, nc, q, h * p), f32),
            jax.ShapeDtypeStruct((b, nc, q, g * n), f32),
            jax.ShapeDtypeStruct((b, nc, q, g * n), f32),
            jax.ShapeDtypeStruct(cum_col.shape, f32),
            jax.ShapeDtypeStruct(cum_row.shape, f32),
        ],
        scratch_shapes=[pltpu.VMEM((blocks, n, hb * p), f32),
                        by_lanes(q, xdt.dtype), by_lanes(n, f32),
                        by_lanes(q, f32), by_lanes(q, f32), by_lanes(1, f32),
                        by_lanes(q, f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=BWD_VMEM_LIMIT),
        cost_estimate=pl.CostEstimate(
            flops=b * nc * blocks * (6 * q * q * n
                                     + hb * (4 * q * q * p + 10 * q * n * p)),
            transcendentals=b * t * h * (q + 3),
            bytes_accessed=(xdt.size + b_mat.size + c_mat.size) * size
            + 4 * (2 * xdt.size + s_prev.size + 2 * b * t * g * n
                   + 4 * cum.size)),
        interpret=interpret,
    )(xdt.reshape(b, t, h * p), b_mat.reshape(b, t, g * n),
      c_mat.reshape(b, t, g * n), cum_col, cum_row,
      dy.astype(f32).reshape(b, t, h * p), s_prev)
    dcum = (dcum_col.transpose(0, 2, 1, 3)
            + dcum_row.transpose(0, 3, 1, 2)).reshape(cum.shape)
    return (dxdt.reshape(b, t, h, p), db.reshape(b, t, g, n),
            dc.reshape(b, t, g, n), dcum)


# jitted, both, as ``gdn._gdn_pallas`` is: a step's layers, their
# recomputation and every trace of the step then trace and lower each
# kernel's body once a shape and not once a call, and a lowered module
# holds each kernel once, as a private function the layers call (XLA
# inlines the calls: the compiled step is what it was)
@functools.partial(jax.jit, static_argnums=(5, 6))
def _ssd_pallas(x, dt, a, b_mat, c_mat, plan, interpret):
    """y, and for the backward the running sum and the state each chunk
    starts from."""
    b, t, h, p = x.shape
    g = b_mat.shape[2]
    cum, xdt = _prep(x, dt, a, plan.chunk)
    y, states = _chunks_pallas(xdt, b_mat, c_mat, cum, plan, interpret)
    cr = c_mat.reshape(b, plan.chunks, plan.chunk, g, -1)
    s_prev = _states_before(states, cum)
    inter = _read_states(s_prev, cum, cr, h // g)
    return y + inter.reshape(b, t, h, p), cum, s_prev


@functools.partial(jax.jit, static_argnums=(8, 9))
def _ssd_pallas_bwd(x, dt, a, b_mat, c_mat, cum, s_prev, dy, plan,
                    interpret):
    """The cotangents of :func:`_ssd_pallas`'s y: the kernel's, taken back
    from ``x dt`` and the running sum to x, dt and a by JAX's derivative
    of :func:`_prep` (which makes ``x dt`` again; the running sum, 2 MB
    that a windowed sum takes 0.3-0.6 ms to make, is kept).  The
    derivative is taken of ``x dt`` BEFORE its cast to the operand type
    (a cast's derivative is the identity), so the kernel's float32
    d(x dt) goes into it as it is: nothing rounds it to ``x.dtype`` on the
    way, and dx is rounded once, as a gradient of ``x`` has to be."""
    with jax.named_scope("ssd_chunk_bwd"):
        (_, xdt), prep_vjp = jax.vjp(
            functools.partial(_prep, chunk=plan.chunk, dtype=jnp.float32),
            x, dt, a)
        dxdt, db, dc, dcum = _chunks_bwd_pallas(
            xdt.astype(x.dtype), b_mat, c_mat, cum, s_prev, dy, plan,
            interpret)
        dx, ddt, da = prep_vjp((dcum, dxdt))
    return dx, ddt, da, db.astype(b_mat.dtype), dc.astype(c_mat.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _ssd(x, dt, a, b_mat, c_mat, plan, interpret):
    return _ssd_pallas(x, dt, a, b_mat, c_mat, plan, interpret)[0]


def _ssd_fwd(x, dt, a, b_mat, c_mat, plan, interpret):
    y, *kept = _ssd_pallas(x, dt, a, b_mat, c_mat, plan, interpret)
    return y, (x, dt, a, b_mat, c_mat, *kept)


def _ssd_bwd(plan, interpret, res, dy):
    return _ssd_pallas_bwd(*res, dy, plan, interpret)


_ssd.defvjp(_ssd_fwd, _ssd_bwd)


def ssd_scan(x, dt, a, b_mat, c_mat, *, chunk: int = 128,
             impl: str = "auto", interpret: Optional[bool] = None):
    """y (B, T, H, P), float32, of the scan over x (B, T, H, P) with steps
    dt (B, T, H) > 0, decay rates a (H,) < 0 and b_mat / c_mat
    (B, T, G, N).  ``impl``: "pallas" (the kernels, forward and backward;
    interpreted off the TPU), "xla" (the chunked ``jax.numpy`` form and
    JAX's derivative of it) or "auto" (the kernels on the TPU, the XLA
    form elsewhere)."""
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
    plan = ssd_plan(b, t, h, g, chunk, head_dim=p, state=n,
                    itemsize=x.dtype.itemsize)
    _report_plan(plan, x.shape, g, n, x.dtype, impl)
    if interpret is None:
        interpret = off_tpu
    return _ssd(x, dt, a, b_mat, c_mat, plan, bool(interpret))
