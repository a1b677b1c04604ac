"""Mamba-1's selective scan, forward and backward, as Pallas TPU kernels.

Capability add over the reference (MXNet has no recurrent-state layer
beyond cuDNN RNNs).  Every channel ``c`` carries ``N`` states with a decay
of their own::

    s_t[c, n] = exp(dt_t[c] A[c, n]) s_{t-1}[c, n] + dt_t[c] B_t[n] x_t[c]
    y_t[c]    = sum_n C_t[n] s_t[c, n]                       (s_0 = 0)

with ``A < 0``, ``dt_t > 0`` per channel and step, and ``B_t``, ``C_t``
shared by all channels.  :mod:`mxnet_tpu.ops.ssd` cannot express it: its
decay is one scalar a head and its chunk a matrix product; here there is
no head and no product, the work is elementwise, on the vector unit.  As
an associative scan in ``jax.numpy`` it materialises (T, channels, N)
float32 several times; the kernels never do:

* ``sscan_fwd``: a grid step takes ``chunk`` steps of ``channels`` (a
  whole number of 128 lanes) with the state an (N, channels) float32 tile
  in VMEM — states on sublanes, channels on lanes — carried across the
  time axis of the grid.  It reads x, dt, B, C and writes y once, and
  keeps the state each chunk STARTS from, (T / chunk, N, channels), for
  the backward.
* ``sscan_bwd``: a grid step takes the same chunk, time running
  backwards: it walks the chunk forwards once more from its kept state,
  holding every step's state in VMEM ((chunk, N, channels) float32), then
  walks it backwards with ``g_t = C_t dy_t + exp(dt_{t+1} A) g_{t+1}``,
  the cotangent of the state, in the carry.  ``dB`` and ``dC`` sum over
  channels: a step writes its channels' part, the parts are added in XLA.

Inside a grid step time goes in sub-blocks of 16 steps (a packed bf16
tile's sublanes): a ``fori_loop`` over sub-blocks, each straight-line.
B and C travel as (T / 16, N, 16) so that a sub-block's is one (N, 16)
tile found by its leading index, and a step's column of it broadcasts
along lanes.

The state, the decays and every sum are float32 whatever the operands
are.  A sequence that is no whole number of chunks is padded with steps
of ``dt = 0``, which leave the state as it is.  Off the TPU the
recurrence itself (:func:`sscan_recurrence`, ``lax.scan``) is the
default; ``impl="pallas"`` forces the kernels (interpreted off the TPU,
for tests).
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash import _default_interpret, plan_event

__all__ = ["selective_scan", "sscan_recurrence", "sscan_plan", "SscanPlan"]

SUB = 16                  # steps of one straight-line sub-block
DEFAULT_CHUNK = 128       # steps a grid step; a state is kept a chunk
CHANNELS_A_STEP = 512     # lanes of the state tile, where they divide


class SscanPlan(NamedTuple):
    """Sizes of one scan call: steps a grid step, channels a grid step,
    grid steps of ``sscan_fwd`` (``sscan_bwd`` takes as many), and the
    bytes of VMEM the larger of the two (the backward) asks for."""
    chunk: int
    channels: int
    grid_steps: int
    vmem_bytes: int


def _tile(rows, cols, size):
    return -(-rows // 8) * 8 * -(-cols // 128) * 128 * size


def step_vmem_bytes(chunk: int, channels: int, n: int, itemsize: int) -> int:
    """Bytes of one ``sscan_bwd`` grid step: its blocks, each twice (the
    pipeline's double buffer) — x in the operand type; dt, dy, dx, ddt in
    float32; B, C, dB, dC as (chunk / 16) tiles of (N, 16); A, dA and the
    kept state — and its scratch: every step's state and the carry."""
    rows = 2 * (_tile(chunk, channels, itemsize)
                + 4 * _tile(chunk, channels, 4)
                + 4 * (chunk // SUB) * _tile(n, SUB, 4)
                + 3 * _tile(n, channels, 4))
    return rows + (chunk + 1) * _tile(n, channels, 4)


def sscan_plan(b: int, t: int, c: int, n: int, *, chunk: Optional[int] = None,
               itemsize: int = 2) -> SscanPlan:
    """``chunk`` steps (a whole number of sub-blocks; the sequence is
    padded to whole chunks) of the widest of 512 / 256 / 128 channels
    that divides ``c``, or of all of them where none does (small sizes:
    the chip's compiler wants whole tiles of 128 lanes)."""
    chunk = int(chunk or DEFAULT_CHUNK)
    if chunk % SUB:
        raise ValueError(f"a chunk of {chunk} steps is no whole number of "
                         f"sub-blocks of {SUB}")
    channels = next((w for w in (CHANNELS_A_STEP, 256, 128) if c % w == 0),
                    c)
    chunks = -(-t // chunk)
    return SscanPlan(chunk, channels, b * (c // channels) * chunks,
                     step_vmem_bytes(chunk, channels, n, itemsize))


def _decay(dta):
    """What a step keeps of the state: ``exp(dt A)``."""
    return jnp.exp(dta)


def sscan_recurrence(x, dt, a, b_mat, c_mat):
    """The definition, one step at a time in float32: x, dt (B, T, C),
    a (C, N), b_mat / c_mat (B, T, N); returns y (B, T, C) float32.  Small
    sizes only: differentiated, it keeps (T, C, N)."""
    f32 = jnp.float32
    x, dt, a, b_mat, c_mat = (v.astype(f32) for v in (x, dt, a, b_mat, c_mat))

    def step(s, xs):
        xt, dtt, bt, ct = xs                      # (B, C), (B, C), (B, N)
        s = (_decay(dtt[..., None] * a) * s
             + (dtt * xt)[..., None] * bt[:, None, :])
        return s, jnp.sum(s * ct[:, None, :], axis=-1)

    s0 = jnp.zeros(x.shape[:1] + a.shape, f32)
    _, y = jax.lax.scan(step, s0, tuple(v.swapaxes(0, 1)
                                        for v in (x, dt, b_mat, c_mat)))
    return y.swapaxes(0, 1)


# ------------------------------------------------------------ the kernels

def _put_row(tile, j, row):
    """``tile`` with its row ``j`` (static) replaced by ``row`` (1, lanes)."""
    at = jax.lax.broadcasted_iota(jnp.int32, tile.shape, 0) == j
    return jnp.where(at, row, tile)


def _put_col(tile, j, col):
    """``tile`` with its column ``j`` (static) replaced by ``col`` (N, 1)."""
    at = jax.lax.broadcasted_iota(jnp.int32, tile.shape, 1) == j
    return jnp.where(at, col, tile)


def _fwd_kernel(x_ref, dt_ref, b_ref, c_ref, a_ref, y_ref, keep_ref, s_ref,
                *, chunk):
    @pl.when(pl.program_id(2) == 0)
    def _start():
        s_ref[:] = jnp.zeros_like(s_ref)

    keep_ref[0, 0] = s_ref[:]
    a = a_ref[:]                                           # (N, channels)
    channels = a.shape[1]

    def sub_block(i, s):
        t0 = pl.multiple_of(i * SUB, SUB)
        dt = dt_ref[0, pl.ds(t0, SUB), :]                  # (16, channels)
        dtx = dt * x_ref[0, pl.ds(t0, SUB), :].astype(jnp.float32)
        y = jnp.zeros((SUB, channels), jnp.float32)
        for j in range(SUB):
            s = (_decay(dt[j:j + 1, :] * a) * s
                 + b_ref[0, i, :, j:j + 1] * dtx[j:j + 1, :])
            y = _put_row(y, j, jnp.sum(c_ref[0, i, :, j:j + 1] * s, axis=0,
                                       keepdims=True))
        y_ref[0, pl.ds(t0, SUB), :] = y
        return s

    s_ref[:] = jax.lax.fori_loop(0, chunk // SUB, sub_block, s_ref[:])


def _bwd_kernel(x_ref, dt_ref, b_ref, c_ref, a_ref, keep_ref, dy_ref,
                dx_ref, ddt_ref, db_ref, dc_ref, da_ref, all_ref, g_ref,
                *, chunk):
    @pl.when(pl.program_id(2) == 0)            # the LAST chunk: time runs back
    def _start():
        g_ref[:] = jnp.zeros_like(g_ref)
        da_ref[0] = jnp.zeros_like(da_ref[0])

    a = a_ref[:]                                           # (N, channels)
    n, channels = a.shape
    nsub = chunk // SUB

    def again(i, s):                   # forwards once more, every state kept
        t0 = pl.multiple_of(i * SUB, SUB)
        dt = dt_ref[0, pl.ds(t0, SUB), :]
        dtx = dt * x_ref[0, pl.ds(t0, SUB), :].astype(jnp.float32)
        for j in range(SUB):
            s = (_decay(dt[j:j + 1, :] * a) * s
                 + b_ref[0, i, :, j:j + 1] * dtx[j:j + 1, :])
            all_ref[t0 + j] = s
        return s

    jax.lax.fori_loop(0, nsub, again, keep_ref[0, 0])

    def back(k, carry):
        g, da = carry                  # exp(dt_{t+1} A) g_{t+1}; dA so far
        i = nsub - 1 - k
        t0 = pl.multiple_of(i * SUB, SUB)
        dt = dt_ref[0, pl.ds(t0, SUB), :]
        x = x_ref[0, pl.ds(t0, SUB), :].astype(jnp.float32)
        dy = dy_ref[0, pl.ds(t0, SUB), :]
        dtx = dt * x
        dx = jnp.zeros((SUB, channels), jnp.float32)
        ddt = jnp.zeros((SUB, channels), jnp.float32)
        db = jnp.zeros((n, SUB), jnp.float32)
        dc = jnp.zeros((n, SUB), jnp.float32)
        before = all_ref[t0 + SUB - 1]
        for j in reversed(range(SUB)):
            bcol = b_ref[0, i, :, j:j + 1]                 # (N, 1)
            # the step's state, and the one it starts from: the one
            # before it, or the chunk's kept one
            s = before
            if j:
                before = all_ref[t0 + j - 1]
            else:
                before = jnp.where(i == 0, keep_ref[0, 0],
                                   all_ref[jnp.maximum(t0 - 1, 0)])
            dyj = dy[j:j + 1, :]
            g = g + c_ref[0, i, :, j:j + 1] * dyj
            dc = _put_col(dc, j, jnp.sum(dyj * s, axis=1, keepdims=True))
            db = _put_col(db, j, jnp.sum(g * dtx[j:j + 1, :], axis=1,
                                         keepdims=True))
            decay = _decay(dt[j:j + 1, :] * a)
            gb = jnp.sum(g * bcol, axis=0, keepdims=True)  # (1, channels)
            turn = g * before * decay                      # d(decay) decay
            dx = _put_row(dx, j, gb * dt[j:j + 1, :])
            ddt = _put_row(ddt, j, gb * x[j:j + 1, :]
                           + jnp.sum(turn * a, axis=0, keepdims=True))
            da = da + turn * dt[j:j + 1, :]
            g = decay * g
        dx_ref[0, pl.ds(t0, SUB), :] = dx
        ddt_ref[0, pl.ds(t0, SUB), :] = ddt
        db_ref[0, 0, i] = db
        dc_ref[0, 0, i] = dc
        return g, da

    g, da = jax.lax.fori_loop(0, nsub, back, (g_ref[:], da_ref[0]))
    g_ref[:] = g
    da_ref[0] = da


_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"),
    vmem_limit_bytes=48 << 20)


def _by_sub_block(m):
    """(B, T, N) -> (B, T / 16, N, 16) float32: a sub-block's steps along
    the lanes of one tile."""
    b, t, n = m.shape
    return m.astype(jnp.float32).reshape(b, t // SUB, SUB, n).swapaxes(2, 3)


def _from_sub_blocks(m):
    """(..., T / 16, N, 16) -> (..., T, N)."""
    m = m.swapaxes(-1, -2)
    return m.reshape(m.shape[:-3] + (m.shape[-3] * SUB, m.shape[-1]))


def _specs(plan, n, back):
    """BlockSpecs of (x-like (B, T, C), B-like (B, T/16, N, 16), A-like
    (N, C), the kept states (B, chunks, N, C)) for the grid (batch,
    channel block, chunk); ``back`` = chunks - 1 where time runs
    backwards, else None."""
    chunk, ch = plan.chunk, plan.channels

    def at(k):
        return k if back is None else back - k

    return (pl.BlockSpec((1, chunk, ch), lambda i, c, k: (i, at(k), c)),
            pl.BlockSpec((1, chunk // SUB, n, SUB),
                         lambda i, c, k: (i, at(k), 0, 0)),
            pl.BlockSpec((n, ch), lambda i, c, k: (0, c)),
            pl.BlockSpec((1, 1, n, ch), lambda i, c, k: (i, at(k), 0, c)))


def _fwd(x, dt, a_t, b_sub, c_sub, plan, interpret):
    b, t, c = x.shape
    n = a_t.shape[0]
    chunks = t // plan.chunk
    rows, cols, whole, kept = _specs(plan, n, None)
    with jax.named_scope("sscan_fwd"):
        return pl.pallas_call(
            functools.partial(_fwd_kernel, chunk=plan.chunk),
            name="sscan_fwd",
            grid=(b, c // plan.channels, chunks),
            in_specs=[rows, rows, cols, cols, whole],
            out_specs=[rows, kept],
            out_shape=[jax.ShapeDtypeStruct((b, t, c), jnp.float32),
                       jax.ShapeDtypeStruct((b, chunks, n, c), jnp.float32)],
            scratch_shapes=[pltpu.VMEM((n, plan.channels), jnp.float32)],
            compiler_params=_PARAMS,
            cost_estimate=pl.CostEstimate(
                flops=7 * b * t * c * n, transcendentals=b * t * c * n,
                bytes_accessed=x.size * x.dtype.itemsize
                + 4 * (dt.size + x.size + b_sub.size + c_sub.size)),
            interpret=interpret,
        )(x, dt, b_sub, c_sub, a_t)


def _bwd(x, dt, a_t, b_sub, c_sub, kept, dy, plan, interpret):
    b, t, c = x.shape
    n = a_t.shape[0]
    chunks, blocks = t // plan.chunk, c // plan.channels
    rows, cols, whole, kept_spec = _specs(plan, n, chunks - 1)
    part = pl.BlockSpec((1, 1, plan.chunk // SUB, n, SUB),
                        lambda i, c, k: (i, c, chunks - 1 - k, 0, 0))
    part_shape = jax.ShapeDtypeStruct((b, blocks, t // SUB, n, SUB),
                                      jnp.float32)
    with jax.named_scope("sscan_bwd"):
        dx, ddt, db, dc, da = pl.pallas_call(
            functools.partial(_bwd_kernel, chunk=plan.chunk),
            name="sscan_bwd",
            grid=(b, blocks, chunks),
            in_specs=[rows, rows, cols, cols, whole, kept_spec, rows],
            out_specs=[rows, rows, part, part,
                       pl.BlockSpec((1, n, plan.channels),
                                    lambda i, c, k: (i, 0, c))],
            out_shape=[jax.ShapeDtypeStruct((b, t, c), jnp.float32),
                       jax.ShapeDtypeStruct((b, t, c), jnp.float32),
                       part_shape, part_shape,
                       jax.ShapeDtypeStruct((b, n, c), jnp.float32)],
            scratch_shapes=[
                pltpu.VMEM((plan.chunk, n, plan.channels), jnp.float32),
                pltpu.VMEM((n, plan.channels), jnp.float32)],
            compiler_params=_PARAMS,
            interpret=interpret,
        )(x, dt, b_sub, c_sub, a_t, kept, dy.astype(jnp.float32))
        # the channel blocks' parts of dB and dC, and the batch rows' of dA
        return (dx, ddt, da.sum(axis=0).T, _from_sub_blocks(db.sum(axis=1)),
                _from_sub_blocks(dc.sum(axis=1)))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _sscan(x, dt, a, b_mat, c_mat, plan, interpret):
    return _sscan_fwd(x, dt, a, b_mat, c_mat, plan, interpret)[0]


def _sscan_fwd(x, dt, a, b_mat, c_mat, plan, interpret):
    a_t, b_sub, c_sub = (a.astype(jnp.float32).T, _by_sub_block(b_mat),
                         _by_sub_block(c_mat))
    y, kept = _fwd(x, dt, a_t, b_sub, c_sub, plan, interpret)
    # zero-size stand-ins carry the operands' types to the backward
    like = tuple(jnp.zeros((0,), v.dtype) for v in (x, dt, a, b_mat, c_mat))
    return y, (x, dt, a_t, b_sub, c_sub, kept, like)


def _sscan_bwd(plan, interpret, res, dy):
    x, dt, a_t, b_sub, c_sub, kept, like = res
    grads = _bwd(x, dt, a_t, b_sub, c_sub, kept, dy, plan, interpret)
    return tuple(g.astype(v.dtype) for g, v in zip(grads, like))


_sscan.defvjp(_sscan_fwd, _sscan_bwd)


def selective_scan(x, dt, a, b_mat, c_mat, *, chunk: Optional[int] = None,
                   impl: str = "auto"):
    """y (B, T, C), float32, of the selective scan over x (B, T, C) with
    steps dt (B, T, C) > 0 (float32), decay rates a (C, N) < 0 and b_mat /
    c_mat (B, T, N).  ``impl``: "pallas" (the kernels; interpreted off the
    TPU), "xla" (the recurrence by ``lax.scan``: small sizes) or "auto"
    (the kernels on the TPU, the recurrence elsewhere)."""
    b, t, c = x.shape
    n = a.shape[1]
    off_tpu = _default_interpret(x)
    if impl == "auto":
        impl = "xla" if off_tpu else "pallas"
    if impl not in ("pallas", "xla"):
        raise ValueError(f"impl must be auto, pallas or xla, got {impl!r}")
    plan = sscan_plan(b, t, c, n, chunk=chunk, itemsize=x.dtype.itemsize)
    plan_event("sscan.plan", **plan._asdict()
               | ({"vmem_bytes": 0} if impl == "xla" else {}),
               batch=b, seq=t, channels_all=c, state=n,
               dtype=jnp.dtype(x.dtype).name, impl=impl)
    if impl == "xla":
        return sscan_recurrence(x, dt, a, b_mat, c_mat)
    pad = -t % plan.chunk
    if pad:                      # steps of dt = 0 leave the state as it is
        x, dt, b_mat, c_mat = (jnp.pad(v, ((0, 0), (0, pad), (0, 0)))
                               for v in (x, dt, b_mat, c_mat))
    y = _sscan(x, dt.astype(jnp.float32), a, b_mat, c_mat, plan, off_tpu)
    return y[:, :t] if pad else y
