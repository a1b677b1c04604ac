"""The gated delta rule in chunks (Gated DeltaNet's linear attention),
forward and backward.

One value head's recurrence over a ``(d_k, d_v)`` state ``S`` that starts
at zero is

    S'  = alpha_t S_{t-1}                 alpha_t = exp(g_t),  g_t <= 0
    u_t = beta_t (v_t - S'^T k_t)         0 < beta_t < 1
    S_t = S' + k_t u_t^T
    o_t = S_t^T q_t

so the state's transition is ``alpha_t (I - beta_t k_t k_t^T)``: a decay
and a rank-one correction a step, where Mamba-2's (:mod:`.ssd`) is a decay
alone.  ``q`` and ``k`` have ``H_k`` heads, ``v`` has ``H_v`` (a multiple);
value head ``h`` reads key head ``h // (H_v / H_k)``.

A scan over T steps leaves the MXU idle, so the sequence is cut into
chunks of ``chunk`` steps (64).  With ``G`` the running sum of ``g``
inside the chunk and ``S_0`` the state the chunk starts from, the
``u_t`` of a chunk solve a unit lower-triangular system (the WY form):

    (I + A) U = diag(beta) V - diag(beta exp(G)) K S_0
    A_ij = beta_i exp(G_i - G_j) (k_i . k_j)   for j < i, else 0

* **inside a chunk**: ``T = (I + A)^-1`` in float32.  ``A`` is strictly
  lower triangular, so ``A^chunk = 0`` and the forward substitution is
  the finite product ``(I - A)(I + A^2)(I + A^4)...``: log2(chunk)
  squarings, every one a matmul.  Then ``U' = T (beta V)`` and
  ``W = T (beta exp(G) K)``.
* **between chunks**, in order: ``U = U' - W S_0``;
  ``o = (q exp(G)) S_0 + tril(q k^T * decay) U``;
  ``S_end = exp(G_last) S_0 + (k exp(G_last - G))^T U``.

Every exponent is a difference of running sums that is <= 0, so no decay
overflows and one that underflows is an honest zero.  Decays, ``T`` and
the state are float32 whatever the operands are.

On the TPU the whole of this is the Pallas kernel ``gdn_chunk_fwd``: one
grid step takes one chunk of one key head with the value heads it serves,
walks the chunks of a head in order and keeps the ``(d_k, d_v)`` state in
VMEM, which XLA's between-chunk scan cannot fuse.  The backward pass is
the chunked form written in ``jax.numpy`` (:func:`gdn_chunked`),
differentiated by JAX under a ``custom_vjp`` (scope ``gdn_chunk_bwd``).
Off the TPU the chunked XLA form is the forward too; ``impl="pallas"``
forces the kernel (interpreted off the TPU, for tests).
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

__all__ = ["gdn_scan", "gdn_chunked", "gdn_recurrence", "gdn_plan"]

_MASK = -1e30
_HI = jax.lax.Precision.HIGHEST


class GdnPlan(NamedTuple):
    """Sizes of one call: steps a chunk, chunks, value heads a grid step
    (those one key head serves), grid steps of ``gdn_chunk_fwd``."""
    chunk: int
    chunks: int
    heads_a_step: int
    grid_steps: int


def gdn_plan(b: int, t: int, hk: int, hv: int, chunk: int) -> GdnPlan:
    if t % chunk:
        raise ValueError(f"sequence {t} is not a whole number of chunks "
                         f"of {chunk}")
    if hv % hk:
        raise ValueError(f"{hv} value heads do not divide over {hk} key "
                         f"heads")
    return GdnPlan(chunk, t // chunk, hv // hk, b * hk * (t // chunk))


def _report_plan(plan: GdnPlan, q, v, impl):
    """One ``gdn.plan`` event per distinct plan (as ``ssd.plan``)."""
    b, t, hk, dk = q.shape
    plan_event("gdn.plan", **plan._asdict(), batch=b, seq=t, key_heads=hk,
               value_heads=v.shape[2], key_dim=dk, value_dim=v.shape[3],
               dtype=jnp.dtype(v.dtype).name, impl=impl)


def gdn_recurrence(q, k, v, g, beta):
    """The definition, one step at a time in float32 (tests and the
    reference's anchor; never on a training path).  q, k (B, T, H_k, d_k);
    v (B, T, H_v, d_v); g (log decay, <= 0) and beta (B, T, H_v).
    Returns o (B, T, H_v, d_v)."""
    f32 = jnp.float32
    r = v.shape[2] // q.shape[2]
    q, k, v, g, beta = (x.astype(f32) for x in (q, k, v, g, beta))
    q, k = (jnp.repeat(x, r, axis=2) for x in (q, k))

    def step(s, xs):
        qt, kt, vt, gt, bt = xs                      # (B, H, ...)
        s = jnp.exp(gt)[..., None, None] * s
        u = bt[..., None] * (vt - jnp.einsum("bhkv,bhk->bhv", s, kt,
                                             precision=_HI))
        s = s + kt[..., :, None] * u[..., None, :]
        return s, jnp.einsum("bhkv,bhk->bhv", s, qt, precision=_HI)

    s0 = jnp.zeros((q.shape[0], v.shape[2], q.shape[3], v.shape[3]), f32)
    _, o = jax.lax.scan(step, s0, tuple(x.swapaxes(0, 1)
                                        for x in (q, k, v, g, beta)))
    return o.swapaxes(0, 1)


def _unit_lower_inverse(a, eye, matmul):
    """``(I + a)^-1`` for a strictly lower triangular ``a`` (c, c):
    ``(I - a)(I + a^2)(I + a^4)...`` up to the power c - 1."""
    c = a.shape[-1]
    inv, power, n = eye - a, a, 2
    while n < c:
        power = matmul(power, power)
        inv = inv + matmul(inv, power)
        n *= 2
    return inv


# --------------------------------------------------------- the chunked form

def gdn_chunked(q, k, v, g, beta, *, chunk=64):
    """The chunked rule in ``jax.numpy`` (arguments as
    :func:`gdn_recurrence`).  Returns o (B, T, H_v, d_v) float32.  Matmul
    operands keep ``v.dtype``; decays, ``T`` and the state are float32."""
    b, t, hk, dk = q.shape
    hv, dv = v.shape[2], v.shape[3]
    r, c, nc = hv // hk, chunk, t // chunk
    f32, cd = jnp.float32, v.dtype
    pr = _prec(cd)
    # (b, nc, hv, c): a head's chunk is a row
    gs = jnp.cumsum(g.astype(f32).reshape(b, nc, c, hv), axis=2)
    gs = gs.transpose(0, 1, 3, 2)
    bt = beta.astype(f32).reshape(b, nc, c, hv).transpose(0, 1, 3, 2)
    qr = q.astype(cd).reshape(b, nc, c, hk, dk)
    kr = k.astype(cd).reshape(b, nc, c, hk, dk)
    kk = jnp.einsum("bcihd,bcjhd->bchij", kr, kr, precision=pr,
                    preferred_element_type=f32)
    qk = jnp.einsum("bcihd,bcjhd->bchij", qr, kr, precision=pr,
                    preferred_element_type=f32)
    kk, qk = (jnp.repeat(x, r, axis=2) for x in (kk, qk))   # (b,nc,hv,c,c)
    rows = jnp.arange(c)
    lower = rows[:, None] >= rows[None, :]
    strict = rows[:, None] > rows[None, :]
    decay = jnp.exp(jnp.where(lower, gs[..., :, None] - gs[..., None, :],
                              _MASK))
    a = jnp.where(strict, bt[..., :, None] * kk * decay, 0.0)
    inv = _unit_lower_inverse(
        a, jnp.eye(c, dtype=f32),
        lambda x, y: jnp.einsum("...ij,...jk->...ik", x, y, precision=_HI))
    inv = inv.astype(cd)
    # value heads beside their key head: (b, nc, hv, c, d)
    kv = jnp.repeat(kr.astype(f32), r, axis=3).transpose(0, 1, 3, 2, 4)
    qv = jnp.repeat(qr.astype(f32), r, axis=3).transpose(0, 1, 3, 2, 4)
    vv = v.astype(f32).reshape(b, nc, c, hv, dv).transpose(0, 1, 3, 2, 4)
    eg = jnp.exp(gs)[..., None]
    mm = functools.partial(jnp.einsum, precision=pr,
                           preferred_element_type=f32)
    u0 = mm("bchij,bchjd->bchid", inv, (vv * bt[..., None]).astype(cd))
    w = mm("bchij,bchjd->bchid", inv,
           (kv * (bt[..., None] * eg)).astype(cd)).astype(cd)
    qg = (qv * eg).astype(cd)
    attn = (qk * decay).astype(cd)
    to_end = gs[..., -1:]                                    # (b,nc,hv,1)
    kdec = (kv * jnp.exp(to_end - gs)[..., None]).astype(cd)
    end_decay = jnp.exp(to_end)[..., None]                   # (b,nc,hv,1,1)

    def step(s, xs):
        u0c, wc, qgc, ac, kc, dc = xs
        sc = s.astype(cd)
        u = (u0c - mm("bhik,bhkv->bhiv", wc, sc)).astype(cd)
        o = mm("bhik,bhkv->bhiv", qgc, sc) + mm("bhij,bhjv->bhiv", ac, u)
        return dc * s + mm("bhik,bhiv->bhkv", kc, u), o

    s0 = jnp.zeros((b, hv, dk, dv), f32)
    _, o = jax.lax.scan(step, s0, tuple(
        x.swapaxes(0, 1) for x in (u0, w, qg, attn, kdec, end_decay)))
    # (nc, b, hv, c, dv) -> (b, t, hv, dv)
    return o.transpose(1, 0, 3, 2, 4).reshape(b, t, hv, dv)


# ------------------------------------------------------------- the kernel

def _chunk_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, o_ref, s_ref, *, r,
                  dv):
    @pl.when(pl.program_id(2) == 0)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)

    f32 = jnp.float32
    q, k = q_ref[0], k_ref[0]                                 # (c, dk)
    c, cd = q.shape[0], q.dtype
    kk, qk = _dot(k, k, 1, 1), _dot(q, k, 1, 1)               # (c, c) f32
    qf, kf = q.astype(f32), k.astype(f32)
    row = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    eye = row == col

    def column(x):                    # (1, c) -> (c, 1) with no transpose
        return jnp.sum(jnp.where(eye, x, 0.0), axis=1, keepdims=True)

    for j in range(r):
        g_row, b_row = g_ref[0, 0, 0, j:j + 1, :], b_ref[0, 0, 0, j:j + 1, :]
        g_col, b_col = column(g_row), column(b_row)
        decay = jnp.exp(jnp.where(row >= col, g_col - g_row, _MASK))
        a = jnp.where(row > col, b_col * kk * decay, 0.0)
        inv = _unit_lower_inverse(
            a, eye.astype(f32), lambda x, y: _dot(x, y, 1, 0)).astype(cd)
        vj = v_ref[0, :, j * dv:(j + 1) * dv].astype(f32)     # (c, dv)
        eg = jnp.exp(g_col)
        u0 = _dot(inv, (vj * b_col).astype(cd), 1, 0)
        w = _dot(inv, (kf * (b_col * eg)).astype(cd), 1, 0).astype(cd)
        s = s_ref[j]                                          # (dk, dv)
        sc = s.astype(cd)
        u = (u0 - _dot(w, sc, 1, 0)).astype(cd)
        o_ref[0, :, j * dv:(j + 1) * dv] = (
            _dot((qf * eg).astype(cd), sc, 1, 0)
            + _dot((qk * decay).astype(cd), u, 1, 0))
        g_end = g_row[:, c - 1:c]                             # (1, 1)
        kdec = (kf * jnp.exp(g_end - g_col)).astype(cd)
        # (1, 1) -> lanes first: Mosaic does not broadcast both ways at once
        s_ref[j] = (jnp.exp(jnp.broadcast_to(g_end, (1, dv))) * s
                    + _dot(kdec, u, 0, 0))


def _gdn_pallas(q, k, v, g, beta, chunk, interpret):
    """``gdn_chunk_fwd``: the whole chunked rule, one key head's chunk a
    grid step, the chunks of a head in order."""
    b, t, hk, dk = q.shape
    hv, dv = v.shape[2], v.shape[3]
    r, c, nc = hv // hk, chunk, t // chunk
    f32, cd = jnp.float32, v.dtype

    def rows(x):          # (b, t, hv) -> (b, nc, hk, r, c): a head a row
        return x.reshape(b, nc, c, hk, r).transpose(0, 1, 3, 4, 2)

    gs = rows(jnp.cumsum(g.astype(f32).reshape(b, nc, c, hv), axis=2))
    small = pl.BlockSpec((1, 1, 1, r, c), lambda i, h, n: (i, n, h, 0, 0))
    per_head = 2 * c * c * (2 * dk + dv + dk) + 2 * c * dk * dv * 3
    # two (c, c) products a doubling, log2(c) - 1 doublings
    inverse = 2 * c ** 3 * 2 * max(c.bit_length() - 2, 0)
    o = pl.pallas_call(
        functools.partial(_chunk_kernel, r=r, dv=dv),
        name="gdn_chunk_fwd",
        grid=(b, hk, nc),
        in_specs=[
            pl.BlockSpec((1, c, dk), lambda i, h, n: (i, n, h)),
            pl.BlockSpec((1, c, dk), lambda i, h, n: (i, n, h)),
            pl.BlockSpec((1, c, r * dv), lambda i, h, n: (i, n, h)),
            small, small,
        ],
        out_specs=pl.BlockSpec((1, c, r * dv), lambda i, h, n: (i, n, h)),
        out_shape=jax.ShapeDtypeStruct((b, t, hv * dv), f32),
        scratch_shapes=[pltpu.VMEM((r, dk, dv), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=b * nc * hv * (per_head + inverse),
            transcendentals=b * t * hv * (c + 3),
            bytes_accessed=(2 * b * t * hk * dk + b * t * hv * dv)
            * cd.itemsize + 4 * b * t * hv * (dv + 2)),
        interpret=interpret,
    )(q.astype(cd).reshape(b, t, hk * dk), k.astype(cd).reshape(b, t, hk * dk),
      v.reshape(b, t, hv * dv), gs, rows(beta.astype(f32)))
    return o.reshape(b, t, hv, dv)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _gdn(q, k, v, g, beta, chunk, interpret):
    return _gdn_pallas(q, k, v, g, beta, chunk, interpret)


def _gdn_fwd(q, k, v, g, beta, chunk, interpret):
    return (_gdn_pallas(q, k, v, g, beta, chunk, interpret),
            (q, k, v, g, beta))


# key heads differentiated together: the backward's residuals (a state a
# chunk, T and the powers it was made from) are 3.5 GB for 16 key heads at
# T 8,192 (compiler, PR 30); four at a time need a quarter of that
_BWD_KEY_HEADS = 4


def _gdn_bwd(chunk, interpret, res, ct):
    # the chunked XLA form recomputed and differentiated by JAX, a group
    # of key heads (with the value heads they serve) at a time
    hk = res[0].shape[2]
    groups = hk // _BWD_KEY_HEADS if hk % _BWD_KEY_HEADS == 0 else 1

    def split(x):             # (b, t, h, ..) -> (groups, b, t, h / groups, ..)
        return jnp.moveaxis(x.reshape(
            x.shape[:2] + (groups, x.shape[2] // groups) + x.shape[3:]), 2, 0)

    def merge(x):
        x = jnp.moveaxis(x, 0, 2)
        return x.reshape(x.shape[:2] + (-1,) + x.shape[4:])

    def one(args):
        _, vjp = jax.vjp(functools.partial(gdn_chunked, chunk=chunk),
                         *args[:-1])
        return vjp(args[-1])

    with jax.named_scope("gdn_chunk_bwd"):
        grads = jax.lax.map(one, tuple(split(x) for x in res + (ct,)))
        return tuple(merge(x) for x in grads)


_gdn.defvjp(_gdn_fwd, _gdn_bwd)


def gdn_scan(q, k, v, g, beta, *, chunk: int = 64, impl: str = "auto",
             interpret: Optional[bool] = None):
    """o (B, T, H_v, d_v), float32, of the gated delta rule over q, k
    (B, T, H_k, d_k), v (B, T, H_v, d_v), log decays g <= 0 and write
    strengths beta (B, T, H_v).  ``impl``: "pallas" (the kernel;
    interpreted off the TPU), "xla" (the chunked ``jax.numpy`` form) or
    "auto" (the kernel on the TPU, the XLA form elsewhere)."""
    plan = gdn_plan(q.shape[0], q.shape[1], q.shape[2], v.shape[2], chunk)
    off_tpu = _default_interpret(v)
    if impl == "auto":
        impl = "xla" if off_tpu else "pallas"
    _report_plan(plan, q, v, impl)
    if impl == "xla":
        return gdn_chunked(q, k, v, g, beta, chunk=chunk)
    if impl != "pallas":
        raise ValueError(f"impl must be auto, pallas or xla, got {impl!r}")
    if interpret is None:
        interpret = off_tpu
    return _gdn(q, k, v, g, beta, chunk, bool(interpret))
