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

On the TPU the whole of this is the Pallas kernel ``gdn_chunk_fwd``, which
keeps the ``(d_k, d_v)`` states in VMEM (XLA's between-chunk scan cannot
fuse that).  Its time is the float32 inverse's: a ``highest`` product is
six MXU passes, each pushing its own right operand and counted by the
(8, 128) register whether 64 of its lanes are used or 128, and the MXU
answers in the order it was asked.  So (:func:`gdn_plan` says how far, from
the shapes):

* a key head's value heads go SIDE BY SIDE on a row of lanes while that is
  no wider than 128 (two of chunk 64): ``[a_0 | a_1] @ diag(b_0, b_1)`` is
  both heads' product in one, on full registers, and ``T`` comes out as
  the backward keeps it;
* the two products of a doubling level, ``inv @ power`` and ``power @
  power``, share their right operand and go as ONE of twice the rows: six
  products where there were ten; likewise ``W S`` with ``(q e^G) S``, and
  the value heads' ``T (beta V)``, ``T (beta e^G K)`` and ``attn U`` with
  each head's rows masked to its own lanes;
* a grid step takes one chunk of several key heads (four) and WRITES
  THEIR CHAINS ALTERNATELY (:func:`_in_step`), product by product: they
  do not wait for each other, so one's operands go in while another's
  results come out.  Written one after the other they run one after the
  other.  Only the four products that read ``S_0`` wait for the chunk
  before, which is the grid step before.

A step's VMEM at the cell's shape (T 8,192, 32 value heads over 16 key
heads of 128, bf16; :func:`step_vmem_bytes`): blocks of 64 steps by 4 key
heads, each twice: q and k 64 KB each, v 128, o (float32) 256, the running
sums and beta 16 each, ``T`` 128 and the 8 first states 512 when kept:
2.3 MB, and 0.5 MB of states: 2.8 of the 10 allowed.  A shape whose
blocks do not fit gets fewer key heads a step, at worst one.  Off the
TPU the chunked XLA form (:func:`gdn_chunked`) is the forward;
``impl="pallas"`` forces the kernel (interpreted off the TPU, for tests).

**The backward** is written out, one for both forms (``custom_vjp``, scope
``gdn_chunk_bwd``, in ``jax.numpy``).  A forward pass that will be
differentiated also leaves ``T`` and each chunk's ``S_0`` (float32); ``W``
and ``U`` are made again from them, three products a chunk and no walk.
With ``Sc`` the state as the products take it (cast to the operands'
type), ``D`` the decay mask and ``Kd = k exp(G_last - G)``:

* the chunks in reverse, ``dS`` (of the chunk's last state) carried:
  ``dU = (qk * D)^T dO + Kd dS``;
  ``dS_0 = exp(G_last) dS + (q exp(G))^T dO - W^T dU``; beside them
  ``dKd = U dS^T`` and ``d exp(G_last) = <dS, S_0>``;
* every chunk at once: ``d(qk * D) = dO U^T``, ``d(q exp(G)) = dO Sc^T``,
  ``dW = -dU Sc^T``; ``dT = dU (beta V)^T + dW (beta exp(G) K)^T``,
  ``d(beta V) = T^T dU``, ``d(beta exp(G) K) = T^T dW``;
* the inverse by its identity, ``dA = -T^T dT T^T`` on the strict lower
  triangle: two float32 products, where the derivative of the log2(chunk)
  doublings is six for each of them;
* from there by elements to ``beta``, to ``k k^T`` and ``q k^T`` (and
  through them to q and k, summed over the value heads a key head
  serves), and to ``G``: a decay ``exp(G_i - G_j)`` hands its cotangent
  times itself to row i and takes it from column j, and ``g`` gets the
  sum of what the ``G`` after it in the chunk got.

Matmul operands are cast as the forward casts them; no exponent is
positive, so a decay that underflows gives zeros and never a NaN.
"""
from __future__ import annotations

import functools
from types import SimpleNamespace
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.custom_dce import custom_dce
from jax.experimental.pallas import tpu as pltpu

from .flash import (_default_interpret, _dot, matmul_precision as _prec,
                    plan_event)

__all__ = ["gdn_scan", "gdn_chunked", "gdn_recurrence", "gdn_plan"]

_MASK = -1e30
_HI = jax.lax.Precision.HIGHEST


# what one grid step's blocks, double-buffered, and its states may take of
# the 16 MiB of scoped VMEM a kernel gets by default (as ``ssd.VMEM_A_STEP``:
# the rest is for what the body spills)
VMEM_A_STEP = 10 << 20
# key heads a grid step takes, their chains written alternately.  The rule
# alone at the cell's shape (ms a call that writes o alone / also keeps T and
# the states; my chip run, PR 33): 1 key head 4.7 / 5.1, 2: 2.4 / 2.8,
# 4: 1.9 / 2.3, 8: 1.8 / 2.2, 16: 1.8 / 2.2, and in the cell 18,778 tokens/s
# at 2, 18,893 at 4.  Several CHUNKS a step, walked in a loop, bought nothing
# at any of these (a grid step's own cost is 0.35 us of its 10), so a step
# takes one
KEY_HEADS_A_STEP = 4


class GdnPlan(NamedTuple):
    """Sizes of one call of ``gdn_chunk_fwd``: steps a chunk, chunks; value
    heads a grid step and grid steps a call; key heads a grid step takes
    (one chunk of each); value heads of a key head held side by side on a
    row of lanes; chains the step writes alternately (key heads x groups of
    heads side by side); bytes of VMEM the step's blocks and states were
    reckoned at."""
    chunk: int
    chunks: int
    heads_a_step: int
    grid_steps: int
    key_heads_a_step: int
    side_by_side: int
    chains_a_step: int
    vmem_bytes: int


def step_vmem_bytes(chunk: int, key_heads: int, r: int, p: int, dk: int,
                    dv: int, itemsize: int) -> int:
    """Bytes of one grid step of one chunk of ``key_heads`` key heads with
    ``r`` value heads each, ``p`` side by side: its blocks, each twice (the
    pipeline's double buffer): q, k, v in the operand type, the running
    sums and beta, o, and what the backward keeps (``T`` and a state a
    value head) in float32; and once the value heads' states.  A block's
    last two dimensions are counted padded to float32's (8, 128) tile."""
    def tile(rows, cols, size):
        return -(-rows // 8) * 8 * -(-cols // 128) * 128 * size

    states = key_heads * r * tile(dk, dv, 4)
    blocks = (2 * tile(chunk, key_heads * dk, itemsize)
              + tile(chunk, key_heads * r * dv, itemsize + 4)
              + 2 * key_heads * tile(r // p, p * chunk, 4)
              + key_heads * tile(chunk, r * chunk, 4) + states)
    return 2 * blocks + states


def gdn_plan(b: int, t: int, hk: int, hv: int, chunk: int, *,
             key_dim: int = 128, value_dim: int = 128,
             itemsize: int = 2) -> GdnPlan:
    """A grid step takes one chunk of the most key heads, up to
    ``KEY_HEADS_A_STEP``, that divide the call's and whose blocks fit
    ``VMEM_A_STEP``; where nothing wider fits, of one key head.  Value
    heads go side by side while a row of them is no wider than a
    register's 128 lanes."""
    if t % chunk:
        raise ValueError(f"sequence {t} is not a whole number of chunks "
                         f"of {chunk}")
    if hv % hk:
        raise ValueError(f"{hv} value heads do not divide over {hk} key "
                         f"heads")
    r, nc = hv // hk, t // chunk
    p = max(n for n in range(1, r + 1)
            if r % n == 0 and (n == 1 or n * chunk <= 128))

    def vmem(heads):
        return step_vmem_bytes(chunk, heads, r, p, key_dim, value_dim,
                               itemsize)

    heads = next((h for h in range(min(hk, KEY_HEADS_A_STEP), 0, -1)
                  if hk % h == 0 and vmem(h) <= VMEM_A_STEP), 1)
    return GdnPlan(chunk, nc, heads * r, b * (hk // heads) * nc, heads, p,
                   heads * (r // p), vmem(heads))


def _report_plan(plan: GdnPlan, q, v, impl):
    """One ``gdn.plan`` event per distinct plan (as ``ssd.plan``)."""
    b, t, hk, dk = q.shape
    hv, dv = v.shape[2], v.shape[3]
    kept = _kept_shapes(b, plan.chunks, hk, hv, plan.chunk, dk, dv)
    # walk_handover_bytes: what does not wait for the state reaches the
    # walk in registers and VMEM, inside one grid step; nothing through HBM
    plan_event("gdn.plan", **plan._asdict(), walk_handover_bytes=0, batch=b,
               seq=t, key_heads=hk, value_heads=hv, key_dim=dk, value_dim=dv,
               dtype=jnp.dtype(v.dtype).name, impl=impl, backward="explicit",
               bwd_key_heads=_bwd_key_heads(b, t, hk, hv, plan.chunk, dk, dv),
               residual_bytes=_nbytes(kept))


def _kept_shapes(b, nc, hk, hv, c, dk, dv):
    """What a forward pass leaves for the backward beside its inputs, both
    float32, heads on axis 2 like the inputs': ``T`` (b, nc, hk, c, r c),
    a key head's value heads side by side (a row of 128 lanes where c is
    64 and r 2), and the state each chunk starts from (b, nc, hv, d_k,
    d_v)."""
    return (jax.ShapeDtypeStruct((b, nc, hk, c, hv // hk * c), jnp.float32),
            jax.ShapeDtypeStruct((b, nc, hv, dk, dv), jnp.float32))


def _nbytes(arrays) -> int:
    return sum(x.size * jnp.dtype(x.dtype).itemsize for x in arrays)


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

def _chunk_operands(q, k, v, g, beta, chunk):
    """What both passes make of the inputs before anything depends on the
    state: everything a head's chunk a row, ``(b, nc, hv, c, ...)``.  The
    float32 pieces the backward differentiates through (running sums,
    ``beta``, ``k k^T``, ``q k^T``, the decay mask, q / k / v beside their
    value head) and the operands the products take, cast to ``v.dtype``."""
    b, t, hk, dk = q.shape
    hv, dv = v.shape[2], v.shape[3]
    r, c, nc = hv // hk, chunk, t // chunk
    f32, cd = jnp.float32, v.dtype
    pr = _prec(cd)
    gs = jnp.cumsum(g.astype(f32).reshape(b, nc, c, hv), axis=2)
    gs = gs.transpose(0, 1, 3, 2)                            # (b,nc,hv,c)
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
    decay = jnp.exp(jnp.where(lower, gs[..., :, None] - gs[..., None, :],
                              _MASK))
    # value heads beside their key head: (b, nc, hv, c, d)
    kv = jnp.repeat(kr.astype(f32), r, axis=3).transpose(0, 1, 3, 2, 4)
    qv = jnp.repeat(qr.astype(f32), r, axis=3).transpose(0, 1, 3, 2, 4)
    vv = v.astype(f32).reshape(b, nc, c, hv, dv).transpose(0, 1, 3, 2, 4)
    eg = jnp.exp(gs)
    to_end = jnp.exp(gs[..., -1:] - gs)                      # exp(G_last - G)
    end_decay = eg[..., -1]                                  # (b,nc,hv)
    return SimpleNamespace(
        bt=bt, kk=kk, qk=qk, decay=decay, kv=kv, qv=qv, vv=vv,
        eg=eg, to_end=to_end, end_decay=end_decay,
        strict=rows[:, None] > rows[None, :],
        bv=(vv * bt[..., None]).astype(cd),
        bk=(kv * (bt * eg)[..., None]).astype(cd),
        qg=(qv * eg[..., None]).astype(cd),
        attn=(qk * decay).astype(cd),
        kdec=(kv * to_end[..., None]).astype(cd))


def _chunks_first(x):         # (b, nc, ...) -> (nc, b, ...), for a scan
    return x.swapaxes(0, 1)


def gdn_chunked(q, k, v, g, beta, *, chunk=64):
    """The chunked rule in ``jax.numpy`` (arguments as
    :func:`gdn_recurrence`).  Returns o (B, T, H_v, d_v) float32 and what
    the backward keeps (``T`` and the chunks' first states, laid out as
    :func:`_kept_shapes` says).  Matmul operands keep ``v.dtype``; decays,
    ``T`` and the state are float32."""
    b, t, hk, dk = q.shape
    hv, dv = v.shape[2], v.shape[3]
    r, c, nc = hv // hk, chunk, t // chunk
    f32, cd = jnp.float32, v.dtype
    x = _chunk_operands(q, k, v, g, beta, chunk)
    a = jnp.where(x.strict, x.bt[..., None] * x.kk * x.decay, 0.0)
    inv = _unit_lower_inverse(
        a, jnp.eye(c, dtype=f32),
        lambda m, n: jnp.einsum("...ij,...jk->...ik", m, n, precision=_HI))
    mm = functools.partial(jnp.einsum, precision=_prec(cd),
                           preferred_element_type=f32)
    invc = inv.astype(cd)
    u0 = mm("bchij,bchjd->bchid", invc, x.bv)
    w = mm("bchij,bchjd->bchid", invc, x.bk).astype(cd)

    def step(s, xs):
        u0c, wc, qgc, ac, kc, dc = xs
        sc = s.astype(cd)
        u = (u0c - mm("bhik,bhkv->bhiv", wc, sc)).astype(cd)
        o = mm("bhik,bhkv->bhiv", qgc, sc) + mm("bhij,bhjv->bhiv", ac, u)
        return dc[..., None, None] * s + mm("bhik,bhiv->bhkv", kc, u), (o, s)

    s0 = jnp.zeros((b, hv, dk, dv), f32)
    _, (o, s) = jax.lax.scan(step, s0, tuple(
        _chunks_first(y) for y in (u0, w, x.qg, x.attn, x.kdec,
                                   x.end_decay)))
    inv = inv.reshape(b, nc, hk, r, c, c).swapaxes(3, 4)
    # o: (nc, b, hv, c, dv) -> (b, t, hv, dv)
    return (o.transpose(1, 0, 3, 2, 4).reshape(b, t, hv, dv),
            (inv.reshape(b, nc, hk, c, r * c), _chunks_first(s)))


def _gdn_backward(q, k, v, g, beta, kept, do, chunk):
    """The cotangents of q, k, v, g, beta from ``do`` (B, T, H_v, d_v), by
    the equations of the module's docstring; ``kept`` as
    :func:`gdn_chunked` returns it."""
    b, t, hk, dk = q.shape
    hv, dv = v.shape[2], v.shape[3]
    r, c, nc = hv // hk, chunk, t // chunk
    f32, cd = jnp.float32, v.dtype
    inv, s = kept
    inv = inv.reshape(b, nc, hk, c, r, c).swapaxes(3, 4).reshape(
        b, nc, hv, c, c)
    x = _chunk_operands(q, k, v, g, beta, chunk)
    bt, eg, kv, decay = x.bt, x.eg, x.kv, x.decay
    mm = functools.partial(jnp.einsum, precision=_prec(cd),
                           preferred_element_type=f32)
    hi = functools.partial(jnp.einsum, precision=_HI)
    doc = do.astype(cd).reshape(b, nc, c, hv, dv).transpose(0, 1, 3, 2, 4)
    sc, invc = s.astype(cd), inv.astype(cd)
    # U and W again from T and the states: three products, no walk
    w = mm("bchij,bchjk->bchik", invc, x.bk).astype(cd)
    u = (mm("bchij,bchjv->bchiv", invc, x.bv)
         - mm("bchik,bchkv->bchiv", w, sc)).astype(cd)

    # the chunks in reverse, dS carried: what has to wait for it
    def step(ds, xs):
        doc_, ac, qgc, kc, wc, uc, s0, dc = xs
        dsc = ds.astype(cd)
        du = (mm("bhij,bhiv->bhjv", ac, doc_)
              + mm("bhik,bhkv->bhiv", kc, dsc)).astype(cd)
        d_kdec = mm("bhiv,bhkv->bhik", uc, dsc)
        d_end = jnp.sum(ds * s0, axis=(-2, -1))
        ds = (dc[..., None, None] * ds + mm("bhik,bhiv->bhkv", qgc, doc_)
              - mm("bhik,bhiv->bhkv", wc, du))
        return ds, (du, d_kdec, d_end)

    _, (du, d_kdec, d_end) = jax.lax.scan(
        step, jnp.zeros((b, hv, dk, dv), f32), tuple(
            _chunks_first(y) for y in (doc, x.attn, x.qg, x.kdec,
                                       w, u, s, x.end_decay)),
        reverse=True)
    du, d_kdec, d_end = (_chunks_first(y) for y in (du, d_kdec, d_end))

    # every chunk at once: what the products inside a chunk hand back
    d_attn = mm("bchiv,bchjv->bchij", doc, u)
    d_qg = mm("bchiv,bchkv->bchik", doc, sc)
    dw = (-mm("bchiv,bchkv->bchik", du, sc)).astype(cd)
    d_inv = (mm("bchiv,bchjv->bchij", du, x.bv)
             + mm("bchik,bchjk->bchij", dw, x.bk))
    d_bv = mm("bchji,bchjv->bchiv", invc, du)
    d_bk = mm("bchji,bchjk->bchik", invc, dw)
    # the inverse by its identity: d(I + A)^-1 = -T dA T
    da = -hi("bchil,bchjl->bchij", hi("bchki,bchkl->bchil", inv, d_inv), inv)
    da = jnp.where(x.strict, da, 0.0) * decay
    d_qk = d_attn * decay
    # a decay exp(G_i - G_j) hands its row +, its column -
    through = da * bt[..., None] * x.kk + d_qk * x.qk
    d_bk_k = jnp.sum(d_bk * kv, -1)                 # to beta exp(G), a row
    d_kd_k = jnp.sum(d_kdec * kv, -1) * x.to_end
    d_gs = (jnp.sum(through, -1) - jnp.sum(through, -2)
            + (d_bk_k * bt + jnp.sum(d_qg * x.qv, -1)) * eg - d_kd_k)
    d_last = jnp.sum(d_kd_k, -1) + d_end * x.end_decay
    d_gs = d_gs.at[..., -1].add(d_last)
    d_g = jnp.cumsum(d_gs[..., ::-1], -1)[..., ::-1]        # G is a cumsum
    d_beta = (jnp.sum(da * x.kk, -1) + jnp.sum(d_bv * x.vv, -1)
              + d_bk_k * eg)
    d_vv = d_bv * bt[..., None]
    d_kv = d_bk * (bt * eg)[..., None] + d_kdec * x.to_end[..., None]
    d_qv = d_qg * eg[..., None]

    def per_key_head(y):      # summed over the value heads a key head serves
        return jnp.sum(y.reshape((b, nc, hk, r) + y.shape[3:]), axis=3)

    d_kk = per_key_head(da * bt[..., None])
    d_kk = (d_kk + d_kk.swapaxes(-1, -2)).astype(cd)
    d_qk = per_key_head(d_qk).astype(cd)
    # a key head's chunk a row, like everything they meet here (and XLA's
    # CPU backend has no bf16 product of the other layout transposed)
    qh, kh = (y.astype(cd).reshape(b, nc, c, hk, dk).transpose(0, 1, 3, 2, 4)
              for y in (q, k))
    d_k = (mm("bchij,bchjd->bchid", d_kk, kh)
           + mm("bchji,bchjd->bchid", d_qk, qh) + per_key_head(d_kv))
    d_q = mm("bchij,bchjd->bchid", d_qk, kh) + per_key_head(d_qv)

    def steps(y, like):       # (b, nc, h, c, ..) -> (b, t, h, ..)
        y = jnp.moveaxis(y, 3, 2)
        return y.reshape((b, t) + y.shape[3:]).astype(like.dtype)

    return (steps(d_q, q), steps(d_k, k), steps(d_vv, v), steps(d_g, g),
            steps(d_beta, beta))


# ------------------------------------------------------------- the kernel

def _packed_inverse(a, eye, diag):
    """``(I + a_j)^-1`` of p strictly lower triangular (c, c) matrices held
    side by side, ``a`` = [a_0 | a_1 | ..] (c, p c), by the doublings of
    :func:`_unit_lower_inverse`.  ``diag(x)`` is the (p c, p c) matrix with
    the ``x_j`` on its diagonal, so ``x @ diag(y)`` is every ``x_j y_j`` in
    one product; the two products of a level, ``inv @ power`` and ``power
    @ power``, share their right operand and go as one of twice the rows.
    A generator: it yields after each product (see :func:`_in_step`) and
    returns the inverse."""
    c = a.shape[0]
    inv, n = eye - a, 2
    if n < c:
        power = _dot(a, diag(a), 1, 0)
        yield
    while n < c:
        n *= 2
        if n < c:
            both = _dot(jnp.concatenate([inv, power], axis=0), diag(power),
                        1, 0)
            inv, power = inv + both[:c], both[c:]
        else:
            inv = inv + _dot(inv, diag(power), 1, 0)
        yield
    return inv


def _in_step(chains):
    """Run generators side by side: each to its next ``yield`` in turn,
    until all have ended.  The MXU answers in the order it was asked, and
    the compiler keeps that order, so chains that do not wait for each
    other overlap only if their products are WRITTEN alternately: one
    chain's product goes in while the other's comes out."""
    chains, ended = list(chains), object()
    while chains:
        chains = [x for x in chains if next(x, ended) is not ended]


def _chunk_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, o_ref, *rest):
    # rest: the two arrays the backward keeps (when asked for), the state
    *kept, s_ref = rest

    @pl.when(pl.program_id(2) == 0)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)

    f32, cd = jnp.float32, q_ref.dtype
    # key heads; groups of p value heads side by side, a row of lanes each
    hb, groups, width = g_ref.shape[2:]
    c = q_ref.shape[1]
    p, dk = width // c, q_ref.shape[2] // hb
    dv = o_ref.shape[2] // (hb * groups * p)
    row = jax.lax.broadcasted_iota(jnp.int32, (c, width), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (c, width), 1)
    # lanes [j c, (j + 1) c) are value head j's of the p side by side
    mine = [(lane >= j * c) & (lane < (j + 1) * c) for j in range(p)]
    col = lane
    for j in range(1, p):
        col = jnp.where(mine[j], lane - j * c, col)
    eye, lower, strict = row == col, row >= col, row > col
    eye32 = eye.astype(f32)

    def column(x, j):         # head j's (1, c) of a row -> (c, 1), no transpose
        return jnp.sum(jnp.where(eye & mine[j], x, 0.0), axis=1,
                       keepdims=True)

    def spread(cols):         # p columns (c, 1) -> (c, p c), each over its lanes
        out = cols[0]
        for j in range(1, p):
            out = jnp.where(mine[j], cols[j], out)
        return out

    def stack(x):             # [x_0 | x_1 | ..] -> rows [x_0 0; 0 x_1; ..]
        if p == 1:
            return x
        return jnp.concatenate([jnp.where(m, x, 0) for m in mine], axis=0)

    def chain(kh, gi):
        """The chunk of p value heads of key head ``kh``; a ``yield`` after
        each product or group of products whose results the next needs."""
        q = q_ref[0, :, kh * dk:(kh + 1) * dk]                    # (c, dk)
        k = k_ref[0, :, kh * dk:(kh + 1) * dk]
        kcat = jnp.concatenate([k] * p, axis=0)
        kk, qk = _dot(k, kcat, 1, 1), _dot(q, kcat, 1, 1)         # (c, p c)
        g_row = g_ref[0, 0, kh, gi:gi + 1, :]
        b_row = b_ref[0, 0, kh, gi:gi + 1, :]
        g_cols = [column(g_row, j) for j in range(p)]
        b_cols = [column(b_row, j) for j in range(p)]
        yield
        decay = jnp.exp(jnp.where(lower, spread(g_cols) - g_row, _MASK))
        a = jnp.where(strict, spread(b_cols) * kk * decay, 0.0)
        # what does not wait for the state ...
        inv32 = yield from _packed_inverse(a, eye32, stack)
        if kept:
            kept[0][0, 0, kh, :, gi * width:(gi + 1) * width] = inv32
        inv = stack(inv32).astype(cd)                             # (p c, p c)
        qf, kf = q.astype(f32), k.astype(f32)
        egs = [jnp.exp(y) for y in g_cols]
        heads = [(kh * groups + gi) * p + j for j in range(p)]
        vs = [v_ref[0, :, h * dv:(h + 1) * dv].astype(f32) for h in heads]
        u0 = _dot(inv, jnp.concatenate(
            [(vs[j] * b_cols[j]).astype(cd) for j in range(p)], axis=0),
            1, 0)                                                 # (p c, dv)
        w = _dot(inv, jnp.concatenate(
            [(kf * (b_cols[j] * egs[j])).astype(cd) for j in range(p)],
            axis=0), 1, 0).astype(cd)
        yield
        # ... and what does
        us, read, old = [], [], []
        for j, h in enumerate(heads):
            s = s_ref[h]                                          # (dk, dv)
            if kept:
                kept[1][0, 0, h] = s
            # W S and (q e^G) S: one product of twice the rows
            both = _dot(jnp.concatenate(
                [w[j * c:(j + 1) * c], (qf * egs[j]).astype(cd)], axis=0),
                s.astype(cd), 1, 0)
            us.append((u0[j * c:(j + 1) * c] - both[:c]).astype(cd))
            read.append(both[c:])
            old.append(s)
        yield
        within = _dot(stack((qk * decay).astype(cd)),
                      jnp.concatenate(us, axis=0), 1, 0)
        for j, h in enumerate(heads):
            g_end = g_row[:, (j + 1) * c - 1:(j + 1) * c]         # (1, 1)
            kdec = (kf * jnp.exp(g_end - g_cols[j])).astype(cd)
            # (1, 1) -> lanes first: Mosaic does not broadcast both ways at
            # once
            s_ref[h] = (jnp.exp(jnp.broadcast_to(g_end, (1, dv))) * old[j]
                        + _dot(kdec, us[j], 0, 0))
        yield
        for j, h in enumerate(heads):
            o_ref[0, :, h * dv:(h + 1) * dv] = (
                read[j] + within[j * c:(j + 1) * c])

    _in_step(chain(kh, gi) for kh in range(hb) for gi in range(groups))


def _plan_of(q, v, chunk) -> GdnPlan:
    return gdn_plan(q.shape[0], q.shape[1], q.shape[2], v.shape[2], chunk,
                    key_dim=q.shape[3], value_dim=v.shape[3],
                    itemsize=jnp.dtype(v.dtype).itemsize)


# jitted, so that a step's layers, their recomputation and the pass that
# writes o alone trace and lower the kernel's body once a shape and not once
# a call (a step's trace and lowering: 6.0-6.5 s at the parent of PR 33,
# 7.1-8.0 with four chains in the body, 5.7-5.8 so; here, off the chip)
@functools.partial(jax.jit, static_argnums=(5, 6, 7))
def _gdn_pallas(q, k, v, g, beta, chunk, interpret, keep):
    """``gdn_chunk_fwd``: the whole chunked rule, a grid step as
    :func:`gdn_plan` sizes it, the chunks of a head in order.  Returns o
    and, with ``keep``, what the backward keeps (else an empty tuple: a
    forward pass nobody differentiates writes o alone)."""
    b, t, hk, dk = q.shape
    hv, dv = v.shape[2], v.shape[3]
    r, c, nc = hv // hk, chunk, t // chunk
    f32, cd = jnp.float32, v.dtype
    plan = _plan_of(q, v, chunk)
    hb, p = plan.key_heads_a_step, plan.side_by_side

    def rows(x):      # (b, t, hv) -> (b, nc, hk, r / p, p c): p heads a row
        return x.reshape(b, nc, c, hk, r // p, p).transpose(
            0, 1, 3, 4, 5, 2).reshape(b, nc, hk, r // p, p * c)

    gs = rows(jnp.cumsum(g.astype(f32).reshape(b, nc, c, hv), axis=2))
    small = pl.BlockSpec((1, 1, hb, r // p, p * c),
                         lambda i, h, n: (i, n, h, 0, 0))
    keys = pl.BlockSpec((1, c, hb * dk), lambda i, h, n: (i, n, h))
    steps = pl.BlockSpec((1, c, hb * r * dv), lambda i, h, n: (i, n, h))
    kept = _kept_shapes(b, nc, hk, hv, c, dk, dv) if keep else ()
    per_head = 2 * c * c * (2 * dk + dv + dk) + 2 * c * dk * dv * 3
    # two (c, c) products a doubling, log2(c) - 1 doublings
    inverse = 2 * c ** 3 * 2 * max(c.bit_length() - 2, 0)
    o, *kept = pl.pallas_call(
        _chunk_kernel,
        name="gdn_chunk_fwd",
        grid=(b, hk // hb, nc),
        in_specs=[keys, keys, steps, small, small],
        out_specs=[steps] + [
            pl.BlockSpec((1, 1, hb * x.shape[2] // hk) + x.shape[3:],
                         lambda i, h, n: (i, n, h, 0, 0)) for x in kept],
        out_shape=[jax.ShapeDtypeStruct((b, t, hv * dv), f32), *kept],
        scratch_shapes=[pltpu.VMEM((hb * r, dk, dv), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=b * nc * hv * (per_head + inverse),
            transcendentals=b * t * hv * (c + 3),
            bytes_accessed=(2 * b * t * hk * dk + b * t * hv * dv)
            * cd.itemsize + 4 * b * t * hv * (dv + 2) + _nbytes(kept)),
        interpret=interpret,
    )(q.astype(cd).reshape(b, t, hk * dk), k.astype(cd).reshape(b, t, hk * dk),
      v.reshape(b, t, hv * dv), gs, rows(beta.astype(f32)))
    return o.reshape(b, t, hv, dv), tuple(kept)


# ----------------------------------------------- one backward for both forms

@functools.partial(custom_dce, static_argnums=(5, 6))
def _kernel_keeping(q, k, v, g, beta, chunk, interpret):
    return _gdn_pallas(q, k, v, g, beta, chunk, interpret, True)


@_kernel_keeping.def_dce
def _kernel_keeping_dce(chunk, interpret, used, *args):
    # a pass that is differentiated only later (the first pass over a
    # recomputed block) reads o alone: it gets the kernel that writes o alone
    o, kept = _gdn_pallas(*args, chunk, interpret, any(used[1]))
    return o, kept or (None,) * len(used[1])


def _forward(q, k, v, g, beta, chunk, impl, interpret, keep):
    if impl == "xla":
        return gdn_chunked(q, k, v, g, beta, chunk=chunk)
    if keep:
        return _kernel_keeping(q, k, v, g, beta, chunk, interpret)
    return _gdn_pallas(q, k, v, g, beta, chunk, interpret, False)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _gdn(q, k, v, g, beta, chunk, impl, interpret):
    return _forward(q, k, v, g, beta, chunk, impl, interpret, False)[0]


def _gdn_fwd(q, k, v, g, beta, chunk, impl, interpret):
    o, kept = _forward(q, k, v, g, beta, chunk, impl, interpret, True)
    return o, (q, k, v, g, beta, kept)


# what the backward of one group of key heads may hold: the chip's fast
# memory, in which the compiler then keeps a group's arrays between fusions
_BWD_GROUP_BYTES = 128 << 20


def _bwd_key_heads(b, t, hk, hv, c, dk, dv) -> int:
    """Key heads (with the value heads they serve) differentiated at once:
    every one, unless their working set would pass ``_BWD_GROUP_BYTES``.
    That set is about a dozen float32 arrays with a row a step and value
    head, a row no narrower than a register's 128 lanes: 101 MB a key head
    at T 8,192 with two value heads a key head, so one at a time there
    (the rule alone, forward + backward: 20.3 ms, against 24.3, 29.1, 27.6
    and 26.7 ms two, four, eight and all 16 at a time; my chip run, PR
    31).  What the forward keeps is not divided."""
    a_head = 12 * 4 * b * t * (hv // hk) * max(c, dk, dv, 128)
    fit = max(_BWD_GROUP_BYTES // a_head, 1)
    return max(n for n in range(1, hk + 1) if hk % n == 0 and n <= fit)


def _gdn_bwd(chunk, impl, interpret, res, ct):
    *inputs, kept = res
    (b, t, hk, dk), (hv, dv) = inputs[0].shape, inputs[2].shape[2:]
    heads = _bwd_key_heads(b, t, hk, hv, chunk, dk, dv)
    # the cotangent as the products take it; heads are on axis 2 of all
    whole = (*inputs, kept, ct.astype(inputs[2].dtype))

    def group(i):         # key heads i * heads .. and what belongs to them
        def part(x):
            n = heads * x.shape[2] // hk
            return jax.lax.dynamic_slice_in_dim(x, i * n, n, axis=2)
        return _gdn_backward(*jax.tree.map(part, whole), chunk)

    def merge(x):         # (groups, b, t, h / groups, ..) -> (b, t, h, ..)
        x = jnp.moveaxis(x, 0, 2)
        return x.reshape(x.shape[:2] + (-1,) + x.shape[4:])

    with jax.named_scope("gdn_chunk_bwd"):
        if heads == hk:
            return _gdn_backward(*whole, chunk)
        return tuple(merge(x) for x in
                     jax.lax.map(group, jnp.arange(hk // heads)))


_gdn.defvjp(_gdn_fwd, _gdn_bwd)


def gdn_scan(q, k, v, g, beta, *, chunk: int = 64, impl: str = "auto",
             interpret: Optional[bool] = None):
    """o (B, T, H_v, d_v), float32, of the gated delta rule over q, k
    (B, T, H_k, d_k), v (B, T, H_v, d_v), log decays g <= 0 and write
    strengths beta (B, T, H_v).  ``impl``: "pallas" (the kernel;
    interpreted off the TPU), "xla" (the chunked ``jax.numpy`` form) or
    "auto" (the kernel on the TPU, the XLA form elsewhere).  Both are
    differentiated by the one explicit backward."""
    plan = _plan_of(q, v, chunk)
    off_tpu = _default_interpret(v)
    if impl == "auto":
        impl = "xla" if off_tpu else "pallas"
    if impl not in ("xla", "pallas"):
        raise ValueError(f"impl must be auto, pallas or xla, got {impl!r}")
    _report_plan(plan, q, v, impl)
    if interpret is None:
        interpret = off_tpu
    return _gdn(q, k, v, g, beta, chunk, impl, bool(interpret))
