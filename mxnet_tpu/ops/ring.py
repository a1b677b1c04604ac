"""Ring attention: sequence/context parallelism over the ``sp`` mesh axis.

Capability add over the reference (SURVEY.md §5.7: MXNet has no sequence
parallelism of any kind).  Q stays resident; K/V chunks rotate around the
ring of ``sp`` devices via ``jax.lax.ppermute`` (XLA lowers this to ICI
neighbor RDMA), and partial attention results merge with the numerically
stable online-softmax rule — so a sequence of length T costs each device
O(T/sp) memory and the compute of its own chunk, while the compiler
overlaps each step's ppermute with the previous step's matmuls.

Each per-chunk block is wrapped in ``jax.checkpoint`` so the backward pass
recomputes the (Tl x Tl) score tiles instead of keeping ``sp`` of them
alive, matching flash attention's memory discipline.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .attention import _NEG_INF as _MASK


@functools.partial(jax.checkpoint, static_argnums=(7, 8))
def _block(q, k, v, q_pos, kv_pos, q_seg, kv_seg, causal, scale):
    """Partial attention of local Q against one K/V chunk.

    q: (B, Tl, H, D); k/v: (B, Tc, H, D); optional q_seg (B, Tl) /
    kv_seg (B, Tc) packed segment ids mask cross-segment pairs; returns
    un-normalized (pv (B, H, Tl, D) f32, m (B, H, Tl, 1),
    l (B, H, Tl, 1)).  A fully-masked row yields m = _MASK and l = 0,
    which merges with zero weight — nan-free as long as SOME chunk
    (the diagonal: self-key always matches) is live for the row.
    """
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if causal:
        keep = kv_pos[None, :] <= q_pos[:, None]       # (Tl, Tc)
        s = jnp.where(keep[None, None], s, _MASK)
    if q_seg is not None:
        keep_seg = q_seg[:, None, :, None] == kv_seg[:, None, None, :]
        s = jnp.where(keep_seg, s, _MASK)              # (B, 1, Tl, Tc)
    m = jnp.max(s, axis=-1, keepdims=True)             # (B, H, Tl, 1)
    # zero fully-masked entries (not exp(_MASK - _MASK) = 1) so packed
    # rows whose segment lives in another chunk contribute l = 0 here
    p = jnp.where(s <= _MASK * 0.5, 0.0, jnp.exp(s - m))
    l = jnp.sum(p, axis=-1, keepdims=True)
    pv = jnp.einsum("bhqk,bkhd->bhqd", p.astype(v.dtype), v,
                    preferred_element_type=jnp.float32)
    return pv, m, l


def _merge(state, pv, m_c, l_c):
    """Online-softmax combination of two partial attention results."""
    acc, m, l = state
    m_new = jnp.maximum(m, m_c)
    c_old = jnp.exp(m - m_new)
    c_new = jnp.exp(m_c - m_new)
    return acc * c_old + pv * c_new, m_new, l * c_old + l_c * c_new


def _ring_local(q, k, v, seg=None, *, axis, steps, causal, scale):
    """Per-device body under shard_map: q/k/v are local (B, Tl, H, D);
    ``seg`` (B, Tl) local packed segment ids — the kv-side ids rotate
    around the ring with their K/V chunk."""
    idx = jax.lax.axis_index(axis)
    tl = q.shape[1]
    offs = jax.lax.broadcasted_iota(jnp.int32, (tl, 1), 0)[:, 0]
    q_pos = idx * tl + offs
    perm = [(i, (i + 1) % steps) for i in range(steps)]
    kv_seg = seg

    acc = m = l = None
    for t in range(steps):
        owner = (idx - t) % steps                      # chunk's home device
        kv_pos = owner * tl + offs
        pv, m_c, l_c = _block(q, k, v, q_pos, kv_pos, seg, kv_seg,
                              causal, scale)
        if t == 0:
            # step 0 is the diagonal chunk: every row has >= 1 unmasked
            # key (its own — causal keeps the diagonal, segments always
            # self-match), so m is finite and later fully-masked chunks
            # (m_c = _MASK) merge with weight exp(_MASK - m) = 0, nan-free
            acc, m, l = pv, m_c, l_c
        else:
            acc, m, l = _merge((acc, m, l), pv, m_c, l_c)
        if t + 1 < steps:
            k = jax.lax.ppermute(k, axis, perm)
            v = jax.lax.ppermute(v, axis, perm)
            if kv_seg is not None:
                kv_seg = jax.lax.ppermute(kv_seg, axis, perm)
    out = acc / l                                      # (B, H, Tl, D)
    return out.transpose(0, 2, 1, 3).astype(q.dtype)


def _ring_local_balanced(q, k, v, seg=None, *, axis, steps, scale):
    """Zigzag-balanced CAUSAL ring body: each device's local rows are the
    pair [chunk idx | chunk 2*steps-1-idx] of a 2*steps-way split, so at
    every ring step every device computes exactly two UNMASKED
    half-blocks (plus the two causal diagonals at step 0) — half the
    FLOPs of masking a full block per step, with uniform load.

    ``seg`` (B, Tl) rides the SAME zigzag layout as q/k/v (the caller
    permutes it); its kv-side halves rotate with their K/V chunk, and
    the half-block "fully live" structure is unchanged — segment masking
    only ever REMOVES pairs inside a block, so every _block below passes
    its half-ids and the step-0 self-key guarantee keeps rows nan-free."""
    idx = jax.lax.axis_index(axis)
    tl = q.shape[1]
    hl = tl // 2
    offs = jax.lax.broadcasted_iota(jnp.int32, (hl, 1), 0)[:, 0]
    perm = [(i, (i + 1) % steps) for i in range(steps)]

    def halves(x):
        return x[:, :hl], x[:, hl:]

    q_lo, q_hi = halves(q)
    k_lo, k_hi = halves(k)
    v_lo, v_hi = halves(v)
    s_lo = s_hi = None
    if seg is not None:
        s_lo, s_hi = halves(seg)

    # step 0 (own chunks): high-vs-low is FULLY live (chunk 2s-1-i > i);
    # the two diagonals are the only blocks that ever need a causal mask
    lo = _block(q_lo, k_lo, v_lo, offs, offs, s_lo, s_lo, True, scale)
    hi = _block(q_hi, k_lo, v_lo, offs, offs, s_hi, s_lo, False, scale)
    hi = _merge(hi, *_block(q_hi, k_hi, v_hi, offs, offs, s_hi, s_hi,
                            True, scale))

    kk, vv, ss = k, v, seg
    for t in range(1, steps):
        kk = jax.lax.ppermute(kk, axis, perm)
        vv = jax.lax.ppermute(vv, axis, perm)
        ko_lo, ko_hi = halves(kk)
        vo_lo, vo_hi = halves(vv)
        so_lo = so_hi = None
        if ss is not None:
            ss = jax.lax.ppermute(ss, axis, perm)
            so_lo, so_hi = halves(ss)
        # always live: local HIGH rows vs arriving LOW chunk (no mask:
        # every high-chunk position exceeds every low-chunk position)
        hi = _merge(hi, *_block(q_hi, ko_lo, vo_lo, offs, offs,
                                s_hi, so_lo, False, scale))
        # exactly one of (lo vs lo) / (hi vs hi) is live, fully unmasked:
        # owner o = (idx - t) mod steps; o <= idx  <=>  idx >= t
        pred = idx >= t
        q_s = jnp.where(pred, q_lo, q_hi)
        k_s = jnp.where(pred, ko_lo, ko_hi)
        v_s = jnp.where(pred, vo_lo, vo_hi)
        qs_seg = ks_seg = None
        if ss is not None:
            qs_seg = jnp.where(pred, s_lo, s_hi)
            ks_seg = jnp.where(pred, so_lo, so_hi)
        pv, m_c, l_c = _block(q_s, k_s, v_s, offs, offs, qs_seg, ks_seg,
                              False, scale)
        lo_new = _merge(lo, pv, m_c, l_c)
        hi_new = _merge(hi, pv, m_c, l_c)
        lo = tuple(jnp.where(pred, n, o) for n, o in zip(lo_new, lo))
        hi = tuple(jnp.where(pred, o, n) for n, o in zip(hi_new, hi))
    out_lo = (lo[0] / lo[2]).transpose(0, 2, 1, 3)
    out_hi = (hi[0] / hi[2]).transpose(0, 2, 1, 3)
    return jnp.concatenate([out_lo, out_hi], axis=1).astype(q.dtype)


def _zigzag_perm(t: int, steps: int):
    """new-position -> old-position index map laying the sequence out as
    device i = [chunk i | chunk 2*steps-1-i] of a 2*steps-way split."""
    import numpy as onp
    hl = t // (2 * steps)
    order = []
    for i in range(steps):
        order.append(onp.arange(i * hl, (i + 1) * hl))
        j = 2 * steps - 1 - i
        order.append(onp.arange(j * hl, (j + 1) * hl))
    return onp.concatenate(order)


def ring_attention(q, k, v, *, causal: bool = False,
                   scale: Optional[float] = None, mesh=None,
                   axis: str = "sp", batch_axis: str = "dp",
                   heads_axis: str = "tp", balance: Optional[bool] = None,
                   segment_ids=None):
    """Sequence-parallel attention on global (B, T, H, D) jax arrays.

    Shards T over ``axis`` (and B over ``batch_axis``, H over
    ``heads_axis``) with shard_map; falls back to single-device attention
    when the axis has size 1.  Requires T divisible by the axis size.

    ``balance`` (default: on for causal when shapes allow) uses the
    zigzag layout — each device holds an early and a late half-chunk, so
    causal masking never throws away half of every computed block: 2x
    fewer attention FLOPs at uniform per-device load, for one static
    gather of the inputs and one of the output.

    ``segment_ids`` (B, T) int enables sequence packing: tokens attend
    only within their own segment.  The ids shard over (batch, seq) and
    the kv-side plane rotates around the ring with its K/V chunk; on the
    balanced path the ids ride the same zigzag permutation as q/k/v, so
    callers always pass them in the NATURAL sequence order.
    """
    from ..parallel.mesh import axis_size, current_mesh
    mesh = mesh or current_mesh()
    steps = axis_size(mesh, axis) if mesh is not None else 1
    d = q.shape[-1]
    scale = float(scale) if scale is not None else 1.0 / (d ** 0.5)
    if segment_ids is not None:
        segment_ids = jnp.asarray(segment_ids)
        if tuple(segment_ids.shape) != (q.shape[0], q.shape[1]):
            raise ValueError(
                f"segment_ids must be (B, T)={(q.shape[0], q.shape[1])}, "
                f"got {tuple(segment_ids.shape)}")
    if steps == 1:
        from .. import base as _base
        from .attention import (_attention_ref, _pallas_flash, _use_flash,
                                flash_attention)
        if segment_ids is None:
            return flash_attention(q, k, v, causal=causal, scale=scale)
        if _use_flash(q.shape, causal, None, 0.0, k.shape,
                      platform=_base.resolve_exec_platform(q)):
            # the Pallas kernel masks per-tile from the raw (B, T) ids —
            # never materialize the dense (B, 1, T, T) mask on TPU
            return _pallas_flash(q, k, v, causal=causal, scale=scale,
                                 q_seg=segment_ids, kv_seg=segment_ids)
        seg_mask = (segment_ids[:, None, :, None] ==
                    segment_ids[:, None, None, :])
        return _attention_ref(q, k, v, causal=causal, mask=seg_mask,
                              scale=scale)
    t = q.shape[1]
    if t % steps or k.shape[1] != t:
        raise ValueError(
            f"ring attention needs tq == tk divisible by |{axis}|={steps}, "
            f"got tq={t}, tk={k.shape[1]}")
    spec = P(batch_axis, axis, heads_axis, None)
    seg_spec = P(batch_axis, axis)
    from ._smap import shard_mapped_qkv
    if balance and not causal:
        raise ValueError("balance=True requires causal=True (the zigzag "
                         "layout only pays off under causal masking)")
    if balance is None:
        balance = causal and t % (2 * steps) == 0
    if causal and balance:
        if t % (2 * steps):
            raise ValueError(
                f"balanced causal ring needs T divisible by "
                f"2*|{axis}|={2 * steps}, got {t}")
        perm = jnp.asarray(_zigzag_perm(t, steps))
        inv = jnp.argsort(perm)
        qz, kz, vz = (jnp.take(x, perm, axis=1) for x in (q, k, v))
        body = functools.partial(_ring_local_balanced, axis=axis,
                                 steps=steps, scale=scale)
        if segment_ids is not None:
            segz = jnp.take(segment_ids, perm, axis=1)
            out = shard_mapped_qkv(body, mesh, spec, qz, kz, vz, segz,
                                   extra_specs=(seg_spec,))
        else:
            out = shard_mapped_qkv(body, mesh, spec, qz, kz, vz)
        return jnp.take(out, inv, axis=1)
    body = functools.partial(_ring_local, axis=axis, steps=steps,
                             causal=causal, scale=scale)
    if segment_ids is not None:
        return shard_mapped_qkv(body, mesh, spec, q, k, v, segment_ids,
                                extra_specs=(seg_spec,))
    return shard_mapped_qkv(body, mesh, spec, q, k, v)


def nd_ring_attention(query, key, value, *, causal=False, scale=None,
                      mesh=None, axis="sp", balance=None, segment_ids=None):
    """NDArray-level entry (autograd-recorded) for ring attention.
    ``segment_ids`` (B, T) is a non-differentiable side input."""
    from ..ndarray.ops import _as_nd, invoke
    query, key, value = _as_nd(query), _as_nd(key), _as_nd(value)
    seg = segment_ids.jax if hasattr(segment_ids, "jax") else segment_ids

    def f(q, k, v):
        return ring_attention(q, k, v, causal=causal, scale=scale,
                              mesh=mesh, axis=axis, balance=balance,
                              segment_ids=seg)

    return invoke("ring_attention", f, [query, key, value])
