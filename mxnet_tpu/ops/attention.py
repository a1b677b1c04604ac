"""Attention ops: flash attention (Pallas TPU) + XLA reference path.

Capability add over the reference (SURVEY.md §5.7: MXNet has NO flash/ring
attention — its closest machinery is the fused BERT matmuls in
src/operator/contrib/transformer.cc, whose API is kept below for GluonNLP
parity).  The public entry is :func:`dot_product_attention` on NDArrays;
``impl='auto'`` picks the Pallas kernel on TPU for long sequences and the
XLA reference elsewhere.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .. import base as _base
from .. import random as _random
from ._smap import shard_mapped_qkv

_NEG_INF = -1e30


# ----------------------------------------------------------------- reference

def _attention_ref(q, k, v, *, causal=False, mask=None, scale=None,
                   dropout=0.0, dropout_key=None, window=None, q2=None,
                   k2=None):
    """Pure-jax attention; q/k/v are (B, T, H, D).  XLA fuses this well for
    moderate T; the Pallas kernel takes over for long sequences.  Fewer
    K/V heads than query heads are repeated here (grouped queries; the
    values may have a head count and a head dim of their own).  ``window``
    (with ``causal``): query t sees keys s with ``0 <= t - s < window``.
    ``q2`` / ``k2`` (B, T, H or fewer, D2): a second product added to the
    score, which is then over D + D2 dimensions."""
    d = q.shape[-1] + (0 if q2 is None else q2.shape[-1])

    def per_query_head(x):
        return x if x.shape[2] == q.shape[2] else \
            jnp.repeat(x, q.shape[2] // x.shape[2], axis=2)

    k, v = per_query_head(k), per_query_head(v)
    scale = scale if scale is not None else 1.0 / (d ** 0.5)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32)
    if q2 is not None:
        logits = logits + jnp.einsum("bqhd,bkhd->bhqk", q2,
                                     per_query_head(k2),
                                     preferred_element_type=jnp.float32)
    logits = logits * scale
    if causal:
        tq, tk = logits.shape[-2], logits.shape[-1]
        idx_q = jnp.arange(tq)[:, None] + (tk - tq)
        idx_k = jnp.arange(tk)[None, :]
        seen = idx_k <= idx_q
        if window is not None:
            seen = jnp.logical_and(seen, idx_q - idx_k < window)
        logits = jnp.where(seen, logits, _NEG_INF)
    if mask is not None:
        logits = jnp.where(mask, logits, _NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    if mask is not None or (causal and q.shape[1] > k.shape[1]):
        # fully-masked (degenerate) rows: softmax of an all-_NEG_INF row
        # is a uniform average; zero it instead so this path is
        # bitwise-comparable with the Pallas kernel, which outputs zeros
        # for rows with no matching key (flash.py _finish).  Causal with
        # tq <= tk can never fully mask a row (row i always sees key
        # i + tk - tq), so that common case skips the O(Tq*Tk) scan.
        any_valid = jnp.any(logits > 0.5 * _NEG_INF, axis=-1, keepdims=True)
        probs = jnp.where(any_valid, probs, 0.0)
    if dropout > 0.0 and dropout_key is not None:
        keep = jax.random.bernoulli(dropout_key, 1.0 - dropout, probs.shape)
        probs = jnp.where(keep, probs / (1.0 - dropout),
                          jnp.zeros_like(probs))
    probs = probs.astype(v.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def rope_frequencies(rope_parameters: dict, head_dim: int):
    """``(inv_freq, amplitude)`` of one rotary table from a
    ``rope_parameters`` entry as HF configurations publish it: a float32
    ``(head_dim / 2,)`` array of the angles a position turns each pair by,
    computed in float64 on the host, and the factor on cos and sin.

    ``rope_type`` ``"default"``: ``rope_theta^(-2 j / head_dim)``, 1.
    ``"yarn"`` (Peng et al. 2023, as HF ``_compute_yarn_parameters``): with
    ``e_j`` the default angles and ``e_j / factor`` the interpolated ones,
    ``c(b) = head_dim ln(original_max_position_embeddings / (2 pi b)) / (2
    ln rope_theta)`` the dimension that turns ``b`` times over the original
    context, ``low = floor(c(beta_fast))`` and ``high = ceil(c(beta_slow))``
    clipped to the head, and ``r_j = clip((j - low) / (high - low), 0, 1)``:
    ``e_j (1 - r_j) + e_j / factor r_j``; the amplitude is
    ``attention_factor`` (default ``0.1 ln(factor) + 1``).  The table does
    not depend on the sequence length.  Leaves a ``rope.plan`` event for
    each distinct table."""
    import numpy as np

    from .flash import plan_event
    p = rope_parameters
    kind, theta = p.get("rope_type", "default"), float(p["rope_theta"])
    half = head_dim // 2
    j = np.arange(half, dtype=np.float64)
    inv = theta ** (-2.0 * j / head_dim)
    factor, low, high, amplitude = 1.0, 0, 0, 1.0
    if kind == "yarn":
        factor = float(p["factor"])
        original = float(p["original_max_position_embeddings"])

        def turns(b):
            return (head_dim * math.log(original / (2.0 * math.pi * b))
                    / (2.0 * math.log(theta)))

        low = max(math.floor(turns(p.get("beta_fast") or 32.0)), 0)
        high = min(math.ceil(turns(p.get("beta_slow") or 1.0)), head_dim - 1)
        ramp = np.clip((j - low) / max(high - low, 1e-3), 0.0, 1.0)
        inv = inv * (1.0 - ramp) + inv / factor * ramp
        amplitude = p.get("attention_factor")
        amplitude = (0.1 * math.log(factor) + 1.0 if amplitude is None
                     else float(amplitude))
    elif kind != "default":
        raise ValueError(f"rope_type {kind!r} is not default or yarn")
    plan_event("rope.plan", kind=kind, theta=theta, factor=factor, low=low,
               high=high, amplitude=amplitude, dim=int(head_dim))
    return inv.astype(np.float32), amplitude


def rotary_embedding(x, positions=None, *, theta=10000.0, rotary_dim=None,
                     inv_freq=None, amplitude=1.0):
    """Rotary positions on the first ``rotary_dim`` of x's (B, T, H, D)
    head dimensions (default: all D), the others untouched, in the
    half-rotation layout: dimensions ``i`` and ``i + rotary_dim / 2`` are
    a pair ``(a, b)`` turned to ``(a cos - b sin, b cos + a sin)`` by the
    angle ``position * theta^(-2 i / rotary_dim)``.  ``inv_freq``
    (``rotary_dim / 2`` angles a position, :func:`rope_frequencies`)
    replaces that table, ``theta`` is then unused; ``amplitude`` multiplies
    cos and sin BOTH, so a score of two turned vectors carries its square.
    Float32 angles; returns x's dtype.  ``positions`` (T,), default
    0..T-1."""
    d = x.shape[-1]
    rd = d if rotary_dim is None else int(rotary_dim)
    if rd % 2 or not 0 < rd <= d:
        raise ValueError(f"rotary_dim {rd} is not an even part of {d}")
    half = rd // 2
    if positions is None:
        positions = jnp.arange(x.shape[1])
    if inv_freq is None:
        inv_freq = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32)
                                    * 2.0 / rd))
    else:
        inv_freq = jnp.asarray(inv_freq, jnp.float32)
        if inv_freq.shape != (half,):
            raise ValueError(f"inv_freq {inv_freq.shape} is not the "
                             f"({half},) pairs of rotary_dim {rd}")
    angle = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos = jnp.cos(angle)[None, :, None, :]
    sin = jnp.sin(angle)[None, :, None, :]
    if amplitude != 1.0:
        cos, sin = cos * amplitude, sin * amplitude
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., :half], xf[..., half:rd]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                            xf[..., rd:]], axis=-1).astype(x.dtype)


# ------------------------------------------------------------------ dispatch

def _use_flash(q_shape, causal, mask, dropout, k_shape=None,
               platform=None, q2_shape=None, k2_shape=None) -> bool:
    """Flash kernel handles: SELF-attention (tq == tk — cross-attention
    with a different source length falls back to the XLA path), no
    explicit mask, no attention dropout, long 128-aligned sequences,
    head dims the MXU tiles well (64/128/256), and a score wider than
    that by a second pair of operands of head dim 64 or 128 whose key has
    as many heads as the query or a whole share of them (``q2_shape``,
    ``k2_shape``: 128 + 64 = 192 is latent attention's).  ``platform`` is
    where the op will execute (resolved per-call — a cpu()-context op on a
    TPU host must take the XLA reference path, not compiled Pallas)."""
    if mask is not None or dropout > 0.0:
        return False
    b, t, h, d = q_shape
    if q2_shape is not None:
        d2, h2 = q2_shape[3], k2_shape[2]
        if tuple(q2_shape) != (b, t, h, d2) or d2 not in (64, 128) or \
                tuple(k2_shape) != (b, t, h2, d2) or h2 == 0 or h % h2:
            return False
    if k_shape is not None and tuple(k_shape) != tuple(q_shape):
        # fewer key/value heads than query heads (grouped queries) is
        # the kernel's; another length or head dim is not
        kb, kt, kh, kd = k_shape
        if (kb, kt, kd) != (b, t, d) or kh == 0 or h % kh:
            return False
    if t < 256 or t % 128 or d not in (64, 128, 256):
        return False
    return (platform or jax.default_backend()) == "tpu"


def _pallas_flash(q, k, v, *, causal, scale, q_seg=None, kv_seg=None,
                  window=None, q2=None, k2=None):
    """The Pallas kernel, run per device.  GSPMD cannot partition a Mosaic
    kernel (jax refuses to lower one into a multi-device program), and
    batch rows and heads attend independently — so under an ambient
    multi-device mesh the call is shard_map'd: batch over ``dp`` and heads
    over ``tp`` where the axis divides the dimension, replicated where it
    does not.  Inside another shard_map body (ring / Ulysses) the operands
    are local already."""
    from ..parallel.mesh import axis_size, current_mesh
    from .flash import flash_attention as _pallas

    # beside q, k and v go the segment ids or the second pair of operands
    names = ("q2", "k2") if q2 is not None else \
        ("segment_ids", "kv_segment_ids")
    more = (q2, k2) if q2 is not None else \
        () if q_seg is None else (q_seg, kv_seg)

    def body(q, k, v, *more):
        return _pallas(q, k, v, causal=causal, scale=scale, window=window,
                       **dict(zip(names, more)))

    mesh = current_mesh()
    if mesh is None or mesh.size == 1 or \
            jax.sharding.get_abstract_mesh().manual_axes:
        return body(q, k, v, *more)

    def over(axis, dim):
        return axis if axis in mesh.axis_names and \
            dim % axis_size(mesh, axis) == 0 else None

    b_ax, h_ax = over("dp", q.shape[0]), over("tp", q.shape[2])
    if None in (over("tp", k.shape[2]), over("tp", v.shape[2])):
        h_ax = None                      # fewer K/V heads than tp shards
    specs = (P(b_ax, None),) * len(more)
    if q2 is not None:
        one = k2.shape[2] == 1           # ONE key head is every shard's
        if not one and over("tp", k2.shape[2]) is None:
            h_ax = None
        specs = (P(b_ax, None, h_ax, None),
                 P(b_ax, None, None if one else h_ax, None))
    return shard_mapped_qkv(body, mesh, P(b_ax, None, h_ax, None), q, k, v,
                            *more, extra_specs=specs)


def flash_attention(q, k, v, *, causal=False, scale=None, window=None,
                    q2=None, k2=None):
    """Jax-level flash attention entry (Pallas on TPU, reference on CPU).
    ``v`` may be (B, T, H_v, Dv), its head count and head dim its own;
    ``window`` is a causal window in keys; ``q2`` / ``k2`` a second pair of
    score operands whose product is added to ``q k^T`` (see
    ``ops.flash``)."""
    second = {} if q2 is None else dict(q2_shape=q2.shape, k2_shape=k2.shape)
    if _use_flash(q.shape, causal, None, 0.0, k.shape,
                  platform=_base.resolve_exec_platform(q), **second):
        return _pallas_flash(q, k, v, causal=causal, scale=scale,
                             window=window, q2=q2, k2=k2)
    return _attention_ref(q, k, v, causal=causal, scale=scale, window=window,
                          q2=q2, k2=k2)


def dot_product_attention(query, key, value, *, causal=False, mask=None,
                          segment_ids=None, kv_segment_ids=None,
                          dropout=0.0, scale=None, impl="auto"):
    """NDArray multi-head attention: inputs (B, T, H, D) → (B, T, H, D).

    impl: 'auto' | 'flash' | 'ref'.

    ``segment_ids`` (B, Tq) int enables SEQUENCE PACKING: tokens attend
    only within their own segment (combined with ``causal``/``mask``),
    so multiple short documents share one padded row with zero
    cross-contamination — the standard TPU lever against pad waste.
    ``kv_segment_ids`` (B, Tk) covers cross-attention; it defaults to
    ``segment_ids`` (self-attention).

    Fully-masked rows: a query position whose keys are ALL masked out
    (by ``mask``/``segment_ids``/a degenerate causal shape) returns
    ZEROS, not the historical uniform average over values.  Both impls
    agree on this — the Pallas kernel emits zeros for rows with no
    matching key and the XLA reference path zeroes them to match — so
    padding rows can be sliced away without contaminating reductions.
    """
    from ..ndarray.ops import _as_nd, invoke
    query, key, value = _as_nd(query), _as_nd(key), _as_nd(value)
    nd_in = [query, key, value]
    dkey = None
    if dropout > 0.0 and _base.is_training():
        dkey = _random.next_key(query.context)
    mask_val = mask.jax if hasattr(mask, "jax") else mask
    q_seg = kv_seg = None
    if segment_ids is not None:
        def _seg(x):
            return x.jax if hasattr(x, "jax") else jnp.asarray(x)

        q_seg = _seg(segment_ids)
        kv_seg = _seg(kv_segment_ids) if kv_segment_ids is not None \
            else q_seg
        bq_, tq_ = query.shape[0], query.shape[1]
        tk_ = key.shape[1]
        if tuple(q_seg.shape) != (bq_, tq_) or \
                tuple(kv_seg.shape) != (bq_, tk_):
            raise _base.MXNetError(
                f"segment_ids must be (B, Tq)=({bq_}, {tq_}) and "
                f"kv_segment_ids (B, Tk)=({bq_}, {tk_}); got "
                f"{tuple(q_seg.shape)} / {tuple(kv_seg.shape)} — "
                "cross-attention with Tq != Tk needs an explicit "
                "kv_segment_ids")
    elif kv_segment_ids is not None:
        raise _base.MXNetError("kv_segment_ids requires segment_ids")

    def _full_mask():
        """Segment equality folded into the dense mask — the O(Tq*Tk)
        fallback representation; the Pallas path keeps the raw (B, T) ids
        and masks per-tile in VMEM instead."""
        if q_seg is None:
            return mask_val
        seg_mask = (q_seg[:, None, :, None] ==
                    kv_seg[:, None, None, :])        # (B, 1, Tq, Tk)
        return seg_mask if mask_val is None else \
            jnp.logical_and(mask_val, seg_mask)

    if impl == "flash" and (mask is not None or dropout > 0.0):
        raise _base.MXNetError(
            "impl='flash' does not support an explicit mask or attention "
            "dropout — use impl='auto'/'ref'")

    if impl == "flash" and not _use_flash(query.shape, causal, mask_val,
                                          dropout, key.shape,
                                          platform=_base.resolve_exec_platform(query.jax)):
        raise _base.MXNetError(
            f"impl='flash' requested but the Pallas kernel does not support "
            f"this configuration (shape={tuple(query.shape)}, platform="
            f"{query.jax.devices().pop().platform if hasattr(query.jax, 'devices') else '?'}): "
            "seq_len and head_dim must be multiples of the kernel block "
            "sizes and the device must be a TPU — use impl='auto' to fall "
            "back silently")

    def f(q, k, v):
        if impl != "ref" and _use_flash(q.shape, causal, mask_val, dropout,
                                        k.shape,
                                        platform=_base.resolve_exec_platform(q)):
            return _pallas_flash(q, k, v, causal=causal, scale=scale,
                                 q_seg=q_seg, kv_seg=kv_seg)
        return _attention_ref(q, k, v, causal=causal, mask=_full_mask(),
                              scale=scale, dropout=dropout, dropout_key=dkey)

    return invoke("dot_product_attention", f, nd_in)


# GluonNLP-compat fused attention ops live in mxnet_tpu.ndarray.ops
# (parity: src/operator/contrib/transformer.cc); re-exported here so kernel
# users find the whole attention surface in one namespace.
from ..ndarray.ops import (interleaved_matmul_selfatt_qk,  # noqa: E402,F401
                           interleaved_matmul_selfatt_valatt)
