"""A sliding-window / full attention expert decoder (HF ``mellum``, the
keys of ``Qwen3MoeConfig`` plus per-layer lists) in plain ``jax.numpy``:
forward pass, next-token loss, gradients, float32 under ``highest`` matmul
precision.  No kernels, no sorting, no grouped products.  Imports nothing
of the system under test; Adam, the per-leaf norms, the plain RMS norm and
the rounding of the controls are ``nemotron_h_ref``'s, the expert layer
(softmax over all experts, top-k renormalised, a loop over the experts
held under a mask, following given indices) ``qwen3_next_ref``'s with its
shared expert left out.

Decoder layer ``i`` of kind ``pattern[i]`` (``S`` sliding, ``F`` full):

* ``x = rms(h; g1)``; ``q = x Wq`` as (T, H, D), ``k = x Wk``, ``v = x
  Wv`` as (T, H_kv, D), query head ``h`` reading K/V head ``h // (H /
  H_kv)``; ``q = rms_D(q; gq)``, ``k = rms_D(k; gk)`` per head.
* rotary positions on all D dimensions, half-rotation layout (dimension
  ``j`` pairs with ``j + D/2``): ``q' = q cos + rot(q) sin`` with ``rot([a,
  b]) = [-b, a]``, ``cos = A cos(t f_j)``, ``sin = A sin(t f_j)``; the
  table ``(f, A)`` by the layer's kind, built HERE in numpy float64 from
  the published numbers (:func:`rope_table`): plain, or YaRN's blend.
* scores ``q' k'^T / sqrt(D)`` in blocks of rows; query ``t`` sees key
  ``s`` where ``s <= t`` and, in a sliding layer, ``t - s < W``: both
  masks are comparisons on indices; softmax in float32; times ``v``;
  ``u = h + concat_heads(.) Wo``.
* ``y = rms(u; g2)``; ``p = softmax(y Wr)`` over all experts; the top-k,
  their weights over their sum; ``h' = u + sum over the chosen experts
  HELD of w_e (silu(y Wg_e) * (y Wu_e)) Wd_e``.  No shared expert.

Then ``rms(h; gf)`` and an untied head over the rows held.

Departures from the source, each also in the configuration file:
1. Only the experts and vocabulary rows THIS CHIP holds exist: what the
   other experts would add to a token is left out and the partial result
   goes on; the loss is over the rows held.
2. The routing may FOLLOW given indices (``chosen``), as
   ``nemotron_h_ref``: weights from its own scores at the program's
   indices, and a count of the tokens whose own top-k set differs.
3. q and k are RMS-normalised per head: the convention of the family
   whose keys these are, which no key states.
4. An expert's gate and up products are two matrices; HF fuses them.
5. The multi-token-prediction head of the model's description is in no
   configuration key and is not built.

``precision``: "f32" (the reference proper), "bf16", "fp8": operands of
every matrix product rounded, the router's included.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference.gpt2_ref import PRECISIONS
from chipbench.reference.nemotron_h_ref import (HI, _mm, _rms, _round,
                                                adam_init, adam_step,
                                                leaf_norms)
from chipbench.reference.qwen3_next_ref import expert_layer

__all__ = ["loss_and_grads", "forward", "adam_init", "adam_step",
           "leaf_norms", "rope_table", "rotary", "attention_mask"]


def rope_table(rope, head_dim: int):
    """``(f, A)``: the angle a position turns each of the ``head_dim / 2``
    pairs by (numpy float64) and the factor on cos and sin, from one
    ``rope_parameters`` entry (a dict or its sorted items)."""
    p = dict(rope)
    theta = float(p["rope_theta"])
    j = np.arange(head_dim // 2, dtype=np.float64)
    plain = theta ** (-j / (head_dim // 2))
    if p.get("rope_type", "default") == "default":
        return plain, 1.0
    if p["rope_type"] != "yarn":
        raise ValueError(f"rope_type {p['rope_type']!r}")
    scale = float(p["factor"])
    length = float(p["original_max_position_embeddings"])

    def c(b):             # the dimension that turns b times over `length`
        return head_dim * math.log(length / (2 * math.pi * b)) \
            / (2 * math.log(theta))

    low = max(math.floor(c(float(p["beta_fast"]))), 0)
    high = min(math.ceil(c(float(p["beta_slow"]))), head_dim - 1)
    r = np.clip((j - low) / (high - low), 0.0, 1.0)
    return plain / scale * r + plain * (1.0 - r), float(p["attention_factor"])


def rotary(x, freq, amplitude):
    """x (B, T, H, D): pair ``(j, j + D/2)`` turned by ``t * freq[j]``,
    cos and sin both times ``amplitude``; float32 angles."""
    half = x.shape[-1] // 2
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] \
        * jnp.asarray(freq, jnp.float32)[None, :]
    cos = (amplitude * jnp.cos(ang))[None, :, None, :]
    sin = (amplitude * jnp.sin(ang))[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


def attention_mask(rows_at, keys: int, window=None):
    """(rows, keys) bool: query ``t = rows_at[r]`` sees key ``s`` where
    ``s <= t`` and, under a window, ``t - s < window``."""
    t, s = rows_at[:, None], jnp.arange(keys)[None, :]
    seen = s <= t
    if window is not None:
        seen = jnp.logical_and(seen, t - s < window)
    return seen


def _attention(x, w, s, precision, rows, kind):
    b, t, _u = x.shape
    h, hk, d = s["heads"], s["kv_heads"], s["head_dim"]
    freq, amp = rope_table(s["rope_" + kind], d)
    window = s["window"] if kind == "S" else None
    hn = _rms(x, w["a_norm"], s["eps"])
    q = rotary(_rms(_mm(hn, w["a_q"], precision).reshape(b, t, h, d),
                    w["a_qnorm"], s["eps"]), freq, amp)
    k = rotary(_rms(_mm(hn, w["a_k"], precision).reshape(b, t, hk, d),
                    w["a_knorm"], s["eps"]), freq, amp)
    v = _mm(hn, w["a_v"], precision).reshape(b, t, hk, d)
    k, v = (jnp.repeat(a, h // hk, axis=2) for a in (k, v))
    rows = min(rows, t)
    kr, vr = _round(k, precision), _round(v, precision)

    @jax.checkpoint
    def block(args):
        qb, start = args                                      # (B,rows,H,D)
        sc = jnp.einsum("bqhd,bkhd->bhqk", _round(qb, precision), kr,
                        precision=HI) / (d ** 0.5)
        seen = attention_mask(start + jnp.arange(rows), t, window)
        pr = jax.nn.softmax(jnp.where(seen, sc, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", _round(pr, precision), vr,
                          precision=HI)

    nb = t // rows
    qb = q.reshape(b, nb, rows, h, d).swapaxes(0, 1)
    a = jax.lax.map(block, (qb, jnp.arange(nb) * rows))
    a = a.swapaxes(0, 1).reshape(b, t, h * d)
    return x + _mm(a, w["a_o"], precision)


def _experts(x, w, s, precision, chosen):
    b, t, u = x.shape
    hn = _rms(x, w["e_norm"], s["eps"]).reshape(b * t, u)
    y, chosen, differ = expert_layer(hn, w, s, precision, chosen,
                                     shared=False)
    return x + y.reshape(b, t, u), chosen, differ


def _layer_weights(weights, prefix, i):
    return {k: v[i] for k, v in weights.items() if k.startswith(prefix)}


def forward(weights, tokens, sizes, *, precision="f32", chosen=None,
            rows=512, remat=True):
    """tokens (B, T) -> (logits (B, T, V held) float32, choices per layer,
    differing tokens per layer)."""
    if precision not in PRECISIONS:
        raise ValueError(f"precision must be one of {PRECISIONS}")
    s = sizes
    wrap = jax.checkpoint if remat else (lambda f: f)
    x = weights["embed"][tokens]
    used, differ = [], []
    for i, kind in enumerate(s["pattern"]):
        f = functools.partial(_attention, s=s, precision=precision,
                              rows=rows, kind=kind)
        x = wrap(f)(x, _layer_weights(weights, "a_", i))
        given = None if chosen is None else chosen[i]
        f = functools.partial(_experts, s=s, precision=precision)
        x, c, d = wrap(f)(x, _layer_weights(weights, "e_", i), chosen=given)
        used.append(c)
        differ.append(d)
    x = _rms(x, weights["norm_f"], s["eps"])
    return _mm(x, weights["lm_head"], precision), used, differ


def _loss(weights, tokens, labels, sizes, precision, chosen, rows):
    logits, used, differ = forward(weights, tokens, sizes,
                                   precision=precision, chosen=chosen,
                                   rows=rows)
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - picked), (used, differ)


@functools.partial(jax.jit, static_argnames=("sizes_items", "precision",
                                              "rows"))
def _loss_and_grads(weights, tokens, labels, chosen, *, sizes_items,
                    precision, rows):
    sizes = dict(sizes_items)
    (loss, (used, differ)), grads = jax.value_and_grad(
        _loss, has_aux=True)(weights, tokens, labels, sizes, precision,
                             chosen, rows)
    return loss, grads, used, differ


def loss_and_grads(weights, tokens, labels, sizes, *, precision="f32",
                   chosen=None, rows=512):
    """Loss, gradients, the choice each expert layer used and how many
    tokens' own top-k set differs from it."""
    items = tuple(sorted((k, v) for k, v in sizes.items()))
    return _loss_and_grads(weights, tokens, labels, chosen,
                           sizes_items=items, precision=precision,
                           rows=int(rows))
