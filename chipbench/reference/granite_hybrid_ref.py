"""A dense hybrid Mamba-2 / attention decoder under the Granite family's
four multipliers (HF ``granitemoehybrid``, ``num_local_experts`` 0) in
plain ``jax.numpy``: forward pass, next-token loss, gradients, float32
under ``highest`` matmul precision.  No kernels, no chunks.  Imports
nothing of the system under test; Adam, the per-leaf norms, the rounding
of the controls and the scan by its definition are ``nemotron_h_ref``'s.

``norm(x; w) = x / sqrt(mean(x^2) + eps) * w``.  With ``r`` the
``residual_multiplier``:

* ``h_0 = embedding_multiplier * E[ids]``.
* layer ``i``: ``a = h + r * mixer_i(norm(h; w1_i))``,
  ``h' = a + r * mlp_i(norm(a; w2_i))``; ``mixer_i`` is attention where
  ``layer_types[i] == "attention"``, Mamba-2 otherwise.
* ``mlp(x)``: ``u = x W_in^T``, ``(g, v)`` its two halves in that order,
  ``(silu(g) * v) W_out^T``; no bias.
* attention (``GraniteMoeHybridAttention``): grouped-query, no bias, NO
  positional encoding (``position_embedding_type`` ``nope``), causal,
  ``softmax(q k^T * attention_multiplier) v`` in blocks of rows, ``W_o``.
* Mamba-2 (``GraniteMoeHybridMambaLayer``, Bamba's mixer): ``in_proj`` ->
  z, xBC, dt; causal depthwise conv of K taps with bias, SiLU;
  ``dt = softplus(dt + dt_bias)``; ``A = -exp(A_log)``;
  ``S_t = exp(dt_t A) S_{t-1} + dt_t B_t x_t^T``, ``y_t = S_t^T C_t + D
  x_t``; ``norm(y * silu(z); w_n)`` per group (the gate goes in BEFORE the
  norm); ``out_proj``.  The scan is computed BY ITS DEFINITION as the
  masked quadratic form, one head at a time, one (T, T) matrix a head
  (``nemotron_h_ref.scan_quadratic``, which a CPU test ties to the
  step-by-step recurrence).
* ``logits = norm(h_L; w_f) E^T / logits_scaling`` (the head is tied).

Departures from the source, each also in the configuration file:
1. Only the vocabulary rows THIS CHIP holds exist; the loss is over them.
2. The running sum of ``dt A`` over the whole sequence is float32
   (``nemotron_h_ref``, departure 4).

``precision``: "f32" (the reference proper), "bf16", "fp8": operands of
every matrix product rounded, the scan's and the scores' included.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from chipbench.reference.gpt2_ref import PRECISIONS
from chipbench.reference.nemotron_h_ref import (HI, _conv, _mm, _rms,
                                                _round, adam_init,
                                                adam_step, leaf_norms,
                                                scan_quadratic)

__all__ = ["loss_and_grads", "forward", "adam_init", "adam_step",
           "leaf_norms", "mamba", "attention", "mlp"]


def mamba(hn, w, s, precision="f32"):
    """The mixer on a normalised (B, T, U) input."""
    b, t, _u = hn.shape
    h, p, g, n = s["m_heads"], s["m_head_dim"], s["groups"], s["state"]
    d_inner, gn = h * p, g * n
    proj = _mm(hn, w["m_in_proj"], precision)
    z, xbc, dt = (proj[..., :d_inner], proj[..., d_inner:2 * d_inner + 2 * gn],
                  proj[..., 2 * d_inner + 2 * gn:])
    xbc = jax.nn.silu(_conv(xbc, w["m_conv_w"], w["m_conv_b"]))
    xs = xbc[..., :d_inner].reshape(b, t, h, p)
    bm = xbc[..., d_inner:d_inner + gn].reshape(b, t, g, n)
    cm = xbc[..., d_inner + gn:].reshape(b, t, g, n)
    dt = jax.nn.softplus(dt + w["m_dt_bias"])                 # (B, T, H)
    a = -jnp.exp(w["m_A_log"])
    hpg = h // g

    @jax.checkpoint
    def head(args):
        xh, dth, ah, j = args
        return scan_quadratic(xh, dth, ah, jnp.take(bm, j // hpg, axis=2),
                              jnp.take(cm, j // hpg, axis=2), precision)

    y = jax.lax.map(head, (xs.transpose(2, 0, 1, 3), dt.transpose(2, 0, 1),
                           a, jnp.arange(h)))
    y = y.transpose(1, 2, 0, 3) + w["m_D"][:, None] * xs      # (B,T,H,P)
    y = y.reshape(b, t, d_inner) * jax.nn.silu(z)
    yg = y.reshape(b, t, g, d_inner // g)
    yg = yg * jax.lax.rsqrt(jnp.mean(jnp.square(yg), -1, keepdims=True)
                            + s["eps"])
    return _mm(yg.reshape(b, t, d_inner) * w["m_norm_w"], w["m_out_proj"],
               precision)


def attention(hn, w, s, precision="f32", rows=512):
    b, t, _u = hn.shape
    h, hk, d = s["heads"], s["kv_heads"], s["head_dim"]
    q = _mm(hn, w["a_q"], precision).reshape(b, t, h, d)
    k = jnp.repeat(_mm(hn, w["a_k"], precision).reshape(b, t, hk, d),
                   h // hk, axis=2)
    v = jnp.repeat(_mm(hn, w["a_v"], precision).reshape(b, t, hk, d),
                   h // hk, axis=2)
    rows = min(rows, t)
    kr, vr = _round(k, precision), _round(v, precision)

    @jax.checkpoint
    def block(args):
        qb, start = args                                      # (B,rows,H,D)
        sc = jnp.einsum("bqhd,bkhd->bhqk", _round(qb, precision), kr,
                        precision=HI) * s["attn_mult"]
        seen = (jnp.arange(t)[None, :]
                <= (start + jnp.arange(rows))[:, None])
        pr = jax.nn.softmax(jnp.where(seen, sc, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", _round(pr, precision), vr,
                          precision=HI)

    nb = t // rows
    qb = q.reshape(b, nb, rows, h, d).swapaxes(0, 1)
    o = jax.lax.map(block, (qb, jnp.arange(nb) * rows))
    return _mm(o.swapaxes(0, 1).reshape(b, t, h * d), w["a_o"], precision)


def mlp(hn, w, precision="f32"):
    u = _mm(hn, w["f_in"], precision)
    half = u.shape[-1] // 2
    return _mm(jax.nn.silu(u[..., :half]) * u[..., half:], w["f_out"],
               precision)


def _layer(x, w, kind, s, precision, rows):
    r, eps = s["res_mult"], s["eps"]
    if kind == "M":
        mixed = mamba(_rms(x, w["m_norm"], eps), w, s, precision)
    else:
        mixed = attention(_rms(x, w["a_norm"], eps), w, s, precision, rows)
    a = x + r * mixed
    return a + r * mlp(_rms(a, w["f_norm"], eps), w, precision)


def forward(weights, tokens, sizes, *, precision="f32", rows=512,
            remat=True):
    """tokens (B, T) -> logits (B, T, V held) float32."""
    if precision not in PRECISIONS:
        raise ValueError(f"precision must be one of {PRECISIONS}")
    s = sizes
    x = s["emb_mult"] * weights["embed"][tokens]
    seen = {"M": 0, "A": 0}
    for i, kind in enumerate(s["pattern"]):
        pre = "m_" if kind == "M" else "a_"
        w = {k: v[seen[kind]] for k, v in weights.items()
             if k.startswith(pre)}
        w.update({k: v[i] for k, v in weights.items() if k.startswith("f_")})
        f = functools.partial(_layer, kind=kind, s=s, precision=precision,
                              rows=rows)
        x = (jax.checkpoint(f) if remat else f)(x, w)
        seen[kind] += 1
    x = _rms(x, weights["norm_f"], s["eps"])
    return _mm(x, weights["embed"], precision) / s["logits_scaling"]


def _loss(weights, tokens, labels, sizes, precision, rows):
    logits = forward(weights, tokens, sizes, precision=precision, rows=rows)
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - picked)


@functools.partial(jax.jit, static_argnames=("sizes_items", "precision",
                                              "rows"))
def _loss_and_grads(weights, tokens, labels, *, sizes_items, precision,
                    rows):
    return jax.value_and_grad(_loss)(weights, tokens, labels,
                                     dict(sizes_items), precision, rows)


def loss_and_grads(weights, tokens, labels, sizes, *, precision="f32",
                   rows=512):
    items = tuple(sorted(sizes.items()))
    return _loss_and_grads(weights, tokens, labels, sizes_items=items,
                           precision=precision, rows=int(rows))
