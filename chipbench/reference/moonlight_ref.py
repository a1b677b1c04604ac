"""A latent-attention expert decoder (HF ``deepseek_v3``, the keys of
Moonlight's configuration) in plain ``jax.numpy``: forward pass, next-token
loss, gradients, float32 under ``highest`` matmul precision.  No kernels,
no sorting, no grouped products.  Imports nothing of the system under
test; Adam, the per-leaf norms, the plain RMS norm and the rounding of the
controls are ``nemotron_h_ref``'s.

Decoder layer ``l`` on the stream ``x`` (B, T, d):

* ``n = rms(x; g1)``; ``q = n Wq^T`` as (B, T, H, dn + dr), ``qn = q[...,
  :dn]``, ``qr = q[..., dn:]``; ``c = n Wa^T`` (B, T, r + dr), the latent
  ``c[..., :r]`` and ONE rotary key head ``kr = c[..., r:]`` (B, T, 1, dr);
  ``kv = rms(latent; g_c) Wb^T`` as (B, T, H, dn + dv), ``kn = kv[...,
  :dn]``, ``v = kv[..., dn:]``.
* rotary positions on all ``dr`` dimensions of ``qr`` (every head) and of
  ``kr`` (the one head), half-rotation layout, float32 angles, no scaling.
* ``s_h[t, u] = (qn_h[t] . kn_h[u] + qr_h[t] . kr[u]) / sqrt(dn + dr)``
  in blocks of rows, the causal mask a comparison on indices; softmax in
  float32; times ``v_h``; ``x <- x + concat_heads(.) Wo^T``.
* ``n' = rms(x; g2)``.  A dense layer (``D``): ``x <- x + (silu(n' Wg^T)
  * (n' Wu^T)) Wd^T``.  An expert layer (``E``): ``s = sigmoid(n' Wr^T)``;
  the chosen are the ``k`` largest of ``s + b``; their weights ``scaling *
  s_e / (sum of the chosen s + 1e-20)``; ``x <- x + sum over the chosen
  experts HELD of w_e swiglu_e(n') + swiglu_shared(n')``.

Then ``rms(x; gf)`` and an untied head over the rows held.

Departures from the source (HF ``modeling_deepseek_v3.py``), each also in
the configuration file:
1. Only the experts and vocabulary rows THIS CHIP holds exist (or, with
   every expert held, the uncut layer): what the other experts would add
   to a token is left out and the partial result goes on; the loss is over
   the rows held.
2. The routing may FOLLOW given indices (``chosen``), as
   ``nemotron_h_ref``: weights from its own scores at the program's
   indices, and a count of the tokens whose own top-k set differs.
3. HF reorders the interleaved rotary pairs of ``q_pe`` / ``k_pe`` into
   the half-rotation layout before turning; with seeded weights that is a
   fixed permutation of rows of ``Wq`` and ``Wa``, so the turn here is the
   half-rotation on the columns as drawn.
4. The inner norm's eps is HF's class default (1e-6), not
   ``rms_norm_eps``: ``sizes["latent_eps"]``.
5. The two shared experts are ONE SwiGLU of twice the width, as HF builds
   them; the dense layer's gate and up are one matrix, gate rows first.
6. No auxiliary loss and no step that moves the correction buffer.

``precision``: "f32" (the reference proper), "bf16", "fp8": operands of
every matrix product rounded, the router's included.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from chipbench.reference.gpt2_ref import PRECISIONS
from chipbench.reference.nemotron_h_ref import (HI, _mm, _rms, _round,
                                                adam_init, adam_step,
                                                leaf_norms)

__all__ = ["loss_and_grads", "forward", "expert_layer", "attention",
           "adam_init", "adam_step", "leaf_norms", "rotary"]


def rotary(x, theta):
    """x (B, T, H, D): pair ``(j, j + D/2)`` turned by ``t theta^(-2j/D)``;
    float32 angles."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freq[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


def attention(hn, w, s, precision="f32", rows=512):
    """The latent attention of one layer on normalised ``hn`` (B, T, d),
    before the residual."""
    b, t, _u = hn.shape
    h, dn, dr, dv, r = s["heads"], s["nope"], s["rope"], s["v_dim"], s["rank"]
    q = _mm(hn, w["a_q"], precision).reshape(b, t, h, dn + dr)
    qn, qr = q[..., :dn], rotary(q[..., dn:], s["theta"])
    c = _mm(hn, w["a_kva"], precision)
    kr = rotary(c[..., None, r:], s["theta"])                 # (B, T, 1, dr)
    kv = _mm(_rms(c[..., :r], w["a_cnorm"], s["latent_eps"]), w["a_kvb"],
             precision).reshape(b, t, h, dn + dv)
    kn, v = _round(kv[..., :dn], precision), _round(kv[..., dn:], precision)
    kr = _round(kr[:, :, 0], precision)                       # (B, T, dr)
    rows = min(rows, t)

    @jax.checkpoint
    def block(args):
        qnb, qrb, start = args                                # (B,rows,H,.)
        sc = (jnp.einsum("bqhd,bkhd->bhqk", _round(qnb, precision), kn,
                         precision=HI)
              + jnp.einsum("bqhd,bkd->bhqk", _round(qrb, precision), kr,
                           precision=HI)) / ((dn + dr) ** 0.5)
        seen = jnp.arange(t)[None, :] <= (start + jnp.arange(rows))[:, None]
        pr = jax.nn.softmax(jnp.where(seen, sc, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", _round(pr, precision), v,
                          precision=HI)

    nb = t // rows

    def blocks(x):
        return x.reshape(b, nb, rows, h, x.shape[-1]).swapaxes(0, 1)

    a = jax.lax.map(block, (blocks(qn), blocks(qr), jnp.arange(nb) * rows))
    return _mm(a.swapaxes(0, 1).reshape(b, t, h * dv), w["a_o"], precision)


def _swiglu(x, w_gate, w_up, w_down, precision):
    """(out, in) weights."""
    return _mm(jax.nn.silu(_mm(x, w_gate, precision))
               * _mm(x, w_up, precision), w_down, precision)


def expert_layer(hn, w, s, precision="f32", chosen=None, shared=True):
    """The expert layer on normalised tokens hn (N, U): returns (output,
    the choice used (N, k), tokens whose OWN top-k set differs from the
    choice used).  The experts held are ``first_expert .. first_expert +
    experts_held - 1`` of the ``experts`` the router scores.  ``shared``
    False leaves the shared expert out (what every chip computes alike is
    counted once when shares are summed)."""
    k, first = s["top_k"], s["first_expert"]
    sc = jax.nn.sigmoid(_mm(hn, w["e_router"], precision))
    # the buffer chooses and no gradient reaches it; not in the weights
    _, own = jax.lax.top_k(jax.lax.stop_gradient(sc + w["e_bias"]), k)
    own = own.astype(jnp.int32)
    if chosen is None:
        chosen = own
    differ = jnp.sum(jnp.any(jnp.sort(own, axis=1)
                             != jnp.sort(chosen, axis=1), axis=1))
    wt = jnp.take_along_axis(sc, chosen, axis=1)
    if s["norm_topk"]:
        wt = wt / (jnp.sum(wt, axis=1, keepdims=True) + 1e-20)
    wt = wt * s["scaling"]
    y = jnp.zeros_like(hn)
    if shared:
        y = _swiglu(hn, w["e_sh_gate"], w["e_sh_up"], w["e_sh_down"],
                    precision)

    @jax.checkpoint
    def one(y, xs):
        e, w_gate, w_up, w_down = xs          # (in, out) stacks
        # this expert's weight for every token; zero where it was not chosen
        we = jnp.sum(jnp.where(chosen == first + e, wt, 0.0), axis=1)
        ye = _swiglu(hn, w_gate.T, w_up.T, w_down.T, precision)
        return y + we[:, None] * ye, None

    held = w["e_up"].shape[0]
    y, _ = jax.lax.scan(one, y, (jnp.arange(held), w["e_gate"], w["e_up"],
                                 w["e_down"]))
    return y, chosen, differ


def _attention_half(x, w, s, precision, rows):
    return x + attention(_rms(x, w["a_norm"], s["eps"]), w, s, precision,
                         rows)


def _dense_half(x, w, s, precision):
    f = s["dense_width"]
    return x + _swiglu(_rms(x, w["d_norm"], s["eps"]), w["d_gate_up"][:f],
                       w["d_gate_up"][f:], w["d_down"], precision)


def _expert_half(x, w, s, precision, chosen):
    b, t, u = x.shape
    hn = _rms(x, w["e_norm"], s["eps"]).reshape(b * t, u)
    y, chosen, differ = expert_layer(hn, w, s, precision, chosen)
    return x + y.reshape(b, t, u), chosen, differ


def _layer_weights(weights, prefix, i):
    return {k: v[i] for k, v in weights.items() if k.startswith(prefix)}


def forward(weights, tokens, sizes, *, precision="f32", chosen=None,
            rows=512, remat=True):
    """tokens (B, T) -> (logits (B, T, V held) float32, choices per expert
    layer, differing tokens per expert layer)."""
    if precision not in PRECISIONS:
        raise ValueError(f"precision must be one of {PRECISIONS}")
    s = sizes
    wrap = jax.checkpoint if remat else (lambda f: f)
    x = weights["embed"][tokens]
    seen = {"D": 0, "E": 0}
    used, differ = [], []
    for i, kind in enumerate(s["pattern"]):
        f = functools.partial(_attention_half, s=s, precision=precision,
                              rows=rows)
        x = wrap(f)(x, _layer_weights(weights, "a_", i))
        if kind == "D":
            f = functools.partial(_dense_half, s=s, precision=precision)
            x = wrap(f)(x, _layer_weights(weights, "d_", seen[kind]))
        else:
            given = None if chosen is None else chosen[seen[kind]]
            f = functools.partial(_expert_half, s=s, precision=precision)
            x, c, d = wrap(f)(x, _layer_weights(weights, "e_", seen[kind]),
                              chosen=given)
            used.append(c)
            differ.append(d)
        seen[kind] += 1
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
    """Loss, gradients (zero for the buffer ``e_bias``), the choice each
    expert layer used and how many tokens' own top-k set differs from
    it."""
    items = tuple(sorted((k, v) for k, v in sizes.items()))
    return _loss_and_grads(weights, tokens, labels, chosen,
                           sizes_items=items, precision=precision,
                           rows=int(rows))
