"""A hybrid Gated DeltaNet / gated attention / sparse expert decoder (HF
``qwen3_next``) in plain ``jax.numpy``: forward pass, next-token loss,
gradients, float32 under ``highest`` matmul precision.  No kernels, no
chunks, no sorting, no grouped products.  Imports nothing of the system
under test; Adam, the per-leaf norms and the rounding of the controls are
``nemotron_h_ref``'s.

Decoder layer ``i``: ``x = x + mixer_i(norm(x))``, ``x = x +
experts(norm(x))``; ``norm`` is ``x / sqrt(mean(x^2) + eps) * (1 + w)``;
``mixer_i`` is full attention when ``(i + 1) % full_attention_interval ==
0``, Gated DeltaNet otherwise.  Then a final ``norm`` and an untied head.
The mixers, as HF ``modeling_qwen3_next.py`` defines them:

* Gated DeltaNet (``Qwen3NextGatedDeltaNet``): ``[q, k, v, z] = x W_qkvz``,
  ``[b, a] = x W_ba``; q, k, v through a causal depthwise conv of K taps
  (no bias) and SiLU; q, k L2-normalised per head, q times ``d_k^-1/2``,
  each key head serving ``H_v / H_k`` value heads; ``beta = sigmoid(b)``,
  ``g = -exp(A_log) softplus(a + dt_bias)``; per value head, with ``S`` a
  ``(d_k, d_v)`` state from zero: ``S' = exp(g_t) S``, ``u_t = beta_t
  (v_t - S'^T k_t)``, ``S = S' + k_t u_t^T``, ``o_t = S^T q_t``;
  ``RMSNorm_{d_v}(o; w_n) * silu(z)`` (plain gain); ``W_o``.  The rule is
  computed BY ITS RECURRENCE, one step at a time (:func:`delta_rule`): a
  scan over segments of 64 steps, each a scan over its steps, so that the
  backward pass keeps one state a segment and not one a step.
* gated attention (``Qwen3NextAttention``): ``[q, gate] = x W_q`` per
  head, grouped-query K/V, no bias; q, k RMS-normalised per head with
  ``(1 + w)``; rotary positions (theta) on the first ``rotary_dim``
  dimensions in the half-rotation layout; causal softmax attention in
  blocks of rows; the output times ``sigmoid(gate)``; ``W_o``.
* the experts (``Qwen3NextSparseMoeBlock``): ``p = softmax(x W_r)`` over
  all experts in float32, the top-k, their weights over their sum;
  experts ``(silu(x W_g) * (x W_u)) W_d`` by a plain loop over the experts
  held, each on every token under a mask; one shared expert of the same
  form times ``sigmoid(x . w_s)``.

Departures from the source, each also in the configuration file:
1. Only the experts and vocabulary rows THIS CHIP holds exist: what the
   other experts would add to a token is left out and the partial result
   goes on; the loss is over the rows held.
2. The routing may FOLLOW given indices (``chosen``), as
   ``nemotron_h_ref``: weights from its own scores at the program's
   indices, and a count of the tokens whose own top-k set differs.
3. The projections' outputs are laid out ``[q | k | v | z]``, ``[b | a]``
   and per head ``[query | gate]``; HF interleaves them by head.  That is
   layout: with seeded weights only program and reference have to agree.
4. The multi-token-prediction head of the model's description is in no
   configuration key and is not built.

``precision``: "f32" (the reference proper), "bf16", "fp8": operands of
every matrix product rounded, the router's and the rule's included.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from chipbench.reference.gpt2_ref import PRECISIONS
from chipbench.reference.nemotron_h_ref import (HI, _conv, _mm, _round,
                                                adam_init, adam_step,
                                                leaf_norms)

__all__ = ["loss_and_grads", "forward", "adam_init", "adam_step",
           "leaf_norms", "delta_rule", "rotary", "expert_layer"]

SEGMENT = 64          # steps between the states the backward pass keeps


def _rms(x, w, eps, unit_offset=True):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * (1.0 + w if unit_offset else w)


# ------------------------------------------------------------ the delta rule

def delta_rule(q, k, v, g, beta, precision="f32"):
    """The recurrence, one step at a time.  q, k (B, T, H, d_k) as they
    enter the rule (normalised, q scaled, one per VALUE head); v
    (B, T, H, d_v); g, beta (B, T, H).  Returns o (B, T, H, d_v)."""
    b, t, h, dk = q.shape
    dv = v.shape[-1]
    q, k, v = (_round(x, precision) for x in (q, k, v))

    def step(s, xs):
        qt, kt, vt, gt, bt = xs                               # (B, H, ..)
        s = jnp.exp(gt)[..., None, None] * s
        sr = _round(s, precision)
        u = bt[..., None] * (vt - jnp.sum(sr * kt[..., :, None], axis=-2))
        s = s + kt[..., :, None] * u[..., None, :]
        return s, jnp.sum(_round(s, precision) * qt[..., :, None], axis=-2)

    @jax.checkpoint
    def segment(s, xs):
        return jax.lax.scan(step, s, xs)

    seg = SEGMENT if t % SEGMENT == 0 else t
    xs = tuple(x.swapaxes(0, 1).reshape((t // seg, seg) + x.shape[:1]
                                        + x.shape[2:])
               for x in (q, k, v, g, beta))
    s0 = jnp.zeros((b, h, dk, dv), jnp.float32)
    _, o = jax.lax.scan(segment, s0, xs)
    return o.reshape((t,) + o.shape[2:]).swapaxes(0, 1)


def _delta_net(x, w, s, precision):
    b, t, _u = x.shape
    hk, hv, dk, dv = s["k_heads"], s["v_heads"], s["k_dim"], s["v_dim"]
    kd, vd = hk * dk, hv * dv
    hn = _rms(x, w["l_norm"], s["eps"])
    proj = _mm(hn, w["l_qkvz"], precision)
    ba = _mm(hn, w["l_ba"], precision)
    qkv = jax.nn.silu(_conv(proj[..., :2 * kd + vd], w["l_conv"], 0.0))
    z = proj[..., 2 * kd + vd:].reshape(b, t, hv, dv)

    def unit(a):
        return a * jax.lax.rsqrt(jnp.sum(jnp.square(a), -1, keepdims=True)
                                 + 1e-6)

    q = unit(qkv[..., :kd].reshape(b, t, hk, dk)) * dk ** -0.5
    k = unit(qkv[..., kd:2 * kd].reshape(b, t, hk, dk))
    v = qkv[..., 2 * kd:].reshape(b, t, hv, dv)
    q, k = (jnp.repeat(a, hv // hk, axis=2) for a in (q, k))
    beta = jax.nn.sigmoid(ba[..., :hv])
    g = -jnp.exp(w["l_A_log"]) * jax.nn.softplus(ba[..., hv:]
                                                 + w["l_dt_bias"])
    o = delta_rule(q, k, v, g, beta, precision)
    y = _rms(o, w["l_gnorm"], s["eps"], unit_offset=False) * jax.nn.silu(z)
    return x + _mm(y.reshape(b, t, vd), w["l_out"], precision)


# --------------------------------------------------------------- attention

def rotary(x, theta, rotary_dim):
    """x (B, T, H, D): dimensions ``i`` and ``i + rotary_dim / 2`` (i <
    rotary_dim / 2) turned by ``t * theta^(-2 i / rotary_dim)``; the
    dimensions from ``rotary_dim`` on untouched."""
    half = rotary_dim // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / rotary_dim)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2, rest = x[..., :half], x[..., half:rotary_dim], x[..., rotary_dim:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest],
                           axis=-1)


def _attention(x, w, s, precision, rows):
    b, t, _u = x.shape
    h, hk, d = s["heads"], s["kv_heads"], s["head_dim"]
    hn = _rms(x, w["f_norm"], s["eps"])
    qg = _mm(hn, w["f_q"], precision).reshape(b, t, h, 2 * d)
    gate = qg[..., d:].reshape(b, t, h * d)
    q = rotary(_rms(qg[..., :d], w["f_qnorm"], s["eps"]), s["theta"],
               s["rotary_dim"])
    k = rotary(_rms(_mm(hn, w["f_k"], precision).reshape(b, t, hk, d),
                    w["f_knorm"], s["eps"]), s["theta"], s["rotary_dim"])
    v = _mm(hn, w["f_v"], precision).reshape(b, t, hk, d)
    k, v = (jnp.repeat(a, h // hk, axis=2) for a in (k, v))
    rows = min(rows, t)
    kr, vr = _round(k, precision), _round(v, precision)

    @jax.checkpoint
    def block(args):
        qb, start = args                                      # (B,rows,H,D)
        sc = jnp.einsum("bqhd,bkhd->bhqk", _round(qb, precision), kr,
                        precision=HI) / (d ** 0.5)
        seen = (jnp.arange(t)[None, :]
                <= (start + jnp.arange(rows))[:, None])
        pr = jax.nn.softmax(jnp.where(seen, sc, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", _round(pr, precision), vr,
                          precision=HI)

    nb = t // rows
    qb = q.reshape(b, nb, rows, h, d).swapaxes(0, 1)
    a = jax.lax.map(block, (qb, jnp.arange(nb) * rows))
    a = a.swapaxes(0, 1).reshape(b, t, h * d) * jax.nn.sigmoid(gate)
    return x + _mm(a, w["f_o"], precision)


# ----------------------------------------------------------------- experts

def _swiglu(x, w_gate, w_up, w_down, precision):
    """(out, in) weights."""
    return _mm(jax.nn.silu(_mm(x, w_gate, precision))
               * _mm(x, w_up, precision), w_down, precision)


def expert_layer(hn, w, s, precision="f32", chosen=None, shared=True):
    """The expert layer on normalised tokens hn (N, U): returns (output,
    the choice used (N, k), tokens whose OWN top-k set differs from the
    choice used).  ``shared`` False leaves the shared expert out (what
    every chip computes alike is counted once when shares are summed)."""
    k, first = s["top_k"], s["first_expert"]
    p = jax.nn.softmax(_mm(hn, w["e_router"], precision), axis=-1)
    _, own = jax.lax.top_k(jax.lax.stop_gradient(p), k)
    own = own.astype(jnp.int32)
    if chosen is None:
        chosen = own
    differ = jnp.sum(jnp.any(jnp.sort(own, axis=1)
                             != jnp.sort(chosen, axis=1), axis=1))
    wt = jnp.take_along_axis(p, chosen, axis=1)
    if s["norm_topk"]:
        wt = wt / jnp.sum(wt, axis=1, keepdims=True)
    y = jnp.zeros_like(hn)
    if shared:
        y = jax.nn.sigmoid(_mm(hn, w["e_sh_sig"][None, :], precision)) \
            * _swiglu(hn, w["e_sh_gate"], w["e_sh_up"], w["e_sh_down"],
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


def _experts(x, w, s, precision, chosen):
    b, t, u = x.shape
    hn = _rms(x, w["e_norm"], s["eps"]).reshape(b * t, u)
    y, chosen, differ = expert_layer(hn, w, s, precision, chosen)
    return x + y.reshape(b, t, u), chosen, differ


# ------------------------------------------------------------------ model

def _block_weights(weights, prefix, i):
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
    seen = {"L": 0, "F": 0}
    used, differ = [], []
    for i, kind in enumerate(s["pattern"]):
        w = _block_weights(weights, "l_" if kind == "L" else "f_",
                           seen[kind])
        if kind == "L":
            f = functools.partial(_delta_net, s=s, precision=precision)
        else:
            f = functools.partial(_attention, s=s, precision=precision,
                                  rows=rows)
        x = wrap(f)(x, w)
        seen[kind] += 1
        given = None if chosen is None else chosen[i]
        f = functools.partial(_experts, s=s, precision=precision)
        x, c, d = wrap(f)(x, _block_weights(weights, "e_", i), chosen=given)
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
