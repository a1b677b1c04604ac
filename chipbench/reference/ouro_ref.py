"""A looped decoder (HF ``ouro``; Zhu et al. 2025, "Scaling Latent
Reasoning via Looped Language Models") in plain ``jax.numpy``: forward
pass, the exit-weighted objective, gradients, float32 under ``highest``
matmul precision.  No kernels.  Imports nothing of the system under test;
Adam, the plain RMS norm and the rounding of the controls are
``nemotron_h_ref``'s, the rotary turn and the causal mask
``mellum2_ref``'s.

With ``d`` the hidden size, ``H`` heads of ``D``, ``F`` the feed-forward
width, ``P = total_ut_steps`` passes and ``rms(x; g) = x / sqrt(mean(x^2)
+ eps) * g``:

* Layer ``l``, THE SAME WEIGHTS IN EVERY PASS, on the stream ``x``:
  ``n = rms(x; g1)``; ``q, k, v = n Wq^T, n Wk^T, n Wv^T`` as (T, H, D),
  no bias, no q/k norm; rotary positions on all D dimensions,
  half-rotation layout, ``rope_theta``, float32 angles, positions 0..T-1
  in every pass; ``a`` = causal softmax attention at scale ``1/sqrt(D)``
  over all earlier keys of this pass, in blocks of rows with the mask
  written out; ``x <- x + rms(merge(a) Wo^T; g2)``.  Then ``n' = rms(x;
  g3)``; ``m = (silu(n' Wg^T) * (n' Wu^T)) Wd^T``; ``x <- x + rms(m;
  g4)``.
* Passes, a Python loop over a scan of the layers held: ``h_0 =
  E[tokens]``; ``h_t = rms(stack(h_{t-1}); g_f)``: the final norm closes
  EVERY pass and its output enters the next; ``logits_t = h_t
  W_head^T``; ``lambda_t = sigmoid(h_t . w_gate + b_gate)``.
* Exit distribution, a token: ``p_t = lambda_t prod_{j<t} (1 -
  lambda_j)`` for ``t < P`` and ``p_P = prod_{j<P} (1 - lambda_j)``.
* Objective, a token: ``sum_t p_t CE_t - beta H(p)``, ``CE_t`` the
  next-token cross entropy of ``logits_t`` over the rows held, ``H(p) =
  -sum_t p_t log p_t``; averaged over the tokens.

Departures from the source, each also in the configuration file:
1. Only the vocabulary rows THIS CHIP holds exist (embedding and head);
   the loss is over the rows held.
2. Where the four norms of a layer sit, that the final norm closes every
   pass, the gate's form and the objective are in no configuration key:
   they are the family's published convention (``assumed``).
3. Gate and up are the two halves, in that order, of one ``m_in`` matrix;
   HF keeps two.  Layout, not mathematics.

``precision``: "f32" (the reference proper), "bf16", "fp8": operands of
every matrix product rounded, the gate's dot product included.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference.gpt2_ref import PRECISIONS
from chipbench.reference.mellum2_ref import attention_mask, rotary
from chipbench.reference.nemotron_h_ref import (HI, _mm, _rms, _round,
                                                adam_init, adam_step)

__all__ = ["loss_and_grads", "forward", "exit_distribution", "objective",
           "adam_init", "adam_step", "leaf_norms"]

TOP = ("embed", "norm_f", "lm_head", "gate_w", "gate_b")


def _attention(x, w, s, precision, rows):
    b, t, _u = x.shape
    h, hk, d = s["heads"], s["kv_heads"], s["head_dim"]
    freq = float(s["rope_theta"]) ** (
        -np.arange(d // 2, dtype=np.float64) / (d // 2))
    n = _rms(x, w["a_norm"], s["eps"])
    q = rotary(_mm(n, w["a_q"], precision).reshape(b, t, h, d), freq, 1.0)
    k = rotary(_mm(n, w["a_k"], precision).reshape(b, t, hk, d), freq, 1.0)
    v = _mm(n, w["a_v"], precision).reshape(b, t, hk, d)
    k, v = (jnp.repeat(a, h // hk, axis=2) for a in (k, v))
    rows = min(rows, t)
    kr, vr = _round(k, precision), _round(v, precision)

    @jax.checkpoint
    def block(args):
        qb, start = args                                      # (B,rows,H,D)
        sc = jnp.einsum("bqhd,bkhd->bhqk", _round(qb, precision), kr,
                        precision=HI) / (d ** 0.5)
        seen = attention_mask(start + jnp.arange(rows), t)
        pr = jax.nn.softmax(jnp.where(seen, sc, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", _round(pr, precision), vr,
                          precision=HI)

    nb = t // rows
    qb = q.reshape(b, nb, rows, h, d).swapaxes(0, 1)
    a = jax.lax.map(block, (qb, jnp.arange(nb) * rows))
    a = a.swapaxes(0, 1).reshape(b, t, h * d)
    return x + _rms(_mm(a, w["a_o"], precision), w["a_post"], s["eps"])


def _mlp(x, w, s, precision):
    u = _mm(_rms(x, w["m_norm"], s["eps"]), w["m_in"], precision)
    half = u.shape[-1] // 2
    m = _mm(jax.nn.silu(u[..., :half]) * u[..., half:], w["m_out"],
            precision)
    return x + _rms(m, w["m_post"], s["eps"])


def _layer(x, w, s, precision, rows):
    return _mlp(_attention(x, w, s, precision, rows), w, s, precision)


def _stack(x, weights, s, precision, rows, remat):
    """Every layer held, once: a scan over the leaves' leading axis."""
    f = functools.partial(_layer, s=s, precision=precision, rows=rows)
    if remat:
        f = jax.checkpoint(f)
    layers = {k: v for k, v in weights.items() if k not in TOP}
    return jax.lax.scan(lambda h, w: (f(h, w), None), x, layers)[0]


def _leave(y, weights, s, precision):
    """What closes a pass: the normed stream, its logits, its gate."""
    h = _rms(y, weights["norm_f"], s["eps"])
    logits = _mm(h, weights["lm_head"], precision)
    gate = jax.nn.sigmoid(_mm(h, weights["gate_w"][None], precision)[..., 0]
                          + weights["gate_b"][0])
    return h, logits, gate


def forward(weights, tokens, sizes, *, precision="f32", rows=512,
            remat=True):
    """tokens (B, T) -> (logits (P, B, T, V held), gates (P, B, T)),
    float32."""
    if precision not in PRECISIONS:
        raise ValueError(f"precision must be one of {PRECISIONS}")
    h = weights["embed"][tokens]
    logits, gates = [], []
    for _ in range(sizes["passes"]):
        h, lg, gate = _leave(_stack(h, weights, sizes, precision, rows,
                                    remat), weights, sizes, precision)
        logits.append(lg)
        gates.append(gate)
    return jnp.stack(logits), jnp.stack(gates)


def exit_distribution(gates):
    """p (P, ...) from lambda (P, ...), written out pass by pass."""
    p, left = [], jnp.ones_like(gates[0])
    for t in range(gates.shape[0] - 1):
        p.append(gates[t] * left)
        left = left * (1.0 - gates[t])
    return jnp.stack(p + [left])


def objective(losses, gates, beta):
    """``mean over the tokens of sum_t p_t CE_t - beta H(p)``; also the
    mean ``p_t`` and mean ``CE_t`` of every pass."""
    p = exit_distribution(gates)
    entropy = -jnp.sum(p * jnp.log(jnp.maximum(p, 1e-30)), axis=0)
    per_token = jnp.sum(p * losses, axis=0) - beta * entropy
    over = tuple(range(1, p.ndim))
    return jnp.mean(per_token), p.mean(over), losses.mean(over)


def _token_loss(logits, labels):
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return lse - picked


def _loss(weights, tokens, labels, sizes, precision, rows):
    """As :func:`forward`, with one pass's logits alive at a time."""
    h = weights["embed"][tokens]
    losses, gates = [], []

    @jax.checkpoint
    def leave(y):
        hn, logits, gate = _leave(y, weights, sizes, precision)
        return hn, _token_loss(logits, labels), gate

    for _ in range(sizes["passes"]):
        h, ce, gate = leave(_stack(h, weights, sizes, precision, rows, True))
        losses.append(ce)
        gates.append(gate)
    total, mass, pass_loss = objective(jnp.stack(losses), jnp.stack(gates),
                                       float(sizes["beta"]))
    return total, (mass, pass_loss)


@functools.partial(jax.jit, static_argnames=("sizes_items", "precision",
                                              "rows"))
def _loss_and_grads(weights, tokens, labels, *, sizes_items, precision,
                    rows):
    return jax.value_and_grad(_loss, has_aux=True)(
        weights, tokens, labels, dict(sizes_items), precision, rows)


def loss_and_grads(weights, tokens, labels, sizes, *, precision="f32",
                   rows=512):
    """``(objective, mean p_t (P,), mean CE_t (P,)), gradients``."""
    items = tuple(sorted((k, v) for k, v in sizes.items()))
    (loss, (mass, pass_loss)), grads = _loss_and_grads(
        weights, tokens, labels, sizes_items=items, precision=precision,
        rows=int(rows))
    return (loss, mass, pass_loss), grads


def leaf_norms(tree: dict) -> dict:
    """L2 norm of every leaf, per layer for stacked leaves: name -> list
    of floats."""
    out = {}
    for k, a in tree.items():
        a = a.astype(jnp.float32)
        a = a[None] if k in TOP else a
        out[k] = [float(x) for x in jnp.sqrt(
            jnp.sum(jnp.square(a), axis=tuple(range(1, a.ndim))))]
    return out
