"""One contiguous stage of a SambaY decoder-hybrid-decoder stack
(arXiv:2507.06607; HF ``phi4flash``: Phi-4-mini-flash-reasoning) in plain
``jax.numpy``: forward pass, next-token loss, gradients, float32 under
``highest`` matmul precision.  No kernels, no chunked forms.  Imports
nothing of the system under test; the matrix product, the rounding of the
controls, the convolution and Adam are ``nemotron_h_ref``'s.

``LN(x; g, b) = (x - mean) / sqrt(var + eps) * g + b``.  Layer ``i`` of the
published stack, ``h`` the stream::

    u  = h + mixer_i(LN(h; g1_i, b1_i))
    h' = u + (silu(g) * v) W_fc2^T,  [g, v] = LN(u; g2_i, b2_i) W_fc1^T

and the mixer by the layer's letter (``weights_phi4_flash.kinds_of``):

* ``M`` Mamba-1: ``[a, z] = x W_in^T``; ``a = silu(conv(a))`` (causal,
  depthwise, K taps, bias); ``[r, B, C] = a W_x^T``; ``dt = softplus(r
  W_dt^T + b_dt)``; ``A = -exp(A_log)``; for every channel c and state n
  ``s_t[c,n] = exp(dt_t[c] A[c,n]) s_{t-1}[c,n] + dt_t[c] B_t[n] a_t[c]``,
  ``y_t[c] = sum_n C_t[n] s_t[c,n] + D[c] a_t[c]``; output ``(y * silu(z))
  W_out^T``.  The scan is computed BY ITS RECURRENCE, one step at a time
  (``lax.scan`` in segments, so that the backward keeps a state a
  segment), never by a chunked form.
* ``W``: the same, and the layer's memory is ``m = y`` (after the D skip,
  before the gate).
* ``S`` / ``F`` differential attention, causal, ``S`` also ``t - s <
  window``: ``[Q, K, V] = x W_qkv^T + b_qkv``; differential head j has
  ``q1 = Q[2j]``, ``q2 = Q[2j+1]``; key/value pair ``p = j // 2`` has
  ``k1 = K[2p]``, ``k2 = K[2p+1]``, ``v = [V[2p], V[2p+1]]``;
  ``o1 = softmax(q1 k1^T d^-1/2 + M) v``, ``o2 = softmax(q2 k2^T d^-1/2 +
  M) v``, two explicit softmaxes a head in blocks of rows with the mask
  written out; ``lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init``,
  ``lambda_init = 0.8 - 0.6 exp(-0.3 i)`` with the PUBLISHED index i;
  ``o = rmsnorm(o1 - lambda o2; gain) (1 - lambda_init)``; the heads side
  by side through ``W_o`` and ``b_o``.  ``F`` hands on its K and V.
* ``G`` Gated Memory Unit: ``(m * silu(x W_1^T)) W_2^T`` over layer W's m.
* ``C`` differential cross-attention: ``Q = x W_q^T + b_q`` only, layer
  F's K and V, causal over everything, the rest as ``S``/``F`` with the
  layer's own i, lambdas, gain and ``W_o``.

``logits = LN(h_L; g_f, b_f) E^T`` (the head is tied).  There is no
positional encoding of any kind.

Departures from the source, each also in the configuration file:
1. Only the vocabulary rows THIS CHIP holds exist; the loss is over them.
2. Only the layers held exist; nothing stands in for the others.

``precision``: "f32" (the reference proper), "bf16", "fp8": operands of
every matrix product rounded, the scores' included, and the scan's four
inputs.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from chipbench.reference.gpt2_ref import PRECISIONS
from chipbench.reference.nemotron_h_ref import (HI, _conv, _mm, _round,
                                                adam_init, adam_step)

__all__ = ["loss_and_grads", "forward", "adam_init", "adam_step",
           "leaf_norms", "off_line", "mamba", "attention", "gmu", "mlp",
           "scan", "lambda_init"]

_TOP = ("embed", "norm_f_g", "norm_f_b")
SEGMENT = 128


def _ln(x, g, b, eps):
    xc = x - jnp.mean(x, -1, keepdims=True)
    return xc * jax.lax.rsqrt(jnp.mean(jnp.square(xc), -1, keepdims=True)
                              + eps) * g + b


def lambda_init(layer: int) -> float:
    return 0.8 - 0.6 * math.exp(-0.3 * layer)


def scan(a, dt, decay, bm, cm, segment=SEGMENT):
    """The selective scan by its recurrence: a, dt (B, T, C), decay (C, N)
    negative, bm / cm (B, T, N) -> y (B, T, C), without the D skip."""
    b, t, c = a.shape
    segment = min(segment, t)
    if t % segment:
        raise ValueError(f"{t} steps are no whole number of segments of "
                         f"{segment}")

    def step(s, xs):
        at, dtt, bt, ct = xs
        s = (jnp.exp(dtt[..., None] * decay) * s
             + (dtt * at)[..., None] * bt[:, None, :])
        return s, jnp.sum(s * ct[:, None, :], axis=-1)

    @jax.checkpoint
    def walk(s, xs):
        return jax.lax.scan(step, s, xs)

    def cut(v):                                   # (B, T, .) -> (T/S, S, B, .)
        v = v.swapaxes(0, 1)
        return v.reshape((t // segment, segment) + v.shape[1:])

    s0 = jnp.zeros((b,) + decay.shape, jnp.float32)
    _, y = jax.lax.scan(walk, s0, tuple(cut(v) for v in (a, dt, bm, cm)))
    return y.reshape(t, b, c).swapaxes(0, 1)


def mamba(hn, w, s, precision="f32"):
    """The mixer on a normalised (B, T, U) input: (output, memory)."""
    di, n, r = s["d_inner"], s["state"], s["dt_rank"]
    proj = _mm(hn, w["m_in_proj"], precision)
    a, z = proj[..., :di], proj[..., di:]
    a = jax.nn.silu(_conv(a, w["m_conv_w"], w["m_conv_b"]))
    low = _mm(a, w["m_x_proj"], precision)
    dt = jax.nn.softplus(_mm(low[..., :r], w["m_dt_proj"], precision)
                         + w["m_dt_bias"])
    y = scan(_round(a, precision), _round(dt, precision),
             -jnp.exp(w["m_A_log"]), _round(low[..., r:r + n], precision),
             _round(low[..., r + n:], precision))
    y = y + w["m_D"] * a
    return _mm(y * jax.nn.silu(z), w["m_out_proj"], precision), y


def attention(hn, w, s, layer, window=None, precision="f32", rows=512,
              kv=None, pre="a"):
    """Differential attention at published layer ``layer``: (output, K,
    V); ``kv`` = (K, V) of another layer makes it cross-attention."""
    b, t, _u = hn.shape
    h, hk, d = s["heads"], s["kv_heads"], s["head_dim"]
    proj = _mm(hn, w[f"{pre}_qkv"], precision) + w[f"{pre}_qkv_b"]
    q = proj[..., :h * d].reshape(b, t, h, d)
    if kv is None:
        k = proj[..., h * d:(h + hk) * d].reshape(b, t, hk, d)
        v = proj[..., (h + hk) * d:].reshape(b, t, hk, d)
    else:
        k, v = kv
    per = (h // 2) // (hk // 2)          # differential heads a key/value pair
    q1, q2 = q[:, :, 0::2], q[:, :, 1::2]                     # (B,T,H/2,D)
    k1 = jnp.repeat(k[:, :, 0::2], per, axis=2)
    k2 = jnp.repeat(k[:, :, 1::2], per, axis=2)
    wide = jnp.repeat(jnp.concatenate([v[:, :, 0::2], v[:, :, 1::2]], -1),
                      per, axis=2)                            # (B,T,H/2,2D)
    rows = min(rows, t)
    k1, k2, wide = (_round(x, precision) for x in (k1, k2, wide))
    scale = d ** -0.5

    @jax.checkpoint
    def block(args):
        qa, qb, start = args                                  # (B,rows,H/2,D)
        at = (start + jnp.arange(rows))[:, None]
        seen = jnp.arange(t)[None, :] <= at
        if window is not None:
            seen = jnp.logical_and(seen, at - jnp.arange(t)[None, :] < window)

        def one(qx, kx):
            sc = jnp.einsum("bqhd,bkhd->bhqk", _round(qx, precision), kx,
                            precision=HI) * scale
            pr = jax.nn.softmax(jnp.where(seen, sc, -jnp.inf), axis=-1)
            return jnp.einsum("bhqk,bkhd->bqhd", _round(pr, precision),
                              wide, precision=HI)

        return one(qa, k1), one(qb, k2)

    nb = t // rows

    def cut(x):
        return x.reshape(b, nb, rows, h // 2, d).swapaxes(0, 1)

    o1, o2 = jax.lax.map(block, (cut(q1), cut(q2), jnp.arange(nb) * rows))
    o1, o2 = (o.swapaxes(0, 1).reshape(b, t, h // 2, 2 * d) for o in (o1, o2))
    lam = w[f"{pre}_lambdas"]
    first = lambda_init(layer)
    lam = (jnp.exp(jnp.sum(lam[0] * lam[1]))
           - jnp.exp(jnp.sum(lam[2] * lam[3])) + first)
    o = o1 - lam * o2
    o = o * jax.lax.rsqrt(jnp.mean(jnp.square(o), -1, keepdims=True)
                          + s["eps"]) * w[f"{pre}_subln"] * (1.0 - first)
    out = _mm(o.reshape(b, t, h * d), w[f"{pre}_o"], precision) \
        + w[f"{pre}_o_b"]
    return out, k, v


def gmu(hn, memory, w, precision="f32"):
    return _mm(memory * jax.nn.silu(_mm(hn, w["g_in"], precision)),
               w["g_out"], precision)


def mlp(hn, w, precision="f32"):
    u = _mm(hn, w["f_fc1"], precision)
    half = u.shape[-1] // 2
    return _mm(jax.nn.silu(u[..., :half]) * u[..., half:], w["f_fc2"],
               precision)


def _layer(x, w, side, *, letter, layer, s, precision, rows):
    """One layer: (stream, what it hands on).  ``side``: what earlier
    layers handed on, {"memory", "keys", "values"}."""
    hn = _ln(x, w["n1_g"], w["n1_b"], s["eps"])
    emitted = {}
    if letter in "MW":
        mixed, memory = mamba(hn, w, s, precision)
        if letter == "W":
            emitted = {"memory": memory}
    elif letter in "SF":
        mixed, k, v = attention(hn, w, s, layer,
                                s["window"] if letter == "S" else None,
                                precision, rows)
        if letter == "F":
            emitted = {"keys": k, "values": v}
    elif letter == "G":
        mixed = gmu(hn, side["memory"], w, precision)
    else:
        mixed = attention(hn, w, s, layer, None, precision, rows,
                          kv=(side["keys"], side["values"]), pre="c")[0]
    u = x + mixed
    return u + mlp(_ln(u, w["n2_g"], w["n2_b"], s["eps"]), w,
                   precision), emitted


_KIND = {"M": "m_", "W": "m_", "S": "a_", "F": "a_", "C": "c_", "G": "g_"}
_EVERY = ("n1_", "n2_", "f_")


def forward(weights, tokens, sizes, *, precision="f32", rows=512,
            remat=True):
    """tokens (B, T) -> logits (B, T, V held) float32."""
    if precision not in PRECISIONS:
        raise ValueError(f"precision must be one of {PRECISIONS}")
    s = sizes
    x = weights["embed"][tokens]
    seen, side = {}, {}
    for j, (letter, layer) in enumerate(zip(s["pattern"], s["layers"])):
        pre = _KIND[letter]
        i = seen.get(pre, 0)
        seen[pre] = i + 1
        w = {k: v[i] for k, v in weights.items() if k.startswith(pre)}
        w.update({k: v[j] for k, v in weights.items()
                  if k.startswith(_EVERY)})
        f = functools.partial(_layer, letter=letter, layer=layer, s=s,
                              precision=precision, rows=rows)
        x, emitted = (jax.checkpoint(f) if remat else f)(x, w, side)
        side = {**side, **emitted}
    x = _ln(x, weights["norm_f_g"], weights["norm_f_b"], s["eps"])
    return _mm(x, weights["embed"], precision)


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


def _parts(tree: dict, apart=None):
    """(name, (layers, ...) float32 array) of every part the comparison
    reads.  ``apart``: {leaf: [(name, lo, hi)]}, leaves read in parts
    along the axis after the layers' (the key bias, which no softmax
    sees, apart from the query and value biases it is stored with)."""
    for k, a in tree.items():
        a = a.astype(jnp.float32)
        if k in _TOP:
            yield k, a[None]
            continue
        for name, lo, hi in (apart or {}).get(k, [(k, 0, a.shape[1])]):
            yield name, a[:, lo:hi]


def leaf_norms(tree: dict, apart=None) -> dict:
    """L2 norm of every part (:func:`_parts`), per layer for stacked
    leaves: name -> list of floats."""
    return {name: [float(x) for x in jnp.sqrt(jnp.sum(
        jnp.square(a), axis=tuple(range(1, a.ndim))))]
        for name, a in _parts(tree, apart)}


@functools.partial(jax.jit, static_argnames=("apart_items",))
def _off_line(grads: dict, second: dict, apart_items):
    apart = {k: list(v) for k, v in apart_items}
    tiny = jnp.finfo(jnp.float32).tiny
    out = {}
    for (name, g), (_n, v) in zip(_parts(grads, apart),
                                  _parts(second, apart)):
        over = tuple(range(1, g.ndim))
        # a gradient that is rounding (the key bias's, 1e-10) squares to
        # the edge of float32: each side is scaled by its own largest
        g = jnp.abs(g) / jnp.maximum(
            jnp.max(jnp.abs(g), axis=over, keepdims=True), tiny)
        r = jnp.sqrt(v / jnp.maximum(jnp.max(v, axis=over, keepdims=True),
                                     tiny))
        out[name] = 1.0 - jnp.sum(g * r, axis=over) / jnp.maximum(
            jnp.sqrt(jnp.sum(g * g, axis=over))
            * jnp.sqrt(jnp.sum(r * r, axis=over)), tiny)
    return out


def off_line(grads: dict, second: dict, apart=None) -> dict:
    """How far a gradient's sizes, element for element, lie from those of
    all the gradients so far, whose squares Adam's second moment
    ``second`` averages: 1 - cos(|g|, sqrt(v)) of every part
    (``leaf_norms``' parts), name -> list of floats.  Nought to rounding
    where every gradient of the part is one and the same vector times a
    number."""
    items = tuple((k, tuple(v)) for k, v in sorted((apart or {}).items()))
    return {k: [float(x) for x in v]
            for k, v in _off_line(grads, second, items).items()}
