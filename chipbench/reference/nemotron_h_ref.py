"""A hybrid Mamba-2 / expert / attention decoder (HF ``nemotron_h``) in
plain ``jax.numpy``: forward pass, next-token loss, gradients, float32
under ``highest`` matmul precision.  No kernels, no chunks, no sorting, no
grouped products.  Imports nothing of the system under test; Adam and the
rounding of the controls are ``gpt2_ref``'s.

Every layer is ``x + mixer(RMSNorm(x))``; then a final RMSNorm and an
untied head.  The mixers, as HF ``modeling_nemotron_h.py`` defines them:

* ``M`` (``NemotronHMamba2Mixer``): ``in_proj`` -> z, xBC, dt; causal
  depthwise conv of K taps with bias, SiLU; ``dt = softplus(dt + dt_bias)``
  clipped to ``time_step_limit``; ``A = -exp(A_log)``;
  ``h_t = exp(dt_t A) h_{t-1} + dt_t B_t x_t^T``, ``y_t = C_t h_t + D x_t``;
  ``GroupRMSNorm(y * silu(z))``; ``out_proj``.  The scan is computed BY ITS
  DEFINITION as the masked quadratic form, one head at a time:
  ``y_i = sum_{j<=i} exp(sum_{j<k<=i} dt_k A) (C_i . B_j) dt_j x_j``, one
  (T, T) matrix a head; :func:`scan_by_recurrence` is the step-by-step
  recurrence that a CPU test ties it to.
* ``E`` (``NemotronHMOE``): router logits in float32 over all experts,
  ``s = sigmoid``, choice by ``s + e_score_correction_bias`` (no gradient),
  top-k, weights ``s`` at the chosen over their sum, times
  ``routed_scaling_factor``; experts ``W_down relu(W_up x)^2`` by a plain
  loop over the experts held, each on every token under a mask; one shared
  expert of the same form, always added.
* ``*`` (``NemotronHAttention``): grouped-query causal softmax attention in
  blocks of rows, no bias, no rotary embedding.

Departures from the source, each also in the configuration file:
1. Only the experts and vocabulary rows THIS CHIP holds exist (the
   deployment's share): what the other experts would add to a token is
   left out and the partial result goes on; the loss is over the rows held.
2. The routing may FOLLOW given indices (``chosen``): top-k is discrete, so
   a program in another precision flips near-ties; the reference then
   computes weights from its own scores at the program's indices and counts
   the tokens whose own top-k set differs.
3. The second tower and the diffusion objective of the model's description
   are in no configuration key and are not built.
4. The running sum of ``dt A`` over the whole sequence is float32: at
   T = 8,192 an exponent carries an absolute error of up to about 5e-4 on
   the fastest-decaying heads (the chunked program sums 128 steps).

``precision`` as in ``gpt2_ref``: "f32" (the reference proper), "bf16",
"fp8": operands of every matrix product rounded, the router's and the
scan's included.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from chipbench.reference.gpt2_ref import PRECISIONS, adam_init
from chipbench.reference.gpt2_ref import _round as _round_cast

__all__ = ["loss_and_grads", "forward", "adam_init", "adam_step",
           "leaf_norms", "scan_quadratic", "scan_by_recurrence"]

HI = jax.lax.Precision.HIGHEST
_TOP = ("embed", "norm_f", "lm_head")


def _round(x, precision):
    """``gpt2_ref._round``, except that bfloat16 is rounded by
    ``reduce_precision``: the chip's compiler removes a float32 ->
    bfloat16 -> float32 pair of casts as excess precision it may keep, and
    the bf16 control then computes the float32 reference (my chip runs,
    PR 26: its loss gap read 9e-8)."""
    if precision != "bf16":
        return _round_cast(x, precision)
    q = jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
    return x + jax.lax.stop_gradient(q - x)


def _mm(x, w, precision):
    """x (..., in) times w (out, in) transposed."""
    return jnp.einsum("...i,oi->...o", _round(x, precision),
                      _round(w, precision), precision=HI)


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * g


def _relu2_mlp(x, w_up, w_down, precision):
    """(out, in) weights."""
    return _mm(jnp.square(jax.nn.relu(_mm(x, w_up, precision))), w_down,
               precision)


# ----------------------------------------------------------------- Mamba-2

def scan_quadratic(x, dt, a, bm, cm, precision="f32"):
    """One head: x (B, T, P), dt (B, T) > 0, a scalar < 0, bm / cm
    (B, T, N).  ``y_i = sum_{j<=i} exp(cum_i - cum_j) (C_i . B_j) dt_j x_j``
    with ``cum`` the running sum of ``dt a``: a (T, T) matrix."""
    cum = jnp.cumsum(dt * a, axis=1)                          # (B, T)
    t = x.shape[1]
    seg = cum[:, :, None] - cum[:, None, :]
    lower = jnp.tril(jnp.ones((t, t), bool))
    decay = jnp.exp(jnp.where(lower, seg, -jnp.inf))
    cb = jnp.einsum("bin,bjn->bij", _round(cm, precision),
                    _round(bm, precision), precision=HI)
    m = cb * decay * dt[:, None, :]
    return jnp.einsum("bij,bjp->bip", _round(m, precision),
                      _round(x, precision), precision=HI)


def scan_by_recurrence(x, dt, a, bm, cm):
    """The same head, one step at a time (the definition; tests)."""
    def step(h, xs):
        xt, dtt, bt, ct = xs                                  # (B, ...)
        h = (jnp.exp(dtt * a)[:, None, None] * h
             + (dtt[:, None] * bt)[:, :, None] * xt[:, None, :])
        return h, jnp.einsum("bn,bnp->bp", ct, h, precision=HI)

    h0 = jnp.zeros((x.shape[0], bm.shape[2], x.shape[2]), jnp.float32)
    _, y = jax.lax.scan(step, h0, (x.swapaxes(0, 1), dt.swapaxes(0, 1),
                                   bm.swapaxes(0, 1), cm.swapaxes(0, 1)))
    return y.swapaxes(0, 1)


def _conv(x, w, bias):
    """Causal depthwise conv: x (B, T, C), w (C, K)."""
    k, t = w.shape[1], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    return bias + sum(xp[:, i:i + t, :] * w[:, i] for i in range(k))


def _mamba(x, w, s, precision):
    b, t, _u = x.shape
    h, p, g, n = s["m_heads"], s["m_head_dim"], s["groups"], s["state"]
    d_inner, gn = h * p, g * n
    proj = _mm(_rms(x, w["m_norm"], s["eps"]), w["m_in_proj"], precision)
    z, xbc, dt = (proj[..., :d_inner], proj[..., d_inner:2 * d_inner + 2 * gn],
                  proj[..., 2 * d_inner + 2 * gn:])
    xbc = jax.nn.silu(_conv(xbc, w["m_conv_w"], w["m_conv_b"]))
    xs = xbc[..., :d_inner].reshape(b, t, h, p)
    bm = xbc[..., d_inner:d_inner + gn].reshape(b, t, g, n)
    cm = xbc[..., d_inner + gn:].reshape(b, t, g, n)
    dt = jax.nn.softplus(dt + w["m_dt_bias"])                 # (B, T, H)
    lo, hi = s.get("dt_limit", (0.0, None))
    dt = jnp.clip(dt, lo, hi)
    a = -jnp.exp(w["m_A_log"])
    hpg = h // g

    @jax.checkpoint
    def head(args):
        xh, dth, ah, bh, ch = args
        return scan_quadratic(xh, dth, ah, bh, ch, precision)

    y = jax.lax.map(head, (
        xs.transpose(2, 0, 1, 3), dt.transpose(2, 0, 1), a,
        jnp.repeat(bm, hpg, axis=2).transpose(2, 0, 1, 3),
        jnp.repeat(cm, hpg, axis=2).transpose(2, 0, 1, 3)))
    y = y.transpose(1, 2, 0, 3) + w["m_D"][:, None] * xs      # (B,T,H,P)
    y = y.reshape(b, t, d_inner) * jax.nn.silu(z)
    yg = y.reshape(b, t, g, d_inner // g)
    yg = yg * jax.lax.rsqrt(jnp.mean(jnp.square(yg), -1, keepdims=True)
                            + s["eps"])
    y = yg.reshape(b, t, d_inner) * w["m_norm_w"]
    return x + _mm(y, w["m_out_proj"], precision)


# --------------------------------------------------------------- attention

def _attention(x, w, s, precision, rows):
    b, t, _u = x.shape
    h, hk, d = s["heads"], s["kv_heads"], s["head_dim"]
    hn = _rms(x, w["a_norm"], s["eps"])
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
                        precision=HI) / (d ** 0.5)
        seen = (jnp.arange(t)[None, :]
                <= (start + jnp.arange(rows))[:, None])
        pr = jax.nn.softmax(jnp.where(seen, sc, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", _round(pr, precision), vr,
                          precision=HI)

    nb = t // rows
    qb = q.reshape(b, nb, rows, h, d).swapaxes(0, 1)
    a = jax.lax.map(block, (qb, jnp.arange(nb) * rows))
    a = a.swapaxes(0, 1).reshape(b, t, h * d)
    return x + _mm(a, w["a_o"], precision)


# ----------------------------------------------------------------- experts

def _experts(x, w, s, precision, chosen):
    """Returns (layer output, the choice used (N, k), tokens whose OWN
    top-k set differs from the choice used)."""
    b, t, u = x.shape
    k, first, held = s["top_k"], s["first_expert"], s["experts_held"]
    hn = _rms(x, w["e_norm"], s["eps"]).reshape(b * t, u)
    logits = _mm(hn, w["e_router"], precision)
    sc = jax.nn.sigmoid(logits)
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
    y = _relu2_mlp(hn, w["e_shared_up"], w["e_shared_down"], precision)
    for e in range(held):
        # this expert's weight for every token; zero where it was not chosen
        we = jnp.sum(jnp.where(chosen == first + e, wt, 0.0), axis=1)
        ye = _mm(jnp.square(jax.nn.relu(
            jnp.einsum("ni,io->no", _round(hn, precision),
                       _round(w["e_up"][e], precision), precision=HI))),
            w["e_down"][e].T, precision)
        y = y + we[:, None] * ye
    return x + y.reshape(b, t, u), chosen, differ


# ------------------------------------------------------------------ model

def _layer_weights(weights, kind, i):
    pre = {"M": "m_", "E": "e_", "*": "a_"}[kind]
    return {k: v[i] for k, v in weights.items() if k.startswith(pre)}


def forward(weights, tokens, sizes, *, precision="f32", chosen=None,
            rows=512, remat=True):
    """tokens (B, T) -> (logits (B, T, V held) float32, choices per E
    layer, differing tokens per E layer)."""
    if precision not in PRECISIONS:
        raise ValueError(f"precision must be one of {PRECISIONS}")
    s = sizes
    x = weights["embed"][tokens]
    seen = {"M": 0, "E": 0, "*": 0}
    used, differ = [], []
    for kind in s["pattern"]:
        w = _layer_weights(weights, kind, seen[kind])
        if kind == "M":
            f = functools.partial(_mamba, s=s, precision=precision)
            x = (jax.checkpoint(f) if remat else f)(x, w)
        elif kind == "*":
            f = functools.partial(_attention, s=s, precision=precision,
                                  rows=rows)
            x = (jax.checkpoint(f) if remat else f)(x, w)
        else:
            given = None if chosen is None else chosen[seen[kind]]
            f = functools.partial(_experts, s=s, precision=precision)
            x, c, d = (jax.checkpoint(f) if remat else f)(x, w, chosen=given)
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
    """Loss, gradients (zero for the buffer ``e_bias``), the choice each E
    layer used and how many tokens' own top-k set differs from it."""
    items = tuple(sorted((k, v) for k, v in sizes.items()))
    return _loss_and_grads(weights, tokens, labels, chosen,
                           sizes_items=items, precision=precision,
                           rows=int(rows))


@functools.partial(jax.jit, static_argnames=("t",), donate_argnums=(0, 2))
def adam_step(weights, grads, state, *, t: int, lr: float, beta1=0.9,
              beta2=0.999, eps=1e-8):
    """``gpt2_ref.adam_step`` (Adam as MXNet defines it: ``w -= lr
    sqrt(1-b2^t)/(1-b1^t) m / (sqrt(v) + eps)``) with its arguments
    donated: 16 B a parameter twice over would not fit the chip."""
    step = lr * (1.0 - beta2 ** t) ** 0.5 / (1.0 - beta1 ** t)
    tm = jax.tree_util.tree_map
    m = tm(lambda m, g: beta1 * m + (1 - beta1) * g, state["m"], grads)
    v = tm(lambda v, g: beta2 * v + (1 - beta2) * jnp.square(g),
           state["v"], grads)
    w = tm(lambda w, m, v: w - step * m / (jnp.sqrt(v) + eps), weights, m, v)
    return w, {"m": m, "v": v}


def leaf_norms(tree: dict, skip=()) -> dict:
    """L2 norm of every leaf, per layer for stacked leaves: name -> list
    of floats."""
    out = {}
    for k, a in tree.items():
        if k in skip:
            continue
        a = a.astype(jnp.float32)
        if k in _TOP:
            n = jnp.sqrt(jnp.sum(jnp.square(a)))[None]
        else:
            n = jnp.sqrt(jnp.sum(jnp.square(a),
                                 axis=tuple(range(1, a.ndim))))
        out[k] = [float(x) for x in n]
    return out
