"""GPT-2 in plain ``jax.numpy``: forward pass, next-token loss, gradients
and Adam, float32 under ``highest`` matmul precision.  No kernels, no
cache, no batching tricks; layers run under ``lax.scan`` only so that the
program is small.  Imports nothing of the system under test.

Follows Radford et al. 2019 / the OpenAI ``gpt-2`` model.py: pre-LN
blocks, learned positions, tied output head, causal softmax attention with
1/sqrt(head_dim).  Departure, stated in the configuration files under
``assumed``: the activation is the exact (erf) GELU the system computes,
where OpenAI's code uses the tanh approximation.

``precision`` selects the arithmetic of every matrix product, for the
control that must fail the comparison:
  "f32"  float32 operands, ``highest`` (the reference proper)
  "bf16" operands rounded to bfloat16, float32 accumulation
  "fp8"  operands rounded to float8_e4m3fn (per-tensor scale), float32
         accumulation; gradients pass straight through the rounding
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

PRECISIONS = ("f32", "bf16", "fp8")


def _round(x, precision):
    """Round to the lower precision's grid, straight through for the
    gradient.  fp8 is scaled per tensor so that its largest magnitude
    sits at the top of e4m3's range (448), as fp8 recipes do; without
    the scale small tensors would flush to zero and the control would
    fail for a reason nobody is tempted by."""
    if precision == "f32":
        return x
    if precision == "bf16":
        q = x.astype(jnp.bfloat16).astype(jnp.float32)
    else:
        s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
        q = (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    return x + jax.lax.stop_gradient(q - x)


def _mm(x, w, precision):
    """x (..., in) times w (out, in) transposed."""
    return jnp.einsum("...i,oi->...o", _round(x, precision),
                      _round(w, precision),
                      precision=jax.lax.Precision.HIGHEST)


def _ln(x, g, b, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * g + b


def _block(x, w, n_head, eps, precision):
    b, t, u = x.shape
    d = u // n_head
    h = _ln(x, w["ln1_g"], w["ln1_b"], eps)
    q = (_mm(h, w["q_w"], precision) + w["q_b"]).reshape(b, t, n_head, d)
    k = (_mm(h, w["k_w"], precision) + w["k_b"]).reshape(b, t, n_head, d)
    v = (_mm(h, w["v_w"], precision) + w["v_b"]).reshape(b, t, n_head, d)
    s = jnp.einsum("bqhd,bkhd->bhqk", _round(q, precision),
                   _round(k, precision),
                   precision=jax.lax.Precision.HIGHEST) / (d ** 0.5)
    causal = jnp.tril(jnp.ones((t, t), bool))
    s = jnp.where(causal[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    a = jnp.einsum("bhqk,bkhd->bqhd", _round(p, precision),
                   _round(v, precision),
                   precision=jax.lax.Precision.HIGHEST).reshape(b, t, u)
    x = x + _mm(a, w["o_w"], precision) + w["o_b"]
    h = _ln(x, w["ln2_g"], w["ln2_b"], eps)
    h = jax.nn.gelu(_mm(h, w["fc1_w"], precision) + w["fc1_b"],
                    approximate=False)
    return x + _mm(h, w["fc2_w"], precision) + w["fc2_b"]


_LAYER_KEYS = ("ln1_g", "ln1_b", "q_w", "q_b", "k_w", "k_b", "v_w", "v_b",
               "o_w", "o_b", "ln2_g", "ln2_b", "fc1_w", "fc1_b", "fc2_w",
               "fc2_b")


def forward(weights: dict, tokens, *, n_head: int, eps: float = 1e-5,
            precision: str = "f32", remat: bool = False):
    """tokens (B, T) int32 -> logits (B, T, V) float32."""
    if precision not in PRECISIONS:
        raise ValueError(f"precision must be one of {PRECISIONS}")
    w = {k: v.astype(jnp.float32) for k, v in weights.items()}
    t = tokens.shape[1]
    x = w["wte"][tokens] + w["wpe"][:t][None]
    layers = {k: w[k] for k in _LAYER_KEYS}
    body = functools.partial(_block, n_head=n_head, eps=eps,
                             precision=precision)
    if remat:
        body = jax.checkpoint(body)

    def step(x, lw):
        return body(x, lw), None

    x, _ = jax.lax.scan(step, x, layers)
    x = _ln(x, w["lnf_g"], w["lnf_b"], eps)
    return _mm(x, w["wte"], precision)


def loss_fn(weights: dict, tokens, labels, **kw):
    """Mean next-token cross entropy; ``labels`` (B, T) already shifted."""
    logits = forward(weights, tokens, **kw)
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - picked)


def loss_and_grads(weights: dict, tokens, labels, *, rows_per_block: int,
                   **kw):
    """Loss and gradients over the whole batch, computed in blocks of
    rows so that the float32 activations fit beside nothing else.  The
    mean over the batch is the mean of equal blocks' means."""
    n = tokens.shape[0]
    if n % rows_per_block:
        raise ValueError(f"{n} rows do not split into blocks of "
                         f"{rows_per_block}")
    vg = jax.jit(jax.value_and_grad(
        functools.partial(loss_fn, remat=True, **kw)))
    nblk = n // rows_per_block
    total, acc = 0.0, None
    for i in range(nblk):
        sl = slice(i * rows_per_block, (i + 1) * rows_per_block)
        l, g = vg(weights, tokens[sl], labels[sl])
        total = total + l / nblk
        g = jax.tree_util.tree_map(lambda a: a / nblk, g)
        acc = g if acc is None else jax.tree_util.tree_map(jnp.add, acc, g)
    return total, acc


def adam_init(weights: dict):
    zeros = lambda: jax.tree_util.tree_map(jnp.zeros_like, weights)
    return {"m": zeros(), "v": zeros()}


@functools.partial(jax.jit, static_argnames=("t",))
def adam_step(weights, grads, state, *, t: int, lr: float, beta1=0.9,
              beta2=0.999, eps=1e-8):
    """One Adam step as MXNet defines it (optimizer ``adam``): the bias
    corrections fold into the step size, epsilon sits outside them:
    w -= lr sqrt(1-b2^t)/(1-b1^t) m / (sqrt(v) + eps)."""
    step = lr * (1.0 - beta2 ** t) ** 0.5 / (1.0 - beta1 ** t)
    m = jax.tree_util.tree_map(lambda m, g: beta1 * m + (1 - beta1) * g,
                               state["m"], grads)
    v = jax.tree_util.tree_map(
        lambda v, g: beta2 * v + (1 - beta2) * jnp.square(g),
        state["v"], grads)
    w = jax.tree_util.tree_map(
        lambda w, m, v: w - step * m / (jnp.sqrt(v) + eps), weights, m, v)
    return w, {"m": m, "v": v}


def leaf_norms(tree: dict) -> dict:
    """L2 norm of every leaf, per layer for stacked leaves: name ->
    list of floats."""
    out = {}
    for k, a in tree.items():
        a = a.astype(jnp.float32)
        if k in _LAYER_KEYS:
            n = jnp.sqrt(jnp.sum(jnp.square(a),
                                 axis=tuple(range(1, a.ndim))))
        else:
            n = jnp.sqrt(jnp.sum(jnp.square(a)))[None]
        out[k] = [float(x) for x in n]
    return out


def teacher_forced_gaps(weights: dict, sequences, prompt_lens, *,
                        n_head: int, eps: float = 1e-5,
                        pad_to: int, control: str | None = None):
    """For each served sequence (prompt + served tokens), one reference
    pass over it; per sequence the widest gap by which a served token's
    logit lies below the reference's best at its position, as a share of
    the largest |logit| there (``widest``), with the sum of those gaps,
    the number of served tokens that are not the reference's best and
    the number of served tokens.  With ``control`` set, the token judged at
    each position is the one a pass in that lower precision puts first,
    in place of the served one.  Causal attention: right padding cannot
    reach an earlier position."""
    import numpy as onp

    @jax.jit
    def one(weights, tokens, judged, lo, hi):
        logits = forward(weights, tokens[None], n_head=n_head, eps=eps)[0]
        if control is not None:
            low = forward(weights, tokens[None], n_head=n_head, eps=eps,
                          precision=control)[0]
            judged = jnp.argmax(low, axis=-1).astype(jnp.int32)
        best = jnp.max(logits, axis=-1)
        got = jnp.take_along_axis(logits, judged[:, None], axis=-1)[:, 0]
        share = (best - got) / jnp.max(jnp.abs(logits), axis=-1)
        pos = jnp.arange(tokens.shape[0])
        live = (pos >= lo) & (pos < hi)
        share = jnp.where(live, share, 0.0)
        return jnp.max(share), jnp.sum(share), jnp.sum(share > 0)

    out = []
    for seq, plen in zip(sequences, prompt_lens):
        seq = onp.asarray(seq, "int32")
        tokens = onp.zeros((pad_to,), "int32")
        tokens[:len(seq)] = seq
        judged = onp.zeros((pad_to,), "int32")
        judged[:len(seq) - 1] = seq[1:]   # position i predicts token i+1
        widest, total, below = one(weights, jnp.asarray(tokens),
                                   jnp.asarray(judged), plen - 1,
                                   len(seq) - 1)
        out.append({"widest": float(widest), "sum": float(total),
                    "below_best": int(below), "tokens": len(seq) - plen})
    return out
