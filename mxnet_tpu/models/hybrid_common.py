"""What the hybrid stacks (``nemotron_h``, ``qwen3_next``) compute alike:
the float32 RMS norm, the dense product in the compute type, and the
loss over the vocabulary rows a chip holds."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..ndarray import ops as F
from ..ops.flash import matmul_precision as _prec

__all__ = ["rms", "dense", "lm_loss"]


def rms(x, gain, eps, unit_offset=False):
    """RMS norm over the last axis in float32; the gain is ``gain``, or
    ``1 + gain`` with ``unit_offset``."""
    x = x.astype(jnp.float32)
    gain = gain.astype(jnp.float32)
    return (x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                              + eps) * (1.0 + gain if unit_offset else gain))


def dense(x, w, cd):
    """``x W^T`` with an (out, in) weight, operands in ``cd``, f32 sums."""
    return jnp.einsum("...i,oi->...o", x.astype(cd), w.astype(cd),
                      precision=_prec(cd),
                      preferred_element_type=jnp.float32)


def lm_loss(logits, labels):
    """Next-token cross entropy over the vocabulary rows held; labels
    (B, T) already shifted, every one of them a row held."""
    lse = F.logsumexp(logits, axis=-1)
    return (lse - F.pick(logits, labels, axis=-1)).mean()
