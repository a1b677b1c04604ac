"""Hybrid Mamba-2 / expert / attention decoders (the Nemotron-H family).

A decoder is a string over three letters, one layer each, every layer
``x + mixer(RMSNorm(x))``:

* ``M`` — a Mamba-2 mixer (:class:`Mamba2Mixer`): ``in_proj`` to a gate
  ``z``, the convolved stream ``xBC`` and per-head steps ``dt``; a causal
  depthwise convolution of 4 taps and SiLU; the state-space scan in chunks
  (:mod:`mxnet_tpu.ops.ssd`); ``y * silu(z)`` under an RMS norm per group;
  ``out_proj``.
* ``E`` — routed ``relu(x W_up)^2 W_down`` experts with a shared expert,
  sigmoid scores and a biased top-k choice, dropless
  (:class:`~mxnet_tpu.models.moe.MoELayer` with ``routing="dropless"``),
  told how many experts there are and which this chip holds.
* ``*`` — causal attention with fewer key/value than query heads, no
  bias and no rotary embedding (the mixers carry position).

Then a final RMSNorm and an UNTIED head.  ``vocab_held`` rows of the
vocabulary are held here (embedding and head): a chip's share when the
vocabulary is split over chips; the loss is then the cross entropy over
those rows.  What a chip does not hold (experts, vocabulary rows) is left
out, never stood in for.

The family's published second tower (a denoiser for block-diffusion
decoding) is in no published configuration key and is not built.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..gluon.block import HybridBlock
from ..gluon.nn import RMSNorm
from .hybrid_common import (ExpertBlock, HalfLayer, HybridDecoder, OwnHead,
                            QKVOProjections, dense as _dense, lm_loss)

__all__ = ["NemotronHModel", "Mamba2Mixer", "GroupedQueryAttention",
           "HybridLayer", "get_nemotron_h", "lm_loss"]

# name: the published sizes (config.json of the source), whole
_CONFIGS = {
    "nemotron_h_tt_30b_a3b": dict(
        pattern="MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME",
        vocab_size=131072, units=2688, num_heads=32, num_kv_heads=2,
        head_dim=128, mamba_heads=64, mamba_head_dim=64, mamba_groups=8,
        state_size=128, conv_kernel=4, chunk_size=128, num_experts=128,
        top_k=6, expert_hidden=1856, shared_hidden=3712,
        routed_scaling=2.5, norm_topk=True, eps=1e-5),
}


class Mamba2Mixer(HybridBlock):
    """The Mamba-2 mixer as HF ``NemotronHMamba2Mixer`` computes it."""

    def __init__(self, units, num_heads, head_dim, n_groups, state_size,
                 conv_kernel=4, chunk_size=128, eps=1e-5,
                 time_step_limit=(0.0, float("inf")), dtype="float32",
                 **kwargs):
        super().__init__(**kwargs)
        self._h, self._p = num_heads, head_dim
        self._g, self._n = n_groups, state_size
        self._chunk, self._eps = chunk_size, eps
        self._dt_limit = tuple(time_step_limit)
        d_inner = num_heads * head_dim
        conv_dim = d_inner + 2 * n_groups * state_size
        self._d_inner, self._conv_dim = d_inner, conv_dim
        g = self.params.get
        self.in_proj = g("in_proj", dtype=dtype, init="xavier",
                         shape=(d_inner + conv_dim + num_heads, units))
        self.conv_weight = g("conv_weight", shape=(conv_dim, conv_kernel),
                             dtype=dtype, init="xavier")
        self.conv_bias = g("conv_bias", shape=(conv_dim,), dtype=dtype,
                           init="zeros")
        self.dt_bias = g("dt_bias", shape=(num_heads,), dtype=dtype,
                         init="zeros")
        self.A_log = g("A_log", shape=(num_heads,), dtype=dtype,
                       init="zeros")
        self.D = g("D", shape=(num_heads,), dtype=dtype, init="ones")
        self.norm_weight = g("norm_weight", shape=(d_inner,), dtype=dtype,
                             init="ones")
        self.out_proj = g("out_proj", shape=(units, d_inner), dtype=dtype,
                          init="xavier")

    def mix(self, hn, in_w, conv_w, conv_b, dt_b, a_log, d_skip, norm_w,
            out_w, cd):
        """The mixer on a normalised (B, T, U) input; pure ``jax``."""
        from ..ops.ssd import causal_conv1d, ssd_scan
        b, t, _u = hn.shape
        h, p, g, n = self._h, self._p, self._g, self._n
        f32 = jnp.float32
        proj = _dense(hn, in_w, cd)                           # f32
        z = proj[..., :self._d_inner]
        xbc = proj[..., self._d_inner:self._d_inner + self._conv_dim]
        dt = proj[..., self._d_inner + self._conv_dim:]
        xbc = jax.nn.silu(causal_conv1d(xbc, conv_w.astype(f32),
                                        conv_b.astype(f32)))
        x = xbc[..., :self._d_inner].reshape(b, t, h, p)
        bm = xbc[..., self._d_inner:self._d_inner + g * n]
        cm = xbc[..., self._d_inner + g * n:]
        dt = jax.nn.softplus(dt + dt_b.astype(f32))
        dt = jnp.clip(dt, self._dt_limit[0], self._dt_limit[1])
        a = -jnp.exp(a_log.astype(f32))
        y = ssd_scan(x.astype(cd), dt, a,
                     bm.reshape(b, t, g, n).astype(cd),
                     cm.reshape(b, t, g, n).astype(cd),
                     chunk=min(self._chunk, t))
        y = y + d_skip.astype(f32)[:, None] * x
        y = y.reshape(b, t, self._d_inner) * jax.nn.silu(z)
        yg = y.reshape(b, t, g, self._d_inner // g)
        yg = yg * jax.lax.rsqrt(jnp.mean(jnp.square(yg), -1, keepdims=True)
                                + self._eps)
        y = yg.reshape(b, t, self._d_inner) * norm_w.astype(f32)
        return _dense(y, out_w, cd)

    def params_in_order(self):
        return [self.in_proj, self.conv_weight, self.conv_bias,
                self.dt_bias, self.A_log, self.D, self.norm_weight,
                self.out_proj]


class GroupedQueryAttention(QKVOProjections):
    """Causal attention, ``num_kv_heads`` <= ``num_heads``, no bias, no
    rotary embedding.  ``scale`` multiplies the scores before the softmax
    (``head_dim ** -0.5`` when None)."""

    def __init__(self, units, num_heads, num_kv_heads, head_dim,
                 scale=None, dtype="float32", **kwargs):
        super().__init__(units, num_heads, num_kv_heads, head_dim,
                         dtype=dtype, **kwargs)
        self._scale = scale

    def mix(self, hn, wq, wk, wv, wo, cd):
        from ..ops.attention import flash_attention
        q = self.heads(hn, wq, self._h, cd)
        k = self.heads(hn, wk, self._hk, cd)
        v = self.heads(hn, wv, self._hk, cd)
        a = flash_attention(q, k, v, causal=True, scale=self._scale)
        return self.merged(a, wo, cd)


class HybridLayer(HalfLayer):
    """``x + mixer(RMSNorm(x))`` for an ``M`` or a ``*`` of the pattern
    (an ``E`` is :class:`~mxnet_tpu.models.hybrid_common.ExpertBlock`)."""

    def __init__(self, kind, cfg, dtype="float32", **kwargs):
        u = cfg["units"]
        if kind == "M":
            op, mixer = "mamba2_layer", Mamba2Mixer(
                u, cfg["mamba_heads"], cfg["mamba_head_dim"],
                cfg["mamba_groups"], cfg["state_size"],
                conv_kernel=cfg["conv_kernel"],
                chunk_size=cfg["chunk_size"], eps=cfg["eps"], dtype=dtype)
        elif kind == "*":
            op, mixer = "gqa_layer", GroupedQueryAttention(
                u, cfg["num_heads"], cfg["num_kv_heads"], cfg["head_dim"],
                dtype=dtype)
        else:
            raise ValueError(f"layer kind {kind!r} is not M or * (nor E, "
                             f"which is an ExpertBlock)")
        super().__init__(op, cfg, mixer, **kwargs)
        self.kind = kind


class NemotronHModel(HybridDecoder):
    """tokens (B, T) int32 -> logits (B, T, vocab_held); ``pattern`` is
    the list of kinds."""

    def __init__(self, pattern, vocab_size, units, vocab_held=None,
                 experts_held=None, record_choice_rows=0, remat=False,
                 dtype="float32", **cfg):
        cfg = dict(cfg, units=units)

        def block(kind):
            if kind != "E":
                return HybridLayer(kind, cfg, dtype=dtype)
            blk = ExpertBlock(
                cfg, experts="mixer", experts_held=experts_held,
                shared_hidden=cfg["shared_hidden"],
                routed_scaling=cfg["routed_scaling"],
                record_choice_rows=record_choice_rows, dtype=dtype)
            blk.kind = kind
            return blk

        super().__init__(
            ((f"l{i}", block(kind)) for i, kind in enumerate(pattern)),
            RMSNorm, OwnHead(), vocab_size, units, cfg["eps"],
            vocab_held=vocab_held, remat=remat, dtype=dtype)
        self.pattern = pattern


def get_nemotron_h(name="nemotron_h_tt_30b_a3b", **kwargs):
    """The published sizes of ``name``; keyword arguments replace them
    (``pattern`` for fewer layers, ``experts_held=(first, count)`` and
    ``vocab_held`` for one chip's share, small sizes for tests)."""
    cfg = dict(_CONFIGS[name])
    cfg.update(kwargs)
    return NemotronHModel(**cfg)
