"""Hybrid Gated DeltaNet / gated attention / sparse expert decoders (the
Qwen3-Next family, HF ``qwen3_next``).

Decoder layer ``i``: ``x = x + mixer_i(norm(x))``, then ``x = x +
experts(norm(x))``; ``norm`` is an RMS norm with gain ``1 + w`` (``w``
starts at zero).  ``mixer_i`` is full attention when ``(i + 1) %
full_attention_interval == 0`` and Gated DeltaNet otherwise:

* :class:`GatedDeltaNet` — ``in_proj`` to q, k, v and an output gate z,
  and to per-head ``b``, ``a``; a causal depthwise convolution of 4 taps
  (no bias) and SiLU over q, k, v; q and k L2-normalised per head, q
  scaled by ``d_k^-1/2``; ``beta = sigmoid(b)``, ``g = -exp(A_log)
  softplus(a + dt_bias)``; the gated delta rule in chunks
  (:mod:`mxnet_tpu.ops.gdn`); an RMS norm per head (plain gain) times
  ``silu(z)``; ``out_proj``.
* :class:`GatedAttention` — ``q_proj`` to a query and an output gate per
  head, fewer key/value than query heads, no bias; q and k RMS-normalised
  per head (``1 + w``); rotary positions on the first
  ``partial_rotary_factor`` of each head's dimensions; causal softmax
  attention (:mod:`mxnet_tpu.ops.flash` on the TPU); the output times
  ``sigmoid(gate)``; ``o_proj``.
* the experts — :class:`~mxnet_tpu.models.moe.MoELayer` with
  ``routing="dropless"``, softmax scores, SwiGLU experts and one shared
  expert behind a sigmoid gate, told how many experts there are and which
  this chip holds.

Then a final norm and an UNTIED head.  ``vocab_held`` rows of the
vocabulary are held here (embedding and head); the loss is then the cross
entropy over those rows.  What a chip does not hold (experts, vocabulary
rows) is left out, never stood in for.  The family's multi-token
prediction head is in no configuration key and is not built.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..gluon.block import HybridBlock
from ..gluon.nn import RMSNorm
from .hybrid_common import (ExpertBlock, HalfLayer, HybridDecoder, OwnHead,
                            QKVOProjections, dense as _dense, lm_loss,
                            positioned, rms as _rms, two_halves)

__all__ = ["Qwen3NextModel", "GatedDeltaNet", "GatedAttention",
           "MixerBlock", "ExpertBlock", "get_qwen3_next", "lm_loss"]

# name: the published sizes (config.json of the source), whole
_CONFIGS = {
    "qwen3_next_80b_a3b": dict(
        num_layers=48, full_attention_interval=4, vocab_size=151936,
        units=2048, num_heads=16, num_kv_heads=2, head_dim=256,
        partial_rotary_factor=0.25, rope_theta=1e7, linear_key_heads=16,
        linear_value_heads=32, linear_key_dim=128, linear_value_dim=128,
        conv_kernel=4, chunk_size=64, num_experts=512, top_k=10,
        expert_hidden=512, shared_hidden=512, norm_topk=True, eps=1e-6),
}


class GatedDeltaNet(HybridBlock):
    """The linear-attention mixer as HF ``Qwen3NextGatedDeltaNet`` computes
    it (its interleaving of the projections' outputs by head is layout:
    here they are ``[q | k | v | z]`` and ``[b | a]``)."""

    def __init__(self, units, key_heads, value_heads, key_dim, value_dim,
                 conv_kernel=4, chunk_size=64, eps=1e-6, dtype="float32",
                 **kwargs):
        super().__init__(**kwargs)
        self._hk, self._hv = key_heads, value_heads
        self._dk, self._dv = key_dim, value_dim
        self._chunk, self._eps = chunk_size, eps
        kd, vd = key_heads * key_dim, value_heads * value_dim
        self._kd, self._vd = kd, vd
        g = self.params.get
        self.in_proj_qkvz = g("in_proj_qkvz", shape=(2 * kd + 2 * vd, units),
                              dtype=dtype, init="xavier")
        self.in_proj_ba = g("in_proj_ba", shape=(2 * value_heads, units),
                            dtype=dtype, init="xavier")
        self.conv_weight = g("conv_weight", shape=(2 * kd + vd, conv_kernel),
                             dtype=dtype, init="xavier")
        self.dt_bias = g("dt_bias", shape=(value_heads,), dtype=dtype,
                         init="ones")
        self.A_log = g("A_log", shape=(value_heads,), dtype=dtype,
                       init="zeros")
        self.norm_weight = g("norm_weight", shape=(value_dim,), dtype=dtype,
                             init="ones")
        self.out_proj = g("out_proj", shape=(units, vd), dtype=dtype,
                          init="xavier")

    def mix(self, hn, w_qkvz, w_ba, conv_w, dt_b, a_log, norm_w, w_out, cd):
        """The mixer on a normalised (B, T, U) input; pure ``jax``."""
        from ..ops.gdn import gdn_scan
        from ..ops.ssd import causal_conv1d
        b, t, _u = hn.shape
        hk, hv, dk, dv = self._hk, self._hv, self._dk, self._dv
        kd, vd = self._kd, self._vd
        f32 = jnp.float32
        proj = _dense(hn, w_qkvz, cd)                         # f32
        ba = _dense(hn, w_ba, cd)
        qkv = jax.nn.silu(causal_conv1d(proj[..., :2 * kd + vd],
                                        conv_w.astype(f32), None))
        z = proj[..., 2 * kd + vd:].reshape(b, t, hv, dv)

        def unit(x):              # L2 norm over a head's dimensions
            return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), -1,
                                             keepdims=True) + 1e-6)

        q = unit(qkv[..., :kd].reshape(b, t, hk, dk)) * dk ** -0.5
        k = unit(qkv[..., kd:2 * kd].reshape(b, t, hk, dk))
        v = qkv[..., 2 * kd:].reshape(b, t, hv, dv)
        beta = jax.nn.sigmoid(ba[..., :hv])
        g = -jnp.exp(a_log.astype(f32)) * jax.nn.softplus(
            ba[..., hv:] + dt_b.astype(f32))
        o = gdn_scan(q.astype(cd), k.astype(cd), v.astype(cd), g, beta,
                     chunk=min(self._chunk, t))
        y = _rms(o, norm_w, self._eps) * jax.nn.silu(z)
        return _dense(y.reshape(b, t, vd), w_out, cd)

    def params_in_order(self):
        return [self.in_proj_qkvz, self.in_proj_ba, self.conv_weight,
                self.dt_bias, self.A_log, self.norm_weight, self.out_proj]


class GatedAttention(QKVOProjections):
    """Causal attention as HF ``Qwen3NextAttention`` computes it:
    ``num_kv_heads`` <= ``num_heads``, no bias, q/k norm, partial rotary
    positions, an output gate from ``q_proj`` (per head [query | gate])."""

    def __init__(self, units, num_heads, num_kv_heads, head_dim,
                 partial_rotary_factor=1.0, rope_theta=10000.0, eps=1e-6,
                 dtype="float32", **kwargs):
        super().__init__(units, num_heads, num_kv_heads, head_dim, q_width=2,
                         qk_norm="zeros", dtype=dtype, **kwargs)
        self._rotary_dim = int(head_dim * partial_rotary_factor)
        self._theta, self._eps = float(rope_theta), eps

    def mix(self, hn, wq, wk, wv, q_gain, k_gain, wo, cd):
        from ..ops.attention import flash_attention
        b, t, _u = hn.shape
        h, hk, d = self._h, self._hk, self._d
        qg = self.heads(hn, wq, h, cd, cast=False)
        gate = qg[..., d:].reshape(b, t, h * d)

        def at(x, gain):
            return positioned(x, gain, self._eps, cd, unit_offset=True,
                              theta=self._theta, rotary_dim=self._rotary_dim)

        q = at(qg[..., :d], q_gain)
        k = at(self.heads(hn, wk, hk, cd, cast=False), k_gain)
        v = self.heads(hn, wv, hk, cd)
        a = flash_attention(q, k, v, causal=True).astype(jnp.float32)
        return _dense(a.reshape(b, t, h * d) * jax.nn.sigmoid(gate), wo, cd)


class MixerBlock(HalfLayer):
    """``x + mixer(norm(x))``: the first half of a decoder layer."""

    def __init__(self, kind, cfg, dtype="float32", **kwargs):
        u = cfg["units"]
        if kind == "linear":
            op, mixer = "gdn_layer", GatedDeltaNet(
                u, cfg["linear_key_heads"], cfg["linear_value_heads"],
                cfg["linear_key_dim"], cfg["linear_value_dim"],
                conv_kernel=cfg["conv_kernel"],
                chunk_size=cfg["chunk_size"], eps=cfg["eps"], dtype=dtype)
        elif kind == "full":
            op, mixer = "gated_attn_layer", GatedAttention(
                u, cfg["num_heads"], cfg["num_kv_heads"], cfg["head_dim"],
                partial_rotary_factor=cfg["partial_rotary_factor"],
                rope_theta=cfg["rope_theta"], eps=cfg["eps"], dtype=dtype)
        else:
            raise ValueError(f"mixer kind {kind!r} is not linear or full")
        super().__init__(op, cfg, mixer, unit_offset=True, **kwargs)
        self.kind = kind


class Qwen3NextModel(HybridDecoder):
    """tokens (B, T) int32 -> logits (B, T, vocab_held); every
    ``full_attention_interval``-th of ``num_layers`` is full attention."""

    def __init__(self, num_layers, full_attention_interval, vocab_size,
                 units, vocab_held=None, experts_held=None,
                 record_choice_rows=0, remat=False, dtype="float32", **cfg):
        cfg = dict(cfg, units=units)
        kinds = ["full" if (i + 1) % full_attention_interval == 0
                 else "linear" for i in range(num_layers)]
        super().__init__(
            two_halves(
                kinds, lambda kind: MixerBlock(kind, cfg, dtype=dtype),
                lambda _: ExpertBlock(
                    cfg, unit_offset=True, scoring="softmax",
                    expert_form="swiglu", experts_held=experts_held,
                    shared_hidden=cfg["shared_hidden"], shared_gate=True,
                    record_choice_rows=record_choice_rows, dtype=dtype)),
            functools.partial(RMSNorm, unit_offset=True), OwnHead(),
            vocab_size, units, cfg["eps"], vocab_held=vocab_held,
            remat=remat, dtype=dtype)
        self.kinds = kinds


def get_qwen3_next(name="qwen3_next_80b_a3b", **kwargs):
    """The published sizes of ``name``; keyword arguments replace them
    (``num_layers`` for fewer layers, ``experts_held=(first, count)`` and
    ``vocab_held`` for one chip's share, small sizes for tests)."""
    cfg = dict(_CONFIGS[name])
    cfg.update(kwargs)
    return Qwen3NextModel(**cfg)
