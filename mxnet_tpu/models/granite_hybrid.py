"""Dense hybrid Mamba-2 / attention decoders under the Granite family's
four multipliers (HF ``granitemoehybrid`` with ``num_local_experts`` 0).

With ``r`` the ``residual_multiplier``, decoder layer ``i`` is TWO residual
sublayers::

    a  = h + r * mixer_i(RMSNorm(h))
    h' = a + r * mlp(RMSNorm(a))

* ``mixer_i`` is :class:`~mxnet_tpu.models.nemotron_h.Mamba2Mixer` where
  ``layer_types[i] == "mamba"`` (the family runs it at ONE B/C group: all
  heads read one B and one C, and the gated norm is over the whole inner
  width) and :class:`~mxnet_tpu.models.nemotron_h.GroupedQueryAttention`
  where it is ``"attention"``: no positional encoding at all (``nope``;
  the mixers carry position), and ``attention_multiplier`` as the softmax
  scale in ``d^-1/2``'s place.
* ``mlp`` is the dense gated feed-forward: one ``input_linear`` to twice
  the hidden width, halved into gate and up in that order,
  ``(silu(gate) * up) output_linear``; no bias.

The stream starts at ``embedding_multiplier * E[ids]``; the head is TIED:
``logits = RMSNorm(h_L) E^T / logits_scaling``.  ``vocab_held`` rows of the
vocabulary are held here (a chip's share when the table is split by rows);
the loss is then the cross entropy over those rows.

A layer (both sublayers) is one block of ``run_blocks``, so a layer is the
unit of recomputation; the sublayers carry the named scopes ``mixer`` and
``mlp``.
"""
from __future__ import annotations

import jax

from ..gluon.block import HybridBlock
from ..gluon.nn import RMSNorm
from ..ops.flash import plan_event
from .hybrid_common import (HybridDecoder, TiedHead, dense as _dense, fused,
                            gated_mlp, lm_loss, rms as _rms)
from .nemotron_h import GroupedQueryAttention, Mamba2Mixer

__all__ = ["GraniteHybridModel", "GraniteHybridLayer", "gated_mlp",
           "get_granite_hybrid", "lm_loss"]

# name: the published sizes (config.json of the source), whole
_CONFIGS = {
    "granite_4_0_h_micro": dict(
        layer_types=tuple("attention" if i % 10 == 5 else "mamba"
                          for i in range(40)),
        vocab_size=100352, units=2048, num_heads=32, num_kv_heads=8,
        head_dim=64, mamba_heads=64, mamba_head_dim=64, mamba_groups=1,
        state_size=128, conv_kernel=4, chunk_size=256, mlp_hidden=8192,
        embedding_multiplier=12.0, residual_multiplier=0.22,
        attention_multiplier=0.015625, logits_scaling=8.0, eps=1e-5),
}


class GraniteHybridLayer(HybridBlock):
    """Both residual sublayers of one decoder layer."""

    def __init__(self, kind, cfg, dtype="float32", **kwargs):
        super().__init__(**kwargs)
        self.kind = kind
        self._eps, self._r = cfg["eps"], float(cfg["residual_multiplier"])
        u, f = cfg["units"], cfg["mlp_hidden"]
        self.norm1 = RMSNorm(epsilon=cfg["eps"], in_channels=u)
        if kind == "mamba":
            self.mixer = Mamba2Mixer(
                u, cfg["mamba_heads"], cfg["mamba_head_dim"],
                cfg["mamba_groups"], cfg["state_size"],
                conv_kernel=cfg["conv_kernel"],
                chunk_size=cfg["chunk_size"], eps=cfg["eps"], dtype=dtype)
        elif kind == "attention":
            self.mixer = GroupedQueryAttention(
                u, cfg["num_heads"], cfg["num_kv_heads"], cfg["head_dim"],
                scale=float(cfg["attention_multiplier"]), dtype=dtype)
        else:
            raise ValueError(f"layer type {kind!r} is not mamba or "
                             f"attention")
        self.norm2 = RMSNorm(epsilon=cfg["eps"], in_channels=u)
        self.mlp_in = self.params.get("mlp_in", shape=(2 * f, u),
                                      dtype=dtype, init="xavier")
        self.mlp_out = self.params.get("mlp_out", shape=(u, f),
                                       dtype=dtype, init="xavier")

    def forward(self, x, mask=None):
        mixer, eps, r = self.mixer, self._eps, self._r

        def body(xv, g1, g2, w_in, w_out, *ws, cd):
            with jax.named_scope("mixer"):
                a = xv + r * mixer.mix(_rms(xv, g1, eps), *ws,
                                       cd).astype(xv.dtype)
            with jax.named_scope("mlp"):
                return a + r * gated_mlp(_rms(a, g2, eps), w_in, w_out,
                                         cd).astype(xv.dtype)

        return fused(f"granite_{self.kind}_layer", body, x,
                     [self.norm1.gamma, self.norm2.gamma, self.mlp_in,
                      self.mlp_out] + mixer.params_in_order())


def _tied_logits(net, xv, gain, w, cd):
    return _dense(_rms(xv, gain, net._eps), w, cd) / net._logits


class GraniteHybridModel(HybridDecoder):
    """tokens (B, T) int32 -> logits (B, T, vocab_held) float32;
    ``layer_types`` is the list of kinds."""

    def __init__(self, layer_types, vocab_size, units, vocab_held=None,
                 remat=False, dtype="float32", **cfg):
        cfg = dict(cfg, units=units)
        layer_types = tuple(layer_types)
        super().__init__(
            ((f"l{i}", GraniteHybridLayer(kind, cfg, dtype=dtype))
             for i, kind in enumerate(layer_types)),
            RMSNorm, TiedHead("granite_tied_head", _tied_logits),
            vocab_size, units, cfg["eps"], vocab_held=vocab_held,
            remat=remat, dtype=dtype,
            embed_multiplier=float(cfg["embedding_multiplier"]))
        self.layer_types = layer_types
        self._logits = float(cfg["logits_scaling"])
        plan_event("granite.plan",
                   mamba_layers=layer_types.count("mamba"),
                   attention_layers=layer_types.count("attention"),
                   embedding_multiplier=self._emb,
                   residual_multiplier=float(cfg["residual_multiplier"]),
                   attention_multiplier=float(cfg["attention_multiplier"]),
                   logits_scaling=self._logits, vocab_held=self.vocab_held,
                   tied=True)


def get_granite_hybrid(name="granite_4_0_h_micro", **kwargs):
    """The published sizes of ``name``; keyword arguments replace them
    (``layer_types`` for fewer layers, ``vocab_held`` for one chip's rows
    of the tied table, small sizes for tests)."""
    cfg = dict(_CONFIGS[name])
    cfg.update(kwargs)
    return GraniteHybridModel(**cfg)
