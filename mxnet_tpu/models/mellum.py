"""Sliding-window / full attention expert decoders (the Mellum 2 family,
HF ``mellum``, whose keys are ``Qwen3MoeConfig``'s plus per-layer lists).

Decoder layer ``i``: ``x = x + attention_i(norm(x))``, then ``x = x +
experts(norm(x))``; ``norm`` is an RMS norm with a plain gain.

* :class:`SlidingGQAttention` — ``q_proj``, ``k_proj``, ``v_proj`` with
  fewer key/value than query heads, no bias; q and k RMS-normalised per
  head (plain gain); rotary positions on all of a head's dimensions BY
  THE TABLE OF THE LAYER'S KIND (``rope_parameters[layer_types[i]]``:
  :func:`~mxnet_tpu.ops.attention.rope_frequencies`, plain or YaRN);
  causal softmax attention (:mod:`mxnet_tpu.ops.flash` on the TPU), in a
  ``sliding_attention`` layer over the last ``sliding_window`` keys, in a
  ``full_attention`` layer over all; ``o_proj``.
* the experts — :class:`~mxnet_tpu.models.moe.MoELayer` with
  ``routing="dropless"``, softmax scores over all experts, the top k
  renormalised, SwiGLU experts and NO shared expert, told how many experts
  there are and which this chip holds.

Then a final norm and an UNTIED head.  ``vocab_held`` rows of the
vocabulary are held here (embedding and head); the loss is then the cross
entropy over those rows.  What a chip does not hold (experts, vocabulary
rows) is left out, never stood in for.  The family's multi-token
prediction head is in no configuration key and is not built.
"""
from __future__ import annotations

from ..gluon.nn import RMSNorm
from .hybrid_common import (ExpertBlock, HalfLayer, HybridDecoder, OwnHead,
                            QKVOProjections, lm_loss, positioned, two_halves)

__all__ = ["MellumModel", "SlidingGQAttention", "AttentionBlock",
           "ExpertBlock", "get_mellum", "lm_loss"]

_YARN_X16 = dict(rope_type="yarn", rope_theta=500000, factor=16,
                 original_max_position_embeddings=8192, beta_fast=32,
                 beta_slow=1, attention_factor=1.2772588722239782)

# name: the published sizes (config.json of the source), whole
_CONFIGS = {
    "mellum2_12b_a2p5b": dict(
        layer_types=("sliding_attention",) * 3 + ("full_attention",),
        num_layers=28, vocab_size=98304, units=2304, num_heads=32,
        num_kv_heads=4, head_dim=128, sliding_window=1024,
        rope_parameters=dict(
            full_attention=_YARN_X16,
            sliding_attention=dict(rope_type="default", rope_theta=500000)),
        num_experts=64, top_k=8, expert_hidden=896, norm_topk=True,
        eps=1e-6),
}


class SlidingGQAttention(QKVOProjections):
    """Causal grouped-query attention with q/k norm, under ``window`` keys
    (None: over everything), its rotary table from ``rope`` (one
    ``rope_parameters`` entry)."""

    def __init__(self, units, num_heads, num_kv_heads, head_dim, rope,
                 window=None, eps=1e-6, dtype="float32", **kwargs):
        super().__init__(units, num_heads, num_kv_heads, head_dim,
                         qk_norm="ones", dtype=dtype, **kwargs)
        self._rope, self._window, self._eps = dict(rope), window, eps

    def mix(self, hn, wq, wk, wv, q_gain, k_gain, wo, cd):
        """The mixer on a normalised (B, T, U) input; pure ``jax``."""
        from ..ops.attention import flash_attention, rope_frequencies
        inv_freq, amplitude = rope_frequencies(self._rope, self._d)

        def at(x, gain):
            return positioned(x, gain, self._eps, cd, inv_freq=inv_freq,
                              amplitude=amplitude)

        q = at(self.heads(hn, wq, self._h, cd, cast=False), q_gain)
        k = at(self.heads(hn, wk, self._hk, cd, cast=False), k_gain)
        v = self.heads(hn, wv, self._hk, cd)
        a = flash_attention(q, k, v, causal=True, window=self._window)
        return self.merged(a, wo, cd)


class AttentionBlock(HalfLayer):
    """``x + attention(norm(x))``: the first half of a decoder layer."""

    def __init__(self, kind, cfg, dtype="float32", **kwargs):
        if kind not in ("sliding_attention", "full_attention"):
            raise ValueError(f"layer type {kind!r} is not sliding_attention "
                             f"or full_attention")
        super().__init__(kind + "_layer", cfg, SlidingGQAttention(
            cfg["units"], cfg["num_heads"], cfg["num_kv_heads"],
            cfg["head_dim"], cfg["rope_parameters"][kind],
            window=(cfg["sliding_window"] if kind == "sliding_attention"
                    else None), eps=cfg["eps"], dtype=dtype), **kwargs)
        self.kind = kind


class MellumModel(HybridDecoder):
    """tokens (B, T) int32 -> logits (B, T, vocab_held).  ``layer_types``
    names every layer's kind, or one period that ``num_layers`` repeats."""

    def __init__(self, num_layers, layer_types, vocab_size, units,
                 vocab_held=None, experts_held=None, record_choice_rows=0,
                 remat=False, dtype="float32", **cfg):
        cfg = dict(cfg, units=units)
        period = tuple(layer_types)
        kinds = [period[i % len(period)] for i in range(num_layers)]
        super().__init__(
            two_halves(
                kinds, lambda kind: AttentionBlock(kind, cfg, dtype=dtype),
                lambda _: ExpertBlock(
                    cfg, scoring="softmax", expert_form="swiglu",
                    experts_held=experts_held, shared_hidden=0,
                    record_choice_rows=record_choice_rows, dtype=dtype)),
            RMSNorm, OwnHead(), vocab_size, units, cfg["eps"],
            vocab_held=vocab_held, remat=remat, dtype=dtype)
        self.kinds = kinds


def get_mellum(name="mellum2_12b_a2p5b", **kwargs):
    """The published sizes of ``name``; keyword arguments replace them
    (``num_layers`` for fewer layers, ``experts_held=(first, count)`` and
    ``vocab_held`` for one chip's share, small sizes for tests)."""
    cfg = dict(_CONFIGS[name])
    cfg.update(kwargs)
    return MellumModel(**cfg)
