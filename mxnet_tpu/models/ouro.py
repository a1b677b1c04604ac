"""Looped decoders: ONE stack of sandwich-normed full-attention layers run
``total_ut_steps`` times on the same weights, with a head and an exit gate
after every pass (the Ouro family, HF ``ouro``; Zhu et al. 2025, "Scaling
Latent Reasoning via Looped Language Models").

Decoder layer ``l``, the same weights in every pass, is two residual
sublayers, each normalised BEFORE and AFTER its mixer (four RMS norms a
layer, plain gains)::

    x = x + RMSNorm(attention(RMSNorm(x)))
    x = x + RMSNorm(mlp(RMSNorm(x)))

* :class:`OuroAttention` — ``q_proj``, ``k_proj``, ``v_proj`` (as many
  key/value heads as the configuration says; the published model has as
  many as query heads), no bias, no q/k norm; rotary positions on all of a
  head's dimensions (``rope_theta``, the same positions in every pass);
  causal softmax attention over all earlier keys OF THIS PASS
  (:mod:`mxnet_tpu.ops.flash` on the TPU); ``o_proj``.
* :class:`~mxnet_tpu.models.hybrid_common.GatedMLP` — the dense gated
  feed-forward ``(silu(x Wg^T) * (x Wu^T)) Wd^T``, gate and up one
  ``gate_up`` matrix halved in that order.

Pass ``t``: ``h_t = RMSNorm_f(stack(h_{t-1}))``, the final norm closing
EVERY pass and its output entering the next; ``logits_t = h_t W_head^T``
(an UNTIED head over the ``vocab_held`` rows held here); ``lambda_t =
sigmoid(h_t . w_gate + b_gate)``.  ``net(tokens)`` is every pass's logits
and gates, ``net(tokens, labels)`` the objective ``sum_t p_t CE_t - beta
H(p)`` with ``p`` the exit distribution of the gates, a mean over the
tokens: :class:`~mxnet_tpu.models.hybrid_common.HybridDecoder` with
``passes``.  ``early_exit_threshold`` is read by decoding only, which is
not built.
"""
from __future__ import annotations

from ..gluon.nn import RMSNorm
from .hybrid_common import (GatedMLP, HalfLayer, HybridDecoder, OwnHead,
                            QKVOProjections, read_loop_counters, two_halves)

__all__ = ["OuroModel", "OuroAttention", "GatedMLP", "get_ouro",
           "read_loop_counters"]

# name: the published sizes (config.json of the source), whole
_CONFIGS = {
    "ouro_2p6b": dict(
        num_layers=48, vocab_size=49152, units=2048, num_heads=16,
        num_kv_heads=16, head_dim=128, mlp_hidden=5632, rope_theta=1000000,
        total_ut_steps=4, eps=1e-6,
        # no key of config.json: the paper's later-stage value
        exit_beta=0.05),
}


class OuroAttention(QKVOProjections):
    """Causal attention with rotary positions and no q/k norm."""

    def __init__(self, units, num_heads, num_kv_heads, head_dim, rope_theta,
                 dtype="float32", **kwargs):
        super().__init__(units, num_heads, num_kv_heads, head_dim,
                         dtype=dtype, **kwargs)
        self._theta = float(rope_theta)

    def mix(self, hn, wq, wk, wv, wo, cd):
        """The mixer on a normalised (B, T, U) input; pure ``jax``."""
        from ..ops.attention import flash_attention, rotary_embedding

        def turned(w, n):       # the turn reads the product's float32
            return rotary_embedding(self.heads(hn, w, n, cd, cast=False),
                                    theta=self._theta).astype(cd)

        q, k = turned(wq, self._h), turned(wk, self._hk)
        v = self.heads(hn, wv, self._hk, cd)
        return self.merged(flash_attention(q, k, v, causal=True), wo, cd)


class OuroModel(HybridDecoder):
    """tokens (B, T) int32 -> ``(logits (P, B, T, vocab_held), gates (P, B,
    T))``; with labels, the looped objective.  ``total_ut_steps`` is P.
    A layer is TWO blocks of ``run_blocks``, ``l{i}_mixer`` (attention)
    and ``l{i}_mlp``, each sandwich half-layer recomputed on its own."""

    def __init__(self, num_layers, vocab_size, units, total_ut_steps,
                 exit_beta, vocab_held=None, remat=False, dtype="float32",
                 **cfg):
        cfg = dict(cfg, units=units)
        super().__init__(
            two_halves(
                range(num_layers),
                lambda _: HalfLayer(
                    "ouro_attention_layer", cfg, OuroAttention(
                        units, cfg["num_heads"], cfg["num_kv_heads"],
                        cfg["head_dim"], cfg["rope_theta"], dtype=dtype),
                    post_norm=True),
                lambda _: HalfLayer(
                    "ouro_mlp_layer", cfg,
                    GatedMLP(units, cfg["mlp_hidden"], dtype=dtype),
                    post_norm=True),
                second="mlp"),
            RMSNorm, OwnHead(), vocab_size, units, cfg["eps"],
            vocab_held=vocab_held, remat=remat, dtype=dtype,
            passes=total_ut_steps, exit_beta=exit_beta)


def get_ouro(name="ouro_2p6b", **kwargs):
    """The published sizes of ``name``; keyword arguments replace them
    (``num_layers`` for one pipeline stage, ``vocab_held`` for one chip's
    rows, ``exit_beta``, small sizes for tests)."""
    cfg = dict(_CONFIGS[name])
    cfg.update(kwargs)
    return OuroModel(**cfg)
