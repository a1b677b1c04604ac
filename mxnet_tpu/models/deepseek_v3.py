"""Latent-attention expert decoders (the DeepSeek-V3 family, HF
``deepseek_v3``, whose keys Moonlight's configuration uses; DeepSeek-V2,
arXiv:2405.04434, for the attention; DeepSeek-V3, arXiv:2412.19437, for
the router).

Decoder layer ``l``: ``x = x + attention(norm(x))``, then ``x = x +
second_l(norm(x))``; ``norm`` is an RMS norm with a plain gain.

* :class:`LatentAttention` — keys and values come through ONE low-rank
  latent a token.  ``q_proj`` gives every head ``qk_nope + qk_rope``
  query dimensions; ``kv_a_proj_with_mqa`` gives the ``kv_lora_rank``
  latent and, beside it, ONE rotary key head of ``qk_rope`` dimensions
  that all query heads share; the latent is RMS-normalised
  (``kv_a_layernorm``) and ``kv_b_proj`` expands it to every head's
  ``qk_nope`` key and ``v_head`` value dimensions.  Rotary positions turn
  the ``qk_rope`` dimensions of the queries and the shared key, and only
  those.  A head's score is the SUM of two products of different widths,
  ``q_nope . k_nope + q_rope . k_rope`` over ``sqrt(qk_nope + qk_rope)``;
  causal softmax; times the head's values; ``o_proj``.  On the TPU the
  sum is made inside the flash kernels' tile
  (:func:`~mxnet_tpu.ops.flash.flash_attention` with ``q2`` / ``k2``),
  the shared key read at its one head, nothing broadcast or padded.
* the second half — layers ``0 .. first_k_dense - 1`` a dense SwiGLU of
  ``mlp_hidden`` (:class:`~mxnet_tpu.models.hybrid_common.GatedMLP`); the
  others :class:`~mxnet_tpu.models.moe.MoELayer` with
  ``routing="dropless"``: sigmoid scores, the top k chosen by score plus a
  correction buffer that is not trained, their weights renormalised and
  scaled, SwiGLU experts, and the shared experts as ONE SwiGLU of
  ``shared_hidden`` on every token with no gate; told how many experts
  there are and which this chip holds.

Then a final norm and an UNTIED head over the ``vocab_held`` rows held
here.  What a chip does not hold (experts, vocabulary rows) is left out,
never stood in for.  Not built: a low-rank QUERY projection
(``q_lora_rank``, null in Moonlight), group-limited routing (``n_group``
> 1), the step that moves the correction buffer, the sequence-wise
auxiliary loss, YaRN's amplitude on the softmax scale, multi-token
prediction, and the latent cache of decoding.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..gluon.block import HybridBlock
from ..gluon.nn import RMSNorm
from ..ops.flash import plan_event
from .hybrid_common import (ExpertBlock, GatedMLP, HalfLayer, HybridDecoder,
                            OwnHead, QKVOProjections, dense, lm_loss, rms,
                            two_halves)

__all__ = ["DeepseekV3Model", "LatentAttention", "get_deepseek_v3",
           "lm_loss"]

# name: the published sizes (config.json of the source), whole
_CONFIGS = {
    "moonlight_16b_a3b": dict(
        num_layers=27, first_k_dense=1, vocab_size=163840, units=2048,
        num_heads=16, qk_nope_dim=128, qk_rope_dim=64, v_head_dim=128,
        kv_lora_rank=512, rope_theta=50000, mlp_hidden=11264,
        num_experts=64, top_k=6, expert_hidden=1408, shared_hidden=2816,
        routed_scaling=2.446, norm_topk=True, eps=1e-5,
        # no key of config.json: HF builds kv_a_layernorm with its class
        # default, not rms_norm_eps
        latent_eps=1e-6),
}

class LatentAttention(HybridBlock):
    """Causal latent attention; the five parameters are HF's by name."""

    def __init__(self, units, num_heads, qk_nope_dim, qk_rope_dim,
                 v_head_dim, kv_lora_rank, rope_theta, latent_eps=1e-6,
                 dtype="float32", **kwargs):
        super().__init__(**kwargs)
        self._h, self._dn, self._dr = num_heads, qk_nope_dim, qk_rope_dim
        self._dv, self._r = v_head_dim, kv_lora_rank
        self._theta, self._eps = float(rope_theta), latent_eps
        g = self.params.get
        self.q_proj = g(
            "q_proj", shape=(num_heads * (qk_nope_dim + qk_rope_dim), units),
            dtype=dtype, init="xavier")
        self.kv_a_proj_with_mqa = g(
            "kv_a_proj_with_mqa", shape=(kv_lora_rank + qk_rope_dim, units),
            dtype=dtype, init="xavier")
        self.kv_a_layernorm = g("kv_a_layernorm", shape=(kv_lora_rank,),
                                dtype=dtype, init="ones")
        self.kv_b_proj = g(
            "kv_b_proj", dtype=dtype, init="xavier",
            shape=(num_heads * (qk_nope_dim + v_head_dim), kv_lora_rank))
        self.o_proj = g("o_proj", shape=(units, num_heads * v_head_dim),
                        dtype=dtype, init="xavier")

    def params_in_order(self):
        return [self.q_proj, self.kv_a_proj_with_mqa, self.kv_a_layernorm,
                self.kv_b_proj, self.o_proj]

    def mix(self, hn, wq, wa, latent_gain, wb, wo, cd):
        """The mixer on a normalised (B, T, U) input; pure ``jax``."""
        from ..ops.attention import flash_attention, rotary_embedding
        b, t = hn.shape[:2]
        h, dn, dr, dv, r = self._h, self._dn, self._dr, self._dv, self._r
        size = jnp.dtype(cd).itemsize
        plan_event("mla.plan", heads=h, dn=dn, dr=dr, dv=dv, r=r,
                   score_form="two_products",
                   compute_dtype=jnp.dtype(cd).name,
                   latent_bytes=b * t * (r + dr) * size,
                   expanded_bytes=b * t * h * (dn + dv) * size)

        def turned(x):          # the turn reads the product's float32
            return rotary_embedding(x, theta=self._theta).astype(cd)

        with jax.named_scope("mla_latent"):
            q = QKVOProjections.heads(hn, wq, h, cd, cast=False)
            qn, qr = q[..., :dn].astype(cd), turned(q[..., dn:])
            c = dense(hn, wa, cd)
            kr = turned(c[..., None, r:])               # ONE head
        with jax.named_scope("mla_expand"):
            kv = QKVOProjections.heads(
                rms(c[..., :r], latent_gain, self._eps), wb, h, cd)
            kn, v = kv[..., :dn], kv[..., dn:]
        with jax.named_scope("mla_scores"):
            a = flash_attention(qn, kn, v, q2=qr, k2=kr, causal=True,
                                scale=(dn + dr) ** -0.5)
        return QKVOProjections.merged(a, wo, cd)


class DeepseekV3Model(HybridDecoder):
    """tokens (B, T) int32 -> logits (B, T, vocab_held).  Layers ``0 ..
    first_k_dense - 1`` are ``l{i}_mixer`` + ``l{i}_mlp``, the others
    ``l{i}_mixer`` + ``l{i}_experts``: each half a block recomputed on its
    own.  Each trace of a forward leaves a ``deepseek.plan`` event: what
    is held here of the published stack."""

    def __init__(self, num_layers, first_k_dense, vocab_size, units,
                 vocab_held=None, experts_held=None, record_choice_rows=0,
                 remat=False, dtype="float32", **cfg):
        cfg = dict(cfg, units=units)
        dense_first = min(int(first_k_dense), num_layers)
        first, held = experts_held or (0, cfg["num_experts"])

        def second_half(i):
            if i < dense_first:
                return HalfLayer(
                    "deepseek_mlp_layer", cfg,
                    GatedMLP(units, cfg["mlp_hidden"], dtype=dtype))
            return ExpertBlock(
                cfg, scoring="sigmoid", expert_form="swiglu",
                shared_hidden=cfg["shared_hidden"],
                routed_scaling=cfg["routed_scaling"],
                experts_held=experts_held,
                record_choice_rows=record_choice_rows, dtype=dtype)

        super().__init__(
            two_halves(
                range(num_layers),
                lambda _: HalfLayer(
                    "latent_attention_layer", cfg, LatentAttention(
                        units, cfg["num_heads"], cfg["qk_nope_dim"],
                        cfg["qk_rope_dim"], cfg["v_head_dim"],
                        cfg["kv_lora_rank"], cfg["rope_theta"],
                        latent_eps=cfg["latent_eps"], dtype=dtype)),
                second_half,
                second=lambda i: "mlp" if i < dense_first else "experts"),
            RMSNorm, OwnHead(), vocab_size, units, cfg["eps"],
            vocab_held=vocab_held, remat=remat, dtype=dtype,
            plan=("deepseek.plan", dict(
                layers=num_layers, dense_first=dense_first,
                experts=cfg["num_experts"], first_expert=first,
                experts_held=held, vocab_rows=vocab_size,
                vocab_held=int(vocab_held or vocab_size))))
        self.first_k_dense = dense_first


def get_deepseek_v3(name="moonlight_16b_a3b", **kwargs):
    """The published sizes of ``name``; keyword arguments replace them
    (``num_layers`` for fewer layers, ``experts_held=(first, count)`` and
    ``vocab_held`` for one chip's share, small sizes for tests)."""
    cfg = dict(_CONFIGS[name])
    cfg.update(kwargs)
    return DeepseekV3Model(**cfg)
