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

from .. import parallel as _par
from ..gluon.block import HybridBlock
from ..gluon.nn import Embedding, RMSNorm
from ..ndarray import ops as F
from ..ndarray.ops import invoke
from ..parallel.sharding import annotate
from .hybrid_common import dense as _dense, lm_loss, rms as _rms
from .moe import MoELayer, amp_compute_dtype as _compute_dtype

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


class SlidingGQAttention(HybridBlock):
    """Causal grouped-query attention with q/k norm, under ``window`` keys
    (None: over everything), its rotary table from ``rope`` (one
    ``rope_parameters`` entry)."""

    def __init__(self, units, num_heads, num_kv_heads, head_dim, rope,
                 window=None, eps=1e-6, dtype="float32", **kwargs):
        super().__init__(**kwargs)
        if num_heads % num_kv_heads:
            raise ValueError(f"{num_heads} query heads do not divide over "
                             f"{num_kv_heads} key/value heads")
        self._h, self._hk, self._d = num_heads, num_kv_heads, head_dim
        self._rope, self._window, self._eps = dict(rope), window, eps
        g = self.params.get
        self.q_proj = g("q_proj", shape=(num_heads * head_dim, units),
                        dtype=dtype, init="xavier")
        self.k_proj = g("k_proj", shape=(num_kv_heads * head_dim, units),
                        dtype=dtype, init="xavier")
        self.v_proj = g("v_proj", shape=(num_kv_heads * head_dim, units),
                        dtype=dtype, init="xavier")
        self.q_norm = g("q_norm", shape=(head_dim,), dtype=dtype,
                        init="ones")
        self.k_norm = g("k_norm", shape=(head_dim,), dtype=dtype,
                        init="ones")
        self.o_proj = g("o_proj", shape=(units, num_heads * head_dim),
                        dtype=dtype, init="xavier")

    def mix(self, hn, wq, wk, wv, q_gain, k_gain, wo, cd):
        """The mixer on a normalised (B, T, U) input; pure ``jax``."""
        from ..ops.attention import (flash_attention, rope_frequencies,
                                     rotary_embedding)
        b, t, _u = hn.shape
        h, hk, d = self._h, self._hk, self._d
        inv_freq, amplitude = rope_frequencies(self._rope, d)

        def positioned(x, gain):
            return rotary_embedding(_rms(x, gain, self._eps),
                                    inv_freq=inv_freq,
                                    amplitude=amplitude).astype(cd)

        q = positioned(_dense(hn, wq, cd).reshape(b, t, h, d), q_gain)
        k = positioned(_dense(hn, wk, cd).reshape(b, t, hk, d), k_gain)
        v = _dense(hn, wv, cd).astype(cd).reshape(b, t, hk, d)
        a = flash_attention(q, k, v, causal=True, window=self._window)
        return _dense(a.reshape(b, t, h * d), wo, cd)

    def params_in_order(self):
        return [self.q_proj, self.k_proj, self.v_proj, self.q_norm,
                self.k_norm, self.o_proj]


class AttentionBlock(HybridBlock):
    """``x + attention(norm(x))``: the first half of a decoder layer."""

    def __init__(self, kind, cfg, dtype="float32", **kwargs):
        super().__init__(**kwargs)
        if kind not in ("sliding_attention", "full_attention"):
            raise ValueError(f"layer type {kind!r} is not sliding_attention "
                             f"or full_attention")
        self.kind = kind
        self._eps = cfg["eps"]
        self.norm = RMSNorm(epsilon=cfg["eps"], in_channels=cfg["units"])
        self.mixer = SlidingGQAttention(
            cfg["units"], cfg["num_heads"], cfg["num_kv_heads"],
            cfg["head_dim"], cfg["rope_parameters"][kind],
            window=(cfg["sliding_window"] if kind == "sliding_attention"
                    else None), eps=cfg["eps"], dtype=dtype)

    def forward(self, x, mask=None):
        mixer, eps = self.mixer, self._eps

        def f(xv, gain, *ws):
            cd = _compute_dtype(xv)
            return xv + mixer.mix(_rms(xv, gain, eps), *ws,
                                  cd).astype(xv.dtype)

        out = invoke(self.kind + "_layer", f, [x, self.norm.gamma.data()]
                     + [p.data() for p in mixer.params_in_order()])
        return _par.with_sharding_constraint(out, "batch", None, None)


class ExpertBlock(HybridBlock):
    """``x + experts(norm(x))``: the second half of a decoder layer."""

    def __init__(self, cfg, experts_held=None, record_choice_rows=0,
                 dtype="float32", **kwargs):
        super().__init__(**kwargs)
        self.norm = RMSNorm(epsilon=cfg["eps"], in_channels=cfg["units"])
        self.moe = MoELayer(
            cfg["units"], cfg["expert_hidden"], cfg["num_experts"],
            top_k=cfg["top_k"], routing="dropless", scoring="softmax",
            expert_form="swiglu", experts_held=experts_held,
            shared_hidden=0, norm_topk=cfg["norm_topk"],
            record_choice_rows=record_choice_rows, dtype=dtype)

    def forward(self, x, mask=None):
        return x + self.moe(self.norm(x))


class MellumModel(HybridBlock):
    """tokens (B, T) int32 -> logits (B, T, vocab_held).  ``layer_types``
    names every layer's kind, or one period that ``num_layers`` repeats."""

    def __init__(self, num_layers, layer_types, vocab_size, units,
                 vocab_held=None, experts_held=None, record_choice_rows=0,
                 remat=False, dtype="float32", **cfg):
        super().__init__()
        cfg = dict(cfg, units=units)
        period = tuple(layer_types)
        self.kinds = [period[i % len(period)] for i in range(num_layers)]
        self.vocab_size = vocab_size
        self.vocab_held = int(vocab_held or vocab_size)
        self._remat = remat
        self.embed = Embedding(self.vocab_held, units, dtype=dtype)
        annotate(self.embed.weight, "vocab", "embed")
        # a decoder layer is two blocks, each recomputed on its own
        self.blocks = []
        for i, kind in enumerate(self.kinds):
            halves = (AttentionBlock(kind, cfg, dtype=dtype),
                      ExpertBlock(cfg, experts_held=experts_held,
                                  record_choice_rows=record_choice_rows,
                                  dtype=dtype))
            for half, name in zip(halves, ("mixer", "experts")):
                self.register_child(half, f"l{i}_{name}")
                self.blocks.append(half)
        self.norm_f = RMSNorm(epsilon=cfg["eps"], in_channels=units)
        self.lm_head = self.params.get(
            "lm_head", shape=(self.vocab_held, units), dtype=dtype,
            init="xavier")
        annotate(self.lm_head, "vocab", "embed")

    def forward(self, tokens):
        from .transformer import run_blocks
        x = self.embed(tokens)
        x = _par.with_sharding_constraint(x, "batch", None, None)
        x = run_blocks(self.blocks, x, scan=False, remat=self._remat)
        x = self.norm_f(x)
        logits = F.FullyConnected(x, self.lm_head.data(), None,
                                  num_hidden=self.vocab_held, no_bias=True,
                                  flatten=False)
        return _par.with_sharding_constraint(logits, "batch", None, "vocab")


def get_mellum(name="mellum2_12b_a2p5b", **kwargs):
    """The published sizes of ``name``; keyword arguments replace them
    (``num_layers`` for fewer layers, ``experts_held=(first, count)`` and
    ``vocab_held`` for one chip's share, small sizes for tests)."""
    cfg = dict(_CONFIGS[name])
    cfg.update(kwargs)
    return MellumModel(**cfg)
