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

import jax
import jax.numpy as jnp

from .. import parallel as _par
from ..gluon.block import HybridBlock
from ..gluon.nn import Embedding, RMSNorm
from ..ndarray import ops as F
from ..ndarray.ops import invoke
from ..parallel.sharding import annotate
from .hybrid_common import dense as _dense, lm_loss, rms as _rms
from .moe import MoELayer, amp_compute_dtype as _compute_dtype

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


class GatedAttention(HybridBlock):
    """Causal attention as HF ``Qwen3NextAttention`` computes it:
    ``num_kv_heads`` <= ``num_heads``, no bias, q/k norm, partial rotary
    positions, an output gate from ``q_proj``."""

    def __init__(self, units, num_heads, num_kv_heads, head_dim,
                 partial_rotary_factor=1.0, rope_theta=10000.0, eps=1e-6,
                 dtype="float32", **kwargs):
        super().__init__(**kwargs)
        if num_heads % num_kv_heads:
            raise ValueError(f"{num_heads} query heads do not divide over "
                             f"{num_kv_heads} key/value heads")
        self._h, self._hk, self._d = num_heads, num_kv_heads, head_dim
        self._rotary_dim = int(head_dim * partial_rotary_factor)
        self._theta, self._eps = float(rope_theta), eps
        g = self.params.get
        # per head [query | gate]
        self.q_proj = g("q_proj", shape=(num_heads * head_dim * 2, units),
                        dtype=dtype, init="xavier")
        self.k_proj = g("k_proj", shape=(num_kv_heads * head_dim, units),
                        dtype=dtype, init="xavier")
        self.v_proj = g("v_proj", shape=(num_kv_heads * head_dim, units),
                        dtype=dtype, init="xavier")
        self.q_norm = g("q_norm", shape=(head_dim,), dtype=dtype,
                        init="zeros")
        self.k_norm = g("k_norm", shape=(head_dim,), dtype=dtype,
                        init="zeros")
        self.o_proj = g("o_proj", shape=(units, num_heads * head_dim),
                        dtype=dtype, init="xavier")

    def mix(self, hn, wq, wk, wv, q_gain, k_gain, wo, cd):
        from ..ops.attention import flash_attention, rotary_embedding
        b, t, _u = hn.shape
        h, hk, d = self._h, self._hk, self._d
        qg = _dense(hn, wq, cd).reshape(b, t, h, 2 * d)
        gate = qg[..., d:].reshape(b, t, h * d)

        def positioned(x, gain):
            return rotary_embedding(_rms(x, gain, self._eps, unit_offset=True),
                                    theta=self._theta,
                                    rotary_dim=self._rotary_dim).astype(cd)

        q = positioned(qg[..., :d], q_gain)
        k = positioned(_dense(hn, wk, cd).reshape(b, t, hk, d), k_gain)
        v = _dense(hn, wv, cd).astype(cd).reshape(b, t, hk, d)
        a = flash_attention(q, k, v, causal=True).astype(jnp.float32)
        return _dense(a.reshape(b, t, h * d) * jax.nn.sigmoid(gate), wo, cd)

    def params_in_order(self):
        return [self.q_proj, self.k_proj, self.v_proj, self.q_norm,
                self.k_norm, self.o_proj]


class MixerBlock(HybridBlock):
    """``x + mixer(norm(x))``: the first half of a decoder layer."""

    def __init__(self, kind, cfg, dtype="float32", **kwargs):
        super().__init__(**kwargs)
        self.kind = kind
        self._eps = cfg["eps"]
        u = cfg["units"]
        self.norm = RMSNorm(epsilon=cfg["eps"], in_channels=u,
                            unit_offset=True)
        if kind == "linear":
            self.mixer = GatedDeltaNet(
                u, cfg["linear_key_heads"], cfg["linear_value_heads"],
                cfg["linear_key_dim"], cfg["linear_value_dim"],
                conv_kernel=cfg["conv_kernel"],
                chunk_size=cfg["chunk_size"], eps=cfg["eps"], dtype=dtype)
        elif kind == "full":
            self.mixer = GatedAttention(
                u, cfg["num_heads"], cfg["num_kv_heads"], cfg["head_dim"],
                partial_rotary_factor=cfg["partial_rotary_factor"],
                rope_theta=cfg["rope_theta"], eps=cfg["eps"], dtype=dtype)
        else:
            raise ValueError(f"mixer kind {kind!r} is not linear or full")

    def forward(self, x, mask=None):
        mixer, eps = self.mixer, self._eps
        ps = mixer.params_in_order()

        def f(xv, gain, *ws):
            cd = _compute_dtype(xv)
            return xv + mixer.mix(_rms(xv, gain, eps, unit_offset=True), *ws,
                                  cd).astype(xv.dtype)

        name = "gdn_layer" if self.kind == "linear" else "gated_attn_layer"
        out = invoke(name, f, [x, self.norm.gamma.data()]
                     + [p.data() for p in ps])
        return _par.with_sharding_constraint(out, "batch", None, None)


class ExpertBlock(HybridBlock):
    """``x + experts(norm(x))``: the second half of a decoder layer."""

    def __init__(self, cfg, experts_held=None, record_choice_rows=0,
                 dtype="float32", **kwargs):
        super().__init__(**kwargs)
        self.norm = RMSNorm(epsilon=cfg["eps"], in_channels=cfg["units"],
                            unit_offset=True)
        self.moe = MoELayer(
            cfg["units"], cfg["expert_hidden"], cfg["num_experts"],
            top_k=cfg["top_k"], routing="dropless", scoring="softmax",
            expert_form="swiglu", experts_held=experts_held,
            shared_hidden=cfg["shared_hidden"], shared_gate=True,
            norm_topk=cfg["norm_topk"],
            record_choice_rows=record_choice_rows, dtype=dtype)

    def forward(self, x, mask=None):
        return x + self.moe(self.norm(x))


class Qwen3NextModel(HybridBlock):
    """tokens (B, T) int32 -> logits (B, T, vocab_held)."""

    def __init__(self, num_layers, full_attention_interval, vocab_size,
                 units, vocab_held=None, experts_held=None,
                 record_choice_rows=0, remat=False, dtype="float32", **cfg):
        super().__init__()
        cfg = dict(cfg, units=units)
        self.kinds = ["full" if (i + 1) % full_attention_interval == 0
                      else "linear" for i in range(num_layers)]
        self.vocab_size = vocab_size
        self.vocab_held = int(vocab_held or vocab_size)
        self._remat = remat
        self.embed = Embedding(self.vocab_held, units, dtype=dtype)
        annotate(self.embed.weight, "vocab", "embed")
        # a decoder layer is two blocks, each recomputed on its own
        self.blocks = []
        for i, kind in enumerate(self.kinds):
            halves = (MixerBlock(kind, cfg, dtype=dtype),
                      ExpertBlock(cfg, experts_held=experts_held,
                                  record_choice_rows=record_choice_rows,
                                  dtype=dtype))
            for half, name in zip(halves, ("mixer", "experts")):
                self.register_child(half, f"l{i}_{name}")
                self.blocks.append(half)
        self.norm_f = RMSNorm(epsilon=cfg["eps"], in_channels=units,
                              unit_offset=True)
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


def get_qwen3_next(name="qwen3_next_80b_a3b", **kwargs):
    """The published sizes of ``name``; keyword arguments replace them
    (``num_layers`` for fewer layers, ``experts_held=(first, count)`` and
    ``vocab_held`` for one chip's share, small sizes for tests)."""
    cfg = dict(_CONFIGS[name])
    cfg.update(kwargs)
    return Qwen3NextModel(**cfg)
