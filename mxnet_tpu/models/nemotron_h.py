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

from .. import parallel as _par
from ..gluon.block import HybridBlock
from ..gluon.nn import Embedding, RMSNorm
from ..ndarray import ops as F
from ..ndarray.ops import invoke
from ..parallel.sharding import annotate
from .hybrid_common import dense as _dense, lm_loss, rms as _rms
from .moe import MoELayer, amp_compute_dtype as _compute_dtype

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


class GroupedQueryAttention(HybridBlock):
    """Causal attention, ``num_kv_heads`` <= ``num_heads``, no bias, no
    rotary embedding.  ``scale`` multiplies the scores before the softmax
    (``head_dim ** -0.5`` when None)."""

    def __init__(self, units, num_heads, num_kv_heads, head_dim,
                 scale=None, dtype="float32", **kwargs):
        super().__init__(**kwargs)
        self._scale = scale
        if num_heads % num_kv_heads:
            raise ValueError(f"{num_heads} query heads do not divide over "
                             f"{num_kv_heads} key/value heads")
        self._h, self._hk, self._d = num_heads, num_kv_heads, head_dim
        g = self.params.get
        self.q_proj = g("q_proj", shape=(num_heads * head_dim, units),
                        dtype=dtype, init="xavier")
        self.k_proj = g("k_proj", shape=(num_kv_heads * head_dim, units),
                        dtype=dtype, init="xavier")
        self.v_proj = g("v_proj", shape=(num_kv_heads * head_dim, units),
                        dtype=dtype, init="xavier")
        self.o_proj = g("o_proj", shape=(units, num_heads * head_dim),
                        dtype=dtype, init="xavier")

    def mix(self, hn, wq, wk, wv, wo, cd):
        from ..ops.attention import flash_attention
        b, t, _u = hn.shape
        q = _dense(hn, wq, cd).astype(cd).reshape(b, t, self._h, self._d)
        k = _dense(hn, wk, cd).astype(cd).reshape(b, t, self._hk, self._d)
        v = _dense(hn, wv, cd).astype(cd).reshape(b, t, self._hk, self._d)
        a = flash_attention(q, k, v, causal=True, scale=self._scale)
        return _dense(a.reshape(b, t, self._h * self._d), wo, cd)

    def params_in_order(self):
        return [self.q_proj, self.k_proj, self.v_proj, self.o_proj]


class HybridLayer(HybridBlock):
    """``x + mixer(RMSNorm(x))`` for one letter of the pattern."""

    def __init__(self, kind, cfg, experts_held=None, record_choice_rows=0,
                 dtype="float32", **kwargs):
        super().__init__(**kwargs)
        self.kind = kind
        self._eps = cfg["eps"]
        u = cfg["units"]
        self.norm = RMSNorm(epsilon=cfg["eps"], in_channels=u)
        if kind == "M":
            self.mixer = Mamba2Mixer(
                u, cfg["mamba_heads"], cfg["mamba_head_dim"],
                cfg["mamba_groups"], cfg["state_size"],
                conv_kernel=cfg["conv_kernel"],
                chunk_size=cfg["chunk_size"], eps=cfg["eps"], dtype=dtype)
        elif kind == "*":
            self.mixer = GroupedQueryAttention(
                u, cfg["num_heads"], cfg["num_kv_heads"], cfg["head_dim"],
                dtype=dtype)
        elif kind == "E":
            self.mixer = MoELayer(
                u, cfg["expert_hidden"], cfg["num_experts"],
                top_k=cfg["top_k"], routing="dropless",
                experts_held=experts_held,
                shared_hidden=cfg["shared_hidden"],
                routed_scaling=cfg["routed_scaling"],
                norm_topk=cfg["norm_topk"],
                record_choice_rows=record_choice_rows, dtype=dtype)
        else:
            raise ValueError(f"layer kind {kind!r} is not M, E or *")

    def forward(self, x, mask=None):
        if self.kind == "E":
            return x + self.mixer(self.norm(x))
        mixer, eps = self.mixer, self._eps
        ps = mixer.params_in_order()

        def f(xv, gain, *ws):
            cd = _compute_dtype(xv)
            hn = _rms(xv, gain, eps)
            return xv + mixer.mix(hn, *ws, cd).astype(xv.dtype)

        name = "mamba2_layer" if self.kind == "M" else "gqa_layer"
        out = invoke(name, f, [x, self.norm.gamma.data()]
                     + [p.data() for p in ps])
        return _par.with_sharding_constraint(out, "batch", None, None)


class NemotronHModel(HybridBlock):
    """tokens (B, T) int32 -> logits (B, T, vocab_held)."""

    def __init__(self, pattern, vocab_size, units, vocab_held=None,
                 experts_held=None, record_choice_rows=0, remat=False,
                 dtype="float32", **cfg):
        super().__init__()
        cfg = dict(cfg, units=units)
        self.pattern = pattern
        self.vocab_size = vocab_size
        self.vocab_held = int(vocab_held or vocab_size)
        self._remat = remat
        self.embed = Embedding(self.vocab_held, units, dtype=dtype)
        annotate(self.embed.weight, "vocab", "embed")
        self.blocks = []
        for i, kind in enumerate(pattern):
            blk = HybridLayer(kind, cfg, experts_held=experts_held,
                              record_choice_rows=record_choice_rows,
                              dtype=dtype)
            self.register_child(blk, f"l{i}")
            self.blocks.append(blk)
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


def get_nemotron_h(name="nemotron_h_tt_30b_a3b", **kwargs):
    """The published sizes of ``name``; keyword arguments replace them
    (``pattern`` for fewer layers, ``experts_held=(first, count)`` and
    ``vocab_held`` for one chip's share, small sizes for tests)."""
    cfg = dict(_CONFIGS[name])
    cfg.update(kwargs)
    return NemotronHModel(**cfg)
