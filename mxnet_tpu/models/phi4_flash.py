"""Decoder-hybrid-decoder stacks (SambaY, arXiv:2507.06607; HF ``phi4flash``):
a self-decoder of Mamba-1 and windowed differential attention, one full
attention layer whose keys and values every later attention layer reads,
and a cross-decoder of Gated Memory Units and differential
cross-attention.

Layer ``i`` of the published stack, ``h`` the stream, LayerNorm with gain
and bias::

    u  = h + mixer_i(LN1_i(h))
    h' = u + fc2_i(silu(g) * v),   [g, v] = fc1_i(LN2_i(u))

and the mixer is one of six kinds (:func:`layer_kinds`):

* ``mamba`` — :class:`Mamba1Mixer`: ``in_proj`` to the stream ``a`` and a
  gate ``z``; a causal depthwise convolution with bias and SiLU; ``x_proj``
  to a low-rank step, ``B`` and ``C``; ``dt = softplus(dt_proj(.) + b)``;
  the selective scan (:mod:`mxnet_tpu.ops.sscan`) and the ``D`` skip;
  ``(y * silu(z)) out_proj``.
* ``mamba_mem`` — the same, and the layer EMITS ``m = y`` (after the skip,
  before the gate): the memory the Gated Memory Units read.
* ``swa`` / ``full`` — :class:`DifferentialAttention`, causal, under a
  window of ``window`` keys or over everything: query heads pair off by
  neighbours, each pair's two softmaxes read two neighbouring key heads
  and ONE value twice a key head wide, ``o = subnorm(o1 - lambda o2) (1 -
  lambda_init)``.  ``full`` EMITS its keys and values.
* ``gmu`` — :class:`GatedMemoryUnit`: ``(m * silu(x W_1)) W_2`` over the
  emitted memory.
* ``cross`` — differential attention with queries of its own over the
  emitted keys and values.

What a layer emits travels beside the stream through
:func:`~mxnet_tpu.models.transformer.run_blocks` (``side_out`` /
``side_in``), differentiable: the cotangents of ``m``, ``K`` and ``V``
flow back from every reader into the layer that made them.  There is no
positional encoding (the mixers carry position), the head is TIED, and
``vocab_held`` rows of the table are held here (a chip's share when the
table is split by rows): the loss is then the cross entropy over them.

Not built: dropout (the published rates are 0) and the caches of
generation (one KV cache for the whole cross-decoder, the scan's state).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ..gluon.block import HybridBlock
from ..gluon.nn import LayerNorm
from ..ndarray.ops import _layer_norm as _layer_norm_op
from ..ops.flash import plan_event
from .hybrid_common import (HybridDecoder, TiedHead, dense as _dense, fused,
                            gated_mlp, lm_loss, rms as _rms)

__all__ = ["Phi4FlashModel", "Phi4FlashLayer", "Mamba1Mixer",
           "DifferentialAttention", "GatedMemoryUnit", "layer_kinds",
           "lambda_init", "get_phi4_flash", "lm_loss"]

# name: the published sizes (config.json of the source; the Mamba sizes,
# which it does not carry, by the family's convention), whole
_CONFIGS = {
    "phi4_mini_flash_reasoning": dict(
        num_layers=32, vocab_size=200064, units=2560, num_heads=40,
        num_kv_heads=20, head_dim=64, window=512, mlp_hidden=10240,
        d_inner=5120, state_size=16, conv_kernel=4, dt_rank=160, eps=1e-5),
}


def layer_kinds(num_layers: int = 32) -> tuple:
    """The kind of every layer of a stack of ``num_layers`` (a whole
    number of Mamba / attention pairs): the first half is the
    self-decoder, the pair at the middle feeds the cross-decoder."""
    if num_layers % 4:
        raise ValueError(f"{num_layers} layers are no two halves of "
                         f"Mamba / attention pairs")
    half = num_layers // 2
    return tuple(
        ("mamba" if i < half else "mamba_mem" if i == half else "gmu")
        if i % 2 == 0 else
        ("swa" if i < half else "full" if i == half + 1 else "cross")
        for i in range(num_layers))


def lambda_init(layer: int) -> float:
    """The differential heads' starting weight at published layer
    ``layer`` (0-based): ``0.8 - 0.6 exp(-0.3 layer)``."""
    return 0.8 - 0.6 * math.exp(-0.3 * layer)


def _layer_norm(x, gain, bias, eps):
    """LayerNorm over the last axis in float32 (``ndarray.ops``'s, whose
    backward keeps the input and two numbers a row)."""
    f32 = jnp.float32
    return _layer_norm_op(x.astype(f32), gain.astype(f32), bias.astype(f32),
                          -1, eps)


class Mamba1Mixer(HybridBlock):
    """The Mamba-1 mixer; ``mix`` returns (output, memory)."""

    def __init__(self, units, d_inner, state_size, conv_kernel, dt_rank,
                 dtype="float32", **kwargs):
        super().__init__(**kwargs)
        self._di, self._n, self._r = d_inner, state_size, dt_rank
        g = self.params.get
        self.in_proj = g("in_proj", shape=(2 * d_inner, units), dtype=dtype,
                         init="xavier")
        self.conv_weight = g("conv_weight", shape=(d_inner, conv_kernel),
                             dtype=dtype, init="xavier")
        self.conv_bias = g("conv_bias", shape=(d_inner,), dtype=dtype,
                           init="zeros")
        self.x_proj = g("x_proj", shape=(dt_rank + 2 * state_size, d_inner),
                        dtype=dtype, init="xavier")
        self.dt_proj = g("dt_proj", shape=(d_inner, dt_rank), dtype=dtype,
                         init="xavier")
        self.dt_bias = g("dt_bias", shape=(d_inner,), dtype=dtype,
                         init="zeros")
        self.A_log = g("A_log", shape=(d_inner, state_size), dtype=dtype,
                       init="zeros")
        self.D = g("D", shape=(d_inner,), dtype=dtype, init="ones")
        self.out_proj = g("out_proj", shape=(units, d_inner), dtype=dtype,
                          init="xavier")

    def mix(self, hn, in_w, conv_w, conv_b, x_w, dt_w, dt_b, a_log, d_skip,
            out_w, cd):
        """The mixer on a normalised (B, T, U) input; pure ``jax``."""
        from ..ops.ssd import causal_conv1d
        from ..ops.sscan import selective_scan
        di, n, r = self._di, self._n, self._r
        f32 = jnp.float32
        proj = _dense(hn, in_w, cd)                               # f32
        a, z = proj[..., :di], proj[..., di:]
        a = jax.nn.silu(causal_conv1d(a, conv_w.astype(f32),
                                      conv_b.astype(f32)))
        low = _dense(a, x_w, cd)
        dt = jax.nn.softplus(_dense(low[..., :r], dt_w, cd)
                             + dt_b.astype(f32))
        y = selective_scan(a.astype(cd), dt, -jnp.exp(a_log.astype(f32)),
                           low[..., r:r + n].astype(cd),
                           low[..., r + n:].astype(cd))
        y = y + d_skip.astype(f32) * a
        return _dense(y * jax.nn.silu(z), out_w, cd), y

    def params_in_order(self):
        return [self.in_proj, self.conv_weight, self.conv_bias, self.x_proj,
                self.dt_proj, self.dt_bias, self.A_log, self.D,
                self.out_proj]


def _differential(o1, o2, lam):
    """What a differential head keeps of its two softmaxes' results."""
    return o1 - lam * o2


class DifferentialAttention(HybridBlock):
    """Differential attention at published layer ``layer``: causal, under
    ``window`` keys (None: over everything); ``cross``: queries only, the
    keys and values are handed to ``mix``.  ``mix`` returns (output, K,
    V), K and V as (B, T, kv heads, head dim) in the compute type."""

    def __init__(self, units, num_heads, num_kv_heads, head_dim, layer,
                 window=None, cross=False, eps=1e-5, dtype="float32",
                 **kwargs):
        super().__init__(**kwargs)
        if num_heads % 2 or num_kv_heads % 2 or \
                (num_heads // 2) % (num_kv_heads // 2):
            raise ValueError(f"{num_heads} query heads over {num_kv_heads} "
                             f"key/value heads do not pair off")
        self._h, self._hk, self._d = num_heads, num_kv_heads, head_dim
        self._window, self._cross, self._eps = window, bool(cross), eps
        self._lambda_init = lambda_init(layer)
        g = self.params.get
        wide = num_heads * head_dim
        if not cross:
            wide += 2 * num_kv_heads * head_dim
        self.qkv_proj = g("qkv_proj", shape=(wide, units), dtype=dtype,
                          init="xavier")
        self.qkv_bias = g("qkv_bias", shape=(wide,), dtype=dtype,
                          init="zeros")
        self.o_proj = g("o_proj", shape=(units, num_heads * head_dim),
                        dtype=dtype, init="xavier")
        self.o_bias = g("o_bias", shape=(units,), dtype=dtype, init="zeros")
        # lambda_q1, lambda_k1, lambda_q2, lambda_k2
        self.lambdas = g("lambdas", shape=(4, head_dim), dtype=dtype,
                         init="zeros")
        self.subln = g("subln", shape=(2 * head_dim,), dtype=dtype,
                       init="ones")

    def mix(self, hn, w_qkv, b_qkv, w_o, b_o, lambdas, gain, cd, kv=None):
        from ..ops.attention import flash_attention
        b, t, _u = hn.shape
        h, hk, d = self._h, self._hk, self._d
        f32 = jnp.float32
        proj = (_dense(hn, w_qkv, cd) + b_qkv.astype(f32)).astype(cd)
        q = proj[..., :h * d]
        if self._cross:
            k, v = kv
        else:
            k = proj[..., h * d:(h + hk) * d].reshape(b, t, hk, d)
            v = proj[..., (h + hk) * d:].reshape(b, t, hk, d)
        # a key/value pair p serves the differential heads 2p and 2p + 1,
        # that is query heads 4p .. 4p + 3 as (head a, score s): put the
        # score ahead of the head, and query head 4p + 2s + a reads key
        # head 2p + s and the pair's one value, two key heads wide
        share = (h // 2) // (hk // 2)
        q = q.reshape(b, t, hk // 2, share, 2, d).swapaxes(3, 4)
        with jax.named_scope("diff_attn"):
            o = flash_attention(q.reshape(b, t, h, d), k,
                                v.reshape(b, t, hk // 2, 2 * d),
                                causal=True, window=self._window)
        o = o.astype(f32).reshape(b, t, hk // 2, 2, share, 2 * d)
        lq1, lk1, lq2, lk2 = (lambdas[i].astype(f32) for i in range(4))
        lam = (jnp.exp(jnp.sum(lq1 * lk1)) - jnp.exp(jnp.sum(lq2 * lk2))
               + self._lambda_init)
        o = _differential(o[:, :, :, 0], o[:, :, :, 1], lam)
        o = _rms(o, gain, self._eps) * (1.0 - self._lambda_init)
        out = _dense(o.reshape(b, t, h * d), w_o, cd) + b_o.astype(f32)
        return out, k, v

    def params_in_order(self):
        return [self.qkv_proj, self.qkv_bias, self.o_proj, self.o_bias,
                self.lambdas, self.subln]


class GatedMemoryUnit(HybridBlock):
    """``(m * silu(x W_1)) W_2`` over the memory ``m`` a Mamba layer
    emitted."""

    def __init__(self, units, d_inner, dtype="float32", **kwargs):
        super().__init__(**kwargs)
        self.in_proj = self.params.get("in_proj", shape=(d_inner, units),
                                       dtype=dtype, init="xavier")
        self.out_proj = self.params.get("out_proj", shape=(units, d_inner),
                                        dtype=dtype, init="xavier")

    def mix(self, hn, w_in, w_out, cd, memory=None):
        with jax.named_scope("gmu"):
            gate = jax.nn.silu(_dense(hn, w_in, cd))
            return _dense(memory.astype(jnp.float32) * gate, w_out, cd)

    def params_in_order(self):
        return [self.in_proj, self.out_proj]


# kind: (what the layer reads of earlier layers, what it emits)
_SIDES = {"mamba": ((), ()), "swa": ((), ()), "mamba_mem": ((), ("memory",)),
          "full": ((), ("keys", "values")), "gmu": (("memory",), ()),
          "cross": (("keys", "values"), ())}


class Phi4FlashLayer(HybridBlock):
    """Both residual sublayers of the published layer ``layer``.  Called
    with the stream, a mask (unused) and what ``side_in`` names; returns
    the stream, or (stream, *what ``side_out`` names)."""

    def __init__(self, kind, layer, cfg, dtype="float32", **kwargs):
        super().__init__(**kwargs)
        if kind not in _SIDES:
            raise ValueError(f"layer kind {kind!r} is not one of "
                             f"{sorted(_SIDES)}")
        self.kind, self.layer = kind, int(layer)
        self.side_in, self.side_out = _SIDES[kind]
        self._eps = cfg["eps"]
        u, f = cfg["units"], cfg["mlp_hidden"]
        self.norm1 = LayerNorm(epsilon=cfg["eps"], in_channels=u)
        if kind in ("mamba", "mamba_mem"):
            self.mixer = Mamba1Mixer(u, cfg["d_inner"], cfg["state_size"],
                                     cfg["conv_kernel"], cfg["dt_rank"],
                                     dtype=dtype)
        elif kind == "gmu":
            self.mixer = GatedMemoryUnit(u, cfg["d_inner"], dtype=dtype)
        else:
            self.mixer = DifferentialAttention(
                u, cfg["num_heads"], cfg["num_kv_heads"], cfg["head_dim"],
                layer, window=cfg["window"] if kind == "swa" else None,
                cross=kind == "cross", eps=cfg["eps"], dtype=dtype)
        self.norm2 = LayerNorm(epsilon=cfg["eps"], in_channels=u)
        self.fc1 = self.params.get("fc1", shape=(2 * f, u), dtype=dtype,
                                   init="xavier")
        self.fc2 = self.params.get("fc2", shape=(u, f), dtype=dtype,
                                   init="xavier")

    def forward(self, x, mask=None, *side):
        mixer, eps, kind = self.mixer, self._eps, self.kind
        ps = mixer.params_in_order()
        if len(side) != len(self.side_in):
            raise ValueError(f"a {kind} layer reads {self.side_in}, got "
                             f"{len(side)} values")

        def body(xv, g1, b1, g2, b2, w1, w2, *rest, cd):
            ws, given = rest[:len(ps)], rest[len(ps):]
            emitted = ()
            with jax.named_scope("mixer"):
                hn = _layer_norm(xv, g1, b1, eps)
                if kind == "gmu":
                    mixed = mixer.mix(hn, *ws, cd, memory=given[0])
                elif kind == "cross":
                    mixed = mixer.mix(hn, *ws, cd, kv=given)[0]
                elif kind in ("swa", "full"):
                    mixed, *kv = mixer.mix(hn, *ws, cd)
                    emitted = tuple(kv) if kind == "full" else ()
                else:
                    mixed, memory = mixer.mix(hn, *ws, cd)
                    emitted = (memory,) if kind == "mamba_mem" else ()
                u = xv + mixed.astype(xv.dtype)
            with jax.named_scope("mlp"):
                out = u + gated_mlp(_layer_norm(u, g2, b2, eps), w1, w2,
                                    cd).astype(xv.dtype)
            return (out,) + emitted if emitted else out

        return fused(f"phi4flash_{kind}_layer", body, x,
                     [self.norm1.gamma, self.norm1.beta, self.norm2.gamma,
                      self.norm2.beta, self.fc1, self.fc2] + ps, side)


def _tied_logits(net, xv, gain, bias, w, cd):
    return _dense(_layer_norm(xv, gain, bias, net._eps), w, cd)


class Phi4FlashModel(HybridDecoder):
    """tokens (B, T) int32 -> logits (B, T, vocab_held) float32.
    ``layers``: the published indices of the layers held, in order (all
    of them when None); a layer that reads the memory or the keys and
    values needs the layer that emits them among those held."""

    def __init__(self, num_layers, vocab_size, units, layers=None,
                 vocab_held=None, remat=False, dtype="float32", **cfg):
        cfg = dict(cfg, units=units)
        every = layer_kinds(num_layers)
        layers = tuple(range(num_layers) if layers is None else layers)
        kinds = tuple(every[i] for i in layers)
        for want, by in (("memory", "mamba_mem"), ("keys", "full")):
            readers = [k for k in kinds if want in _SIDES[k][0]]
            if readers and by not in kinds:
                raise ValueError(f"layers {layers} read the {want} "
                                 f"and hold no {by} layer")
        super().__init__(
            ((f"l{i}", Phi4FlashLayer(kind, i, cfg, dtype=dtype))
             for i, kind in zip(layers, kinds)),
            LayerNorm, TiedHead("phi4flash_tied_head", _tied_logits),
            vocab_size, units, cfg["eps"], vocab_held=vocab_held,
            remat=remat, dtype=dtype)
        self.layers, self.kinds = layers, kinds
        plan_event("phi4flash.plan", layers=layers, kinds=kinds,
                   window=cfg["window"], vocab_held=self.vocab_held,
                   tied=True)


def get_phi4_flash(name="phi4_mini_flash_reasoning", **kwargs):
    """The published sizes of ``name``; keyword arguments replace them
    (``layers`` for a contiguous stage, ``vocab_held`` for one chip's rows
    of the tied table, small sizes for tests)."""
    cfg = dict(_CONFIGS[name])
    cfg.update(kwargs)
    return Phi4FlashModel(**cfg)
