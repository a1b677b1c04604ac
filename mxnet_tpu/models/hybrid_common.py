"""How a hybrid decoder (``nemotron_h``, ``qwen3_next``, ``granite_hybrid``,
``phi4_flash``, ``mellum``) is put together, written once: the float32 RMS
norm, the dense product in the compute type and the loss over the
vocabulary rows a chip holds; the shell (:class:`HybridDecoder`) with its
two heads; the residual half-layer over a mixer (:class:`HalfLayer`, over
:func:`fused`); the expert half (:class:`ExpertBlock`); and grouped-query
attention's projections (:class:`QKVOProjections`, :func:`positioned`).

A family is then its mixers (a block with ``mix(normed, *weights, cd)``
and ``params_in_order()``), its sizes, and how its configuration spells
the list of layer kinds: docs/hybrid.md, "Adding a family".
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .. import parallel as _par
from ..gluon.block import HybridBlock
from ..gluon.nn import Embedding, RMSNorm
from ..ndarray import ops as F
from ..ndarray.ops import invoke
from ..ops.flash import matmul_precision as _prec
from ..parallel.sharding import annotate
from .moe import MoELayer, amp_compute_dtype as _compute_dtype
from .transformer import run_blocks

__all__ = ["rms", "dense", "gated_mlp", "lm_loss", "fused", "HalfLayer",
           "ExpertBlock", "two_halves", "QKVOProjections", "positioned",
           "OwnHead", "TiedHead", "HybridDecoder"]


def rms(x, gain, eps, unit_offset=False):
    """RMS norm over the last axis in float32; the gain is ``gain``, or
    ``1 + gain`` with ``unit_offset``."""
    x = x.astype(jnp.float32)
    gain = gain.astype(jnp.float32)
    return (x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                              + eps) * (1.0 + gain if unit_offset else gain))


def dense(x, w, cd):
    """``x W^T`` with an (out, in) weight, operands in ``cd``, f32 sums."""
    return jnp.einsum("...i,oi->...o", x.astype(cd), w.astype(cd),
                      precision=_prec(cd),
                      preferred_element_type=jnp.float32)


def gated_mlp(x, w_in, w_out, cd):
    """``(silu(g) * v) W_out^T`` with ``(g, v)`` the two halves, in that
    order, of ``x W_in^T``; (out, in) weights, operands in ``cd``."""
    u = dense(x, w_in, cd)
    half = u.shape[-1] // 2
    return dense(jax.nn.silu(u[..., :half]) * u[..., half:], w_out, cd)


def lm_loss(logits, labels):
    """Next-token cross entropy over the vocabulary rows held; labels
    (B, T) already shifted, every one of them a row held."""
    lse = F.logsumexp(logits, axis=-1)
    return (lse - F.pick(logits, labels, axis=-1)).mean()


def fused(name, body, x, params, side=(), last=None):
    """A block's arithmetic as ONE operation, named ``name`` for the AMP
    policy: ``body(x, *the values of params, *side, cd=)``, pure ``jax``,
    ``cd`` the compute type.  Its result, or the first of several (what a
    layer emits beside the stream follows untouched), comes back held to
    ``("batch", None, last)``."""
    def f(xv, *rest):
        return body(xv, *rest, cd=_compute_dtype(xv))

    out = invoke(name, f, [x] + [p.data() for p in params] + list(side))
    if not isinstance(out, list):
        return _par.with_sharding_constraint(out, "batch", None, last)
    return (_par.with_sharding_constraint(out[0], "batch", None, last),
            *out[1:])


class HalfLayer(HybridBlock):
    """``x + mixer.mix(RMSNorm(x), *mixer.params_in_order(), cd)`` as the
    operation ``op``: one residual sublayer, a block of ``run_blocks``.
    The children are ``norm`` and ``mixer``, in that order."""

    def __init__(self, op, cfg, mixer, unit_offset=False, **kwargs):
        super().__init__(**kwargs)
        self._op, self._eps, self._unit_offset = op, cfg["eps"], unit_offset
        self.norm = RMSNorm(epsilon=cfg["eps"], in_channels=cfg["units"],
                            unit_offset=unit_offset)
        self.mixer = mixer

    def forward(self, x, mask=None):
        mixer, eps, unit_offset = self.mixer, self._eps, self._unit_offset

        def body(xv, gain, *ws, cd):
            return xv + mixer.mix(rms(xv, gain, eps, unit_offset), *ws,
                                  cd).astype(xv.dtype)

        return fused(self._op, body, x,
                     [self.norm.gamma] + mixer.params_in_order())


class ExpertBlock(HybridBlock):
    """``x + experts(RMSNorm(x))``: dropless routed experts at ``cfg``'s
    sizes (:class:`~mxnet_tpu.models.moe.MoELayer`, which takes
    ``moe_kwargs``: the scoring, the experts' form, the shared expert, the
    experts held).  The children are ``norm`` and the experts, under the
    name ``experts``."""

    def __init__(self, cfg, experts="moe", unit_offset=False, **moe_kwargs):
        super().__init__()
        self.norm = RMSNorm(epsilon=cfg["eps"], in_channels=cfg["units"],
                            unit_offset=unit_offset)
        self._experts = experts
        setattr(self, experts, MoELayer(
            cfg["units"], cfg["expert_hidden"], cfg["num_experts"],
            top_k=cfg["top_k"], routing="dropless",
            norm_topk=cfg["norm_topk"], **moe_kwargs))

    def forward(self, x, mask=None):
        return x + getattr(self, self._experts)(self.norm(x))


def two_halves(kinds, mixer_half, expert_half):
    """``(name, block)`` for decoder layers of TWO blocks, each recomputed
    on its own: ``l{i}_mixer = mixer_half(kinds[i])`` and ``l{i}_experts =
    expert_half()``."""
    for i, kind in enumerate(kinds):
        yield f"l{i}_mixer", mixer_half(kind)
        yield f"l{i}_experts", expert_half()


class QKVOProjections(HybridBlock):
    """What grouped-query attention mixers declare alike: ``q_proj``
    (``q_width`` times as wide where a query brings a gate), ``k_proj``,
    ``v_proj``, with ``qk_norm`` (an ``init``) the per-head gains
    ``q_norm`` / ``k_norm``, and ``o_proj``; no bias.  ``params_in_order``
    is that order; ``mix`` is the mixer's own."""

    def __init__(self, units, num_heads, num_kv_heads, head_dim, q_width=1,
                 qk_norm=None, dtype="float32", **kwargs):
        super().__init__(**kwargs)
        if num_heads % num_kv_heads:
            raise ValueError(f"{num_heads} query heads do not divide over "
                             f"{num_kv_heads} key/value heads")
        self._h, self._hk, self._d = num_heads, num_kv_heads, head_dim
        g = self.params.get
        self.q_proj = g("q_proj", dtype=dtype, init="xavier",
                        shape=(num_heads * head_dim * q_width, units))
        self.k_proj = g("k_proj", shape=(num_kv_heads * head_dim, units),
                        dtype=dtype, init="xavier")
        self.v_proj = g("v_proj", shape=(num_kv_heads * head_dim, units),
                        dtype=dtype, init="xavier")
        if qk_norm:
            self.q_norm = g("q_norm", shape=(head_dim,), dtype=dtype,
                            init=qk_norm)
            self.k_norm = g("k_norm", shape=(head_dim,), dtype=dtype,
                            init=qk_norm)
        self.o_proj = g("o_proj", shape=(units, num_heads * head_dim),
                        dtype=dtype, init="xavier")

    def params_in_order(self):
        return list(self._reg_params.values())

    @staticmethod
    def heads(hn, w, n, cd, cast=True):
        """``hn W^T`` as (B, T, n, .) heads: in ``cd``, or (``cast``
        False) the product's float32 for a norm to read."""
        y = dense(hn, w, cd)
        return (y.astype(cd) if cast else y).reshape(*hn.shape[:2], n, -1)

    @staticmethod
    def merged(a, wo, cd):
        """``o_proj`` over the heads (B, T, H, D) laid side by side."""
        return dense(a.reshape(*a.shape[:2], -1), wo, cd)


def positioned(x, gain, eps, cd, unit_offset=False, **table):
    """q/k norm, then rotary positions by ``table`` (the keywords of
    :func:`~mxnet_tpu.ops.attention.rotary_embedding` that name one), in
    ``cd``."""
    from ..ops.attention import rotary_embedding
    return rotary_embedding(rms(x, gain, eps, unit_offset),
                            **table).astype(cd)


class OwnHead:
    """An UNTIED head: ``lm_head``, the vocabulary rows held, read through
    ``norm_f`` and ``F.FullyConnected``."""

    def declare(self, net, units, dtype):
        net.lm_head = net.params.get(
            "lm_head", shape=(net.vocab_held, units), dtype=dtype,
            init="xavier")
        annotate(net.lm_head, "vocab", "embed")

    def __call__(self, net, x):
        logits = F.FullyConnected(net.norm_f(x), net.lm_head.data(), None,
                                  num_hidden=net.vocab_held, no_bias=True,
                                  flatten=False)
        return _par.with_sharding_constraint(logits, "batch", None, "vocab")


class TiedHead:
    """The embedding's rows as the head: the final norm and the product
    in ONE operation ``op`` in the compute type, ``logits(net, x,
    *norm_f's parameters, table, cd)`` pure ``jax``."""

    def __init__(self, op, logits):
        self._op, self._logits = op, logits

    def declare(self, net, units, dtype):
        pass

    def __call__(self, net, x):
        logits = self._logits
        return fused(self._op, lambda *a, cd: logits(net, *a, cd), x,
                     list(net.norm_f.collect_params().values())
                     + [net.embed.weight], last="vocab")


class HybridDecoder(HybridBlock):
    """tokens (B, T) int32 -> logits (B, T, vocab_held): the embedding at
    the ``vocab_held`` rows held here (a chip's share when the vocabulary
    is split over chips; all of ``vocab_size`` when None), times
    ``embed_multiplier`` if there is one; ``blocks``, an iterable of
    ``(name, block)`` drawn AFTER the embedding is made, through
    ``run_blocks`` as a loop, recomputed block by block under ``remat``;
    ``norm_f = norm(epsilon=eps, in_channels=units)``; and ``head``
    (:class:`OwnHead` or :class:`TiedHead`).  What the benchmark and the
    tests read stays where it was: ``vocab_size``, ``vocab_held``,
    ``blocks``, ``embed``, ``norm_f``, and an own head's ``lm_head``."""

    def __init__(self, blocks, norm, head, vocab_size, units, eps,
                 vocab_held=None, remat=False, dtype="float32",
                 embed_multiplier=None):
        super().__init__()
        self.vocab_size = vocab_size
        self.vocab_held = int(vocab_held or vocab_size)
        self._remat, self._eps, self._emb = remat, eps, embed_multiplier
        self._head = head
        self.embed = Embedding(self.vocab_held, units, dtype=dtype)
        annotate(self.embed.weight, "vocab", "embed")
        self.blocks = []
        for name, blk in blocks:
            self.register_child(blk, name)
            self.blocks.append(blk)
        self.norm_f = norm(epsilon=eps, in_channels=units)
        head.declare(self, units, dtype)

    def forward(self, tokens):
        x = self.embed(tokens)
        if self._emb is not None:
            x = x * self._emb
        x = _par.with_sharding_constraint(x, "batch", None, None)
        x = run_blocks(self.blocks, x, scan=False, remat=self._remat)
        return self._head(self, x)
