"""How a hybrid decoder (``nemotron_h``, ``qwen3_next``, ``granite_hybrid``,
``phi4_flash``, ``mellum``, ``ouro``, ``deepseek_v3``) is put together,
written once: the
float32 RMS norm, the dense product in the compute type and the loss over
the vocabulary rows a chip holds; the shell (:class:`HybridDecoder`) with
its two heads, run once or, on ONE set of weights, several times with an
exit gate a pass (:func:`exit_distribution`, :func:`exit_objective`); the
residual half-layer over a mixer (:class:`HalfLayer`, over :func:`fused`);
the expert half (:class:`ExpertBlock`) and the dense gated one
(:class:`GatedMLP`); and grouped-query attention's projections
(:class:`QKVOProjections`, :func:`positioned`).

A family is then its mixers (a block with ``mix(normed, *weights, cd)``
and ``params_in_order()``), its sizes, and how its configuration spells
the list of layer kinds: docs/hybrid.md, "Adding a family".
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .. import parallel as _par
from ..gluon.block import HybridBlock
from ..gluon.nn import Embedding, RMSNorm
from ..ndarray import ops as F
from ..ndarray.ops import invoke
from ..ops.flash import (matmul_precision as _prec, named_residuals,
                         plan_event)
from ..parallel.sharding import annotate
from .moe import MoELayer, amp_compute_dtype as _compute_dtype
from .transformer import run_blocks

__all__ = ["rms", "dense", "gated_mlp", "lm_loss", "token_loss",
           "exit_distribution", "exit_objective", "fused", "HalfLayer",
           "ExpertBlock", "two_halves", "GatedMLP", "QKVOProjections",
           "positioned",
           "OwnHead", "TiedHead", "HybridDecoder", "read_loop_counters"]


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


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _rounded_back(u, cd):
    """``u`` itself; its cotangent goes back rounded to ``cd`` and in its
    own type, so the product that makes it writes it in ``cd``.  Nothing
    is kept for the backward pass."""
    return u


_rounded_back.defvjp(
    lambda u, cd: (u, None),
    lambda cd, _, ct: (ct.astype(cd).astype(ct.dtype),))


def gated_mlp(x, w_in, w_out, cd):
    """``(silu(g) * v) W_out^T`` with ``g = x W_in[:F]^T`` and ``v = x
    W_in[F:]^T``: TWO products over the halves, gate rows first, of the
    ONE leaf ``w_in`` (2F, D); (out, in) weights, operands in ``cd``,
    float32 sums, the gate in float32.  No (rows, 2F) value exists, so
    ``silu(g) * v`` is a product's epilogue and leaves in ``cd``; going
    back, ``dg`` and ``dv`` are rounded to ``cd`` ONCE, where they are
    made (a float32 form bought no digit: the MXU's single pass rounds
    its operands on entry), and nothing beyond what JAX keeps for the
    products is held.  Event ``mlp.plan`` a distinct shape."""
    half = w_in.shape[0] // 2
    rows = x.size // x.shape[-1]
    plan_event("mlp.plan", form="halves", rows=rows, half=half,
               compute_dtype=jnp.dtype(cd).name,
               wide_bytes_a_call=rows * half * 4)
    g = _rounded_back(dense(x, w_in[:half], cd), cd)
    v = _rounded_back(dense(x, w_in[half:], cd), cd)
    return dense(jax.nn.silu(g) * v, w_out, cd)


def lm_loss(logits, labels):
    """Next-token cross entropy over the vocabulary rows held; labels
    (B, T) already shifted, every one of them a row held."""
    lse = F.logsumexp(logits, axis=-1)
    return (lse - F.pick(logits, labels, axis=-1)).mean()


def token_loss(logits, labels):
    """:func:`lm_loss` before its mean: (B, T) float32, pure ``jax``."""
    logits = logits.astype(jnp.float32)
    picked = jnp.take_along_axis(logits, labels[..., None].astype(jnp.int32),
                                 axis=-1)[..., 0]
    return jax.nn.logsumexp(logits, axis=-1) - picked


def _exit_gate(normed, w, b):
    """``sigmoid(normed . w + b)`` over the last axis, in float32."""
    return jax.nn.sigmoid(jnp.sum(
        normed.astype(jnp.float32) * w.astype(jnp.float32), -1)
        + b.astype(jnp.float32))


def exit_distribution(gates):
    """``p`` (P, ...) from the exit gates ``lambda`` (P, ...) of P passes:
    ``p_t = lambda_t prod_{j<t} (1 - lambda_j)``, and the last pass takes
    what is left, ``p_P = prod_{j<P} (1 - lambda_j)``; sums to one over
    the passes.  Pure ``jax``, float32."""
    gates = gates.astype(jnp.float32)
    stay = jnp.cumprod(1.0 - gates[:-1], axis=0)
    before = jnp.concatenate([jnp.ones_like(gates[:1]), stay], axis=0)
    return jnp.concatenate([gates[:-1] * before[:-1], before[-1:]], axis=0)


def exit_objective(losses, gates, beta):
    """The looped objective of one token, for every token: ``sum_t p_t
    CE_t - beta H(p)`` with ``p = exit_distribution(gates)`` and ``H(p) =
    -sum_t p_t log p_t``; ``losses`` and ``gates`` (P, ...).  Returns
    ``(objective (...), p (P, ...))``; the gradient flows through ``p``
    into the gates.  Pure ``jax``, float32."""
    p = exit_distribution(gates)
    entropy = -jnp.sum(p * jnp.log(jnp.maximum(p, 1e-30)), axis=0)
    return jnp.sum(p * losses.astype(jnp.float32), axis=0) - beta * entropy, p


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
    The children are ``norm`` and ``mixer``, in that order.  With
    ``post_norm`` the mixer's OUTPUT is normalised too before it joins
    the stream (a sandwich): ``x + RMSNorm(mixer.mix(RMSNorm(x), ...))``,
    still one operation, the third child ``post_norm``."""

    def __init__(self, op, cfg, mixer, unit_offset=False, post_norm=False,
                 **kwargs):
        super().__init__(**kwargs)
        self._op, self._eps, self._unit_offset = op, cfg["eps"], unit_offset
        self.norm = RMSNorm(epsilon=cfg["eps"], in_channels=cfg["units"],
                            unit_offset=unit_offset)
        self.mixer = mixer
        self.post_norm = RMSNorm(
            epsilon=cfg["eps"], in_channels=cfg["units"],
            unit_offset=unit_offset) if post_norm else None

    def forward(self, x, mask=None):
        mixer, eps, unit_offset = self.mixer, self._eps, self._unit_offset
        after = [] if self.post_norm is None else [self.post_norm.gamma]

        def body(xv, gain, *ws, cd):
            if after:
                *ws, post_gain = ws
            y = mixer.mix(rms(xv, gain, eps, unit_offset), *ws, cd)
            if after:
                y = rms(y, post_gain, eps, unit_offset)
            return xv + y.astype(xv.dtype)

        return fused(self._op, body, x,
                     [self.norm.gamma] + mixer.params_in_order() + after)


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


def two_halves(kinds, mixer_half, second_half, second="experts"):
    """``(name, block)`` for decoder layers of TWO blocks, each recomputed
    on its own: ``l{i}_mixer = mixer_half(kinds[i])`` and ``l{i}_experts =
    second_half(i)``.  The second half is made BY LAYER: its factory is
    handed the layer's index (a stack whose leading layers are dense and
    whose others hold experts answers with a block of either kind), and
    ``second``, a name or a function of that index, names a second half
    that is no expert half."""
    for i, kind in enumerate(kinds):
        yield f"l{i}_mixer", mixer_half(kind)
        name = second(i) if callable(second) else second
        yield f"l{i}_{name}", second_half(i)


class GatedMLP(HybridBlock):
    """The dense SwiGLU feed-forward as a mixer: ``gate_up`` (2 F, U),
    gate rows first, and ``down`` (U, F); no bias."""

    def __init__(self, units, hidden, dtype="float32", **kwargs):
        super().__init__(**kwargs)
        self.gate_up = self.params.get("gate_up", shape=(2 * hidden, units),
                                       dtype=dtype, init="xavier")
        self.down = self.params.get("down", shape=(units, hidden),
                                    dtype=dtype, init="xavier")

    def mix(self, hn, w_in, w_out, cd):
        return gated_mlp(hn, w_in, w_out, cd)

    def params_in_order(self):
        return [self.gate_up, self.down]


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
    ``norm_f`` and ``F.FullyConnected``.  :meth:`read` is the product
    alone on a stream already normalised; given labels it returns the
    per-token cross entropy (B, T) in the logits' place."""

    def declare(self, net, units, dtype):
        net.lm_head = net.params.get(
            "lm_head", shape=(net.vocab_held, units), dtype=dtype,
            init="xavier")
        annotate(net.lm_head, "vocab", "embed")

    def read(self, net, normed, labels=None):
        logits = F.FullyConnected(normed, net.lm_head.data(), None,
                                  num_hidden=net.vocab_held, no_bias=True,
                                  flatten=False)
        logits = _par.with_sharding_constraint(logits, "batch", None,
                                               "vocab")
        if labels is None:
            return logits
        return invoke("token_loss", token_loss, [logits, labels])

    def __call__(self, net, x):
        return self.read(net, net.norm_f(x))


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
    ``blocks``, ``embed``, ``norm_f``, and an own head's ``lm_head``.

    ``passes`` > 1 runs the SAME blocks that many times on one set of
    parameters (their gradient is the sum over the uses): ``norm_f``
    closes every pass and its output enters the next; after every pass an
    :class:`OwnHead` reads it, and so does an exit gate ``sigmoid(h .
    exit_gate + exit_bias)`` (two more parameters).  ``net(tokens)`` is
    then ``(logits (P, B, T, vocab_held), gates (P, B, T))``, and
    ``net(tokens, labels)`` the objective :func:`exit_objective` with
    ``exit_beta`` (which several passes have to be given: the shell has
    no value of its own), one number (with one pass: :func:`lm_loss`).
    Under a trace the passes are ONE ``lax.scan`` whose body is the stack
    as ``run_blocks`` runs it, the weights closed over: the stack is
    traced and compiled once, and what ``remat`` keeps stacks along the
    pass; the head is read and its loss taken inside the pass, recomputed
    in the backward pass under ``remat``, so one pass's logits are alive
    at a time.  Eagerly they are a Python loop, because the imperative
    tape records operations as they run and cannot record a scan's body.
    The last step's mean ``p_t`` and mean ``CE_t`` of every pass, the
    steps counted and the sum of those mean ``p_t`` over them are left in
    the payload ``loop_stats`` (P, 4: :func:`read_loop_counters`), and
    each trace leaves a ``loop.plan`` event, whose ``layers`` counts the
    blocks' names before an underscore (``l3_mixer`` and ``l3_experts``
    are one layer).

    ``plan`` = ``(name, attributes)``: an event a family wants left as its
    forward is traced (what it holds of the published stack).
    """

    def __init__(self, blocks, norm, head, vocab_size, units, eps,
                 vocab_held=None, remat=False, dtype="float32",
                 embed_multiplier=None, passes=1, exit_beta=None, plan=None):
        super().__init__()
        self._plan = plan
        self.vocab_size = vocab_size
        self.vocab_held = int(vocab_held or vocab_size)
        self._remat, self._eps, self._emb = remat, eps, embed_multiplier
        self._head = head
        self.passes, self._beta = int(passes), exit_beta
        self.embed = Embedding(self.vocab_held, units, dtype=dtype)
        annotate(self.embed.weight, "vocab", "embed")
        self.blocks, layers = [], set()
        for name, blk in blocks:
            self.register_child(blk, name)
            self.blocks.append(blk)
            layers.add(name.partition("_")[0])
        self._layers = len(layers)
        self.norm_f = norm(epsilon=eps, in_channels=units)
        head.declare(self, units, dtype)
        if self.passes > 1:
            self._declare_loop(units, dtype)

    def forward(self, tokens, labels=None):
        if self._plan is not None:
            plan_event(self._plan[0], **self._plan[1])
        x = self.embed(tokens)
        if self._emb is not None:
            x = x * self._emb
        x = _par.with_sharding_constraint(x, "batch", None, None)
        if self.passes > 1:
            return self._looped(x, labels)
        x = run_blocks(self.blocks, x, scan=False, remat=self._remat)
        out = self._head(self, x)
        return out if labels is None else lm_loss(out, labels)

    # ---- several passes over one set of weights
    def _declare_loop(self, units, dtype):
        if not hasattr(self._head, "read"):
            raise ValueError("several passes read the head after each: "
                             "that takes an OwnHead")
        if self._beta is None:
            raise ValueError("several passes take an exit_beta")
        self._beta = float(self._beta)
        if any(p.grad_req == "null" for blk in self.blocks
               for p in blk.collect_params().values()):
            raise ValueError("a block that rewrites a payload of its own "
                             "cannot be run several times in one scan")
        g = self.params.get
        self.exit_gate = g("exit_gate", shape=(units,), dtype=dtype,
                           init="zeros")
        self.exit_bias = g("exit_bias", shape=(1,), dtype=dtype,
                           init="zeros")
        # a pass: mean p_t, mean CE_t of the last step with labels, the
        # steps so far and the sum of their mean p_t; rewritten by every
        # such step in training
        self.loop_stats = g("loop_stats", shape=(self.passes, 4),
                            dtype="float32", init="zeros",
                            differentiable=False)

    def _report_loop(self, runs_as, kept_bytes):
        """Event ``loop.plan``, as a step is traced: how often the stack
        is applied, how the passes run, and the named residuals ONE pass
        keeps for its backward pass (the blocks' own ``remat.plan`` events
        read 0 under the scan; the passes stack this many bytes each)."""
        plan_event("loop.plan", passes=self.passes, layers=self._layers,
                   blocks=len(self.blocks),
                   applications=self.passes * self._layers,
                   runs_as=runs_as, remat=bool(self._remat),
                   kept_bytes_a_pass=kept_bytes, exit_beta=self._beta)

    def _leave(self, y, labels):
        """What closes a pass: ``norm_f``, then the head (its per-token
        loss where there are labels) and the exit gate on the normed
        stream.  NDArrays."""
        hn = self.norm_f(y)
        with jax.named_scope("exit_loss"):
            out = self._head.read(self, hn, labels)
            gate = _par.with_sharding_constraint(invoke(
                "exit_gate", _exit_gate,
                [hn, self.exit_gate.data(), self.exit_bias.data()]),
                "batch", None)
        return hn, out, gate

    def _one_pass(self, h, labels):
        """``(normed stream, logits or per-token loss, gate)`` of one pass
        over the stack.  NDArrays."""
        from ..ndarray import NDArray

        def leave(v):
            return tuple(a.jax for a in self._leave(NDArray(v), labels))

        with jax.named_scope("pass"):
            y = run_blocks(self.blocks, h, scan=False, remat=self._remat)
            if self._remat and labels is not None \
                    and isinstance(y.jax, jax.core.Tracer):
                # the (B, T, vocab) logits are remade in the backward pass
                return tuple(map(NDArray, jax.checkpoint(leave)(y.jax)))
            return self._leave(y, labels)

    def _run_passes(self, x, labels):
        """``(outs, gates)``, (P, ...) NDArrays, of the passes over the
        embedded stream ``x``: one scan under a trace."""
        from ..ndarray import NDArray

        if not isinstance(x.jax, jax.core.Tracer):
            return self._loop_passes(x, labels)

        def body(h, _):
            hn, out, gate = self._one_pass(NDArray(h), labels)
            return hn.jax, (out.jax, gate.jax)

        # the body is differentiated as the scan is bound, not as
        # run_blocks traces it: what it names is met here
        with named_residuals() as kept:
            _, (outs, gates) = jax.lax.scan(body, x.jax, None,
                                            length=self.passes)
        self._report_loop("scan", sum(size for _, size in kept))
        return NDArray(outs), NDArray(gates)

    def _loop_passes(self, x, labels):
        """:meth:`_run_passes` pass by pass, for the imperative tape,
        which sees every operation as it runs."""
        outs, gates = [], []
        for _ in range(self.passes):
            x, out, gate = self._one_pass(x, labels)
            outs.append(out)
            gates.append(gate)
        self._report_loop("loop", 0)
        return F.stack(*outs, axis=0), F.stack(*gates, axis=0)

    def _looped(self, x, labels):
        from .. import base as _base

        outs, gates = self._run_passes(x, labels)
        if labels is None:
            return outs, gates
        with jax.named_scope("exit_loss"):
            objective, p = invoke(
                "exit_objective",
                lambda ce, lam: exit_objective(ce, lam, self._beta),
                [outs, gates])
            if _base.is_training():
                stats = self.loop_stats.data()
                mass = p.jax.mean((1, 2))
                stats._rebind(jax.lax.stop_gradient(jnp.stack(
                    [mass, outs.jax.mean((1, 2)), stats.jax[:, 2] + 1.0,
                     stats.jax[:, 3] + mass], axis=1)))
            return objective.mean()


def read_loop_counters(net) -> dict:
    """What the last step with labels left in ``net.loop_stats`` (no
    program is launched): ``loop.exit_mass`` (the mean ``p_t`` over the
    step's tokens, a number a pass), ``loop.pass_loss`` (the mean
    ``CE_t``), ``steps`` and ``loop.exit_mass_sum`` (``loop.exit_mass``
    summed over those steps: two readings give a window's mean)."""
    import numpy as np

    v = np.asarray(net.loop_stats.data().asnumpy(), np.float64)
    return {"loop.exit_mass": v[:, 0].tolist(),
            "loop.pass_loss": v[:, 1].tolist(), "steps": int(v[0, 2]),
            "loop.exit_mass_sum": v[:, 3].tolist()}
