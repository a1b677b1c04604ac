"""Faults planted UNDER the latent-attention expert cell's timed path, for
the builder's chip runs and the CPU tests: each is a context manager that
changes the program (never the reference) the way a wrong implementation
would, so that a run inside it has to come out not ``correct``.  Nothing
here is on any measured path.

    scale_of_128        the softmax scale is 1/sqrt(128): the head product's
                        width, not the score's 192
    rope_on_nope        positions turn the first 64 of the 128 head-product
                        dimensions of q and k; the 64 rotary ones stay still
    latent_norm_out     the latent goes to kv_b_proj without its RMS norm
    rope_key_per_head   every query head reads a rotary key of its own (the
                        shared key's dimensions rolled by the head's index):
                        not ONE key shared by all
    scaling_out         routed_scaling_factor left out (1.0)
    bias_in_weights     the top-k weights are taken from s + b, the
                        correction bias inside them
    shared_half         the shared expert is one expert wide (1,408), not two
"""
from __future__ import annotations

import contextlib
from unittest import mock

FAULTS = ("scale_of_128", "rope_on_nope", "latent_norm_out",
          "rope_key_per_head", "scaling_out", "bias_in_weights",
          "shared_half")


@contextlib.contextmanager
def planted(fault: str):
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.models import deepseek_v3 as model
    from mxnet_tpu.models import moe
    from mxnet_tpu.ops import attention

    flash, turn = attention.flash_attention, attention.rotary_embedding
    patches = []

    def scores(change):
        """``change(q, k, q2, k2, kw)`` on the way into the score."""
        def call(q, k, v, *, q2, k2, **kw):
            q, k, q2, k2, kw = change(q, k, q2, k2, kw)
            return flash(q, k, v, q2=q2, k2=k2, **kw)
        patches.append(mock.patch.object(attention, "flash_attention", call))

    if fault == "scale_of_128":
        scores(lambda q, k, q2, k2, kw: (
            q, k, q2, k2, dict(kw, scale=q.shape[-1] ** -0.5)))
    elif fault == "rope_on_nope":
        patches.append(mock.patch.object(
            attention, "rotary_embedding", lambda x, **kw: x))

        def on_nope(q, k, q2, k2, kw):
            theta = model._CONFIGS["moonlight_16b_a3b"]["rope_theta"]
            at = dict(theta=float(theta), rotary_dim=q2.shape[-1])
            return turn(q, **at), turn(k, **at), q2, k2, kw
        scores(on_nope)
    elif fault == "latent_norm_out":
        patches.append(mock.patch.object(
            model, "rms", lambda x, gain, eps: x.astype(jnp.float32)))
    elif fault == "rope_key_per_head":
        scores(lambda q, k, q2, k2, kw: (q, k, q2, jnp.concatenate(
            [jnp.roll(k2, h, axis=-1) for h in range(q.shape[2])], 2), kw))
    elif fault == "scaling_out":
        real = moe.route_sigmoid_topk
        patches.append(mock.patch.object(
            moe, "route_sigmoid_topk",
            lambda *a, scaling=1.0, **kw: real(*a, scaling=1.0, **kw)))
    elif fault == "bias_in_weights":
        def biased(x, w_router, choice_bias, *, top_k, norm_topk=True,
                   scaling=1.0, chosen=None):
            s = jax.nn.sigmoid(jnp.einsum(
                "nd,ed->ne", x.astype(jnp.float32),
                w_router.astype(jnp.float32),
                precision=jax.lax.Precision.HIGHEST)) \
                + choice_bias.astype(jnp.float32)[None, :]
            if chosen is None:
                _, chosen = jax.lax.top_k(jax.lax.stop_gradient(s), top_k)
            chosen = chosen.astype(jnp.int32)
            w = jnp.take_along_axis(s, chosen, axis=1)
            if norm_topk:
                w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
            return w * scaling, chosen
        patches.append(mock.patch.object(moe, "route_sigmoid_topk", biased))
    elif fault == "shared_half":
        real = moe.swiglu_mlp

        def half(x, w_gate, w_up, w_down, cd=None):
            f = w_gate.shape[0] // 2
            return real(x, w_gate[:f], w_up[:f], w_down[:, :f], cd)
        patches.append(mock.patch.object(moe, "swiglu_mlp", half))
    else:
        raise ValueError(f"fault {fault!r} is not one of {FAULTS}")
    with contextlib.ExitStack() as stack:
        for p in patches:
            stack.enter_context(p)
        yield
