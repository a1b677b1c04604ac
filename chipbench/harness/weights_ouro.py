"""Seeded weights of a looped decoder (HF ``ouro``), made on the device in
one jitted call.  As ``weights_mellum2.py`` (whose maker's form it keeps,
and whose laws it draws by): the benchmark owns the weights, and the
program under test and the plain reference both take them from here.

Layout: ONE stack's leaves, whatever the number of passes: ``a_*`` (the
attention half) and ``m_*`` (the feed-forward half) stacked on a leading
axis in layer order; dense weights are ``(out, in)``; ``m_in`` is gate
over up, ``(2 F, d)``.  Only the vocabulary rows this chip holds exist.

The laws (the configuration file states them under ``assumed``):
  dense, embedding, head, gate_w   normal(0, initializer_range)
  norm gains (four a layer, final) 1 + normal(0, initializer_range)
  gate_b                           0
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from chipbench.harness.weights import seed_key
from chipbench.harness.weights_hybrid import _draw


def sizes_of(config: dict) -> dict:
    """The sizes the benchmark's arithmetic needs, under short names, from
    the configuration's published keys; every value can be hashed."""
    c = config
    if len(c["layer_types"]) != c["num_hidden_layers"] or \
            set(c["layer_types"]) != {"full_attention"}:
        raise ValueError("layer_types and num_hidden_layers disagree, or "
                         "a layer is not full_attention")
    if c.get("rope_scaling") is not None or c["hidden_act"] != "silu" \
            or c["tie_word_embeddings"]:
        raise ValueError("rope_scaling, hidden_act or tie_word_embeddings "
                         "is not what this family's files are written for")
    return {
        "layers": c["num_hidden_layers"], "passes": c["total_ut_steps"],
        "units": c["hidden_size"], "vocab": c["vocab_size"],
        "heads": c["num_attention_heads"],
        "kv_heads": c["num_key_value_heads"], "head_dim": c["head_dim"],
        "mlp_width": c["intermediate_size"], "rope_theta": c["rope_theta"],
        "eps": c["rms_norm_eps"],
        "init_range": c.get("initializer_range", 0.02),
        "beta": c["training"]["exit_beta"],
    }


def leaves(sizes: dict):
    """(name, shape, law) of every leaf."""
    s = sizes
    n, u, v, f = s["layers"], s["units"], s["vocab"], s["mlp_width"]
    hq, hk = s["heads"] * s["head_dim"], s["kv_heads"] * s["head_dim"]
    return [("embed", (v, u), "w"), ("norm_f", (u,), "g"),
            ("lm_head", (v, u), "w"), ("gate_w", (u,), "w"),
            ("gate_b", (1,), "zero"),
            ("a_norm", (n, u), "g"), ("a_q", (n, hq, u), "w"),
            ("a_k", (n, hk, u), "w"), ("a_v", (n, hk, u), "w"),
            ("a_o", (n, u, hq), "w"), ("a_post", (n, u), "g"),
            ("m_norm", (n, u), "g"), ("m_in", (n, 2 * f, u), "w"),
            ("m_out", (n, u, f), "w"), ("m_post", (n, u), "g")]


@functools.lru_cache(maxsize=64)
def _maker(items: tuple, dtype_name: str, only: str = ""):
    sizes = dict(items)
    dtype = jnp.dtype(dtype_name)
    spec = leaves(sizes)

    def make(key):
        return {name: (jnp.zeros(shape, jnp.float32) if law == "zero" else
                       _draw(key, i, shape, law, sizes)).astype(dtype)
                for i, (name, shape, law) in enumerate(spec)
                if not only or name == only}

    return jax.jit(make)


def make_weights(sizes: dict, seed: int, dtype="float32") -> dict:
    """All weights of the stack from ``seed``, as ``dtype`` device arrays."""
    items = tuple(sorted(sizes.items()))
    return _maker(items, jnp.dtype(dtype).name)(seed_key(seed))


def make_leaf(sizes: dict, seed: int, name: str, dtype="float32"):
    """One leaf of :func:`make_weights`, the same values, alone on the
    device."""
    items = tuple(sorted(sizes.items()))
    return _maker(items, jnp.dtype(dtype).name, name)(seed_key(seed))[name]


def parameter_count(sizes: dict) -> int:
    """Parameters from the leaves' shapes; nothing is allocated."""
    return sum(math.prod(shape) for _name, shape, _law in leaves(sizes))
