"""Seeded weights of a sliding-window / full attention expert decoder (HF
``mellum``), made on the device in one jitted call.  As
``weights_qwen3_next.py`` (whose maker's form it keeps, and whose laws it
draws by): the benchmark owns the weights, and the program under test and
the plain reference both take them from here.

Layout: the leaves of one half of a layer are stacked on a leading axis in
layer order: ``a_*`` over the attention halves (sliding and full alike:
their shapes are the same, their kind is ``sizes["pattern"]``), ``e_*``
over the expert halves; dense weights are ``(out, in)``; expert stacks are
``(layer, expert held, in, out)``.  Only the experts and vocabulary rows
this chip holds exist.

The laws (the configuration file states them under ``assumed``):
  dense, router, embedding, head   normal(0, initializer_range); so are
                                   the projections back to the stream
                                   (o_proj, expert down), with no centring
  plain norm gains                 1 + normal(0, initializer_range)
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from chipbench.harness.weights import seed_key
from chipbench.harness.weights_hybrid import _draw

BUFFERS = ()                     # every leaf is trained
KINDS = {"sliding_attention": "S", "full_attention": "F"}


def _entry(rope: dict) -> tuple:
    return tuple(sorted(rope.items()))


def sizes_of(config: dict) -> dict:
    """The sizes the benchmark's arithmetic needs, under short names, from
    the configuration's published keys; every value can be hashed."""
    c = config
    if len(c["layer_types"]) != c["num_hidden_layers"] or \
            set(c["mlp_layer_types"]) != {"sparse"}:
        raise ValueError("layer_types, mlp_layer_types and "
                         "num_hidden_layers disagree")
    return {
        "pattern": "".join(KINDS[k] for k in c["layer_types"]),
        "units": c["hidden_size"], "vocab": c["vocab_size"],
        "heads": c["num_attention_heads"],
        "kv_heads": c["num_key_value_heads"], "head_dim": c["head_dim"],
        "window": c["sliding_window"],
        "rope_S": _entry(c["rope_parameters"]["sliding_attention"]),
        "rope_F": _entry(c["rope_parameters"]["full_attention"]),
        "experts": c["num_experts_published"],
        "experts_held": c["num_experts"],
        "first_expert": c.get("first_expert_held", 0),
        "top_k": c["num_experts_per_tok"],
        "expert_width": c["moe_intermediate_size"],
        "norm_topk": bool(c["norm_topk_prob"]), "eps": c["rms_norm_eps"],
        "init_range": c.get("initializer_range", 0.02),
    }


def leaves(sizes: dict):
    """(name, shape, law) of every leaf."""
    s = sizes
    n = len(s["pattern"])
    u, v, d = s["units"], s["vocab"], s["head_dim"]
    hq, hk = s["heads"] * d, s["kv_heads"] * d
    f, e, held = s["expert_width"], s["experts"], s["experts_held"]
    return [("embed", (v, u), "w"), ("norm_f", (u,), "g"),
            ("lm_head", (v, u), "w"),
            ("a_norm", (n, u), "g"), ("a_q", (n, hq, u), "w"),
            ("a_k", (n, hk, u), "w"), ("a_v", (n, hk, u), "w"),
            ("a_qnorm", (n, d), "g"), ("a_knorm", (n, d), "g"),
            ("a_o", (n, u, hq), "w"),
            ("e_norm", (n, u), "g"), ("e_router", (n, e, u), "w"),
            ("e_gate", (n, held, u, f), "w"), ("e_up", (n, held, u, f), "w"),
            ("e_down", (n, held, f, u), "w")]


@functools.lru_cache(maxsize=64)
def _maker(items: tuple, dtype_name: str, only: str = ""):
    sizes = dict(items)
    dtype = jnp.dtype(dtype_name)
    spec = leaves(sizes)

    def make(key):
        return {name: _draw(key, i, shape, law, sizes).astype(dtype)
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
