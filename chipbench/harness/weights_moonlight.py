"""Seeded weights of a latent-attention expert decoder (HF ``deepseek_v3``,
the keys of Moonlight's configuration), made on the device in one jitted
call.  As ``weights_hybrid.py`` (whose maker's form it keeps, and whose
laws it draws by): the benchmark owns the weights, and the program under
test and the plain reference both take them from here.

Layout: the leaves of one kind of half-layer are stacked on a leading axis
in layer order: ``a_*`` over the attention halves (every layer has one),
``d_*`` over the leading dense feed-forwards, ``e_*`` over the expert
halves; dense weights are ``(out, in)``; expert stacks are ``(layer, expert
held, in, out)``.  Only the experts and vocabulary rows this chip holds
exist.  ``d_gate_up`` is one leaf, gate rows first.

The laws (the configuration file states them under ``assumed``):
  dense, router, embedding, head   normal(0, initializer_range)
  projections back to the stream   normal(0, initializer_range / sqrt(layers))
                                   (o_proj, the dense, expert and shared
                                   down), then CENTRED, as
                                   ``weights_hybrid.py`` centres them and
                                   for its reason: sigmoid routers with a
                                   seeded buffer keep no balance of their own
  plain norm gains                 1 + normal(0, initializer_range)
  e_score_correction_bias          normal(0, initializer_range)  (a buffer)
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from chipbench.harness.weights import seed_key
from chipbench.harness.weights_hybrid import _draw

BUFFERS = ("e_bias",)            # leaves that route but are not trained


def sizes_of(config: dict) -> dict:
    """The sizes the benchmark's arithmetic needs, under short names, from
    the configuration's published keys; every value can be hashed."""
    c = config
    dense = min(int(c["first_k_dense_replace"]), int(c["num_hidden_layers"]))
    if c.get("q_lora_rank") is not None or c["n_group"] != 1 \
            or c["topk_group"] != 1 or c["scoring_func"] != "sigmoid":
        raise ValueError("a low-rank query, grouped routing or another "
                         "scoring is not built")
    return {
        "pattern": "D" * dense + "E" * (c["num_hidden_layers"] - dense),
        "units": c["hidden_size"], "vocab": c["vocab_size"],
        "heads": c["num_attention_heads"], "nope": c["qk_nope_head_dim"],
        "rope": c["qk_rope_head_dim"], "v_dim": c["v_head_dim"],
        "rank": c["kv_lora_rank"], "theta": float(c["rope_theta"]),
        "dense_width": c["intermediate_size"],
        "experts": c["n_routed_experts_published"],
        "experts_held": c["n_routed_experts"],
        "first_expert": c.get("first_expert_held", 0),
        "top_k": c["num_experts_per_tok"],
        "expert_width": c["moe_intermediate_size"],
        "shared_width": c["moe_intermediate_size"] * c["n_shared_experts"],
        "scaling": c["routed_scaling_factor"],
        "norm_topk": bool(c["norm_topk_prob"]), "eps": c["rms_norm_eps"],
        "latent_eps": c["kv_a_layernorm_eps"],
        "init_range": c.get("initializer_range", 0.02),
    }


def leaves(sizes: dict):
    """(name, shape, law) of every leaf."""
    s = sizes
    n = len(s["pattern"])
    nd, ne = (s["pattern"].count(k) for k in "DE")
    u, v, h, r = s["units"], s["vocab"], s["heads"], s["rank"]
    f0, f, fs = s["dense_width"], s["expert_width"], s["shared_width"]
    e, held = s["experts"], s["experts_held"]
    out = [("embed", (v, u), "w"), ("norm_f", (u,), "g"),
           ("lm_head", (v, u), "w"),
           ("a_norm", (n, u), "g"),
           ("a_q", (n, h * (s["nope"] + s["rope"]), u), "w"),
           ("a_kva", (n, r + s["rope"], u), "w"), ("a_cnorm", (n, r), "g"),
           ("a_kvb", (n, h * (s["nope"] + s["v_dim"]), r), "w"),
           ("a_o", (n, u, h * s["v_dim"]), "proj")]
    if nd:
        out += [("d_norm", (nd, u), "g"),
                ("d_gate_up", (nd, 2 * f0, u), "w"),
                ("d_down", (nd, u, f0), "proj")]
    if ne:
        out += [("e_norm", (ne, u), "g"), ("e_router", (ne, e, u), "w"),
                ("e_bias", (ne, e), "b"),
                ("e_gate", (ne, held, u, f), "w"),
                ("e_up", (ne, held, u, f), "w"),
                ("e_down", (ne, held, f, u), "proj_in"),
                ("e_sh_gate", (ne, fs, u), "w"), ("e_sh_up", (ne, fs, u), "w"),
                ("e_sh_down", (ne, u, fs), "proj")]
    return out


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
    """TRAINED parameters from the leaves' shapes (the routing buffers are
    beside them); nothing is allocated."""
    return sum(math.prod(shape) for name, shape, _law in leaves(sizes)
               if name not in BUFFERS)
