"""Seeded weights of a hybrid Mamba-2 / expert / attention stack, made on
the device in one jitted call.  As ``weights.py``: the benchmark owns the
weights, and the program under test and the plain reference both take them
from here.

Layout: the leaves of one kind of layer are stacked on a leading axis over
the layers of that kind, in the order the pattern has them (``m_*`` over
the ``M`` layers, ``e_*`` over ``E``, ``a_*`` over ``*``); dense weights
are ``(out, in)``; expert stacks are ``(layer, expert held, in, out)``.
Only the experts and vocabulary rows this chip holds exist.

The laws (the configuration file states them under ``assumed``):
  dense, router, embedding, head   normal(0, initializer_range)
  projections back to the stream   normal(0, initializer_range / sqrt(layers)),
                                   (out_proj, o_proj, expert and shared down)
                                   then CENTRED: each output unit's weights
                                   sum to zero over its inputs (see below)
  norm gains                       1 + normal(0, initializer_range)
  conv weight                      uniform(-1/sqrt(K), 1/sqrt(K)); bias normal(0, range)
  A_log                            log(uniform(1, 16))
  dt_bias                          inverse softplus of dt, log-uniform in
                                   [time_step_min, time_step_max], floor time_step_floor
  D                                1 + normal(0, initializer_range)
  e_score_correction_bias          normal(0, initializer_range)  (a buffer: no gradient)

Why the projections are centred.  SiLU and relu^2 features have positive
means, so with plain random projections every mixer adds the SAME vector
to every token's stream; the router scores that common part identically
for all tokens, and by the third expert layer a few experts take several
times their share while the eight held here get half of theirs (my chip
runs, PR 26: 0.17 pairs a token and layer against the 0.375 of even
routing, the largest held expert at 4 times the mean).  A trained model's
correction bias removes exactly this; a seeded buffer cannot.  Centring
the projections removes the common part at its source, and seeded routers
then route near-evenly at every depth (held share 6.1-7.2% a layer against
6.25%, float32 on the CPU at T 8,192), as the deployment's do.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from chipbench.harness.weights import seed_key

BUFFERS = ("e_bias",)            # leaves that route but are not trained


def sizes_of(config: dict) -> dict:
    """The sizes the benchmark's arithmetic needs, under short names, from
    the configuration's published keys."""
    c = config
    pattern = c["hybrid_override_pattern"]
    if len(pattern) != c["num_hidden_layers"]:
        raise ValueError("hybrid_override_pattern and num_hidden_layers "
                         "disagree")
    return {
        "pattern": pattern, "units": c["hidden_size"],
        "vocab": c["vocab_size"], "heads": c["num_attention_heads"],
        "kv_heads": c["num_key_value_heads"], "head_dim": c["head_dim"],
        "m_heads": c["mamba_num_heads"], "m_head_dim": c["mamba_head_dim"],
        "groups": c["n_groups"], "state": c["ssm_state_size"],
        "conv": c["conv_kernel"], "chunk": c["chunk_size"],
        "experts": c["n_routed_experts_published"],
        "experts_held": c["n_routed_experts"],
        "first_expert": c.get("first_expert_held", 0),
        "top_k": c["num_experts_per_tok"],
        "expert_width": c["moe_intermediate_size"],
        "shared_width": c["moe_shared_expert_intermediate_size"],
        "scaling": c["routed_scaling_factor"],
        "norm_topk": bool(c["norm_topk_prob"]),
        "eps": c["layer_norm_epsilon"],
        "init_range": c.get("initializer_range", 0.02),
        "dt_min": c["time_step_min"], "dt_max": c["time_step_max"],
        "dt_floor": c["time_step_floor"],
    }


def leaves(sizes: dict):
    """(name, shape, law) of every leaf."""
    s = sizes
    nm, ne, na = (s["pattern"].count(k) for k in "ME*")
    u, v = s["units"], s["vocab"]
    d_inner = s["m_heads"] * s["m_head_dim"]
    conv_dim = d_inner + 2 * s["groups"] * s["state"]
    hq, hk = s["heads"] * s["head_dim"], s["kv_heads"] * s["head_dim"]
    f, fs, e, held = (s["expert_width"], s["shared_width"], s["experts"],
                      s["experts_held"])
    out = [("embed", (v, u), "w"), ("norm_f", (u,), "g"),
           ("lm_head", (v, u), "w")]
    if nm:
        out += [("m_norm", (nm, u), "g"),
                ("m_in_proj", (nm, d_inner + conv_dim + s["m_heads"], u), "w"),
                ("m_conv_w", (nm, conv_dim, s["conv"]), "conv"),
                ("m_conv_b", (nm, conv_dim), "b"),
                ("m_dt_bias", (nm, s["m_heads"]), "dt"),
                ("m_A_log", (nm, s["m_heads"]), "alog"),
                ("m_D", (nm, s["m_heads"]), "g"),
                ("m_norm_w", (nm, d_inner), "g"),
                ("m_out_proj", (nm, u, d_inner), "proj")]
    if ne:
        out += [("e_norm", (ne, u), "g"), ("e_router", (ne, e, u), "w"),
                ("e_bias", (ne, e), "b"),
                ("e_up", (ne, held, u, f), "w"),
                ("e_down", (ne, held, f, u), "proj_in"),
                ("e_shared_up", (ne, fs, u), "w"),
                ("e_shared_down", (ne, u, fs), "proj")]
    if na:
        out += [("a_norm", (na, u), "g"), ("a_q", (na, hq, u), "w"),
                ("a_k", (na, hk, u), "w"), ("a_v", (na, hk, u), "w"),
                ("a_o", (na, u, hq), "proj")]
    return out


def _draw(key, i, shape, law, sizes):
    std = float(sizes["init_range"])
    k = jax.random.fold_in(key, i)
    if law in ("w", "b"):
        return std * jax.random.normal(k, shape, jnp.float32)
    if law in ("proj", "proj_in"):
        a = std / math.sqrt(len(sizes["pattern"])) * jax.random.normal(
            k, shape, jnp.float32)
        # (out, in) weights sum to zero over "in"; expert stacks are
        # (.., in, out)
        axis = -1 if law == "proj" else -2
        return a - jnp.mean(a, axis=axis, keepdims=True)
    if law == "g":
        return 1.0 + std * jax.random.normal(k, shape, jnp.float32)
    if law == "conv":
        bound = 1.0 / math.sqrt(shape[-1])
        return jax.random.uniform(k, shape, jnp.float32, -bound, bound)
    if law == "alog":
        return jnp.log(jax.random.uniform(k, shape, jnp.float32, 1.0, 16.0))
    lo, hi = math.log(sizes["dt_min"]), math.log(sizes["dt_max"])
    dt = jnp.exp(jax.random.uniform(k, shape, jnp.float32, lo, hi))
    dt = jnp.maximum(dt, sizes["dt_floor"])
    return dt + jnp.log(-jnp.expm1(-dt))                  # dt_bias


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
    device (a trained state is compared leaf by leaf: a second whole copy
    of the weights would not fit beside it)."""
    items = tuple(sorted(sizes.items()))
    return _maker(items, jnp.dtype(dtype).name, name)(seed_key(seed))[name]
