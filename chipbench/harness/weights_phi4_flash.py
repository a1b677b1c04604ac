"""Seeded weights of one contiguous stage of a SambaY decoder-hybrid-decoder
stack (HF ``phi4flash``), made on the device in one jitted call.  As
``weights_hybrid.py`` (whose laws it draws by): the benchmark owns the
weights, and the program under test and the plain reference both take
them from here.

Layout: the leaves of one kind of mixer are stacked on a leading axis over
the layers of that kind, in the order the stage holds them: ``m_*`` over
the Mamba-1 layers (``mamba`` and ``mamba_mem``), ``a_*`` over the
differential self-attention layers (``swa`` and ``full``), ``c_*`` over
the cross-attention layers, ``g_*`` over the Gated Memory Units; ``n*``
and ``f_*``, the two LayerNorms and the feed-forward every layer has,
over all of them; dense weights are ``(out, in)``.  The head is tied:
there is no head leaf.

The laws (the configuration file states them under ``assumed``):
  dense, embedding, biases  normal(0, initializer_range)
  LayerNorm, sub-norm gains, D   1 + normal(0, initializer_range)
  conv weight             uniform(-1/sqrt(K), 1/sqrt(K)); bias normal(0, range)
  A_log[c, n]             log(n + 1) + normal(0, range)   (S4D-real)
  dt_bias                 inverse softplus of dt, log-uniform in
                          [time_step_min, time_step_max], floor time_step_floor
  dt_proj                 uniform(-R^-1/2, R^-1/2)
  the four lambda vectors normal(0, lambda_std)
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from chipbench.harness.weights import seed_key
from chipbench.harness.weights_hybrid import _draw as _draw_hybrid

MAMBA, SELF, CROSS, GMU = "MW", "SF", "C", "G"


def kinds_of(num_layers: int) -> str:
    """One letter a published layer: M Mamba-1, S windowed and F full
    differential attention, W the Mamba-1 layer that emits the memory, G a
    Gated Memory Unit, C differential cross-attention."""
    half = num_layers // 2
    return "".join(
        ("M" if i < half else "W" if i == half else "G") if i % 2 == 0 else
        ("S" if i < half else "F" if i == half + 1 else "C")
        for i in range(num_layers))


def sizes_of(config: dict) -> dict:
    """The sizes the benchmark's arithmetic needs, under short names, from
    the configuration's published keys and the sizes it assumes."""
    c = config
    held = tuple(c["layers_held"])
    if len(held) != c["num_hidden_layers"]:
        raise ValueError("layers_held and num_hidden_layers disagree")
    if not c["tie_word_embeddings"] or c["mlp_bias"] or c["lm_head_bias"]:
        raise ValueError("this stack's head is tied and its feed-forward "
                         "and head have no bias")
    letters = kinds_of(c["published"]["num_hidden_layers"])
    return {
        "pattern": "".join(letters[i] for i in held), "layers": held,
        "layers_published": c["published"]["num_hidden_layers"],
        "units": c["hidden_size"], "vocab": c["vocab_size"],
        "vocab_published": c["published"]["vocab_size"],
        "heads": c["num_attention_heads"],
        "kv_heads": c["num_key_value_heads"], "head_dim": c["head_dim"],
        "window": c["sliding_window"], "mlp_width": c["intermediate_size"],
        "d_inner": c["mamba_expand"] * c["hidden_size"],
        "state": c["mamba_d_state"], "conv": c["mamba_d_conv"],
        "dt_rank": c["mamba_dt_rank"], "eps": c["layer_norm_eps"],
        "init_range": c["initializer_range"],
        "lambda_std": c["lambda_std"], "dt_min": c["time_step_min"],
        "dt_max": c["time_step_max"], "dt_floor": c["time_step_floor"],
    }


def leaves(sizes: dict):
    """(name, shape, law) of every leaf."""
    s = sizes
    pat = s["pattern"]
    n = len(pat)
    nm, na, nc, ng = (sum(pat.count(k) for k in kinds)
                      for kinds in (MAMBA, SELF, CROSS, GMU))
    u, v, f = s["units"], s["vocab"], s["mlp_width"]
    di, st, r, d = s["d_inner"], s["state"], s["dt_rank"], s["head_dim"]
    hq, hk = s["heads"] * d, s["kv_heads"] * d
    out = [("embed", (v, u), "w"), ("norm_f_g", (u,), "g"),
           ("norm_f_b", (u,), "b")]
    if nm:
        out += [("m_in_proj", (nm, 2 * di, u), "w"),
                ("m_conv_w", (nm, di, s["conv"]), "conv"),
                ("m_conv_b", (nm, di), "b"),
                ("m_x_proj", (nm, r + 2 * st, di), "w"),
                ("m_dt_proj", (nm, di, r), "dtw"),
                ("m_dt_bias", (nm, di), "dt"),
                ("m_A_log", (nm, di, st), "s4d"),
                ("m_D", (nm, di), "g"),
                ("m_out_proj", (nm, u, di), "w")]
    for pre, count, wide in (("a", na, hq + 2 * hk), ("c", nc, hq)):
        if count:
            out += [(f"{pre}_qkv", (count, wide, u), "w"),
                    (f"{pre}_qkv_b", (count, wide), "b"),
                    (f"{pre}_o", (count, u, hq), "w"),
                    (f"{pre}_o_b", (count, u), "b"),
                    (f"{pre}_lambdas", (count, 4, d), "lam"),
                    (f"{pre}_subln", (count, 2 * d), "g")]
    if ng:
        out += [("g_in", (ng, di, u), "w"), ("g_out", (ng, u, di), "w")]
    out += [("n1_g", (n, u), "g"), ("n1_b", (n, u), "b"),
            ("n2_g", (n, u), "g"), ("n2_b", (n, u), "b"),
            ("f_fc1", (n, 2 * f, u), "w"), ("f_fc2", (n, u, f), "w")]
    return out


def compared_apart(sizes: dict) -> dict:
    """Leaves the comparison reads in parts, {leaf: [(name, lo, hi)]} along
    the axis after the layers': the self-attention's one bias vector is
    three parameters, the query's, the key's and the value's bias.  A
    naming of parts and nothing else: which parts a number reads is
    decided from the reference's own numbers (``train_p4f.compare``)."""
    hq = sizes["heads"] * sizes["head_dim"]
    hk = sizes["kv_heads"] * sizes["head_dim"]
    if not any(k in sizes["pattern"] for k in SELF):
        return {}
    return {"a_qkv_b": [("a_q_b", 0, hq), ("a_k_b", hq, hq + hk),
                        ("a_v_b", hq + hk, hq + 2 * hk)]}


def _draw(key, i, shape, law, sizes):
    k = jax.random.fold_in(key, i)
    if law == "s4d":
        start = jnp.log(jnp.arange(1, shape[-1] + 1, dtype=jnp.float32))
        return start + float(sizes["init_range"]) * jax.random.normal(
            k, shape, jnp.float32)
    if law == "dtw":
        bound = 1.0 / math.sqrt(shape[-1])
        return jax.random.uniform(k, shape, jnp.float32, -bound, bound)
    if law == "lam":
        return float(sizes["lambda_std"]) * jax.random.normal(
            k, shape, jnp.float32)
    return _draw_hybrid(key, i, shape, law, sizes)


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
    """All weights of the stage from ``seed``, as ``dtype`` device arrays."""
    items = tuple(sorted(sizes.items()))
    return _maker(items, jnp.dtype(dtype).name)(seed_key(seed))


def make_leaf(sizes: dict, seed: int, name: str, dtype="float32"):
    """One leaf of :func:`make_weights`, the same values, alone on the
    device."""
    items = tuple(sorted(sizes.items()))
    return _maker(items, jnp.dtype(dtype).name, name)(seed_key(seed))[name]


def parameter_count(sizes: dict) -> int:
    """Parameters from the leaves' shapes."""
    return sum(math.prod(shape) for _name, shape, _law in leaves(sizes))
