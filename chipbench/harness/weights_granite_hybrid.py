"""Seeded weights of a dense hybrid Mamba-2 / attention stack under the
Granite family's multipliers, made on the device in one jitted call.  As
``weights_hybrid.py`` (whose laws it draws by): the benchmark owns the
weights, and the program under test and the plain reference both take
them from here.

Layout: the leaves of one kind of layer are stacked on a leading axis over
the layers of that kind, in the order ``layer_types`` has them (``m_*``
over the Mamba layers, ``a_*`` over the attention layers, ``f_*``, the
feed-forward every layer has, over all of them); dense weights are
``(out, in)``.  The head is tied: there is no head leaf.

The laws (the configuration file states them under ``assumed``):
  dense, embedding        normal(0, initializer_range)
  norm gains, D           1 + normal(0, initializer_range)
  conv weight             uniform(-1/sqrt(K), 1/sqrt(K)); bias normal(0, range)
  A_log                   log(uniform(1, 16))
  dt_bias                 inverse softplus of dt, log-uniform in
                          [time_step_min, time_step_max], floor time_step_floor
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from chipbench.harness.weights import seed_key
from chipbench.harness.weights_hybrid import _draw


def sizes_of(config: dict) -> dict:
    """The sizes the benchmark's arithmetic needs, under short names, from
    the configuration's published keys (``pattern``: M a Mamba-2 layer, A
    an attention layer)."""
    c = config
    types = c["layer_types"]
    if len(types) != c["num_hidden_layers"]:
        raise ValueError("layer_types and num_hidden_layers disagree")
    if c["num_local_experts"] or not c["tie_word_embeddings"]:
        raise ValueError("this stack is dense and its head is tied")
    return {
        "pattern": "".join({"mamba": "M", "attention": "A"}[t]
                           for t in types),
        "units": c["hidden_size"], "vocab": c["vocab_size"],
        "heads": c["num_attention_heads"],
        "kv_heads": c["num_key_value_heads"], "head_dim": c["head_dim"],
        "m_heads": c["mamba_n_heads"], "m_head_dim": c["mamba_d_head"],
        "groups": c["mamba_n_groups"], "state": c["mamba_d_state"],
        "conv": c["mamba_d_conv"],
        "chunk": c["program"].get("scan_chunk", c["mamba_chunk_size"]),
        "mlp_width": c["shared_intermediate_size"],
        "emb_mult": float(c["embedding_multiplier"]),
        "res_mult": float(c["residual_multiplier"]),
        "attn_mult": float(c["attention_multiplier"]),
        "logits_scaling": float(c["logits_scaling"]),
        "eps": c["rms_norm_eps"], "init_range": c["initializer_range"],
        "dt_min": c["time_step_min"], "dt_max": c["time_step_max"],
        "dt_floor": c["time_step_floor"],
    }


def leaves(sizes: dict):
    """(name, shape, law) of every leaf."""
    s = sizes
    nm, na = s["pattern"].count("M"), s["pattern"].count("A")
    n = nm + na
    u, v, f = s["units"], s["vocab"], s["mlp_width"]
    d_inner = s["m_heads"] * s["m_head_dim"]
    conv_dim = d_inner + 2 * s["groups"] * s["state"]
    hq, hk = s["heads"] * s["head_dim"], s["kv_heads"] * s["head_dim"]
    out = [("embed", (v, u), "w"), ("norm_f", (u,), "g")]
    if nm:
        out += [("m_norm", (nm, u), "g"),
                ("m_in_proj", (nm, d_inner + conv_dim + s["m_heads"], u), "w"),
                ("m_conv_w", (nm, conv_dim, s["conv"]), "conv"),
                ("m_conv_b", (nm, conv_dim), "b"),
                ("m_dt_bias", (nm, s["m_heads"]), "dt"),
                ("m_A_log", (nm, s["m_heads"]), "alog"),
                ("m_D", (nm, s["m_heads"]), "g"),
                ("m_norm_w", (nm, d_inner), "g"),
                ("m_out_proj", (nm, u, d_inner), "w")]
    if na:
        out += [("a_norm", (na, u), "g"), ("a_q", (na, hq, u), "w"),
                ("a_k", (na, hk, u), "w"), ("a_v", (na, hk, u), "w"),
                ("a_o", (na, u, hq), "w")]
    out += [("f_norm", (n, u), "g"), ("f_in", (n, 2 * f, u), "w"),
            ("f_out", (n, u, f), "w")]
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
    """Parameters from the leaves' shapes."""
    total = 0
    for _name, shape, _law in leaves(sizes):
        n = 1
        for d in shape:
            n *= d
        total += n
    return total
