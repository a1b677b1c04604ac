"""Seeded weights of a hybrid Gated DeltaNet / gated attention / sparse
expert stack (HF ``qwen3_next``), made on the device in one jitted call.
As ``weights_hybrid.py`` (whose laws it draws by): the benchmark owns the
weights, and the program under test and the plain reference both take them
from here.

Layout: the leaves of one kind of block are stacked on a leading axis, in
layer order: ``l_*`` over the Gated DeltaNet mixers, ``f_*`` over the full
attention mixers, ``e_*`` over the expert blocks (every layer has one);
dense weights are ``(out, in)``; expert stacks are ``(layer, expert held,
in, out)``.  Only the experts and vocabulary rows this chip holds exist.

The laws (the configuration file states them under ``assumed``):
  dense, router, embedding, head   normal(0, initializer_range); so are
                                   the projections back to the stream
                                   (out_proj, o_proj, expert and shared
                                   down), with no centring: the softmax
                                   routers keep their balance without it
  (1 + w) norm gains, shared gate  normal(0, initializer_range)
  the per-head norm of the rule    1 + normal(0, initializer_range)
  conv weight                      uniform(-1/sqrt(K), 1/sqrt(K)), no bias
  A_log                            log(uniform(1, 16))
  dt_bias                          inverse softplus of dt, log-uniform in
                                   [time_step_min, time_step_max], floor
                                   time_step_floor
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from chipbench.harness.weights import seed_key
from chipbench.harness.weights_hybrid import _draw

BUFFERS = ()                     # every leaf is trained


def sizes_of(config: dict) -> dict:
    """The sizes the benchmark's arithmetic needs, under short names, from
    the configuration's published keys."""
    c = config
    every = int(c["full_attention_interval"])
    pattern = "".join("F" if (i + 1) % every == 0 else "L"
                      for i in range(int(c["num_hidden_layers"])))
    return {
        "pattern": pattern, "units": c["hidden_size"],
        "vocab": c["vocab_size"], "heads": c["num_attention_heads"],
        "kv_heads": c["num_key_value_heads"], "head_dim": c["head_dim"],
        "rotary_dim": int(c["head_dim"] * c["partial_rotary_factor"]),
        "theta": float(c["rope_theta"]),
        "k_heads": c["linear_num_key_heads"],
        "v_heads": c["linear_num_value_heads"],
        "k_dim": c["linear_key_head_dim"],
        "v_dim": c["linear_value_head_dim"],
        "conv": c["linear_conv_kernel_dim"], "chunk": c.get("chunk_size", 64),
        "experts": c["num_experts_published"],
        "experts_held": c["num_experts"],
        "first_expert": c.get("first_expert_held", 0),
        "top_k": c["num_experts_per_tok"],
        "expert_width": c["moe_intermediate_size"],
        "shared_width": c["shared_expert_intermediate_size"],
        "norm_topk": bool(c["norm_topk_prob"]), "eps": c["rms_norm_eps"],
        "init_range": c.get("initializer_range", 0.02),
        "dt_min": c["time_step_min"], "dt_max": c["time_step_max"],
        "dt_floor": c["time_step_floor"],
    }


def leaves(sizes: dict):
    """(name, shape, law) of every leaf."""
    s = sizes
    nl, nf = s["pattern"].count("L"), s["pattern"].count("F")
    n = nl + nf
    u, v = s["units"], s["vocab"]
    kd, vd = s["k_heads"] * s["k_dim"], s["v_heads"] * s["v_dim"]
    hq, hk = s["heads"] * s["head_dim"], s["kv_heads"] * s["head_dim"]
    f, fs, e, held = (s["expert_width"], s["shared_width"], s["experts"],
                      s["experts_held"])
    out = [("embed", (v, u), "w"), ("norm_f", (u,), "b"),
           ("lm_head", (v, u), "w")]
    if nl:
        out += [("l_norm", (nl, u), "b"),
                ("l_qkvz", (nl, 2 * kd + 2 * vd, u), "w"),
                ("l_ba", (nl, 2 * s["v_heads"], u), "w"),
                ("l_conv", (nl, 2 * kd + vd, s["conv"]), "conv"),
                ("l_dt_bias", (nl, s["v_heads"]), "dt"),
                ("l_A_log", (nl, s["v_heads"]), "alog"),
                ("l_gnorm", (nl, s["v_dim"]), "g"),
                ("l_out", (nl, u, vd), "w")]
    if nf:
        out += [("f_norm", (nf, u), "b"), ("f_q", (nf, 2 * hq, u), "w"),
                ("f_k", (nf, hk, u), "w"), ("f_v", (nf, hk, u), "w"),
                ("f_qnorm", (nf, s["head_dim"]), "b"),
                ("f_knorm", (nf, s["head_dim"]), "b"),
                ("f_o", (nf, u, hq), "w")]
    out += [("e_norm", (n, u), "b"), ("e_router", (n, e, u), "w"),
            ("e_gate", (n, held, u, f), "w"), ("e_up", (n, held, u, f), "w"),
            ("e_down", (n, held, f, u), "w"),
            ("e_sh_gate", (n, fs, u), "w"), ("e_sh_up", (n, fs, u), "w"),
            ("e_sh_down", (n, u, fs), "w"), ("e_sh_sig", (n, u), "b")]
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
