"""Seeded GPT-2 weights, made on the device in one jitted call.

The benchmark owns the weights: the same function feeds the program under
test (through its driver) and the plain reference, so neither takes
anything the other has made.  Layout: per-layer tensors are stacked on a
leading layer axis; dense weights are ``(out, in)``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def seed_key(seed: int):
    """A PRNG key from any non-negative whole number (more than 32 signed
    bits): the low 31 bits seed the key, the rest is folded in."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


# name -> (shape builder, kind).  kind: "w" normal(0, std), "proj" the
# residual projections (std / sqrt(2 L), GPT-2's scaled init), "b" small
# biases, "g" LayerNorm gains around 1.
def _leaves(sizes: dict):
    L, U, V, T = (sizes["n_layer"], sizes["n_embd"], sizes["vocab_size"],
                  sizes["n_positions"])
    F = 4 * U
    return [
        ("wte", (V, U), "w"), ("wpe", (T, U), "pos"),
        ("ln1_g", (L, U), "g"), ("ln1_b", (L, U), "b"),
        ("q_w", (L, U, U), "w"), ("q_b", (L, U), "b"),
        ("k_w", (L, U, U), "w"), ("k_b", (L, U), "b"),
        ("v_w", (L, U, U), "w"), ("v_b", (L, U), "b"),
        ("o_w", (L, U, U), "proj"), ("o_b", (L, U), "b"),
        ("ln2_g", (L, U), "g"), ("ln2_b", (L, U), "b"),
        ("fc1_w", (L, F, U), "w"), ("fc1_b", (L, F), "b"),
        ("fc2_w", (L, U, F), "proj"), ("fc2_b", (L, U), "b"),
        ("lnf_g", (U,), "g"), ("lnf_b", (U,), "b"),
    ]


@functools.lru_cache(maxsize=8)
def _maker(sizes_items: tuple, dtype_name: str):
    sizes = dict(sizes_items)
    dtype = jnp.dtype(dtype_name)
    std = float(sizes.get("initializer_range", 0.02))
    leaves = _leaves(sizes)

    def make(key):
        out = {}
        for i, (name, shape, kind) in enumerate(leaves):
            z = jax.random.normal(jax.random.fold_in(key, i), shape,
                                  jnp.float32)
            if kind == "w":
                a = std * z
            elif kind == "pos":
                a = 0.5 * std * z
            elif kind == "proj":
                a = std * z / (2.0 * sizes["n_layer"]) ** 0.5
            elif kind == "b":
                a = std * z
            else:                     # LayerNorm gain
                a = 1.0 + std * z
            out[name] = a.astype(dtype)
        return out

    return jax.jit(make)


def make_weights(sizes: dict, seed: int, dtype="float32") -> dict:
    """All weights of the stack from ``seed``, as ``dtype`` device arrays."""
    keep = ("n_layer", "n_embd", "vocab_size", "n_positions",
            "initializer_range")
    items = tuple(sorted((k, sizes[k]) for k in keep if k in sizes))
    return _maker(items, jnp.dtype(dtype).name)(seed_key(seed))
