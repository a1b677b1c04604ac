#!/usr/bin/env python3
"""Time the Gated DeltaNet stack's kernels alone on the chip, at the sizes
of ``train_qwen3_next_seq8192`` (builder's tool; fails without a TPU):

* the gated delta rule at (1, 8192, 32 value heads over 16 key heads,
  128): ``gdn_chunk_fwd`` against the chunked XLA form, forward, and
  forward + backward (one written-out backward for both: PERF.md §5);
* ``moe_gmm`` with gated experts: up, gate -> silu * -> down over the
  worst-case buffer of 81,920 rows with 32 experts of 2,048 x 512 held,
  forward and backward, as the ROUTED rows vary;
* the flash kernels with 16 query heads over 2 key/value heads of 256 at
  T 8,192.

    chiprun -- python3 tools/gdn_kernels_bench.py
"""
from __future__ import annotations

import json
import sys

import jax
import jax.numpy as jnp

sys.path.insert(0, ".")

from tools.hybrid_kernels_bench import timed  # noqa: E402


def main():
    if jax.devices()[0].platform != "tpu":
        print("no TPU", file=sys.stderr)
        return 2
    import mxnet_tpu  # noqa: F401
    from mxnet_tpu.ops.attention import flash_attention
    from mxnet_tpu.ops.gdn import gdn_plan, gdn_scan
    from mxnet_tpu.ops.gmm import gmm_plan, grouped_matmul

    key = jax.random.PRNGKey(0)
    bf = jnp.bfloat16
    b, t, hk, hv, d = 1, 8192, 16, 32, 128
    ks = jax.random.split(key, 6)

    def unit(x):
        return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)

    q = (unit(jax.random.normal(ks[0], (b, t, hk, d))) * d ** -0.5).astype(bf)
    k = unit(jax.random.normal(ks[1], (b, t, hk, d))).astype(bf)
    v = jax.random.normal(ks[2], (b, t, hv, d), bf)
    g = -jnp.exp(jax.random.uniform(ks[3], (hv,), minval=0.0, maxval=2.7)) \
        * jax.nn.softplus(jax.random.normal(ks[4], (b, t, hv)) - 4.0)
    beta = jax.nn.sigmoid(jax.random.normal(ks[5], (b, t, hv)))
    w = jax.random.normal(ks[5], (b, t, hv, d))
    print(json.dumps({"gdn_plan": gdn_plan(b, t, hk, hv, 64)._asdict()}))
    outs = {}
    for impl in ("pallas", "xla"):
        fn = jax.jit(lambda *a, impl=impl: gdn_scan(*a, chunk=64, impl=impl))
        gr = jax.jit(jax.value_and_grad(lambda *a, impl=impl: jnp.sum(
            gdn_scan(*a, chunk=64, impl=impl) * w), argnums=(0, 1, 2, 3, 4)))
        outs[impl] = fn(q, k, v, g, beta)
        print(json.dumps({"gdn": impl,
                          "fwd_ms": timed(fn, q, k, v, g, beta),
                          "fwd_bwd_ms": timed(gr, q, k, v, g, beta)}),
              flush=True)
    print(json.dumps({"gdn_pallas_vs_xla_max_gap": float(jnp.max(jnp.abs(
        outs["pallas"] - outs["xla"]))),
        "max": float(jnp.max(jnp.abs(outs["xla"])))}))

    m, u, f, held = 81920, 2048, 512, 32
    rows = jax.random.normal(key, (m, u), bf)
    w_up, w_gate = (0.02 * jax.random.normal(jax.random.fold_in(key, i),
                                             (held, u, f), bf)
                    for i in (1, 2))
    w_dn = 0.02 * jax.random.normal(jax.random.fold_in(key, 3),
                                    (held, f, u), bf)

    def ffn(rows, w_up, w_gate, w_dn, sizes):
        valid = (jnp.arange(m) < jnp.sum(sizes))[:, None]
        up = jnp.where(valid, grouped_matmul(rows, w_up, sizes), 0)
        gate = jnp.where(valid, grouped_matmul(rows, w_gate, sizes), 0)
        h = (jax.nn.silu(gate.astype(jnp.float32))
             * up.astype(jnp.float32)).astype(bf)
        return jnp.where(valid, grouped_matmul(h, w_dn, sizes), 0)

    fwd = jax.jit(ffn)
    both = jax.jit(jax.grad(
        lambda r, a, c, e, s: ffn(r, a, c, e, s).astype(jnp.float32).sum(),
        argnums=(0, 1, 2, 3)))
    print(json.dumps({"gmm_plan_up": gmm_plan(m, u, f),
                      "gmm_plan_down": gmm_plan(m, f, u)}))
    cases = {"even_5120": [160] * 32, "skewed_5120": [5120] + [0] * 31,
             "even_10240": [320] * 32, "even_81920": [2560] * 32,
             "none": [0] * 32}
    for name, sizes in cases.items():
        s = jnp.asarray(sizes, jnp.int32)
        print(json.dumps({"moe_gmm_glu": name, "rows": sum(sizes),
                          "fwd_ms": timed(fwd, rows, w_up, w_gate, w_dn, s),
                          "fwd_bwd_ms": timed(both, rows, w_up, w_gate, w_dn,
                                              s)}), flush=True)

    q = jax.random.normal(ks[0], (1, 8192, 16, 256), bf)
    k = jax.random.normal(ks[1], (1, 8192, 2, 256), bf)
    v = jax.random.normal(ks[2], (1, 8192, 2, 256), bf)
    fn = jax.jit(lambda q, k, v: flash_attention(q, k, v, causal=True))
    gr = jax.jit(jax.grad(lambda q, k, v: flash_attention(
        q, k, v, causal=True).astype(jnp.float32).sum(), argnums=(0, 1, 2)))
    print(json.dumps({"flash_heads": 16, "kv_heads": 2, "head_dim": 256,
                      "fwd_ms": timed(fn, q, k, v),
                      "fwd_bwd_ms": timed(gr, q, k, v)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
