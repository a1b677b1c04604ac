#!/usr/bin/env python3
"""Time the hybrid stack's kernels alone on the chip, at the sizes of
``train_nemotron_tt_seq8192`` and, for the scan, of
``train_granite_4h_p10`` too (builder's tool; fails without a TPU):

* ``moe_gmm``: up -> relu^2 -> down over the worst-case buffer of 49,152
  rows with 8 experts held, forward and backward, as the ROUTED rows vary:
  even and skewed at the expected 3,072 rows, then 6,144, 12,288 and the
  full buffer — time has to follow the rows routed, not the buffer;
* the scan at both cells' sizes (8 B/C groups at chunk 128; one group at
  chunk 128, which the Granite cell runs, and at its published blocking
  of 256): the kernel path (``ssd_chunk_fwd``, ``ssd_chunk_bwd``)
  against the chunked XLA form and JAX's derivative of it — forward,
  forward + backward, and the backward ALONE (the pullback of ``jax.vjp``,
  its residuals computed outside the timing), then each path's gradients
  against the chunked form in float32.  Until PR 41 the kernel path's
  backward was the chunked form run once more and differentiated;
* the flash kernels at the eight training cells' shapes
  (``FLASH_SHAPES``): forward, and the backward alone as the one fused
  call and as the two calls it falls back to;
* ``gather``: the expert buffer's gather into sorted order
  (``models.moe._gather_routed``: a walk over the routed rows, a chunk a
  turn) alone, at the three expert cells' buffers, with 1/16, 1/4 and ALL
  of the pairs held, from the stream (N, D) and from a buffer-sized source
  (``dy``), against the whole-buffer gather it replaced; then the walk at
  other chunk sizes, at Qwen3-Next's buffer.

    chiprun -- python3 tools/hybrid_kernels_bench.py [gmm] [ssd] [flash] [gather]

(no name: all four parts).
"""
from __future__ import annotations

import functools
import json
import statistics
import sys
import time

import jax
import jax.numpy as jnp

sys.path.insert(0, ".")


def timed(fn, *args, n=10):
    out = fn(*args)
    jax.block_until_ready(out)
    ts = []
    for _ in range(n):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    return 1e3 * statistics.median(ts)


def bench_gmm(key):
    from mxnet_tpu.ops.gmm import gmm_plan, grouped_matmul

    m, u, f, held = 49152, 2688, 1856, 8
    bf = jnp.bfloat16
    rows = jax.random.normal(key, (m, u), bf)
    w_up = 0.02 * jax.random.normal(jax.random.fold_in(key, 1),
                                    (held, u, f), bf)
    w_dn = 0.02 * jax.random.normal(jax.random.fold_in(key, 2),
                                    (held, f, u), bf)

    def ffn(rows, w_up, w_dn, sizes):
        valid = (jnp.arange(m) < jnp.sum(sizes))[:, None]
        h = grouped_matmul(rows, w_up, sizes)
        h = jnp.square(jax.nn.relu(jnp.where(valid, h, 0)))
        y = grouped_matmul(h, w_dn, sizes)
        return jnp.where(valid, y, 0)

    fwd = jax.jit(ffn)
    both = jax.jit(jax.grad(
        lambda r, a, b, s: ffn(r, a, b, s).astype(jnp.float32).sum(),
        argnums=(0, 1, 2)))
    print(json.dumps({"gmm_plan_up": gmm_plan(m, u, f),
                      "gmm_plan_down": gmm_plan(m, f, u)}))
    cases = {"even_3072": [384] * 8, "skewed_3072": [3072] + [0] * 7,
             "ragged_3072": [1000, 24, 700, 0, 500, 348, 300, 200],
             "even_6144": [768] * 8, "even_12288": [1536] * 8,
             "even_49152": [6144] * 8, "none": [0] * 8}
    for name, sizes in cases.items():
        s = jnp.asarray(sizes, jnp.int32)
        print(json.dumps({"moe_gmm": name, "rows": sum(sizes),
                          "fwd_ms": timed(fwd, rows, w_up, w_dn, s),
                          "fwd_bwd_ms": timed(both, rows, w_up, w_dn, s)}),
              flush=True)


def bench_ssd(key):
    from mxnet_tpu.ops.ssd import ssd_scan

    bf = jnp.bfloat16
    b, t, h, p, n = 1, 8192, 64, 64, 128
    ks = jax.random.split(key, 6)
    x = jax.random.normal(ks[0], (b, t, h, p), bf)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, t, h)) - 4.0)
    a = -jnp.exp(jax.random.uniform(ks[2], (h,), minval=0.0, maxval=2.7))
    dy = jax.random.normal(ks[5], (b, t, h, p))
    for g, chunk in ((8, 128), (1, 128), (1, 256)):
        bm = jax.random.normal(ks[3], (b, t, g, n), bf)
        cm = jax.random.normal(ks[4], (b, t, g, n), bf)
        args = (x, dt, a, bm, cm)
        grads = {}
        for impl in ("pallas", "xla"):
            scan = functools.partial(ssd_scan, chunk=chunk, impl=impl)
            fn = jax.jit(scan)
            gr = jax.jit(jax.grad(lambda *v: jnp.sum(scan(*v) * dy),
                                  argnums=(0, 1, 2, 3, 4)))
            y, pull = jax.jit(lambda *v: jax.vjp(scan, *v))(*args)
            back = jax.jit(lambda pull, dy: pull(dy))
            print(json.dumps({"ssd": impl, "groups": g, "chunk": chunk,
                              "fwd_ms": timed(fn, *args),
                              "fwd_bwd_ms": timed(gr, *args),
                              "bwd_ms": timed(back, pull, dy)}), flush=True)
            grads[impl] = (y,) + back(pull, dy)
            del pull
        # both paths against the chunked form in float32 on the same
        # (bf16-rounded) operands: largest gap over largest value
        wide = tuple(v.astype(jnp.float32) for v in args)
        scan = functools.partial(ssd_scan, chunk=chunk, impl="xla")
        y, pull = jax.jit(lambda *v: jax.vjp(scan, *v))(*wide)
        ref = (y,) + jax.jit(lambda pull, dy: pull(dy))(pull, dy)
        del pull
        names = ("y", "dx", "ddt", "da", "db", "dc")
        print(json.dumps({
            "ssd_gap_to_float32": {
                impl: {name: float(jnp.max(jnp.abs(
                    k.astype(jnp.float32) - r)) / jnp.max(jnp.abs(r)))
                    for name, k, r in zip(names, got, ref)}
                for impl, got in grads.items()},
            "groups": g, "chunk": chunk}), flush=True)


# the eight training cells' attention calls: (batch, T, query heads, D),
# key heads, (value heads, Dv), window, (second operand's width, its heads)
FLASH_SHAPES = {
    "ouro": ((1, 8192, 16, 128), 16, None, None, None),
    "moonlight": ((1, 8192, 16, 128), 16, None, None, (64, 1)),
    "mellum2_window": ((1, 8192, 32, 128), 4, None, 1024, None),
    "phi4flash_window": ((1, 8192, 40, 64), 20, (10, 128), 512, None),
    "gpt2": ((8, 1024, 12, 64), 12, None, None, None),
    "nemotron": ((1, 8192, 32, 128), 2, None, None, None),
    "qwen3_next": ((1, 8192, 16, 256), 2, None, None, None),
    "granite": ((1, 8192, 32, 64), 8, None, None, None),
    "phi4flash_full": ((1, 8192, 40, 64), 20, (10, 128), None, None),
}


def bench_flash(key):
    """Forward, forward + backward and the backward ALONE (the pullback
    of ``jax.vjp``, residuals computed outside the timing) at the cells'
    shapes; the backward as the plan has it and, the plan's limit planted
    at 0 from here, as the two calls it falls back to, with the largest
    gap between the two forms' gradients."""
    from mxnet_tpu.ops import flash

    bf = jnp.bfloat16
    ks = jax.random.split(key, 6)
    for name, ((b, t, h, d), hk, values, window, second) in \
            FLASH_SHAPES.items():
        hv, dv = values or (hk, d)
        args = [jax.random.normal(ks[0], (b, t, h, d), bf),
                jax.random.normal(ks[1], (b, t, hk, d), bf),
                jax.random.normal(ks[2], (b, t, hv, dv), bf)]
        if second:
            args += [jax.random.normal(ks[3], (b, t, h, second[0]), bf),
                     jax.random.normal(ks[4], (b, t, second[1], second[0]),
                                       bf)]
        do = jax.random.normal(ks[5], (b, t, h, dv), bf)

        def call(q, k, v, *more):
            return flash.flash_attention(
                q, k, v, causal=True, window=window,
                **dict(zip(("q2", "k2"), more)))

        line, grads, fits = {"flash": name}, {}, flash._VMEM_FUSED
        for form, limit in (("fused", fits), ("split", 0)):
            flash._VMEM_FUSED = limit
            try:
                plan = flash.tile_plan(
                    t, t, d, bf, True, heads=h, kv_heads=hk, window=window,
                    dv=dv, v_heads=hv, d2=second[0] if second else 0,
                    k2_heads=second[1] if second else None)
                if form == "fused":
                    line["fwd_ms"] = timed(jax.jit(call), *args)
                _, pull = jax.jit(lambda *a: jax.vjp(call, *a))(*args)
                back = jax.jit(lambda pull, do: pull(do))
                line[f"bwd_{form}_ms"] = timed(back, pull, do)
                grads[form] = back(pull, do)
                del pull
            finally:
                flash._VMEM_FUSED = fits
            line[f"plan_{form}"] = (plan.backward, plan.dq_bytes,
                                    plan.bwd_vmem)
        line["gap"] = max(float(jnp.max(jnp.abs(
            a.astype(jnp.float32) - b.astype(jnp.float32))))
            for a, b in zip(grads["fused"], grads["split"]))
        print(json.dumps(line), flush=True)


def bench_gather(key):
    from mxnet_tpu.models import moe

    bf, n = jnp.bfloat16, 8192
    whole = jax.jit(lambda src, idx: src[idx])
    walk = jax.jit(moe._gather_routed)

    def sources(top_k, d):
        rows = n * top_k
        order = jax.random.permutation(jax.random.fold_in(key, d), rows)
        return {"stream": (jax.random.normal(key, (n, d), bf),
                           (order % n).astype(jnp.int32)),
                "buffer": (jax.random.normal(key, (rows, d), bf),
                           order.astype(jnp.int32))}

    cells = {"nemotron_tt": (6, 2688), "qwen3_next": (10, 2048),
             "mellum2": (8, 2304)}
    for cell, (top_k, d) in cells.items():
        rows = n * top_k
        for name, (src, idx) in sources(top_k, d).items():
            line = {"gather": cell, "source": name, "buffer_rows": rows,
                    "d": d, "chunk_rows": moe.gather_chunk_rows(rows),
                    "whole_ms": timed(whole, src, idx, n=20)}
            for share in (16, 4, 1):
                line[f"held_1/{share}_ms"] = timed(
                    walk, src, idx, jnp.asarray(rows // share, jnp.int32),
                    n=20)
            print(json.dumps(line), flush=True)
    top_k, d = cells["qwen3_next"]
    rows, default = n * top_k, moe._GATHER_CHUNK_ROWS
    src, idx = sources(top_k, d)["buffer"]
    try:
        for chunk in (256, 512, 1024, 2048, 4096, 8192):
            moe._GATHER_CHUNK_ROWS = chunk
            walk = jax.jit(moe._gather_routed)   # the size is read at trace
            # 5,100 rows: what the cell's seeded routers send this chip
            print(json.dumps({"gather_chunk_rows": chunk, **{
                f"rows_{count}_ms": timed(walk, src, idx,
                                          jnp.asarray(count, jnp.int32), n=20)
                for count in (0, 5100, rows // 4, rows)}}), flush=True)
    finally:
        moe._GATHER_CHUNK_ROWS = default


def main(argv):
    if jax.devices()[0].platform != "tpu":
        print("no TPU", file=sys.stderr)
        return 2
    import mxnet_tpu  # noqa: F401

    parts = {"gmm": bench_gmm, "ssd": bench_ssd, "flash": bench_flash,
             "gather": bench_gather}
    unknown = [name for name in argv if name not in parts]
    if unknown:
        print(f"unknown part {unknown}: one of {list(parts)}",
              file=sys.stderr)
        return 2
    key = jax.random.PRNGKey(0)
    for name in argv or parts:
        parts[name](key)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
