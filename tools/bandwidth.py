#!/usr/bin/env python
"""Collective bandwidth benchmark (parity: tools/bandwidth/ — the kvstore
allreduce bandwidth measurement, SURVEY.md §2.7/§6).

Measures psum (allreduce) and all_gather throughput over the device mesh
for a sweep of tensor sizes — the numbers that size dp gradient exchange
(KVStore's role).  On one chip the collectives are no-ops; on a real
mesh/pod the same script reports ICI/DCN bandwidth.

    python tools/bandwidth.py [--sizes-mb 1 4 16 64] [--iters 20]
"""
from __future__ import annotations

import argparse
import sys
import time

import numpy as onp

sys.path.insert(0, __file__.rsplit("/", 2)[0])


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--sizes-mb", type=float, nargs="+",
                    default=[1, 4, 16, 64])
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--cpu-devices", type=int, default=0,
                    help="force a virtual CPU mesh of this size")
    args = ap.parse_args(argv)

    if args.cpu_devices:
        from mxnet_tpu.utils.platform import force_cpu
        force_cpu(args.cpu_devices)
    else:
        from mxnet_tpu.utils.platform import require_tpu
        require_tpu()

    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    devs = onp.array(jax.devices())
    n = len(devs)
    mesh = Mesh(devs, ("dp",))
    print(f"# {n} x {devs.flat[0].device_kind} mesh", flush=True)

    shard = NamedSharding(mesh, P("dp"))
    repl = NamedSharding(mesh, P())

    # dp-sharded input, replicated reduction out: XLA lowers this to the
    # hardware allreduce over the mesh axis
    psum_fn = jax.jit(lambda x: jnp.sum(x, axis=0),
                      out_shardings=repl)
    gather_fn = jax.jit(lambda x: x.reshape(-1), out_shardings=repl)

    print(f"{'size':>8} {'allreduce GB/s':>15} {'allgather GB/s':>15}")
    for mb in args.sizes_mb:
        elems = int(mb * 1e6 / 4)
        per = max(1, elems // n)
        x = jax.device_put(
            onp.random.rand(n, per).astype(onp.float32), shard)
        nbytes = n * per * 4

        def timeit(fn):
            o = fn(x)
            jax.block_until_ready(o)
            t0 = time.perf_counter()
            for _ in range(args.iters):
                o = fn(x)
            jax.block_until_ready(o)
            dt = (time.perf_counter() - t0) / args.iters
            # allreduce moves 2*(n-1)/n of the data per classic ring
            return nbytes / dt / 1e9

        print(f"{mb:>6}MB {timeit(psum_fn):>15.2f} "
              f"{timeit(gather_fn):>15.2f}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
