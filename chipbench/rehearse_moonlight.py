#!/usr/bin/env python3
"""``rehearse_hybrid.py`` for the latent-attention expert training cell: compile
its step (and, with ``--what reference``, the plain reference's loss and
gradients) at the real size for a DESCRIBED ``v5e`` with no chip attached,
and print the compiler's bytes and the Pallas kernels in the program.  Run
by hand before a chip call; a compile that passes is not a chip run.

    JAX_PLATFORMS=cpu python3 chipbench/rehearse_moonlight.py \\
        --workload train_moonlight_seq8192 [--batch 2] [--seq 4096]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)


from chipbench.rehearse_hybrid import _report  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--what", default="step", choices=("step", "reference"))
    ap.add_argument("--batch", type=int, default=0)
    ap.add_argument("--seq", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

    from chipbench import run as R
    from chipbench.drivers import moonlight_program as prog
    from chipbench.harness.weights_moonlight import leaves

    bench = R.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell, cfg_entry = R.find_cell(bench, args.workload)
    config = R.load_json(os.path.join(ROOT, cfg_entry["file"]))
    traffic = R.load_json(os.path.join(HERE, "traffic",
                                       cell["traffic"] + ".json"))
    tr = config["training"]
    b = traffic["batches"]
    batch, seq = args.batch or int(b["batch"]), args.seq or int(b["seq"])
    sizes = prog.sizes_of(config)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    devices = list(topo.devices)[:1]
    jax.config.update("jax_enable_compilation_cache", False)
    extra = {"workload": args.workload, "batch": batch, "seq": seq}

    if args.what == "reference":
        from chipbench.reference import moonlight_ref as ref

        sh = SingleDeviceSharding(devices[0])
        w = {n: jax.ShapeDtypeStruct(s, jnp.float32, sharding=sh)
             for n, s, _law in leaves(sizes)}
        tok = jax.ShapeDtypeStruct((batch, seq), jnp.int32, sharding=sh)
        ne = sizes["pattern"].count("E")
        chosen = [jax.ShapeDtypeStruct((batch * seq, sizes["top_k"]),
                                       jnp.int32, sharding=sh)] * ne
        t0 = time.monotonic()
        compiled = ref._loss_and_grads.lower(
            w, tok, tok, chosen,
            sizes_items=tuple(sorted(sizes.items())), precision="f32",
            rows=int(tr["reference_attention_rows_per_block"])).compile()
        _report("reference", compiled, time.monotonic() - t0, extra)
        return 0

    import mxnet_tpu as mx
    from mxnet_tpu import amp, base
    from mxnet_tpu import parallel as par
    from mxnet_tpu.models.deepseek_v3 import lm_loss
    from mxnet_tpu.parallel import sharding, trainer as trainer_mod

    # steer the program from outside (as rehearse.py): the backend is said
    # to be "tpu" and placement onto the described devices is skipped
    base.resolve_exec_platform = lambda x=None: "tpu"
    jax.default_backend = lambda: "tpu"
    keep = lambda value, sh: value
    sharding.mesh_device_put = keep
    trainer_mod._mesh_device_put = keep

    if tr.get("amp"):
        amp.init(tr["amp"])
    net = prog.build_net(config, remat=tr.get("remat", True),
                         record_choice_rows=batch * seq)
    from chipbench.harness.weights_moonlight import make_weights
    prog.load_weights(net, make_weights(sizes, 0, "float32"))
    mesh = par.make_mesh(devices=devices)
    sample = tuple(mx.nd.array(jnp.zeros((batch, seq), jnp.int32),
                               dtype="int32") for _ in range(2))
    with par.use_mesh(mesh):
        t = par.ShardedTrainer(
            net, tr["optimizer"], loss=lm_loss,
            optimizer_params={"learning_rate": float(tr["learning_rate"])},
            mesh=mesh)
        t.build(*sample)
        params, aux, states, batch_v = t._device_args(
            *[(s,) for s in sample])
        repl = NamedSharding(mesh, P())

        def sds(vals, shs):
            return tuple(jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=s)
                         for v, s in zip(vals, shs))

        p_sh = tuple(sharding.param_sharding(p, mesh, t.rules)
                     for _n, p in t._trainable)
        a_sh = tuple(sharding.param_sharding(p, mesh, t.rules)
                     for _n, p in t._aux)
        scalar = lambda dt: jax.ShapeDtypeStruct((), dt, sharding=repl)
        key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=repl)
        t0 = time.monotonic()
        compiled = t._step_fn.lower(
            sds(params, p_sh), sds(aux, a_sh),
            sds(states, t._state_shardings),
            sds(batch_v, t.batch_shardings), key, scalar(jnp.float32),
            scalar(jnp.int32)).compile()
        _report("step", compiled, time.monotonic() - t0, extra)
    return 0


if __name__ == "__main__":
    sys.exit(main())
