#!/usr/bin/env python3
"""Compile a training cell's step at its real size for a DESCRIBED
``v5e:2x2`` (no chip attached) and print what the chip's compiler says:
bytes per device, the Pallas kernels and the collectives in the program.
Run by hand before a chip call; a compile that passes is not a chip run.

    JAX_PLATFORMS=cpu python3 chipbench/rehearse.py --workload <cell> \\
        [--layout dp=2,tp=2] [--remat dots]
    JAX_PLATFORMS=cpu python3 chipbench/rehearse.py --config <config> \\
        --traffic seq1024 --chips 4 --layout tp=4 --remat dots

The program builds its mesh from live devices and places its own
parameters, so this script steers it from outside, adding no option to
the program: the default backend is said to be "tpu" (the code under
test then takes its kernel paths), and placement onto the described
devices is skipped (they hold no arrays); the step is lowered with
shapes carrying the shardings the trainer chose.
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


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default="")
    ap.add_argument("--config", default="", help="with --traffic and "
                    "--chips: a cell that BENCHMARK.json does not hold yet")
    ap.add_argument("--traffic", default="")
    ap.add_argument("--layout", default="")
    ap.add_argument("--remat", default="")
    ap.add_argument("--chips", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import NamedSharding, PartitionSpec as P

    from chipbench import run as R
    from chipbench.drivers import gpt2_program as prog
    from chipbench.harness.weights import make_weights

    if args.workload:
        bench = R.load_json(os.path.join(ROOT, "BENCHMARK.json"))
        cell, cfg_entry = R.find_cell(bench, args.workload)
        cfg_file, mix = cfg_entry["file"], cell["traffic"]
    else:
        cell = {"chips": args.chips}
        cfg_file = os.path.join("chipbench", "configs",
                                args.config + ".json")
        mix = args.traffic
    config = R.load_json(os.path.join(ROOT, cfg_file))
    traffic = R.load_json(os.path.join(HERE, "traffic", mix + ".json"))
    tr = config["training"]
    lay = {k: int(v) for k, v in
           (kv.split("=") for kv in args.layout.split(","))} \
        if args.layout else {}
    remat = args.remat or tr.get("remat", False)
    chips = args.chips or int(cell["chips"])

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    devices = list(topo.devices)[:chips]
    jax.config.update("jax_enable_compilation_cache", False)

    import mxnet_tpu as mx
    from mxnet_tpu import amp, base
    from mxnet_tpu import parallel as par
    from mxnet_tpu.models import gpt2_lm_loss
    from mxnet_tpu.parallel import sharding, trainer as trainer_mod

    base.resolve_exec_platform = lambda x=None: "tpu"
    jax.default_backend = lambda: "tpu"
    keep = lambda value, sh: value
    sharding.mesh_device_put = keep
    trainer_mod._mesh_device_put = keep

    if tr.get("amp"):
        amp.init(tr["amp"])
    sizes = prog.sizes_of(config)
    net = prog.build_net(config, remat=remat)
    prog.load_weights(net, make_weights(sizes, 0, "float32"),
                      dtype="float32", trainable=True)
    mesh = par.make_mesh(dp=int(lay.get("dp", 1)), tp=int(lay.get("tp", 1)),
                         devices=devices)
    b = traffic["batches"]
    sample = tuple(mx.nd.array(jnp.zeros((int(b["batch"]), int(b["seq"])),
                                         jnp.int32), dtype="int32")
                   for _ in range(2))
    with par.use_mesh(mesh):
        t = par.ShardedTrainer(
            net, tr["optimizer"], loss=gpt2_lm_loss,
            optimizer_params={"learning_rate": float(tr["learning_rate"])},
            mesh=mesh)
        t.build(*sample)
        params, aux, states, batch = t._device_args(*[(s,) for s in sample])
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
        lowered = t._step_fn.lower(
            sds(params, p_sh), sds(aux, a_sh),
            sds(states, t._state_shardings),
            sds(batch, t.batch_shardings), key, scalar(jnp.float32),
            scalar(jnp.int32))
        compiled = lowered.compile()
        seconds = time.monotonic() - t0
    mem = compiled.memory_analysis()
    text = compiled.as_text()
    usable = 15.75e9
    total = (mem.temp_size_in_bytes + mem.argument_size_in_bytes
             + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    print(json.dumps({
        "workload": args.workload or f"{args.config}/{mix}",
        "chips": chips, "layout": lay,
        "remat": remat, "compile_seconds": round(seconds, 1),
        "per_device_bytes": {"temp": mem.temp_size_in_bytes,
                             "arguments": mem.argument_size_in_bytes,
                             "outputs": mem.output_size_in_bytes,
                             "aliased": mem.alias_size_in_bytes,
                             "total": total},
        "fits_15.75GB": total < usable,
        "pallas_kernels": text.count("tpu_custom_call"),
        "collectives": {k: text.count(f" {k}(") + text.count(f" {k}-start(")
                        for k in ("all-reduce", "all-gather",
                                  "reduce-scatter", "all-to-all",
                                  "collective-permute")},
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
