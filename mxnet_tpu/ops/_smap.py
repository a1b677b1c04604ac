"""Shared eager-entry scaffold for shard_map'd attention ops (ring,
ulysses): spread single-device arrays over the mesh, run the mapped
body, and restore the caller's placement so downstream eager math sees
a consistent device."""
from __future__ import annotations

import jax

__all__ = ["shard_mapped_qkv"]


def shard_mapped_qkv(body, mesh, spec, q, k, v, *extra, extra_specs=()):
    """Run ``body(q, k, v, *extra)`` under shard_map.  ``extra`` carries
    side inputs with their own partition specs (e.g. packed segment-id
    planes, sharded over batch+sequence only)."""
    if len(extra) != len(extra_specs):
        raise ValueError(
            f"shard_mapped_qkv: {len(extra)} extra inputs but "
            f"{len(extra_specs)} extra_specs — each side input needs "
            "exactly one partition spec")
    restore = None
    if not isinstance(q, jax.core.Tracer):
        from jax.sharding import NamedSharding
        sh = NamedSharding(mesh, spec)
        if q.sharding != sh:
            restore = q.sharding
        q, k, v = (jax.device_put(x, sh) for x in (q, k, v))
        extra = tuple(jax.device_put(x, NamedSharding(mesh, s))
                      for x, s in zip(extra, extra_specs))
    f = jax.shard_map(body, mesh=mesh,
                      in_specs=(spec, spec, spec, *extra_specs),
                      out_specs=spec, check_vma=False)
    out = f(q, k, v, *extra)
    if restore is not None:
        out = jax.device_put(out, restore)
    return out
