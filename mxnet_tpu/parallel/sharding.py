"""Logical-axis sharding rules (TPU-native parameter placement).

MXNet has no parameter sharding (params are replicated per context by
``Trainer``/KVStore broadcast — src/kvstore/comm.h Broadcast).  On TPU,
placement is the performance model, so parameters carry *logical* axis names
("embed", "mlp", "heads", "vocab", …) and a rules table maps logical axes →
mesh axes (the flax/t5x partitioning idiom).  Replication is just the empty
mapping, so data-parallel MXNet semantics fall out as the default.
"""
from __future__ import annotations

import functools as _functools
from typing import Dict, Optional, Sequence, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .. import base as _base

# Default logical→mesh mapping (Megatron-style TP + sequence axis).
DEFAULT_RULES: Dict[str, Optional[str]] = {
    "batch": "dp",
    "layers": "pp",
    "vocab": "tp",
    "embed": None,
    "heads": "tp",
    "kv": None,
    "mlp": "tp",
    "expert": "ep",
    "seq": "sp",
    "norm": None,
}


class ShardingRules(dict):
    """dict logical-axis-name → mesh-axis-name (or None = replicate)."""

    def __init__(self, rules: Optional[Dict[str, Optional[str]]] = None,
                 **overrides):
        super().__init__(DEFAULT_RULES)
        if rules:
            self.update(rules)
        self.update(overrides)

    def spec(self, logical_axes: Optional[Sequence[Optional[str]]]) -> P:
        """PartitionSpec for a parameter annotated with logical axes."""
        if not logical_axes:
            return P()
        return P(*[self.get(a) if a is not None else None
                   for a in logical_axes])


def annotate(param, *logical_axes):
    """Attach logical axis names to a Parameter (one per dimension)."""
    param._logical_axes = tuple(logical_axes)
    return param


def logical_axes_of(param) -> Optional[Tuple[Optional[str], ...]]:
    return getattr(param, "_logical_axes", None)


def mesh_device_put(value, sharding):
    """``jax.device_put`` that also works onto MULTI-PROCESS meshes.

    A process-local committed array cannot be device_put to
    non-addressable devices (no raw DCN transport on the CPU/test
    backends), so it hops through host memory — every process holds the
    full value and materializes its own shards (the standard multihost
    ingest pattern).  An already-GLOBAL array cannot be fetched to host
    either; it is resharded inside a compiled identity whose collectives
    ride the coordination service/ICI/DCN."""
    if isinstance(value, jax.Array) and \
            not getattr(sharding, "is_fully_addressable", True):
        if getattr(value, "sharding", None) == sharding:
            return value
        if value.is_fully_addressable:
            import numpy as onp
            value = onp.asarray(value)
        else:
            return _reshard_fn(sharding)(value)
    return jax.device_put(value, sharding)


@_functools.lru_cache(maxsize=None)
def _reshard_fn(sharding):
    """One cached compiled identity per target sharding (jax.jit caches by
    function identity — a fresh lambda per call would recompile every
    state-leaf reshard)."""
    return jax.jit(lambda x: x, out_shardings=sharding)


def param_sharding(param, mesh: Mesh,
                   rules: Optional[ShardingRules] = None) -> NamedSharding:
    """Where an initialized parameter lives on the mesh: its logical axes
    through the rules, with any dimension its mesh axis does not divide
    evenly REPLICATED (:func:`divisible_spec`) — GPT-2's 50,257-row
    embedding on a ``tp`` axis — the same rule the serving mesh uses."""
    rules = rules or ShardingRules()
    return NamedSharding(mesh, divisible_spec(
        param._data.shape, logical_axes_of(param), mesh, rules))


def divisible_spec(shape, logical_axes, mesh: Mesh, mapping) -> P:
    """PartitionSpec mapping each logical axis through ``mapping``
    (logical name → mesh axis name), REPLICATING any dimension whose
    size its mesh axis does not divide — the pragmatic t5x-style
    fallback: an odd-sized vocab table (GPT-2's 50,257 rows on a 2-way
    ``tp``) replicates instead of erroring, on the training mesh
    (:func:`param_sharding`) and the serving mesh alike.  Axes that MUST
    shard evenly (the KV head dimension) are validated separately by the
    caller (`InferenceEngine`'s typed construction checks,
    docs/serving.md "Sharded decode")."""
    from .mesh import axis_size
    spec = []
    for dim, a in zip(shape, tuple(logical_axes or ())):
        m = mapping.get(a) if a is not None else None
        fits = m in mesh.axis_names and dim % axis_size(mesh, m) == 0
        spec.append(m if fits else None)
    return P(*spec)


def shard_params(block, mesh: Mesh, rules: Optional[ShardingRules] = None):
    """Place every initialized parameter of ``block`` onto the mesh per the
    rules (replacing KVStore broadcast: parity src/kvstore/comm.h
    Comm::Broadcast — replication is now a NamedSharding, sharding is free).
    """
    rules = rules or ShardingRules()
    for _, p in block.collect_params().items():
        if p._data is None:
            continue
        sh = param_sharding(p, mesh, rules)
        p._sharding = sh
        p._data._rebind(mesh_device_put(p._data.jax, sh))
    return block


def batch_spec(ndim: int, batch_axis: int = 0, seq_axis: Optional[int] = None
               ) -> P:
    """PartitionSpec for an input batch: batch dim over dp, optional
    sequence dim over sp, rest replicated."""
    axes: list = [None] * ndim
    axes[batch_axis] = "dp"
    if seq_axis is not None:
        axes[seq_axis] = "sp"
    return P(*axes)


def global_batch_sharding(mesh: Mesh, ndim: int, batch_axis: int = 0,
                          seq_axis: Optional[int] = None) -> NamedSharding:
    """The ``NamedSharding`` an input batch lands under — the one-liner
    the data pipeline needs: feed it to ``ShardedLoader`` /
    ``DevicePrefetcher`` and to the trainer's ``data_specs`` and both
    sides agree on placement by construction."""
    return NamedSharding(mesh, batch_spec(ndim, batch_axis, seq_axis))
