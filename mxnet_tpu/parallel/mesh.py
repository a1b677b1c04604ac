"""Device mesh management (TPU-native replacement for MXNet's context lists
and KVStore comm topology; SURVEY.md §2.4/§7.1).

MXNet scales by enumerating contexts (``[mx.gpu(0..7)]``) and reducing
gradients through KVStore comm trees.  The TPU-native realization is a named
:class:`jax.sharding.Mesh`: every parallelism strategy is an axis name, and
XLA inserts the collectives (psum over ICI) that CommDevice/NCCL performed
by hand (parity: src/kvstore/comm.h — the topology role, not the code).

Canonical axes (all always present; unused axes have size 1 so sharding
rules can reference them unconditionally):

- ``pp``  pipeline stages (outermost: lowest-bandwidth links)
- ``dp``  data parallel replicas
- ``ep``  expert parallel (MoE)
- ``sp``  sequence/context parallel (ring attention)
- ``tp``  tensor parallel (innermost: highest-bandwidth ICI neighbors)
"""
from __future__ import annotations

import math
from typing import Dict, List, Optional, Sequence

import jax
import numpy as onp
from jax.sharding import Mesh

from .. import base as _base

AXES = ("pp", "dp", "ep", "sp", "tp")

_current: List[Mesh] = []


def make_mesh(dp: Optional[int] = None, tp: int = 1, pp: int = 1, sp: int = 1,
              ep: int = 1, devices: Optional[Sequence] = None) -> Mesh:
    """Build a 5-axis mesh over ``devices`` (default: all local devices).

    ``dp=None`` absorbs whatever device count the other axes leave over.
    """
    if devices is None:
        devices = jax.devices()
    n = len(devices)
    fixed = tp * pp * sp * ep
    if dp is None:
        if n % fixed:
            raise _base.MXNetError(
                f"{n} devices not divisible by tp*pp*sp*ep={fixed}")
        dp = n // fixed
    if dp * fixed != n:
        raise _base.MXNetError(
            f"mesh {dp}x{fixed} needs {dp * fixed} devices, have {n}")
    sizes = {"pp": pp, "dp": dp, "ep": ep, "sp": sp, "tp": tp}
    grid = onp.asarray(devices, dtype=object).reshape(
        [sizes[a] for a in AXES])
    return Mesh(grid, AXES)


def current_mesh() -> Optional[Mesh]:
    """Innermost active mesh (set via ``with use_mesh(m):`` or default)."""
    if _current:
        return _current[-1]
    # jax's own ``with mesh:`` scope; 0.9.0 has no public accessor for it
    from jax._src import mesh as _mesh_lib
    m = _mesh_lib.thread_resources.env.physical_mesh
    if len(m.axis_names) > 0:
        return m
    return None


class use_mesh:
    """Context manager installing a mesh as the ambient default."""

    def __init__(self, mesh: Mesh):
        self.mesh = mesh

    def __enter__(self):
        _current.append(self.mesh)
        return self.mesh

    def __exit__(self, *a):
        _current.pop()


def axis_size(mesh: Mesh, axis: str) -> int:
    return dict(zip(mesh.axis_names, mesh.devices.shape)).get(axis, 1)
