"""Production mesh builders.

`make_production_mesh` is a FUNCTION (not module state) so importing this
module never touches jax device state; `dryrun.py` sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 before first jax use.

Mesh axes:
  pod    — crosses the data-centre network (DCN); only DP gradient
           reductions ride it.
  data   — in-pod data parallel / ZeRO-1 / sequence parallel.
  model  — in-pod tensor/expert parallel; all TP collectives stay on ICI.

Every mesh is built with `Auto` axis types, here and nowhere else:
`jax.make_mesh` defaults to `Explicit` axes, which the sharding rules'
`with_sharding_constraint` calls and the vocab-sharded embedding gather
refuse, while a `Mesh` built directly gets `Auto` — meshes of one process
would otherwise disagree.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
from jax.sharding import AxisType


def _auto_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    return make_mesh((2, 16, 16) if multi_pod else (16, 16))


def make_mesh(shape: Tuple[int, ...], axes: Optional[Tuple[str, ...]] = None):
    """General mesh helper for tests/examples (e.g. (2, 2) on 4 host devs)."""
    if axes is None:
        axes = ("data", "model") if len(shape) == 2 else ("pod", "data", "model")
    return _auto_mesh(shape, axes)


def single_device_mesh():
    return _auto_mesh((1, 1), ("data", "model"))


def tp_mesh(tp: int):
    """A (1, tp) data x model serving mesh over the FIRST `tp` local
    devices.  Deliberately a device SUBSET (jax.make_mesh insists on using
    every device), so one multi-device host process can race tp=1/2/4
    meshes side by side — the TP bench sweep and the cross-mesh
    byte-identity differential both depend on that."""
    import numpy as np
    from jax.sharding import Mesh

    devs = jax.devices()
    if tp > len(devs):
        raise ValueError(
            f"tp={tp} needs {tp} devices but the process has {len(devs)}; "
            "set --devices (repro.platform) before the first jax import")
    return Mesh(np.asarray(devs[:tp]).reshape(1, tp), ("data", "model"),
                axis_types=(AxisType.Auto, AxisType.Auto))


def mesh_axis_size(mesh, name: str) -> int:
    return mesh.shape[name] if name in mesh.shape else 1
