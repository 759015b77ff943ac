"""Global device mesh management.

TPU-native re-design of the reference's communicator plumbing
(ref: paddle/fluid/distributed/collective/process_group_nccl.cc and
fleet/base/topology.py).  Where the reference builds one NCCL communicator
per process subgroup, here there is ONE ``jax.sharding.Mesh`` whose named
axes are the parallelism dimensions; a "communication group" is a view of
one (or more, fused) mesh axes.  Collectives ride the ICI torus because XLA
lays the innermost axes on neighbouring chips — so the axis order
[dp, pp, sharding, sep, mp] (mp innermost) mirrors the reference's
NVLink-innermost topology choice.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

# canonical axis order, outermost → innermost (ref: fleet/base/topology.py
# HybridCommunicateGroup order ["data", "pipe", "sharding", "sep", "model"]).
HYBRID_AXES = ("dp", "pp", "sharding", "sep", "mp")

_global_mesh: Optional[Mesh] = None


def build_mesh(axes: Dict[str, int], devices: Optional[Sequence] = None) -> Mesh:
    """Build a named mesh from {axis_name: degree}.

    Degrees must multiply to the device count; a degree of -1 absorbs the
    remainder (like the reference's strategy auto-degree).
    """
    devices = list(devices) if devices is not None else jax.devices()
    n = len(devices)
    names = [a for a in axes]
    degrees = [int(axes[a]) for a in names]
    if any(d == -1 for d in degrees):
        known = int(np.prod([d for d in degrees if d != -1]))
        if n % known:
            raise ValueError(f"device count {n} not divisible by {known}")
        degrees = [n // known if d == -1 else d for d in degrees]
    total = int(np.prod(degrees)) if degrees else 1
    if total != n:
        raise ValueError(
            f"mesh degrees {dict(zip(names, degrees))} multiply to {total} "
            f"but there are {n} devices")
    arr = np.array(devices).reshape(degrees)
    return Mesh(arr, tuple(names))


def set_mesh(mesh: Mesh):
    global _global_mesh
    _global_mesh = mesh


def get_mesh() -> Optional[Mesh]:
    return _global_mesh


def ensure_mesh(axes: Optional[Dict[str, int]] = None) -> Mesh:
    """Return the global mesh, building a default one if absent.

    Default: all devices on a single 'dp' axis (pure data parallel) —
    the same default as the reference's ``init_parallel_env``.
    """
    global _global_mesh
    if _global_mesh is None:
        axes = axes or {"dp": len(jax.devices())}
        _global_mesh = build_mesh(axes)
    return _global_mesh


def reset_mesh():
    global _global_mesh
    _global_mesh = None
    # the auto_parallel ProcessMesh global mirrors this one (its
    # set_mesh writes both) — clearing only one leaves a stale mesh for
    # Engine/get_mesh() callers
    try:
        from .auto_parallel import api as _ap_api
        _ap_api._auto_mesh = None
    except ImportError:  # auto_parallel not imported yet
        pass


def in_axis_scope(axis_name) -> bool:
    """True when called under shard_map/pmap with ``axis_name`` bound —
    i.e. we are per-rank SPMD code and must emit lax collectives."""
    names = axis_name if isinstance(axis_name, (tuple, list)) else (axis_name,)
    try:
        for a in names:
            jax.lax.axis_size(a)
        return True
    except BaseException:
        return False


def auto_axes() -> Tuple[str, ...]:
    """Axes of the global mesh with degree > 1 that the current trace
    leaves to GSPMD (not bound by an enclosing shard_map).  Non-empty
    means a Mosaic kernel called here would have to be partitioned
    automatically, which XLA refuses."""
    mesh = _global_mesh
    if mesh is None:
        return ()
    return tuple(a for a in mesh.axis_names
                 if mesh.shape[a] > 1 and not in_axis_scope(a))


def axis_degree(mesh: Mesh, axis_name) -> int:
    names = axis_name if isinstance(axis_name, (tuple, list)) else (axis_name,)
    d = 1
    for a in names:
        d *= mesh.shape[a]
    return d
