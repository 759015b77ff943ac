"""Sharding annotation helpers — the GSPMD substrate of the parallel layers.

Where the reference's mp/sharding layers call explicit c_* collectives
(ref: fleet/layers/mpu/mp_ops.py), the TPU-native layers *annotate*:
parameters carry a per-dim PartitionSpec (consumed by the jit engine as
in_shardings) and activations get ``with_sharding_constraint`` — XLA/GSPMD
then inserts the all-gather/psum/reduce-scatter on ICI, fused and
overlapped, which is exactly the "completion" pass the reference implements
by hand (SURVEY.md §3.5 TPU note).
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from ..core.dispatch import call_op
from ..core.tensor import Tensor
from .mesh import get_mesh, in_axis_scope


def annotate_param(p: Tensor, spec: Sequence) -> Tensor:
    """Attach a per-dim sharding spec (axis name, tuple of names, or None
    per dim) to a parameter."""
    da = p._dist_attr or {}
    da["spec"] = tuple(spec)
    p._dist_attr = da
    return p


def param_spec(p: Tensor) -> Optional[Tuple]:
    da = p._dist_attr
    return None if da is None else da.get("spec")


def param_partition_spec(p: Tensor) -> PartitionSpec:
    s = param_spec(p)
    return PartitionSpec(*s) if s else PartitionSpec()


def _mesh_axes_active(mesh: Mesh, spec) -> bool:
    for s in spec:
        for a in (s if isinstance(s, (tuple, list)) else (s,)):
            if a is not None and mesh.shape.get(a, 1) > 1:
                return True
    return False


def resolve_shard_state_axis(optimizer, mesh: Mesh):
    """(axis, degree) for ZeRO optimizer-state sharding — the single
    resolution rule shared by the jit TrainStep engine and the pipeline
    step: the optimizer's ``_shard_state_axis`` marker, with 'sharding'
    falling back to 'dp' when only dp ranks back the sharding group
    (the reference's sharding-overlapping-dp configuration)."""
    axis = getattr(optimizer, "_shard_state_axis", None) \
        if optimizer is not None else None
    degree = mesh.shape.get(axis, 1) if (axis and mesh is not None) else 1
    if degree <= 1 and axis == "sharding" and mesh is not None:
        axis = "dp"
        degree = mesh.shape.get("dp", 1)
    return axis, degree


def largest_dim_spec(shape, axis: str, degree: int):
    """Largest-divisible-dim sharding rule — the single source of truth
    for ZeRO-style layouts (used by both stage-3 param sharding and the
    engine's optimizer-state sharding, which must agree)."""
    order = sorted(range(len(shape)), key=lambda i: -shape[i])
    for i in order:
        if shape[i] % degree == 0 and shape[i] >= degree:
            spec = [None] * len(shape)
            spec[i] = axis
            return tuple(spec)
    return None


def _is_staged(v) -> bool:
    """True iff `v` is (or wraps, through JVP/batch tracer levels) a
    jaxpr-staging tracer — i.e. we are inside a jit/pjit trace rather
    than an eagerly-executing vjp/vmap over concrete arrays."""
    from jax._src.interpreters.partial_eval import DynamicJaxprTracer
    seen = set()
    while isinstance(v, jax.core.Tracer):
        if isinstance(v, DynamicJaxprTracer):
            return True
        nxt = getattr(v, "primal", None)
        if nxt is None:
            nxt = getattr(v, "val", None)
        if nxt is None or id(nxt) in seen:
            return False
        seen.add(id(nxt))
        v = nxt
    return False


def _constrain(v, sh):
    """Apply a sharding constraint where it has meaning.

    - under a STAGING trace (jit/pjit): a hard GSPMD constraint — THE
      mechanism that partitions compute/storage across the mesh;
    - eagerly (including the tape's eager vjp/vmap, whose primitives
      execute immediately over concrete arrays): identity.  Eager arrays
      are global values — committing them to the mesh buys nothing and
      poisons later ops, because jax refuses to mix arrays committed to
      different device sets (e.g. the step engine pins the RNG key to
      device 0, committing everything derived from it)."""
    if _is_staged(v):
        try:
            return jax.lax.with_sharding_constraint(v, sh)
        except ValueError:
            # partial-manual shard_map region (e.g. the pp ring with
            # auto mp/dp axes): a NamedSharding on the global mesh is
            # rejected because the context mesh marks the manual axes;
            # a bare PartitionSpec resolves against the context mesh
            # and constrains the auto axes only
            return jax.lax.with_sharding_constraint(v, sh.spec)
    return v


def mesh_replicated(x: Tensor) -> Tensor:
    """Replication constraint on the CURRENT mesh (jit-time semantics;
    eager identity — see _constrain).  No-op without a mesh."""
    mesh = get_mesh()
    if mesh is None or len(mesh.devices.ravel()) == 1:
        return x
    if any(in_axis_scope(a) for a in mesh.axis_names):
        return x
    sh = NamedSharding(mesh, PartitionSpec())
    return call_op(lambda v: _constrain(v, sh), (x,),
                   op_name="mesh_replicated")


def sharding_constraint(x: Tensor, *spec) -> Tensor:
    """Constrain an activation's sharding (no-op when there is no mesh, the
    named axes are trivial, or we're inside shard_map explicit SPMD)."""
    mesh = get_mesh()
    if mesh is None or not _mesh_axes_active(mesh, spec):
        return x
    names = [a for s in spec
             for a in (s if isinstance(s, (tuple, list)) else (s,))
             if a is not None]
    if any(in_axis_scope(a) for a in names):
        return x  # explicit-mode code owns its collectives
    sh = NamedSharding(mesh, PartitionSpec(*spec))
    return call_op(lambda v: _constrain(v, sh), (x,),
                   op_name="sharding_constraint")
