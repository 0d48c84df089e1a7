"""Mesh context + sharding-constraint helpers shared by all model code.

Model code never names a concrete mesh: it calls :func:`constrain` with a
*logical* PartitionSpec.  The active mesh is carried in a context variable set
by the launcher (`use_mesh`); axes absent from the active mesh are silently
dropped, so the same model lowers on the single-pod ``(data, model)`` mesh,
the multi-pod ``(pod, data, model)`` mesh, and bare CPU (no mesh → no-op).
"""
from __future__ import annotations

import contextlib
from contextvars import ContextVar
from typing import Optional, Sequence, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_ACTIVE_MESH: ContextVar[Optional[Mesh]] = ContextVar("repro_active_mesh", default=None)


def shard_map(f, *, mesh, in_specs, out_specs, check_vma: bool = False):
    """``jax.shard_map`` with the repo's default ``check_vma=False``."""
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=check_vma)


def make_mesh(shape, axis_names, *, devices=None):
    """``jax.make_mesh`` with explicit Auto axis types."""
    kw = {} if devices is None else {"devices": devices}
    return jax.make_mesh(
        shape, axis_names,
        axis_types=(jax.sharding.AxisType.Auto,) * len(axis_names), **kw)

# Canonical logical axes (DESIGN.md §3.3):
#   batch  → ('pod', 'data')   data parallelism (pods are pure DP)
#   fsdp   → 'data'            ZeRO parameter/optimizer sharding
#   tp     → 'model'           tensor parallelism (heads / d_ff / vocab / experts)
BATCH_AXES: Tuple[str, ...] = ("pod", "data")
FSDP_AXIS = "data"
TP_AXIS = "model"


def active_mesh() -> Optional[Mesh]:
    return _ACTIVE_MESH.get()


@contextlib.contextmanager
def use_mesh(mesh: Optional[Mesh]):
    token = _ACTIVE_MESH.set(mesh)
    try:
        yield mesh
    finally:
        _ACTIVE_MESH.reset(token)


def _filter_spec(spec: Sequence, mesh: Mesh) -> P:
    """Drop mesh axes the active mesh doesn't have (e.g. 'pod' on one pod)."""
    names = set(mesh.axis_names)
    out = []
    for entry in spec:
        if entry is None:
            out.append(None)
        elif isinstance(entry, str):
            out.append(entry if entry in names else None)
        else:  # tuple of axis names
            kept = tuple(a for a in entry if a in names)
            out.append(kept if kept else None)
    return P(*out)


def resolve_spec(spec: Union[P, Sequence], mesh: Optional[Mesh] = None) -> Optional[P]:
    mesh = mesh or active_mesh()
    if mesh is None:
        return None
    return _filter_spec(tuple(spec), mesh)


def sharding_for(spec: Union[P, Sequence], mesh: Optional[Mesh] = None) -> Optional[NamedSharding]:
    mesh = mesh or active_mesh()
    if mesh is None:
        return None
    return NamedSharding(mesh, _filter_spec(tuple(spec), mesh))


def _divisible(entry, dim: int, mesh: Mesh):
    """Drop spec entries whose mesh-axis product does not divide the dim."""
    if entry is None:
        return None
    axes = (entry,) if isinstance(entry, str) else tuple(entry)
    kept = []
    prod = 1
    for a in axes:
        size = mesh.shape[a]
        if dim % (prod * size) == 0:
            kept.append(a)
            prod *= size
    if not kept:
        return None
    return kept[0] if len(kept) == 1 else tuple(kept)


def shard_by_shape(spec: Union[P, Sequence], shape: Tuple[int, ...],
                   mesh: Optional[Mesh] = None) -> Optional[NamedSharding]:
    """Divisibility-aware NamedSharding: axes that don't divide are dropped
    (pjit in_shardings reject uneven shards; replication is always legal)."""
    mesh = mesh or active_mesh()
    if mesh is None:
        return None
    filtered = tuple(_filter_spec(tuple(spec), mesh))
    entries = [_divisible(e, d, mesh) for e, d in zip(filtered, shape)]
    return NamedSharding(mesh, P(*entries))


def constrain(x: jax.Array, *spec) -> jax.Array:
    """`with_sharding_constraint` against the active mesh (no-op without one).

    Divisibility-aware: axes that don't divide the dimension are dropped
    (e.g. 40 rwkv heads on a 16-way model axis → replicated)."""
    mesh = active_mesh()
    if mesh is None:
        return x
    s = shard_by_shape(P(*spec), x.shape, mesh)
    return jax.lax.with_sharding_constraint(x, s)


def batch_spec(*trailing) -> P:
    """P(('pod','data'), *trailing) — the activation batch sharding."""
    return P(BATCH_AXES, *trailing)


def axis_size(name: str, mesh: Optional[Mesh] = None) -> int:
    mesh = mesh or active_mesh()
    if mesh is None or name not in mesh.axis_names:
        return 1
    return mesh.shape[name]


def mesh_batch_shards(mesh: Optional[Mesh] = None) -> int:
    return axis_size("pod", mesh) * axis_size("data", mesh)
