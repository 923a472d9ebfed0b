"""Ambient logical-axis registry for in-model sharding constraints.

Model code cannot know mesh axis names (smoke tests run on 1 device, the
dry-run on (data, model) or (pod, data, model)). The launcher registers
the logical->physical axis mapping here; `constrain` becomes a no-op when
nothing is registered, so model code stays mesh-agnostic.
"""
from __future__ import annotations

import jax
from jax.sharding import PartitionSpec as P

shard_map = jax.shard_map

_DP: tuple[str, ...] | None = None
_MODEL: str | None = None
_MESH = None


def set_axes(dp: tuple[str, ...] | None, model: str | None,
             mesh=None) -> None:
    global _DP, _MODEL, _MESH
    _DP, _MODEL, _MESH = dp, model, mesh


def clear() -> None:
    set_axes(None, None, None)


def mesh():
    return _MESH


def dp_axes() -> tuple[str, ...] | None:
    return _DP


def model_axis() -> str | None:
    return _MODEL


def dp_size() -> int:
    if _MESH is None or not _DP:
        return 1
    n = 1
    for a in _DP:
        n *= _MESH.shape[a]
    return n


def model_size() -> int:
    if _MESH is None or not _MODEL:
        return 1
    return _MESH.shape[_MODEL]


def data_size() -> int:
    if _MESH is None or "data" not in (_MESH.axis_names or ()):
        return 1
    return _MESH.shape["data"]


def constrain(x, *dims: str | None):
    """dims entries: 'dp' | 'model' | None per array axis."""
    if _DP is None and _MODEL is None:
        return x
    spec = []
    for d in dims:
        if d == "dp":
            spec.append(_DP if _DP and len(_DP) > 1 else
                        (_DP[0] if _DP else None))
        elif d == "model":
            spec.append(_MODEL)
        else:
            spec.append(None)
    return jax.lax.with_sharding_constraint(x, P(*spec))
