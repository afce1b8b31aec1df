"""Mesh/runtime context shared by model code.

Model code never owns a mesh: the launcher (or a test) installs one with
``use_mesh``; layers consult ``current_mesh()`` at trace time to decide
whether to emit shard_map collectives / sharding constraints. With no mesh
installed everything degrades to single-device dense JAX (used by smoke
tests and CPU examples).
"""
from __future__ import annotations

import contextlib
import os
from pathlib import Path
from typing import Optional

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_MESH_STACK: list[Mesh] = []


def current_mesh() -> Optional[Mesh]:
    return _MESH_STACK[-1] if _MESH_STACK else None


@contextlib.contextmanager
def use_mesh(mesh: Mesh):
    _MESH_STACK.append(mesh)
    try:
        with mesh:
            yield mesh
    finally:
        _MESH_STACK.pop()


def axis_size(name: str) -> int:
    mesh = current_mesh()
    if mesh is None or name not in mesh.shape:
        return 1
    return mesh.shape[name]


def has_axis(name: str) -> bool:
    return axis_size(name) > 1


def batch_axes() -> tuple[str, ...]:
    """Mesh axes the global batch is sharded over (pod composes with data)."""
    axes = tuple(a for a in ("pod", "data") if has_axis(a))
    return axes or ("data",)


def data_axis_size() -> int:
    mesh = current_mesh()
    if mesh is None:
        return 1
    n = 1
    for a in ("pod", "data"):
        n *= mesh.shape.get(a, 1)
    return n


def shard(x, *spec):
    """with_sharding_constraint that no-ops without a mesh."""
    mesh = current_mesh()
    if mesh is None:
        return x
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P(*spec)))


def named_sharding(*spec) -> Optional[NamedSharding]:
    mesh = current_mesh()
    if mesh is None:
        return None
    return NamedSharding(mesh, P(*spec))


def pad_to_multiple(n: int, m: int) -> int:
    return ((n + m - 1) // m) * m


def divides(n: int, name: str) -> bool:
    return n % axis_size(name) == 0


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache for a serving process
    and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own choice and is
    left alone. Otherwise the cache lives at ``<repo root>/.jax_cache``: a
    fixed path, because the path is part of the cache key, so a per-run
    directory would never hit. The thresholds drop to zero so the many
    small bucketed programs (one per batch/candidate/history bucket,
    each well under JAX's default 1 s minimum) are cached too."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(Path(__file__).resolve().parents[2]
                              / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return jax.config.jax_compilation_cache_dir
