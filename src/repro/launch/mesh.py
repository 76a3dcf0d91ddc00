"""Process set-up for the launchers: device meshes and the compile cache.

Functions, not module-level constants — importing this module never touches
jax device state or config.  Callers (dryrun.py) set
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import; smoke tests and benchmarks see the real single CPU device.

The node-axis semantics (which mesh axes a gossip "node" spans under
``DistConfig.node_axis``) are canonical in ``repro.core.mixing`` —
``node_axis_names`` / ``node_shard_count`` — so the shard_map-aware comm
path and these launch helpers can never disagree.
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import Optional

import jax

from repro.core.mixing import (model_axis_names,  # noqa: F401
                               model_shard_count, node_axis_names,
                               node_shard_count)  # re-exported for launchers


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def n_gossip_nodes(mesh: jax.sharding.Mesh, node_axis: str) -> int:
    """Gossip node count for a mesh under DistConfig.node_axis semantics
    (paper-faithful "data" flattens (pod, data); "pod" is hierarchical)."""
    return node_shard_count(mesh, node_axis)


def node_mesh(n_nodes: int) -> Optional[jax.sharding.Mesh]:
    """The training mesh the platform calls for: ``None`` on one device
    (the nodes stack on it); otherwise one ``("data",)`` axis over every
    device, each holding ``n_nodes / device_count`` nodes."""
    devices = jax.devices()
    if len(devices) == 1:
        return None
    if n_nodes % len(devices):
        raise ValueError(f"{n_nodes} nodes do not split evenly over "
                         f"{len(devices)} devices")
    return jax.make_mesh((len(devices),), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,))


CHECKOUT = Path(__file__).resolve().parents[3]


def use_compile_cache() -> str:
    """Turn on JAX's persistent compile cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is read by JAX itself and left
    alone.  Otherwise the cache lives at ``<checkout>/.jax_cache``: a fixed
    path, because the path is part of the cache key.  Entry points call this
    before their first compile; importing a module never does."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
