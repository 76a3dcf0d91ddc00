"""Logical-axis → mesh-axis resolution.

Modes (DESIGN.md §4):
  train/data   — paper-faithful decentralized training: per-node parameter
                 replicas stacked on a leading "node" axis sharded over the
                 mesh data axis (flattened (pod, data) on the multi-pod mesh);
                 tensor-parallel within a node over the model axis.
  train/pod    — hierarchical: gossip nodes = pods; parameters FSDP-sharded
                 over data × TP over model inside each pod node.
  serve/tp     — inference, weights TP over model axis only.
  serve/2d     — inference, weights 2D-sharded over (data, model) (big archs).
"""
from __future__ import annotations

from typing import Any, Optional, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

PyTree = Any

def _IS_AXES(x):
    return isinstance(x, tuple)


def _rules(mode: str, mesh: Mesh) -> dict:
    axis_names = mesh.axis_names
    multi_pod = "pod" in axis_names
    node_phys: Any = ("pod", "data") if multi_pod else "data"
    if mode == "train_data":
        return {"node": node_phys, "batch": "data", "per_node_batch": None,
                "vocab": "model", "embed": None,
                "heads": "model", "kv_heads": "model", "ffn": "model",
                "expert": "model", "layers": None, "kv_seq": None}
    if mode == "train_pod":
        # node axis == "pod" (absent on single-pod mesh -> replicated), FSDP
        # shards the embed dim over "data".
        return {"node": "pod" if multi_pod else None, "batch": "data",
                "per_node_batch": "data", "vocab": "model",
                "embed": "data", "heads": "model", "kv_heads": "model",
                "ffn": "model", "expert": "model", "layers": None,
                "kv_seq": None}
    serve_batch: Any = ("pod", "data") if multi_pod else "data"
    if mode == "serve_tp":
        return {"node": None, "batch": serve_batch, "vocab": "model",
                "embed": None, "heads": "model", "kv_heads": "model",
                "ffn": "model", "expert": "model", "layers": None,
                "kv_seq": None}
    if mode == "serve_2d":
        return {"node": None, "batch": serve_batch, "vocab": "model",
                "embed": "data", "heads": "model", "kv_heads": "model",
                "ffn": "model", "expert": "model", "layers": None,
                "kv_seq": None}
    if mode == "serve_tp_seq":
        # flash-decoding style: KV cache sequence dim sharded over the model
        # axis (partial softmax + small all-reduce) — for GQA archs whose
        # kv_heads don't divide the model axis and would otherwise replicate
        # the whole cache per chip (§Perf hillclimb 1).
        return {"node": None, "batch": serve_batch, "vocab": "model",
                "embed": None, "heads": "model", "kv_heads": None,
                "ffn": "model", "expert": "model", "layers": None,
                "kv_seq": "model"}
    if mode == "serve_cp":
        # context-parallel decode: tiny batch, KV sequence sharded over data
        return {"node": None, "batch": "pod" if multi_pod else None,
                "vocab": "model", "embed": None, "heads": "model",
                "kv_heads": "model", "ffn": "model", "expert": "model",
                "layers": None, "kv_seq": "data"}
    raise ValueError(f"unknown sharding mode {mode!r}")


def logical_to_spec(axes: Tuple[Optional[str], ...], mode: str, mesh: Mesh,
                    shape: Optional[Tuple[int, ...]] = None) -> P:
    """Resolve logical axes to a PartitionSpec.  Mesh axes absent from
    ``mesh`` resolve to replicated.  With ``shape`` given, a mesh
    axis is applied only when the dim size is divisible by it — pjit argument
    shardings require exact divisibility (e.g. kv_heads=8 on a model=16 axis
    stays replicated)."""
    rules = _rules(mode, mesh)
    mesh_sizes = dict(mesh.shape)
    phys, used = [], set()
    for i, a in enumerate(axes):
        if a is None:
            phys.append(None)
            continue
        p = rules.get(a, None)
        # never map two tensor dims to the same mesh axis, nor a dim to an
        # axis this mesh lacks (a ("data",) mesh has no "model" axis)
        flat = tuple(p) if isinstance(p, tuple) else (p,)
        if p is None or any(f in used or f not in mesh_sizes
                            for f in flat if f is not None):
            phys.append(None)
            continue
        if shape is not None:
            size = 1
            for f in flat:
                size *= mesh_sizes.get(f, 1)
            if size == 0 or shape[i] % size != 0:
                phys.append(None)
                continue
        phys.append(p)
        used.update(f for f in flat if f is not None)
    return P(*phys)


def specs_for(axes_tree: PyTree, mode: str, mesh: Mesh) -> PyTree:
    return jax.tree.map(lambda a: logical_to_spec(a, mode, mesh),
                        axes_tree, is_leaf=_IS_AXES)


def shardings_for(axes_tree: PyTree, mode: str, mesh: Mesh,
                  shapes: Optional[PyTree] = None) -> PyTree:
    """NamedShardings for an axes tree; with ``shapes`` (a matching tree of
    arrays or ShapeDtypeStructs) non-divisible dims stay replicated."""
    if shapes is None:
        return jax.tree.map(
            lambda a: NamedSharding(mesh, logical_to_spec(a, mode, mesh)),
            axes_tree, is_leaf=_IS_AXES)
    return jax.tree.map(
        lambda a, s: NamedSharding(
            mesh, logical_to_spec(a, mode, mesh, shape=s.shape)),
        axes_tree, shapes, is_leaf=_IS_AXES)


def constrain(x: jax.Array, spec: P) -> jax.Array:
    """with_sharding_constraint that is a no-op outside jit/mesh context."""
    try:
        return jax.lax.with_sharding_constraint(x, spec)
    except (ValueError, RuntimeError):
        return x


def wire_column_spec(shape: Tuple[int, ...], n_rows: int,
                     node_names: Tuple[str, ...],
                     model_names: Tuple[str, ...], k_model: int) -> P:
    """Leaf→column-slice spec negotiation for the sharded communication
    path's packed/wire arrays (``repro.core.mixing``, DESIGN.md §2.1).

    * arrays carrying the node axis (leading dim == ``n_rows``) shard it
      over ``node_names``;
    * a node-sharded array whose trailing column axis divides the model
      shard count is additionally column-sliced over ``model_names`` —
      the caller guarantees the column layout matches the packed matrix's
      (``mixing_pallas.flatten_nodes_sharded`` chunk order), and passes
      ``model_names=()`` for payloads whose columns cannot slice
      (sparsifier index sets, per-row scales);
    * everything else (leading-axis-1 shared metadata, scalars) rides
      replicated.
    """
    row = tuple(node_names) if shape and shape[0] == n_rows else None
    if (row is not None and model_names and k_model > 1 and len(shape) >= 2
            and shape[-1] >= k_model and shape[-1] % k_model == 0):
        mid = (None,) * (len(shape) - 2)
        return P(row, *mid, tuple(model_names))
    return P(row) if row is not None else P()
