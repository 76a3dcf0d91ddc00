"""Comm-round meters: byte accounting + pipeline occupancy
(DESIGN.md §2.7).

Every ``comm_round`` record carries two independent byte figures:

* ``analytic_bytes`` — ``compress.round_wire_bytes``, the pure
  config-level cost model (what the dry-run / design docs quote);
* ``measured_bytes`` — recomputed here from the **live** round: actual
  leaf shapes and dtypes of the pytree entering the round, the actual
  compressor objects, and (on the sharded lossy path) the packed wire
  arrays themselves.

The two agreeing is the cross-check: the cost model has config-math
inputs (declared dims, declared dtype) while the meter sees what the
runtime actually built (padding, casts, per-leaf wire layouts) — a
divergence is a bug in one of them (this is exactly how PR 5's
column-padding mismatch would have surfaced).

Byte figures are **per node per round** (per device when
``model_shards > 1``), matching ``round_wire_bytes`` semantics.

Occupancy: for an overlapped pipeline (DESIGN.md §2.6), the fraction of
the synchronous round's cost actually hidden under compute::

    occupancy = clip(1 - max(0, t_step_overlap - t_compute) / t_comm_sync,
                     0, 1)

1.0 = the overlapped step costs no more than bare compute (comm fully
hidden); 0.0 = the full synchronous round cost is still visible.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional

PyTree = Any


def _itemsize(dtype) -> int:
    import numpy as np
    return int(np.dtype(dtype).itemsize)


def _arr_nbytes(a) -> int:
    """Bytes of an array-like from shape/dtype (works on jax tracers,
    which have no .nbytes)."""
    size = 1
    for s in a.shape:
        size *= int(s)
    return size * _itemsize(a.dtype)


def per_node_leaf_sizes(params: PyTree, n_nodes: int) -> List[int]:
    """Per-node flattened element count of each leaf, from live shapes
    (a leading axis of size ``n_nodes`` is the stacked node axis)."""
    import jax
    sizes = []
    for leaf in jax.tree.leaves(params):
        shape = tuple(leaf.shape)
        dims = shape[1:] if (shape and shape[0] == n_nodes) else shape
        per = 1
        for s in dims:
            per *= int(s)
        sizes.append(per)
    return sizes


def round_sends(phase: str, topology: str, n_nodes: int,
                step: int = 0) -> int:
    """Number of payload transmissions in one round: nonzero off-diagonal
    shifts for gossip (one collective-permute each), 1 for the averaging
    collectives, 0 when no bytes move."""
    if n_nodes <= 1 or phase == "none":
        return 0
    if phase in ("global", "pod_avg"):
        return 1
    if phase != "gossip" or topology == "disconnected":
        return 0
    from repro.core import topology as topo
    if topology == "grid":
        return sum(1 for s in topo.grid_shift_weights(n_nodes)
                   if s != (0, 0))
    return sum(1 for s in topo.shift_weights(topology, n_nodes, step)
               if s != 0)


def measured_round_bytes(params: PyTree, *, phase: str, topology: str,
                         n_nodes: int, step: int = 0, n_pods: int = 1,
                         comm_dtype=None, compressor=None,
                         global_compressor=None, model_shards: int = 1,
                         wires=None) -> int:
    """Per-node (per-device when ``model_shards > 1``) wire bytes of one
    round, derived from the live pytree / wire arrays — see the module
    docstring for how this differs from ``round_wire_bytes``."""
    import jax
    leaves = jax.tree.leaves(params)
    n, ms = n_nodes, max(int(model_shards), 1)
    if not leaves or n <= 1 or phase == "none":
        return 0
    sizes = per_node_leaf_sizes(params, n)
    elems = [(_itemsize(comm_dtype) if comm_dtype is not None
              else _itemsize(leaf.dtype)) for leaf in leaves]
    if phase == "gossip" and topology == "grid":
        elems = [4] * len(elems)   # mix_array_grid ignores comm_dtype
    lossy = compressor is not None and compressor.lossy
    quant = lossy and compressor.name in ("int8", "fp8")
    glossy = (global_compressor is not None and global_compressor.lossy)
    sends = round_sends(phase, topology, n, step)

    if phase in ("global", "pod_avg") and glossy:
        # compressed reduce-scatter -> all-gather: whole QBLOCK blocks of
        # codes + one exponent byte each, model-sliced on block boundaries
        from repro.compress import QBLOCK
        nb = -(-sum(sizes) // QBLOCK)
        return (-(-nb // ms)) * (QBLOCK + 1)

    if wires is not None:
        # sharded lossy path: the packed wire arrays ARE the payload —
        # sum their bytes (leading stacked node axis -> per node)
        per_send = 0
        for w in wires:
            payload = w["payload"] if isinstance(w, dict) else w.payload
            aux = w["aux"] if isinstance(w, dict) else w.aux
            for a in tuple(payload) + tuple(aux):
                per_send += _arr_nbytes(a) // (n if a.shape
                                               and a.shape[0] == n else 1)
        if phase == "pod_avg":
            return (max(n // max(n_pods, 1), 1) - 1) * per_send
        return sends * per_send

    if lossy and phase in ("gossip", "pod_avg"):
        if quant and ms > 1:
            # code bytes column-slice over the model axis; the per-row
            # scale word (wire_bytes_per_send - d code bytes) stays whole
            per_send = sum(-(-d // ms)
                           + int(compressor.wire_bytes_per_send(1, d)) - d
                           for d in sizes)
        else:
            per_send = sum(int(compressor.wire_bytes_per_send(1, d))
                           for d in sizes)
        if phase == "pod_avg":
            return (max(n // max(n_pods, 1), 1) - 1) * per_send
        return sends * per_send

    if phase == "global" and lossy and not quant:
        # sparsifier rounds run model-replicated end to end: the global
        # psum operand stays full width per device
        return sum(s * e for s, e in zip(sizes, elems))
    # dense operand, column-sliced over the model axis per leaf
    return sends * sum((-(-s // ms)) * e for s, e in zip(sizes, elems))


def comm_round_fields(params: PyTree, *, phase: str, topology: str,
                      n_nodes: int, step: int = 0, n_pods: int = 1,
                      backend: str = "reference", sharded: bool = False,
                      comm_dtype=None, compressor=None,
                      global_compressor=None, model_shards: int = 1,
                      wires=None, role: str = "round",
                      staged_bytes: int = 0) -> Dict[str, Any]:
    """Build one ``comm_round`` record's fields: tags + analytic bytes
    (``round_wire_bytes``) + measured bytes (live tree/wires) + the
    per-node bytes a pallas round still packs into its ``(n, D)``
    staging buffer (``staged_bytes``: 0 where every leaf is mixed in its
    own layout or the backend is the reference)."""
    import jax
    import numpy as np
    from repro.compress import round_wire_bytes
    sizes = per_node_leaf_sizes(params, n_nodes)
    comp_name = compressor.name if compressor is not None else "none"
    gcomp_name = (global_compressor.name
                  if global_compressor is not None else "none")
    dtype_name = (np.dtype(comm_dtype).name if comm_dtype is not None
                  else "float32")
    analytic = round_wire_bytes(
        phase, topology, n_nodes, sum(sizes), comm_dtype=dtype_name,
        compression=comp_name, k=getattr(compressor, "k", 32), step=step,
        n_pods=n_pods, leaf_sizes=sizes, global_compression=gcomp_name,
        model_shards=model_shards)
    measured = measured_round_bytes(
        params, phase=phase, topology=topology, n_nodes=n_nodes,
        step=step, n_pods=n_pods, comm_dtype=comm_dtype,
        compressor=compressor, global_compressor=global_compressor,
        model_shards=model_shards, wires=wires)
    leaves = jax.tree.leaves(params)
    traced = bool(leaves) and isinstance(leaves[0], jax.core.Tracer)
    return {
        "phase": phase, "role": role, "shift": int(step),
        "topology": topology, "backend": backend, "sharded": bool(sharded),
        "n_nodes": int(n_nodes), "n_pods": int(n_pods),
        "model_shards": int(model_shards), "comm_dtype": dtype_name,
        "compression": comp_name, "global_compression": gcomp_name,
        "sends": round_sends(phase, topology, n_nodes, step),
        "analytic_bytes": int(analytic), "measured_bytes": int(measured),
        "staged_bytes": int(staged_bytes), "traced": traced,
    }


def occupancy(t_compute_s: float, t_comm_sync_s: float,
              t_step_overlap_s: float) -> float:
    """Fraction of the synchronous comm cost hidden under compute by the
    overlapped pipeline (see module docstring)."""
    if t_comm_sync_s <= 0.0:
        return 1.0
    visible = max(0.0, t_step_overlap_s - t_compute_s)
    return max(0.0, min(1.0, 1.0 - visible / t_comm_sync_s))
