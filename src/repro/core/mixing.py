"""Mixing primitives: gossip communication and global averaging.

Three interchangeable implementations, proven equivalent by tests, selected
by the ``backend`` argument on :func:`communicate` (DESIGN.md §2.1):

* **roll-based (pjit / GSPMD)** — ``backend="reference"``:
  ``W·x = Σ_s w_s · roll(x, s, node_axis)``.  Used inside jitted train steps
  where parameters carry a leading node axis sharded over the mesh ``data``
  (or flattened ``(pod, data)``) axis.  Each roll along the sharded axis
  lowers to one ICI ``collective-permute``; the global average lowers to an
  ``all-reduce``.  This is the proven-equivalent oracle every other path is
  tested against.

* **fused Pallas kernels** — ``backend="pallas"``
  (:mod:`repro.kernels.mixing_pallas`): the whole round (optional SGD
  half-step, mix, optional consensus residual) in one pass over parameter
  blocks — one HBM round-trip instead of ``1 + |shifts|``.  Runs in
  interpret mode on CPU (same convention as kernels/ops.py) and compiles to
  Mosaic on TPU.

* **shard_map + ppermute** — the explicit decentralized runtime: each mesh
  slot *is* a node and exchanges its block with neighbors via
  ``jax.lax.ppermute`` / ``psum``.  Semantically identical; exposed for users
  who keep per-node state unstacked.

When :func:`communicate` is given a ``mesh`` whose node axis is sharded,
the pallas backend routes through :func:`communicate_sharded` — a
shard_map wrapper that halo-exchanges neighbor shard blocks via
``ppermute`` and runs the fused per-shard kernel
(:func:`repro.kernels.mixing_pallas.shard_mix_block`) on each shard's
row-block, so ``backend="pallas"`` is safe (and collective-sparse) under
mesh sharding (DESIGN.md §2.1 dispatch table).  A mesh that also carries
the tensor-parallel ``model_axis`` runs the round 2-D: the packed
state's columns are sliced over it, so every halo/psum/collective stage
touches only ``D/k_model`` columns per device.

None of the views materialize W across nodes in the sharded hot path
(DESIGN.md §2.1; the Pallas backend keeps a tiny n×n circulant factor in
VMEM, which DESIGN.md §2.1 argues is the correct single-chip encoding).

**Wire compression** (DESIGN.md §2.3): :func:`communicate` and
:func:`communicate_sharded` take ``compressor=`` /  ``ef_state=`` /
``seed=``.  A lossy compressor (repro.compress) replaces the neighbor
payload with its compressed estimate ``q`` and the round runs in the
self-compensated form ``x + (M·q − (1−d)⊙q)`` — the node's own state
stays exact, the node average is preserved for any compressor, and the
shared per-step randomness makes a constant state an exact fixed point.
``compressor=None`` (or the identity compressor) routes to the exact
pre-compression code path, bit-identically.  With a compressor the
return value is ``(mixed, new_ef_state)``.

**CommSpec** (DESIGN.md §2.6): the ~12 round-invariant knobs above are
captured once in a frozen :class:`CommSpec` —
``communicate(params, spec, phase=..., step=...)`` is the primary
signature, built canonically by ``DistConfig.comm_spec()``.  The legacy
kwarg form still works as a thin shim that builds a spec (and emits a
``DeprecationWarning``); per-round arguments (``phase``/``step``/
``axis``/``ef_state``/``seed``) stay keyword arguments.

**Async overlap** (DESIGN.md §2.6): :func:`start_round` /
:func:`finish_round` split one gossip round around the compute of the
next step — ``start_round`` captures (and compresses) the double-buffered
wire payload, ``finish_round`` issues the ppermute of the *buffered*
state inside the next step's graph and mixes on arrival as the
self-compensated correction ``x ← y + (M·b − (1−d)⊙b)`` (≡
``y + (W − I)·b``), which preserves the node average exactly for any
buffer.  Global/PGA rounds stay synchronous — :func:`overlap_flush` runs
the exact collective and re-primes the buffer at the period boundary.
"""
from __future__ import annotations

import dataclasses
import functools
import warnings
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import topology as topo

PyTree = Any

BACKENDS = ("reference", "pallas")
SHARD_MODES = ("auto", "stacked", "sharded")


@dataclasses.dataclass(frozen=True)
class CommSpec:
    """Round-invariant communication configuration (DESIGN.md §2.6).

    One frozen value object carries every knob of a communication round
    that does not change between rounds — topology, node/pod counts,
    backend routing (mesh/axes/shard mode), wire dtype, and the gossip /
    global compressors — so call sites thread *one* argument instead of
    hand-forwarding ~12 kwargs (the hand-forwarding is how PR 5's
    ``model_axis`` was silently dropped by ``Decentralized.communicate``).
    Per-round values (``phase``, ``step``, ``ef_state``, ``seed``) remain
    arguments of :func:`communicate` / :func:`start_round` /
    :func:`finish_round`.

    Build it with ``DistConfig.comm_spec(n_nodes, mesh=...)`` (the
    canonical constructor) and derive variants with :meth:`replace` —
    e.g. ``spec.replace(compressor=None)`` for a round that must return
    a bare pytree instead of the ``(mixed, ef)`` tuple.
    """
    topology: str
    n_nodes: int
    n_pods: int = 1
    backend: str = "reference"
    mesh: Optional[jax.sharding.Mesh] = None
    node_axis: str = "data"
    model_axis: str = "model"
    shard_mode: str = "auto"
    leaf_threshold: Optional[int] = None
    comm_dtype: Any = None
    compressor: Any = None
    global_compressor: Any = None

    def replace(self, **kw) -> "CommSpec":
        return dataclasses.replace(self, **kw)

    def validate(self) -> "CommSpec":
        if self.backend not in BACKENDS:
            raise ValueError(f"CommSpec: unknown backend {self.backend!r} "
                             f"(expected one of {BACKENDS})")
        if self.shard_mode not in SHARD_MODES:
            raise ValueError(f"CommSpec: unknown shard_mode "
                             f"{self.shard_mode!r} "
                             f"(expected one of {SHARD_MODES})")
        if self.n_nodes < 1:
            raise ValueError("CommSpec: n_nodes must be >= 1")
        if self.n_pods < 1:
            raise ValueError("CommSpec: n_pods must be >= 1")
        return self

    @property
    def lossy(self) -> bool:
        """True when the gossip wire payload is lossy-compressed."""
        return self.compressor is not None and self.compressor.lossy

    def uses_sharded(self) -> bool:
        """True when rounds route through the shard_map + ppermute path."""
        return use_sharded_backend(self.backend, self.mesh, self.node_axis,
                                   self.shard_mode)


# ---------------------------------------------------------------------------
# Telemetry hooks (DESIGN.md §2.7): when an ambient obs.Telemetry hub is
# installed, every round entry point self-reports a `comm_round` record
# (analytic vs measured wire bytes, phase/shift/backend tags).  With no
# hub installed the hooks are a None check — the hot path pays nothing.
# Records emitted while *tracing* (inside jit) carry traced=True and
# appear once per compiled variant; per-executed-round counts come from
# the trainer's step records.  Each entry point also runs under a
# `jax.named_scope` of its role (round, issue, apply, flush): the ops it
# emits carry that name in their HLO op_name, so a profiler trace shows
# them on the device clock, hub or no hub.
# ---------------------------------------------------------------------------
def _hub():
    try:
        from repro import obs
    except ImportError:                              # pragma: no cover
        return None
    return obs.get_telemetry()


def _meter(tel, params: PyTree, spec: CommSpec, *, phase: str, step: int,
           role: str, wires=None) -> None:
    """Emit one ``comm_round`` record."""
    from repro.obs import meters as obs_meters
    sharded = spec.uses_sharded()
    km = 1
    if sharded and spec.mesh is not None:
        names = node_axis_names(spec.mesh, spec.node_axis)
        km = _model_names_count(spec.mesh, spec.model_axis, names)[1]
    fields = obs_meters.comm_round_fields(
        params, phase=phase, topology=spec.topology,
        n_nodes=spec.n_nodes, step=int(step), n_pods=spec.n_pods,
        backend=spec.backend, sharded=sharded,
        comm_dtype=spec.comm_dtype, compressor=spec.compressor,
        global_compressor=spec.global_compressor, model_shards=km,
        wires=wires, role=role,
        staged_bytes=_staged_bytes(
            params, phase=phase, backend=spec.backend, sharded=sharded,
            compressor=spec.compressor,
            global_compressor=spec.global_compressor,
            leaf_threshold=spec.leaf_threshold))
    tel.emit("comm_round", **fields)


def _staged_bytes(params: PyTree, *, phase: str, backend: str,
                  sharded: bool, compressor=None, global_compressor=None,
                  leaf_threshold: Optional[int] = None,
                  weight: Optional[jax.Array] = None) -> int:
    """Per-node bytes of a pallas round that go through a packed ``(n, D)``
    staging buffer: the leaves below the dispatch threshold on the stacked
    uncompressed path; every leaf where the round packs the whole tree
    (the sharded path, a lossy global codec's collective); none where a
    lossy gossip codec mixes leaf by leaf, or on the reference backend.
    A push-sum ``weight`` column rides the buffer beside the leaves."""
    from repro.kernels import mixing_pallas
    if backend != "pallas" or phase == "none":
        return 0
    tree = params if weight is None else {"x": params, "w": weight}
    every_leaf = mixing_pallas.staged_bytes(tree, float("inf"))
    if sharded:
        return every_leaf
    if phase in ("global", "pod_avg") and global_compressor is not None:
        if global_compressor.lossy:
            return every_leaf
    elif compressor is not None and compressor.lossy:
        return 0
    return mixing_pallas.staged_bytes(tree, leaf_threshold)


def meter_round(params: PyTree, spec: CommSpec, *, phase: str,
                step: int = 0, role: str = "round", wires=None) -> None:
    """Public metering hook for step functions whose fused kernels bypass
    :func:`communicate` (e.g. the pallas residual-fused train step): emit
    the same ``comm_round`` record the metered entry points would.  No-op
    without an ambient telemetry hub."""
    tel = _hub()
    if tel is not None:
        _meter(tel, params, spec, phase=phase, step=step, role=role,
               wires=wires)


def _check_backend(backend: str, axis: int,
                   caller: str = "mixing.communicate") -> bool:
    """True if the pallas backend should handle this call.

    ``caller`` names the public entry point that reached the check, so the
    raise is attributable when routed through wrappers like
    ``simulate(backend=...)`` or ``Decentralized.communicate``.
    """
    if backend not in BACKENDS:
        raise ValueError(f"{caller}: unknown mixing backend {backend!r} "
                         f"(expected one of {BACKENDS})")
    if backend == "pallas" and axis != 0:
        raise ValueError(
            f"{caller}: pallas mixing backend requires the node axis at "
            f"position 0 (got axis={axis}); pass axis=0 or select "
            f"backend='reference' for a non-leading node axis")
    return backend == "pallas"


def _check_pods(n_nodes: int, n_pods: int, caller: str) -> None:
    """pod_avg needs equal pod blocks; validated up front (before any no-op
    early return) so a bad ``n_pods`` surfaces as this message instead of
    mis-shaped pod blocks/halos deeper in the round."""
    if n_pods < 1 or n_nodes % n_pods:
        raise ValueError(
            f"{caller}: n_pods={n_pods} does not divide n_nodes={n_nodes} "
            f"— the pod_avg round needs equal pod blocks "
            f"(DistConfig.validate_nodes catches this at config time)")


def node_axis_names(mesh: jax.sharding.Mesh, node_axis: str = "data"
                    ) -> Tuple[str, ...]:
    """Mesh axis names forming the gossip node axis under
    ``DistConfig.node_axis`` semantics (launch/mesh.py): ``"data"`` flattens
    ``(pod, data)`` when a pod axis exists; ``"pod"`` gossips across pods
    only (hierarchical mode)."""
    axes = dict(mesh.shape)
    if node_axis == "data":
        return tuple(a for a in ("pod", "data") if a in axes)
    if node_axis == "pod":
        # single-pod meshes have no 'pod' axis: one gossip node, no shards
        return ("pod",) if "pod" in axes else ()
    if node_axis in axes:  # explicit mesh axis (tests / custom meshes)
        return (node_axis,)
    raise ValueError(f"node_axis must be 'data', 'pod', or a mesh axis "
                     f"name, got {node_axis!r}")


def auto_axes(mesh: jax.sharding.Mesh) -> jax.sharding.Mesh:
    """``mesh`` with every axis of type Auto.  The sharded rounds and the
    train step leave layouts to the compiler; under Explicit axes (the
    default of ``jax.make_mesh``) their outputs would carry sharding types
    that refuse unsharded operands and rolls over the node axis."""
    auto = jax.sharding.AxisType.Auto
    if all(t == auto for t in mesh.axis_types):
        return mesh
    return jax.sharding.Mesh(mesh.devices, mesh.axis_names,
                             axis_types=(auto,) * mesh.devices.ndim)


def node_shard_count(mesh: Optional[jax.sharding.Mesh],
                     node_axis: str = "data") -> int:
    """How many shards the node axis is split over on ``mesh`` (1 = local)."""
    if mesh is None:
        return 1
    names = node_axis_names(mesh, node_axis)
    return int(np.prod([mesh.shape[a] for a in names], dtype=np.int64)) \
        if names else 1


def model_axis_names(mesh: jax.sharding.Mesh, model_axis: str = "model",
                     node_names: Tuple[str, ...] = ()) -> Tuple[str, ...]:
    """Mesh axis names forming the tensor-parallel model axis for the 2-D
    ``(node, model)`` sharded comm path (``DistConfig.model_axis``): the
    named axis when it exists on ``mesh`` and is not already part of the
    node axis, else ``()`` (column-replicated, the 1-D behavior)."""
    if not model_axis:
        return ()
    axes = dict(mesh.shape)
    if model_axis in axes and model_axis not in node_names:
        return (model_axis,)
    return ()


def _model_names_count(mesh: jax.sharding.Mesh, model_axis: str,
                       node_names: Tuple[str, ...]):
    """``(mnames, k_model)`` for one sharded round — the single source of
    the model-axis resolution every sharded entry point shares."""
    mnames = model_axis_names(mesh, model_axis, node_names=node_names)
    km = int(np.prod([mesh.shape[a] for a in mnames], dtype=np.int64)) \
        if mnames else 1
    return mnames, km


def model_shard_count(mesh: Optional[jax.sharding.Mesh],
                      model_axis: str = "model",
                      node_axis: str = "data") -> int:
    """How many column slices the model axis splits the packed comm state
    into on ``mesh`` (1 = replicated columns, the pre-2-D behavior)."""
    if mesh is None:
        return 1
    names = node_axis_names(mesh, node_axis)
    return _model_names_count(mesh, model_axis, names)[1]


def use_sharded_backend(backend: str, mesh: Optional[jax.sharding.Mesh],
                        node_axis: str = "data",
                        shard_mode: str = "auto") -> bool:
    """True when ``communicate`` should route pallas through the shard_map
    wrapper: the node axis is genuinely sharded and the mode allows it."""
    if shard_mode not in SHARD_MODES:
        raise ValueError(f"unknown comm_shard_mode {shard_mode!r} "
                         f"(expected one of {SHARD_MODES})")
    if backend != "pallas" or shard_mode == "stacked":
        return False
    sharded = node_shard_count(mesh, node_axis) > 1
    if shard_mode == "sharded" and not sharded:
        raise ValueError("comm_shard_mode='sharded' requires a mesh whose "
                         "node axis spans more than one device (got "
                         "mesh="
                         f"{'None' if mesh is None else dict(mesh.shape)})")
    return sharded


# ---------------------------------------------------------------------------
# Roll-based mixing (pjit path)
# ---------------------------------------------------------------------------
def mix_array(x: jax.Array, weights: Dict[int, float], axis: int = 0,
              comm_dtype=None) -> jax.Array:
    """(W·x) along ``axis`` for circulant W given its shift decomposition.

    ``roll(x, -s)`` moves node (i+s)'s row into slot i, matching
    ``W[i, i+s] = w_s``; under GSPMD each term is one collective-permute.

    ``comm_dtype`` (e.g. bf16): neighbor terms are cast to the wire dtype
    before the roll — the collective-permute moves half the bytes; the self
    term and the weighted sum stay in the storage dtype (the paper's
    "orthogonal quantization" hook, §2 Related Work).
    """
    acc = None
    for s, w in weights.items():
        if s == 0:
            term = x
        else:
            src = x.astype(comm_dtype) if comm_dtype is not None else x
            term = jnp.roll(src, -s, axis=axis).astype(x.dtype)
        term = term * jnp.asarray(w, dtype=x.dtype)
        acc = term if acc is None else acc + term
    return acc


def mix_array_grid(x: jax.Array, n: int, axis: int = 0) -> jax.Array:
    """Torus-grid mixing: factor node axis into (r, c), roll each dim."""
    r, c = topo.grid_shape(n)
    shape = x.shape
    xg = x.reshape(shape[:axis] + (r, c) + shape[axis + 1:])
    acc = None
    for (dr, dc), w in topo.grid_shift_weights(n).items():
        term = xg
        if dr:
            term = jnp.roll(term, -dr, axis=axis)
        if dc:
            term = jnp.roll(term, -dc, axis=axis + 1)
        term = term * jnp.asarray(w, dtype=x.dtype)
        acc = term if acc is None else acc + term
    return acc.reshape(shape)


def mix_pytree(params: PyTree, topology: str, n: int, step: int = 0,
               axis: int = 0, comm_dtype=None,
               backend: str = "reference",
               leaf_threshold: Optional[int] = None) -> PyTree:
    """Gossip step ``x ← W x`` applied leaf-wise over a pytree whose leaves
    carry the node axis at ``axis``."""
    use_pallas = _check_backend(backend, axis, caller="mixing.mix_pytree")
    if n == 1 or topology == "disconnected":
        return params
    if use_pallas:
        from repro.kernels import mixing_pallas
        return mixing_pallas.fused_step_mix(
            params, phase="gossip", topology=topology, n_nodes=n, step=step,
            comm_dtype=comm_dtype, leaf_threshold=leaf_threshold)
    if topology == "grid":
        return jax.tree.map(lambda p: mix_array_grid(p, n, axis), params)
    weights = topo.shift_weights(topology, n, step)
    return jax.tree.map(lambda p: mix_array(p, weights, axis, comm_dtype),
                        params)


def _collective_round_reference(params: PyTree, compressor, ef_state,
                                seed, n_pods: int = 1):
    """Reference compressed-collective averaging round on the packed
    ``(n, D)`` state (repro.compress.collective; DESIGN.md §2.3
    "Compressed collectives").  Returns ``(mixed, new_ef_state)``."""
    from repro.compress import collective as ccol
    from repro.kernels.mixing_pallas import flatten_nodes

    xf, unflatten = flatten_nodes(params)
    ef2 = ef_unflatten = None
    if ef_state is not None:
        ef2, ef_unflatten = flatten_nodes(ef_state)
    mixed, new_e = ccol.collective_round(xf, ef2, compressor.name, seed,
                                         n_pods=n_pods)
    return unflatten(mixed), (ef_unflatten(new_e) if ef2 is not None
                              else None)


def global_average_pytree(params: PyTree, axis: int = 0,
                          comm_dtype=None,
                          backend: str = "reference",
                          leaf_threshold: Optional[int] = None,
                          compressor=None, ef_state: Optional[PyTree] = None,
                          seed=0):
    """Periodic global averaging ``x ← (1/n)𝟙𝟙ᵀ x`` (All-Reduce step).
    With ``comm_dtype`` the reduction runs on wire-dtype operands — the
    all-reduce moves half the bytes (node counts are small, so bf16
    accumulation over n ≤ 32 replicas is benign).

    With a lossy ``compressor`` (``DistConfig.comm_global_compression``)
    the round runs the compressed collective instead — the compensated
    ``x + (r − ρ)`` around a chunked reduce-scatter → all-gather of
    int8/fp8 blocks (DESIGN.md §2.3 "Compressed collectives"); the payload
    supersedes ``comm_dtype`` and the return value becomes
    ``(mixed, new_ef_state)``.
    """
    use_pallas = _check_backend(backend, axis,
                                caller="mixing.global_average_pytree")
    if compressor is not None and compressor.lossy:
        if axis != 0:
            raise ValueError("mixing.global_average_pytree: the compressed "
                             "collective requires the node axis at "
                             f"position 0 (got axis={axis})")
        if use_pallas:
            from repro.kernels import mixing_pallas
            n = jax.tree.leaves(params)[0].shape[0]
            return mixing_pallas.collective_step_mix(
                params, compressor=compressor, ef_state=ef_state, seed=seed,
                phase="global", n_nodes=n)
        return _collective_round_reference(params, compressor, ef_state,
                                           seed)
    if use_pallas:
        from repro.kernels import mixing_pallas
        leaves = jax.tree.leaves(params)
        out = mixing_pallas.global_average(params, leaves[0].shape[0],
                                           comm_dtype=comm_dtype,
                                           leaf_threshold=leaf_threshold)
        return (out, ef_state) if compressor is not None else out
    def avg(p):
        src = p.astype(comm_dtype) if comm_dtype is not None else p
        m = jnp.mean(src, axis=axis, keepdims=True)
        return jnp.broadcast_to(m, p.shape).astype(p.dtype)
    out = jax.tree.map(avg, params)
    return (out, ef_state) if compressor is not None else out


def pod_average_pytree(params: PyTree, n_pods: int, axis: int = 0,
                       comm_dtype=None,
                       backend: str = "reference",
                       leaf_threshold: Optional[int] = None,
                       compressor=None, ef_state: Optional[PyTree] = None,
                       seed=0):
    """Hierarchical averaging (beyond-paper Hier-PGA, DESIGN.md §4): exact
    average *within* each pod's block of nodes — an all-reduce over the
    cheap intra-pod ICI, leaving cross-pod DCI traffic to the (rarer)
    global step.  With a lossy ``compressor`` the intra-pod collective
    runs compressed, same contract as :func:`global_average_pytree`."""
    use_pallas = _check_backend(backend, axis,
                                caller="mixing.pod_average_pytree")
    n = jax.tree.leaves(params)[0].shape[axis]
    _check_pods(n, n_pods, "mixing.pod_average_pytree")
    if compressor is not None and compressor.lossy:
        if axis != 0:
            raise ValueError("mixing.pod_average_pytree: the compressed "
                             "collective requires the node axis at "
                             f"position 0 (got axis={axis})")
        if use_pallas:
            from repro.kernels import mixing_pallas
            return mixing_pallas.collective_step_mix(
                params, compressor=compressor, ef_state=ef_state, seed=seed,
                phase="pod_avg", n_nodes=n, n_pods=n_pods)
        return _collective_round_reference(params, compressor, ef_state,
                                           seed, n_pods=n_pods)
    if use_pallas:
        from repro.kernels import mixing_pallas
        out = mixing_pallas.pod_average(params, n, n_pods,
                                        comm_dtype=comm_dtype,
                                        leaf_threshold=leaf_threshold)
        return (out, ef_state) if compressor is not None else out
    def avg(p):
        per = p.shape[axis] // n_pods
        shp = p.shape[:axis] + (n_pods, per) + p.shape[axis + 1:]
        src = p.astype(comm_dtype) if comm_dtype is not None else p
        g = src.reshape(shp)
        m = jnp.mean(g, axis=axis + 1, keepdims=True)
        return jnp.broadcast_to(m, g.shape).reshape(p.shape).astype(p.dtype)
    out = jax.tree.map(avg, params)
    return (out, ef_state) if compressor is not None else out


# ---------------------------------------------------------------------------
# shard_map + ppermute (explicit decentralized runtime)
# ---------------------------------------------------------------------------
def _perm_for_shift(n: int, s: int) -> Tuple[Tuple[int, int], ...]:
    # node i receives from node (i + s) mod n  => edge (src=(i+s), dst=i)
    return tuple(((i + s) % n, i) for i in range(n))


def gossip_ppermute(x: jax.Array, axis_name: str, n: int,
                    weights: Dict[int, float]) -> jax.Array:
    """W·x where each mesh slot along ``axis_name`` holds one node's block.
    Must be called inside shard_map."""
    acc = None
    for s, w in weights.items():
        if s == 0:
            term = x
        else:
            term = jax.lax.ppermute(x, axis_name, _perm_for_shift(n, s))
        term = term * jnp.asarray(w, dtype=x.dtype)
        acc = term if acc is None else acc + term
    return acc


def global_average_ppermute(x: jax.Array, axis_name) -> jax.Array:
    """All-Reduce mean over the node axis (inside shard_map)."""
    return jax.lax.pmean(x, axis_name)


def make_shard_map_mixer(mesh: jax.sharding.Mesh, axis_name: str,
                         topology: str, step: int = 0) -> Callable:
    """Build ``f(x_stacked) -> W @ x_stacked`` running as shard_map over
    ``axis_name`` — the explicit runtime equivalent of :func:`mix_pytree`."""
    from jax.sharding import PartitionSpec as P

    n = mesh.shape[axis_name]
    weights = topo.shift_weights(topology, n, step)

    def node_fn(x):
        return gossip_ppermute(x, axis_name, n, weights)

    spec = P(axis_name)
    return jax.shard_map(node_fn, mesh=auto_axes(mesh),
                         in_specs=(spec,), out_specs=spec)


# ---------------------------------------------------------------------------
# Compressed rounds (reference math; DESIGN.md §2.3)
# ---------------------------------------------------------------------------
def compensated_round_factors(phase: str, topology: str, n: int,
                              step: int = 0, n_pods: int = 1):
    """``(w, M)`` for the self-compensated compressed round
    ``mixed = x + (M·q − w ⊙ q)`` with ``w = 1 − diag(W)`` (= the row sums
    of M for a doubly-stochastic round, so the correction vanishes when
    every node transmits the same ``q``)."""
    from repro.kernels.mixing_pallas import phase_matrices
    d, M = phase_matrices(phase, topology, n, step=step, n_pods=n_pods)
    return (1.0 - d).astype(np.float32), M


def _compressed_round_reference(params: PyTree, q: PyTree, phase: str,
                                topology: str, n: int, step: int,
                                n_pods: int, comm_dtype=None) -> PyTree:
    """Apply ``x + (M·q − w ⊙ q)`` leaf-wise (dense M: this is the oracle
    the fused kernels are tested against; n ≤ 64 so the n×n factor is
    trivial on one host).  For the ``"global"`` phase the estimate is
    additionally wire-cast per ``comm_dtype`` — the one collective whose
    operand is not the compressed payload (DESIGN.md §2.3); the cast
    applies to *both* occurrences of q, so the constant fixed point
    survives."""
    w, M = compensated_round_factors(phase, topology, n, step, n_pods)
    wj, Mj = jnp.asarray(w), jnp.asarray(M)
    cast = comm_dtype if phase == "global" else None

    def one(x, qq):
        x2 = x.reshape(n, -1).astype(jnp.float32)
        q2 = qq.reshape(n, -1).astype(jnp.float32)
        if cast is not None:
            q2 = q2.astype(cast).astype(jnp.float32)
        corr = Mj @ q2 - wj * q2
        return (x2 + corr).reshape(x.shape).astype(x.dtype)

    return jax.tree.map(one, params, q)


def _communicate_compressed(params: PyTree, *, spec: CommSpec, ef_state,
                            seed, phase: str, step: int, axis: int):
    """Compressor-aware dispatch behind :func:`communicate` — always
    returns ``(mixed, new_ef_state)``.  ``spec.global_compressor``
    (``DistConfig.comm_global_compression``) overrides the averaging
    phases — a lossy codec with the compressed collective, the identity
    codec with the exact psum path — while ``spec.compressor`` keeps
    handling gossip rounds."""
    compressor = spec.compressor
    global_compressor = spec.global_compressor
    n_nodes, n_pods = spec.n_nodes, spec.n_pods
    if phase not in ("none", "gossip", "global", "pod_avg"):
        raise ValueError(f"unknown communication phase {phase!r}")
    if phase == "pod_avg":
        _check_pods(n_nodes, n_pods, "mixing.communicate")
    if phase == "none" or n_nodes == 1:
        return params, ef_state
    glossy = global_compressor is not None and global_compressor.lossy
    if global_compressor is not None and phase in ("global", "pod_avg"):
        if glossy:
            # the collective supersedes the gossip compressor and
            # comm_dtype for the averaging phases (DESIGN.md §2.3
            # Compressed collectives)
            if spec.uses_sharded():
                return _communicate_sharded_collective(
                    params, compressor=global_compressor, ef_state=ef_state,
                    seed=seed, phase=phase, n_nodes=n_nodes, n_pods=n_pods,
                    mesh=spec.mesh, node_axis=spec.node_axis,
                    model_axis=spec.model_axis,
                    caller="mixing.communicate")
            if phase == "global":
                return global_average_pytree(
                    params, axis=axis, backend=spec.backend,
                    compressor=global_compressor, ef_state=ef_state,
                    seed=seed)
            return pod_average_pytree(
                params, n_pods, axis=axis, backend=spec.backend,
                compressor=global_compressor, ef_state=ef_state, seed=seed)
        # identity global codec: the averaging phase runs the exact psum
        # path bit-identically.  The global codec supersedes the gossip
        # compressor for these phases exactly like a lossy codec does —
        # recursing with the lossy gossip compressor attached would run
        # the compensated-psum gossip round instead (the documented
        # contract is "exact psum path, bit-identically")
        mixed = _communicate_impl(
            params, spec.replace(compressor=None, global_compressor=None),
            phase=phase, step=step, axis=axis)
        return mixed, ef_state
    if compressor is None or not compressor.lossy:
        # identity / no gossip compressor: the exact pre-compression path,
        # bit-identically
        mixed = _communicate_impl(
            params, spec.replace(compressor=None, global_compressor=None),
            phase=phase, step=step, axis=axis)
        return mixed, ef_state
    # gossip/pod_avg: the lossy payload IS the wire, comm_dtype is
    # superseded; global: the psum operand is uncompressed fp32 sums, so
    # comm_dtype still wire-casts it on every backend (DESIGN.md §2.3)
    if spec.uses_sharded():
        return communicate_sharded(
            params, spec.replace(global_compressor=None), phase=phase,
            step=step, ef_state=ef_state, seed=seed)
    if spec.backend == "pallas":
        from repro.kernels import mixing_pallas
        return mixing_pallas.compressed_step_mix(
            params, compressor=compressor, ef_state=ef_state, seed=seed,
            phase=phase, topology=spec.topology, n_nodes=n_nodes, step=step,
            n_pods=n_pods, comm_dtype=spec.comm_dtype)
    from repro import compress as compress_mod
    q, new_ef = compress_mod.apply_tree(compressor, params, ef_state, seed)
    mixed = _compressed_round_reference(params, q, phase, spec.topology,
                                        n_nodes, step, n_pods,
                                        comm_dtype=spec.comm_dtype)
    return mixed, new_ef


# ---------------------------------------------------------------------------
# Communication-op selector used by the training step
# ---------------------------------------------------------------------------
def communicate(params: PyTree, spec: Optional[CommSpec] = None, *,
                phase: str, step: int = 0, axis: int = 0,
                ef_state: Optional[PyTree] = None, seed=0,
                topology: Optional[str] = None,
                n_nodes: Optional[int] = None, comm_dtype=None,
                n_pods: int = 1, backend: str = "reference",
                mesh: Optional[jax.sharding.Mesh] = None,
                node_axis: str = "data", shard_mode: str = "auto",
                leaf_threshold: Optional[int] = None,
                compressor=None, global_compressor=None,
                model_axis: str = "model") -> PyTree:
    """Apply one communication round to decentralized parameters.

    Primary signature: ``communicate(params, spec, phase=..., step=...)``
    with a :class:`CommSpec` carrying every round-invariant knob
    (``DistConfig.comm_spec()`` builds it canonically).  Per-round values
    — ``phase``, ``step``, ``axis``, ``ef_state``, ``seed`` — stay
    keyword arguments.  The legacy all-kwargs form
    (``communicate(params, phase=..., topology=..., n_nodes=..., ...)``)
    still works as a thin shim that builds the spec, and emits a
    ``DeprecationWarning``; mixing ``spec=`` with legacy round-invariant
    kwargs is a ``TypeError`` (derive variants with ``spec.replace``).

    phase:
      "none"    — no communication (Local SGD between syncs; Parallel SGD's
                  gradient all-reduce happens in the grad path instead)
      "gossip"  — x ← W x
      "global"  — x ← x̄ (periodic All-Reduce global averaging)
      "pod_avg" — exact average within each pod block (Hier-PGA)

    backend:
      "reference" — the roll / jnp.mean path (oracle; GSPMD handles any
                    mesh sharding transparently)
      "pallas"    — fused single-pass kernels (repro.kernels.mixing_pallas)

    With a ``mesh`` whose node axis (``node_axis`` under
    ``DistConfig.node_axis`` semantics) spans more than one device, the
    pallas backend routes through :func:`communicate_sharded` — per-shard
    fused kernels with ppermute halo exchange — unless
    ``shard_mode="stacked"`` forces the local path.  ``shard_mode``
    mirrors ``DistConfig.comm_shard_mode``: "auto" (detect), "stacked"
    (never shard), "sharded" (require a sharded mesh, else raise).

    With a ``compressor`` (repro.compress; ``DistConfig.comm_compression``)
    the wire payload is compressed and the return value becomes
    ``(mixed, new_ef_state)``: ``ef_state`` is the per-node error-feedback
    memory (None disables EF — the compensated round still keeps the self
    term exact), ``seed`` the per-round randomness key (pass the training
    step for unbiased stochastic rounding).  The identity compressor
    routes to the exact uncompressed path, bit-identically
    (DESIGN.md §2.3).

    ``global_compressor`` (``DistConfig.comm_global_compression``)
    supersedes ``compressor`` for the ``"global"``/``"pod_avg"`` phases
    (gossip rounds keep their own compressor): a lossy codec runs the
    compressed reduce-scatter → all-gather collective (DESIGN.md §2.3
    "Compressed collectives", superseding ``comm_dtype`` too), the
    identity codec routes them to the exact psum path bit-identically —
    even when the gossip ``compressor`` is lossy.  Either way the return
    value becomes ``(mixed, new_ef_state)`` like ``compressor`` does.

    ``model_axis`` (``DistConfig.model_axis``) names the tensor-parallel
    mesh axis: when present on ``mesh`` the sharded path runs 2-D — the
    packed state's columns are sliced over it, so halos/psums/collective
    stages touch only ``D/k_model`` columns per device (DESIGN.md §2.1).
    """
    if spec is not None:
        overridden = [name for name, val, default in (
            ("topology", topology, None), ("n_nodes", n_nodes, None),
            ("comm_dtype", comm_dtype, None), ("n_pods", n_pods, 1),
            ("backend", backend, "reference"), ("mesh", mesh, None),
            ("node_axis", node_axis, "data"),
            ("shard_mode", shard_mode, "auto"),
            ("leaf_threshold", leaf_threshold, None),
            ("compressor", compressor, None),
            ("global_compressor", global_compressor, None),
            ("model_axis", model_axis, "model")) if val is not default]
        if overridden:
            raise TypeError(
                "mixing.communicate: round-invariant knobs "
                f"({', '.join(overridden)}) must live on the CommSpec — "
                "derive a per-call variant with spec.replace(...) instead "
                "of mixing spec= with legacy kwargs")
        return _communicate_metered(params, spec, phase=phase, step=step,
                                    axis=axis, ef_state=ef_state, seed=seed)
    if topology is None or n_nodes is None:
        raise TypeError("mixing.communicate: pass a CommSpec "
                        "(communicate(params, spec, phase=...)) or, via the "
                        "deprecated kwargs form, both topology= and "
                        "n_nodes=")
    warnings.warn(
        "the all-kwargs form of mixing.communicate is deprecated: build a "
        "CommSpec (DistConfig.comm_spec()) and call "
        "communicate(params, spec, phase=..., step=...)",
        DeprecationWarning, stacklevel=2)
    spec = CommSpec(topology=topology, n_nodes=n_nodes, n_pods=n_pods,
                    backend=backend, mesh=mesh, node_axis=node_axis,
                    model_axis=model_axis, shard_mode=shard_mode,
                    leaf_threshold=leaf_threshold, comm_dtype=comm_dtype,
                    compressor=compressor,
                    global_compressor=global_compressor)
    return _communicate_metered(params, spec, phase=phase, step=step,
                                axis=axis, ef_state=ef_state, seed=seed)


def _communicate_metered(params: PyTree, spec: CommSpec, *, phase: str,
                         step: int = 0, axis: int = 0,
                         ef_state: Optional[PyTree] = None,
                         seed=0) -> PyTree:
    """:func:`communicate` body + telemetry: one ``comm_round`` record
    per public round, its ops under the ``round`` scope (internal
    identity/exact re-dispatches go straight to ``_communicate_impl`` and
    never double-report)."""
    tel = _hub()
    if tel is not None:
        _meter(tel, params, spec, phase=phase, step=step, role="round")
    with jax.named_scope("round"):
        return _communicate_impl(params, spec, phase=phase, step=step,
                                 axis=axis, ef_state=ef_state, seed=seed)


def _communicate_impl(params: PyTree, spec: CommSpec, *, phase: str,
                      step: int = 0, axis: int = 0,
                      ef_state: Optional[PyTree] = None, seed=0) -> PyTree:
    """Spec-driven body of :func:`communicate` (both signature shims land
    here; internal recursions target it directly so identity/exact
    re-dispatches never re-warn)."""
    _check_backend(spec.backend, axis, caller="mixing.communicate")
    if spec.compressor is not None or spec.global_compressor is not None:
        if axis != 0:
            raise ValueError("mixing.communicate: compression requires the "
                             f"node axis at position 0 (got axis={axis})")
        return _communicate_compressed(params, spec=spec, ef_state=ef_state,
                                       seed=seed, phase=phase, step=step,
                                       axis=axis)
    if phase == "pod_avg":
        _check_pods(spec.n_nodes, spec.n_pods, "mixing.communicate")
    if phase == "none" or spec.n_nodes == 1:
        return params
    if spec.uses_sharded():
        return communicate_sharded(params, spec, phase=phase, step=step)
    if phase == "gossip":
        return mix_pytree(params, spec.topology, spec.n_nodes, step=step,
                          axis=axis, comm_dtype=spec.comm_dtype,
                          backend=spec.backend,
                          leaf_threshold=spec.leaf_threshold)
    if phase == "global":
        return global_average_pytree(params, axis=axis,
                                     comm_dtype=spec.comm_dtype,
                                     backend=spec.backend,
                                     leaf_threshold=spec.leaf_threshold)
    if phase == "pod_avg":
        return pod_average_pytree(params, spec.n_pods, axis=axis,
                                  comm_dtype=spec.comm_dtype,
                                  backend=spec.backend,
                                  leaf_threshold=spec.leaf_threshold)
    raise ValueError(f"unknown communication phase {phase!r}")


# ---------------------------------------------------------------------------
# shard_map-aware pallas path: ppermute halo exchange + per-shard kernel
# ---------------------------------------------------------------------------
def _shard_blocks(M: np.ndarray, d: np.ndarray, n: int, k: int):
    """Block decomposition of one round for k node-axis shards of m = n/k
    rows each.

    Returns ``(offsets, Mstack, dstack)``: ``offsets`` is the sorted list of
    shard offsets q such that *some* shard r has a nonzero block
    ``M[r, (r+q) mod k]`` — only those blocks are halo-exchanged;
    ``Mstack[r]`` is shard r's ``(m, |offsets|·m)`` mixing factor over the
    received blocks (circulant topologies make every row identical; pod_avg
    is block-diagonal, hence per-shard rows), and ``dstack[r]`` its rows of
    the self-weight diagonal.  Passing Mstack/dstack as shard_map inputs
    sharded over the node axis hands each shard exactly its own factor with
    no device-side gather."""
    m = n // k
    offsets = [q for q in range(k)
               if any(np.any(M[r * m:(r + 1) * m,
                              ((r + q) % k) * m:(((r + q) % k) + 1) * m])
                      for r in range(k))]
    if not offsets:  # e.g. disconnected gossip: M = 0, the round is d ⊙ x
        offsets = [0]
    Mstack = np.zeros((k, m, len(offsets) * m), np.float32)
    for r in range(k):
        for j, q in enumerate(offsets):
            c = (r + q) % k
            Mstack[r, :, j * m:(j + 1) * m] = \
                M[r * m:(r + 1) * m, c * m:(c + 1) * m]
    return offsets, Mstack, d.reshape(k, m, 1).astype(np.float32)


def communicate_sharded(params: PyTree, spec: Optional[CommSpec] = None, *,
                        phase: str, topology: Optional[str] = None,
                        n_nodes: Optional[int] = None, step: int = 0,
                        comm_dtype=None, n_pods: int = 1,
                        mesh: Optional[jax.sharding.Mesh] = None,
                        node_axis: str = "data",
                        model_axis: str = "model",
                        grads: Optional[PyTree] = None,
                        gamma=None, with_residual: bool = False,
                        block_d: int = 2048,
                        interpret: Optional[bool] = None,
                        compressor=None, ef_state: Optional[PyTree] = None,
                        seed=0, global_compressor=None):
    """One communication round with the node axis sharded over ``mesh``.

    Accepts the round-invariant knobs either on a :class:`CommSpec`
    (``communicate_sharded(params, spec, phase=..., step=...)`` — the
    ``backend``/``shard_mode``/``leaf_threshold`` fields are ignored:
    calling this function *is* the sharded routing decision) or as the
    direct kwargs below.

    The stacked ``(n, D)`` state never exists on one device: a shard_map
    over the node axis gives each shard its ``(m, D)`` row-block, the
    neighbor blocks named by the round's block decomposition arrive via
    ``jax.lax.ppermute`` (wire-cast when ``comm_dtype`` is set — the cast
    bytes are what crosses the ICI), and the fused per-shard kernel
    (:func:`repro.kernels.mixing_pallas.shard_mix_block`) applies
    ``d ⊙ x_local + M_r · xs`` in one pass.  The ``"global"`` phase skips
    the halo machinery: it is a psum of wire-cast column sums (one
    all-reduce, exactly the reference collective).

    With a ``model_axis`` present on ``mesh`` (and distinct from the node
    axis) the round runs **2-D**: the packed matrix's columns are
    additionally sliced over the model axis
    (``flatten_nodes_sharded``, in/out specs ``P(node_axes, model_axes)``),
    so each device holds an ``(m, D/k_model)`` block, every halo
    ``ppermute`` moves only the local column slice (per-device wire bytes
    drop by ``k_model``), the global psum reduces over the node axis only,
    and the per-shard kernels run on the narrower blocks unchanged
    (DESIGN.md §2.1 dispatch table).  A mesh without the model axis
    (``k_model == 1``) follows exactly the 1-D code path.

    With ``grads``/``gamma`` the SGD half-step is applied before the
    exchange (the sent blocks must be half-stepped).  With
    ``with_residual`` returns ``(mixed, x̄, Σ_i‖x_i − x̄‖²)`` where the
    consensus pieces are psum-combined from per-shard kernel partials.

    With a lossy ``compressor`` the ppermute halo exchange moves the
    **compressed wire arrays** (int8/fp8 codes, top-k values + indices,
    per-row scales) instead of the fp32 blocks — this is where the
    wire-bytes reduction physically happens — and each shard rebuilds its
    neighbors' estimates locally before the compensated per-shard kernel
    (DESIGN.md §2.3).  Returns ``(mixed, new_ef_state)``.

    With a lossy ``global_compressor`` the averaging phases route to the
    compressed reduce-scatter → all-gather collective
    (:func:`_communicate_sharded_collective`; DESIGN.md §2.3 "Compressed
    collectives"), superseding ``compressor``/``comm_dtype`` for those
    phases; same ``(mixed, new_ef_state)`` contract.
    """
    from jax.sharding import PartitionSpec as P
    from repro.kernels import mixing_pallas

    if spec is not None:
        topology, n_nodes = spec.topology, spec.n_nodes
        comm_dtype, n_pods = spec.comm_dtype, spec.n_pods
        mesh, node_axis = spec.mesh, spec.node_axis
        model_axis = spec.model_axis
        compressor, global_compressor = spec.compressor, \
            spec.global_compressor
    if mesh is None:
        raise ValueError("communicate_sharded: a mesh is required (pass a "
                         "CommSpec built with mesh=..., or mesh= directly)")
    if topology is None or n_nodes is None:
        raise TypeError("communicate_sharded: pass a CommSpec or both "
                        "topology= and n_nodes=")
    names = node_axis_names(mesh, node_axis)
    if not names:
        raise ValueError(f"communicate_sharded: mesh {dict(mesh.shape)} has "
                         f"no axis for node_axis={node_axis!r} — use the "
                         f"stacked path (communicate) instead")
    k = node_shard_count(mesh, node_axis)
    if n_nodes % k:
        raise ValueError(f"communicate_sharded: n_nodes={n_nodes} not "
                         f"divisible by the {k} node-axis shards of "
                         f"mesh axes {names}")
    if phase not in ("gossip", "global", "pod_avg"):
        raise ValueError(f"communicate_sharded: no sharded kernel for "
                         f"phase {phase!r}")
    if phase == "pod_avg":
        _check_pods(n_nodes, n_pods, "mixing.communicate_sharded")
    mnames, km = _model_names_count(mesh, model_axis, names)
    if global_compressor is not None and phase in ("global", "pod_avg"):
        if grads is not None or with_residual:
            raise ValueError("communicate_sharded: the compressed "
                             "collective composes with neither the fused "
                             "half-step nor the fused residual (apply the "
                             "optimizer first; consensus falls back to "
                             "train.state.consensus_distance)")
        if global_compressor.lossy:
            return _communicate_sharded_collective(
                params, compressor=global_compressor, ef_state=ef_state,
                seed=seed, phase=phase, n_nodes=n_nodes, n_pods=n_pods,
                mesh=mesh, node_axis=node_axis, model_axis=model_axis,
                caller="mixing.communicate_sharded")
        # identity collective: the averaging phase runs the exact psum
        # path, bit-identically.  The global codec supersedes the gossip
        # compressor here (identity and lossy alike), so the recursion
        # must NOT re-attach a lossy gossip compressor — that would run
        # the compensated psum instead of the documented exact one.
        mixed = communicate_sharded(
            params, phase=phase, topology=topology, n_nodes=n_nodes,
            step=step, comm_dtype=comm_dtype, n_pods=n_pods, mesh=mesh,
            node_axis=node_axis, model_axis=model_axis, block_d=block_d,
            interpret=interpret)
        return mixed, ef_state
    if compressor is not None:
        if not compressor.lossy:   # identity: exact uncompressed path
            mixed = communicate_sharded(
                params, phase=phase, topology=topology, n_nodes=n_nodes,
                step=step, comm_dtype=comm_dtype, n_pods=n_pods, mesh=mesh,
                node_axis=node_axis, model_axis=model_axis,
                block_d=block_d, interpret=interpret)
            return mixed, ef_state
        if grads is not None or with_residual:
            raise ValueError("communicate_sharded: compression composes "
                             "with neither the fused half-step nor the "
                             "fused residual (apply the optimizer first; "
                             "consensus falls back to "
                             "train.state.consensus_distance)")
        return _communicate_sharded_compressed(
            params, compressor=compressor, ef_state=ef_state, seed=seed,
            phase=phase, topology=topology, n_nodes=n_nodes, step=step,
            n_pods=n_pods, mesh=mesh, names=names, k=k, mnames=mnames,
            km=km, block_d=block_d, interpret=interpret,
            comm_dtype=comm_dtype)
    with_g = grads is not None
    if with_g and gamma is None:
        raise ValueError("grads given without gamma")
    # grid gossip ignores comm_dtype in the reference path — mirror that
    wire_dtype = None if (phase == "gossip" and topology == "grid") \
        else comm_dtype

    n = n_nodes
    xf, unflatten = mixing_pallas.flatten_nodes_sharded(params, km)
    gf = mixing_pallas.flatten_nodes_sharded(grads, km)[0] if with_g \
        else None
    # 2-D specs: rows over the node axis, columns over the model axis
    # (flatten_nodes_sharded pads so the column split is exact); km == 1
    # keeps yesterday's 1-D specs verbatim
    xspec = P(names, mnames) if mnames else P(names)
    bar_spec = P(None, mnames) if mnames else P()

    d, M = mixing_pallas.phase_matrices(phase, topology, n, step=step,
                                        n_pods=n_pods)
    offsets, Mstack, dstack = _shard_blocks(M, d, n, k)
    perms = {q: tuple(((r + q) % k, r) for r in range(k))
             for q in offsets if q}

    def half_step(xb, gb):
        if gb is None:
            return xb
        return xb - jnp.asarray(gamma, jnp.float32) * gb

    def finish(mixed, cs):
        xbar = jax.lax.psum(cs, names) / n        # (1, D/km) over nodes
        # cancellation-free consensus: Σ‖x_i − x̄‖² directly (the fused
        # Σ‖x‖² − n‖x̄‖² form loses all precision when consensus ≪ ‖x‖²);
        # the extra pass touches only the shard's local (m, D/km) block,
        # and the scalar is completed by a psum over the model slices
        resid = jax.lax.psum(jnp.sum(jnp.square(mixed - xbar)), names)
        if mnames:
            resid = jax.lax.psum(resid, mnames)
        return mixed, xbar, resid

    if phase == "global":
        # x̄ everywhere: one all-reduce of wire-cast column sums over the
        # node axis only (each model shard averages its own column slice);
        # the mixed iterate is the broadcast mean, so the residual is 0.
        def body(xb, *rest):
            x = half_step(xb, rest[0] if with_g else None)
            xw = x.astype(wire_dtype).astype(jnp.float32) \
                if wire_dtype is not None else x
            xbar = jax.lax.psum(jnp.sum(xw, axis=0, keepdims=True),
                                names) / n
            mixed = jnp.broadcast_to(xbar, x.shape)
            if with_residual:
                return mixed, xbar, jnp.zeros((), jnp.float32)
            return mixed

        in_specs = (xspec,) + ((xspec,) if with_g else ())
        operands = (xf,) + ((gf,) if with_g else ())
    else:
        def body(xb, *rest):
            idx = 0
            gb = None
            if with_g:
                gb = rest[idx]; idx += 1
            Mr, dr = rest[idx], rest[idx + 1]
            x = half_step(xb, gb)
            send = x.astype(wire_dtype) if wire_dtype is not None else x
            parts = [send if q == 0
                     else jax.lax.ppermute(send, names, perms[q])
                     for q in offsets]
            xs = jnp.concatenate(parts, axis=0).astype(jnp.float32)
            out = mixing_pallas.shard_mix_block(
                x, xs, dr[0], Mr[0], with_residual=with_residual,
                block_d=block_d, interpret=interpret)
            if with_residual:
                return finish(*out)
            return out

        in_specs = (xspec,) + ((xspec,) if with_g else ()) \
            + (P(names), P(names))
        operands = (xf,) + ((gf,) if with_g else ()) \
            + (jnp.asarray(Mstack), jnp.asarray(dstack))

    out_specs = (xspec, bar_spec, P()) if with_residual else xspec
    fn = jax.shard_map(body, mesh=auto_axes(mesh),
                       in_specs=in_specs, out_specs=out_specs,
                       check_vma=False)
    out = fn(*operands)

    if with_residual:
        mixed, xbar, resid = out
        return unflatten(mixed), unflatten(xbar, drop_node=True), resid
    return unflatten(out)


def _communicate_sharded_compressed(params: PyTree, *, compressor, ef_state,
                                    seed, phase: str, topology: str,
                                    n_nodes: int, step: int, n_pods: int,
                                    mesh: jax.sharding.Mesh, names, k: int,
                                    mnames=(), km: int = 1,
                                    block_d: int,
                                    interpret: Optional[bool],
                                    comm_dtype=None):
    """Compressed halo exchange: each shard compresses its own row-block
    (row-local, so it runs *outside* the shard_map under GSPMD without
    collectives), ``ppermute``s the wire arrays to the neighbors named by
    the round's block decomposition, rebuilds their estimates ``q``, and
    applies the compensated per-shard kernel
    ``x + (M_r · qs − (1 − d_r) ⊙ q_self)``.  Node-independent wire
    arrays (leading axis 1, e.g. randk's shared column indices) ride
    replicated and are never ppermuted.

    2-D meshes (``km > 1``): for the quantizer compressors (int8/fp8,
    whose code arrays share the leaf's column layout) each leaf is padded
    to a ``km`` multiple *before* compression — inert zero columns, so
    scales, column-hash randomness, and therefore every rounding decision
    on real columns are bit-stable under resharding — and the code arrays
    are column-sliced over the model axis alongside the packed matrix
    (``flatten_nodes_sharded`` chunk order, spec negotiation in
    ``models.sharding.wire_column_spec``): the ppermuted wire bytes per
    device drop by ``km``.  Sparsifier payloads (top-k/rand-k values +
    global index sets) cannot column-slice — they ride the
    model-replicated 1-D path unchanged.

    The ``"global"`` phase applies the compensation ``x + (q̄ − q)``
    around one psum of column sums over the node axis; the psum itself is
    the reference collective (compressed all-reduce would need a
    compressed collective — the documented DESIGN.md §2.3 limitation), so
    its operand is wire-cast per ``comm_dtype`` exactly like the
    uncompressed path (every backend applies the same cast to ``q``,
    keeping parity and the constant fixed point).
    """
    from jax.sharding import PartitionSpec as P
    from repro.kernels import mixing_pallas
    from repro.models.sharding import wire_column_spec

    n = n_nodes
    # only the quantizers' code arrays share the leaf column layout, so
    # only they can ride the model-sliced 2-D path (sparsifier index sets
    # are leaf-global); km == 1 keeps the 1-D path bit-identical
    kmq = km if (km > 1 and compressor.name in ("int8", "fp8")) else 1
    mn = mnames if kmq > 1 else ()

    wires, new_ef, chunks = _sharded_wire_build(
        params, compressor=compressor, ef_state=ef_state, seed=seed, n=n,
        kmq=kmq)

    if phase == "global":
        wire_arrs = [a for w in wires for a in (*w.payload, *w.aux)]
        wire_specs = tuple(wire_column_spec(a.shape, n, names, mn, kmq)
                           for a in wire_arrs)
        build_q = _wire_build_q(compressor, wires, chunks)
        xf, unflatten = mixing_pallas.flatten_nodes_sharded(params, kmq)
        xspec = P(names, mn) if mn else P(names)

        def body(xb, *arrs):
            q = build_q(arrs)
            if comm_dtype is not None:
                q = q.astype(comm_dtype).astype(jnp.float32)
            qbar = jax.lax.psum(jnp.sum(q, axis=0, keepdims=True), names) / n
            return xb + (qbar - q)

        fn = jax.shard_map(body, mesh=auto_axes(mesh),
                           in_specs=(xspec,) + wire_specs,
                           out_specs=xspec, check_vma=False)
        return unflatten(fn(xf, *wire_arrs)), new_ef

    out = _sharded_compensated_gossip(
        params, wires, compressor=compressor, chunks=chunks, phase=phase,
        topology=topology, n_nodes=n, step=step, n_pods=n_pods, mesh=mesh,
        names=names, k=k, mn=mn, kmq=kmq, block_d=block_d,
        interpret=interpret)
    return out, new_ef


def _sharded_wire_build(params: PyTree, *, compressor, ef_state, seed,
                        n: int, kmq: int):
    """Row-local compression of the stacked state into per-leaf wire
    arrays (+ EF update) — the ``start_round`` half of a sharded
    compressed exchange.  Compression happens on the column-padded rows
    view when model-sliced (``ccol.pad_cols`` semantics: appended zeros,
    so absmax scales and absolute-column random bits on real columns are
    unchanged and pad columns code to exact zero); row-locality means it
    runs *outside* the shard_map under GSPMD without collectives.
    Passing the 2-D views as a list keeps jax.tree leaf order == salt
    order.  Returns ``(wires, new_ef_state, chunks)`` with ``chunks`` the
    per-leaf local column widths the decode side needs."""
    from repro import compress as compress_mod
    from repro.compress.collective import pad_cols

    leaves = jax.tree.leaves(params)
    sizes = [int(np.prod(lf.shape[1:], dtype=np.int64)) for lf in leaves]
    chunks = [-(-s // kmq) for s in sizes]
    x2 = [pad_cols(lf.reshape(n, -1).astype(jnp.float32), kmq)
          for lf in leaves]
    ef_leaves = jax.tree.leaves(ef_state) if ef_state is not None else None
    e2 = None
    if ef_leaves is not None:
        e2 = [pad_cols(e.reshape(n, -1).astype(jnp.float32), kmq)
              for e in ef_leaves]
    wires, new_e2 = compress_mod.compress_tree(compressor, x2, e2, seed)
    new_ef = None
    if ef_leaves is not None:
        new_ef = jax.tree.unflatten(
            jax.tree.structure(ef_state),
            [e[:, :s].reshape(lf.shape).astype(lf.dtype)
             for e, s, lf in zip(new_e2, sizes, ef_leaves)])
    return wires, new_ef, chunks


def _wire_build_q(compressor, wires, chunks):
    """Factory for the row-block estimate rebuild: ``build_q(arrs)``
    decodes a flat list of wire arrays back into the dense
    ``(rows, D_local)`` estimate (row-local jnp; runs inside the
    shard_map body).  On the model-sliced path each code array arrives as
    its local column chunk, so the concatenation is column-aligned with
    the packed matrix's per-shard layout."""
    from repro import compress as compress_mod

    counts = [len(w.payload) + len(w.aux) for w in wires]

    def build_q(arrs):
        out, off = [], 0
        for w0, c, d_leaf in zip(wires, counts, chunks):
            grp = arrs[off:off + c]
            wire = compress_mod.LeafWire(
                payload=tuple(grp[:len(w0.payload)]),
                aux=tuple(grp[len(w0.payload):]))
            out.append(compressor.decompress_leaf(wire, d_leaf))
            off += c
        return out[0] if len(out) == 1 else jnp.concatenate(out, axis=1)

    return build_q


def _sharded_compensated_gossip(params: PyTree, wires, *, compressor,
                                chunks, phase: str, topology: str,
                                n_nodes: int, step: int, n_pods: int,
                                mesh: jax.sharding.Mesh, names, k: int,
                                mn=(), kmq: int = 1, block_d: int = 2048,
                                interpret: Optional[bool] = None) -> PyTree:
    """The ``finish_round`` half of a sharded compressed gossip round:
    ``ppermute`` the wire arrays to the neighbors named by the round's
    block decomposition, rebuild their estimates ``q``, and apply the
    compensated per-shard kernel
    ``x + (M_r · qs − (1 − d_r) ⊙ q_self)``.  Node-independent wire
    arrays (leading axis 1, e.g. randk's shared column indices) ride
    replicated and are never ppermuted.  ``wires`` may hold *stale*
    payloads (the overlap double buffer) — the compensation preserves the
    node average for any transmitted estimate, which is exactly why the
    overlapped mode reuses this round unchanged."""
    from jax.sharding import PartitionSpec as P
    from repro.kernels import mixing_pallas
    from repro.models.sharding import wire_column_spec

    n = n_nodes
    wire_arrs = [a for w in wires for a in (*w.payload, *w.aux)]
    sharded_arr = [a.shape[0] == n for a in wire_arrs]
    wire_specs = tuple(wire_column_spec(a.shape, n, names, mn, kmq)
                       for a in wire_arrs)
    build_q = _wire_build_q(compressor, wires, chunks)

    xf, unflatten = mixing_pallas.flatten_nodes_sharded(params, kmq)
    xspec = P(names, mn) if mn else P(names)
    d, M = mixing_pallas.phase_matrices(phase, topology, n, step=step,
                                        n_pods=n_pods)
    offsets, Mstack, dstack = _shard_blocks(M, d, n, k)
    wstack = (1.0 - dstack).astype(np.float32)
    perms = {q: tuple(((r + q) % k, r) for r in range(k))
             for q in offsets if q}

    def body(xb, Mr, wr, *arrs):
        q_self = build_q(arrs)
        parts = [q_self if q == 0
                 else build_q([jax.lax.ppermute(a, names, perms[q])
                               if s else a
                               for a, s in zip(arrs, sharded_arr)])
                 for q in offsets]
        qs = jnp.concatenate(parts, axis=0)
        return mixing_pallas.shard_comp_mix_block(
            xb, q_self, qs, wr[0], Mr[0], block_d=block_d,
            interpret=interpret)

    in_specs = (xspec, P(names), P(names)) + wire_specs
    fn = jax.shard_map(body, mesh=auto_axes(mesh),
                       in_specs=in_specs, out_specs=xspec,
                       check_vma=False)
    out = fn(xf, jnp.asarray(Mstack), jnp.asarray(wstack), *wire_arrs)
    return unflatten(out)


# ---------------------------------------------------------------------------
# Async overlap: double-buffered gossip rounds (DESIGN.md §2.6)
# ---------------------------------------------------------------------------
def start_round(params: PyTree, spec: CommSpec, *,
                ef_state: Optional[PyTree] = None, seed=0):
    """Open one overlapped gossip round: capture the double-buffered wire
    payload of ``params`` that :func:`finish_round` will exchange *during
    the next step's compute* (DESIGN.md §2.6).

    Returns ``(round_state, new_ef_state)``.  ``round_state`` is a
    jit-carryable pytree (thread it through the step function / scan
    carry):

    * dense modes (no lossy gossip compressor) — ``{"q": buffer}`` where
      the buffer is ``params`` cast to ``spec.comm_dtype`` when set (the
      cast is the wire cast, applied once at capture: it halves both the
      buffer bytes held across the step and the ppermute bytes, and both
      occurrences of the buffer in the compensated apply use the same
      cast value, so the node average survives exactly);
    * lossy sharded mode — ``{"wire": [...]}`` holding the packed
      codes/scales wire arrays of each leaf (the EF update happens here,
      against the payload actually transmitted);
    * lossy stacked modes — ``{"q": estimate}`` holding the dense
      decompressed estimate (the stacked paths never materialize wire
      bytes; EF updates here too).

    The round is *issued* logically at capture: the mixing matrix
    :func:`finish_round` applies must be the one of the issuing step
    (pass the capture step's ``gossip_shift_step`` as ``step=``).
    """
    with jax.named_scope("issue"):
        out = _start_round_impl(params, spec, ef_state=ef_state, seed=seed)
    tel = _hub()
    if tel is not None:
        _meter(tel, params, spec, phase="gossip", step=0, role="issue",
               wires=out[0].get("wire") if isinstance(out[0], dict)
               else None)
    return out


def _start_round_impl(params: PyTree, spec: CommSpec, *,
                      ef_state: Optional[PyTree] = None, seed=0):
    n = spec.n_nodes
    if n == 1 or not spec.lossy:
        buf = params
        if spec.comm_dtype is not None and n > 1:
            buf = jax.tree.map(lambda p: p.astype(spec.comm_dtype), params)
        return {"q": buf}, ef_state
    if spec.uses_sharded():
        names = node_axis_names(spec.mesh, spec.node_axis)
        mnames, km = _model_names_count(spec.mesh, spec.model_axis, names)
        kmq = km if (km > 1 and spec.compressor.name in ("int8", "fp8")) \
            else 1
        wires, new_ef, _ = _sharded_wire_build(
            params, compressor=spec.compressor, ef_state=ef_state,
            seed=seed, n=n, kmq=kmq)
        return {"wire": [{"payload": tuple(w.payload),
                          "aux": tuple(w.aux)} for w in wires]}, new_ef
    from repro import compress as compress_mod
    q, new_ef = compress_mod.apply_tree(spec.compressor, params, ef_state,
                                        seed)
    return {"q": q}, new_ef


def finish_round(params: PyTree, round_state, spec: CommSpec, *,
                 step: int = 0, block_d: int = 2048,
                 interpret: Optional[bool] = None) -> PyTree:
    """Close the overlapped gossip round opened by :func:`start_round`:
    exchange the buffered payload ``b`` and mix it on arrival into the
    current iterate as the self-compensated correction

        ``x ← params + (M·b − (1 − diag W) ⊙ b)``  (≡ ``params + (W−I)·b``)

    which preserves the node average exactly for *any* buffer — in
    particular the one-step-stale one, giving the reference recursion
    ``x_{t+1} = (x_t − γ g_t) + (W − I)(x_{t−1} − γ g_{t−1})``
    (DESIGN.md §2.6).  ``step`` must be the shift step of the *issuing*
    step (the one that called ``start_round``).  Only gossip rounds
    overlap; global/pod-averaging phases flush via
    :func:`overlap_flush`.
    """
    tel = _hub()
    if tel is not None:
        _meter(tel, params, spec, phase="gossip", step=step, role="apply",
               wires=round_state.get("wire")
               if isinstance(round_state, dict) else None)
    with jax.named_scope("apply"):
        return _finish_round_impl(params, round_state, spec, step=step,
                                  block_d=block_d, interpret=interpret)


def _finish_round_impl(params: PyTree, round_state, spec: CommSpec, *,
                       step: int = 0, block_d: int = 2048,
                       interpret: Optional[bool] = None) -> PyTree:
    if spec.n_nodes == 1:
        return params
    if "wire" in round_state:
        return _overlap_finish_sharded_wire(params, round_state, spec,
                                            step=step, block_d=block_d,
                                            interpret=interpret)
    q = round_state["q"]
    if spec.uses_sharded():
        return _overlap_finish_sharded_dense(params, q, spec, step=step,
                                             block_d=block_d,
                                             interpret=interpret)
    if spec.backend == "pallas":
        from repro.kernels import mixing_pallas
        w, M = compensated_round_factors("gossip", spec.topology,
                                         spec.n_nodes, step, spec.n_pods)
        xf, unflatten = mixing_pallas.flatten_nodes(params)
        qf = mixing_pallas.flatten_nodes(q)[0]
        out = mixing_pallas.shard_comp_mix_block(
            xf, qf, qf, jnp.asarray(w), jnp.asarray(M), block_d=block_d,
            interpret=interpret)
        return unflatten(out)
    return _compressed_round_reference(params, q, "gossip", spec.topology,
                                       spec.n_nodes, step, spec.n_pods)


def overlap_flush(params: PyTree, spec: CommSpec, *, phase: str,
                  step: int = 0, axis: int = 0,
                  ef_state: Optional[PyTree] = None, seed=0):
    """Synchronous round + buffer re-prime at a period boundary.

    Global/pod-averaging phases do not overlap — their collective must
    see the *current* iterate to restore the exact (pod) average, and the
    PGA period boundary is the natural pipeline flush (DESIGN.md §2.6).
    Runs the ordinary synchronous round for ``phase``, then re-opens the
    double buffer from the averaged iterate so the next gossip step
    overlaps against post-flush state.  Returns
    ``(mixed, round_state, new_ef_state)``.

    Note the EF state advances twice here when a lossy gossip compressor
    is active — once inside the collective round, once in the re-prime —
    matching the two payloads actually produced.
    """
    tel = _hub()
    if tel is not None:
        _meter(tel, params, spec, phase=phase, step=step, role="flush")
    with jax.named_scope("flush"):
        out = _communicate_impl(params, spec, phase=phase, step=step,
                                axis=axis, ef_state=ef_state, seed=seed)
        if spec.compressor is not None \
                or spec.global_compressor is not None:
            mixed, ef2 = out
        else:
            mixed, ef2 = out, ef_state
        buf, ef3 = start_round(mixed, spec, ef_state=ef2, seed=seed)
    return mixed, buf, ef3


def _overlap_finish_sharded_dense(params: PyTree, q: PyTree,
                                  spec: CommSpec, *, step: int,
                                  block_d: int,
                                  interpret: Optional[bool]) -> PyTree:
    """Sharded finish for the dense (uncompressed) buffer: ppermute the
    buffered row-blocks over the round's halo offsets and apply the
    compensated per-shard kernel.  The buffer is already wire-cast
    (``start_round``), so the f32 re-pack is an exact upcast and the
    ppermute payload is re-cast to the wire dtype — the bytes crossing
    the ICI match the synchronous path."""
    from jax.sharding import PartitionSpec as P
    from repro.kernels import mixing_pallas

    n, mesh = spec.n_nodes, spec.mesh
    names = node_axis_names(mesh, spec.node_axis)
    if not names:
        raise ValueError(f"mixing.finish_round: mesh {dict(mesh.shape)} "
                         f"has no axis for node_axis={spec.node_axis!r}")
    k = node_shard_count(mesh, spec.node_axis)
    if n % k:
        raise ValueError(f"mixing.finish_round: n_nodes={n} not divisible "
                         f"by the {k} node-axis shards of mesh axes {names}")
    mnames, km = _model_names_count(mesh, spec.model_axis, names)

    xf, unflatten = mixing_pallas.flatten_nodes_sharded(params, km)
    qf = mixing_pallas.flatten_nodes_sharded(q, km)[0]
    xspec = P(names, mnames) if mnames else P(names)
    d, M = mixing_pallas.phase_matrices("gossip", spec.topology, n,
                                        step=step, n_pods=spec.n_pods)
    offsets, Mstack, dstack = _shard_blocks(M, d, n, k)
    wstack = (1.0 - dstack).astype(np.float32)
    perms = {s: tuple(((r + s) % k, r) for r in range(k))
             for s in offsets if s}
    wire = spec.comm_dtype

    def body(xb, qb, Mr, wr):
        send = qb.astype(wire) if wire is not None else qb
        parts = [send if s == 0
                 else jax.lax.ppermute(send, names, perms[s])
                 for s in offsets]
        qs = jnp.concatenate(parts, axis=0).astype(jnp.float32)
        return mixing_pallas.shard_comp_mix_block(
            xb, qb, qs, wr[0], Mr[0], block_d=block_d, interpret=interpret)

    fn = jax.shard_map(body, mesh=auto_axes(mesh),
                       in_specs=(xspec, xspec, P(names), P(names)),
                       out_specs=xspec, check_vma=False)
    return unflatten(fn(xf, qf, jnp.asarray(Mstack), jnp.asarray(wstack)))


def _overlap_finish_sharded_wire(params: PyTree, round_state,
                                 spec: CommSpec, *, step: int,
                                 block_d: int,
                                 interpret: Optional[bool]) -> PyTree:
    """Sharded finish for the lossy buffer: rebuild the LeafWires held in
    ``round_state`` and run the compensated gossip exchange on them — the
    ppermute moves the buffered codes/scales themselves."""
    from repro import compress as compress_mod

    n, mesh = spec.n_nodes, spec.mesh
    names = node_axis_names(mesh, spec.node_axis)
    if not names:
        raise ValueError(f"mixing.finish_round: mesh {dict(mesh.shape)} "
                         f"has no axis for node_axis={spec.node_axis!r}")
    k = node_shard_count(mesh, spec.node_axis)
    if n % k:
        raise ValueError(f"mixing.finish_round: n_nodes={n} not divisible "
                         f"by the {k} node-axis shards of mesh axes {names}")
    mnames, km = _model_names_count(mesh, spec.model_axis, names)
    kmq = km if (km > 1 and spec.compressor.name in ("int8", "fp8")) else 1
    mn = mnames if kmq > 1 else ()
    sizes = [int(np.prod(lf.shape[1:], dtype=np.int64))
             for lf in jax.tree.leaves(params)]
    chunks = [-(-s // kmq) for s in sizes]
    wires = [compress_mod.LeafWire(payload=tuple(w["payload"]),
                                   aux=tuple(w["aux"]))
             for w in round_state["wire"]]
    return _sharded_compensated_gossip(
        params, wires, compressor=spec.compressor, chunks=chunks,
        phase="gossip", topology=spec.topology, n_nodes=n, step=step,
        n_pods=spec.n_pods, mesh=mesh, names=names, k=k, mn=mn, kmq=kmq,
        block_d=block_d, interpret=interpret)


# ---------------------------------------------------------------------------
# Push-sum: runtime dense column-stochastic W (DESIGN.md §2.5)
# ---------------------------------------------------------------------------
def push_sum_shard_offsets(n: int, k: int, shifts) -> Tuple[int, ...]:
    """Static shard-offset superset for sharded push-sum rounds.

    The phase-based sharded path derives its halo offsets from the concrete
    W at trace time (:func:`_shard_blocks`); push-sum W is a *runtime*
    operand, so the offsets must come from the static shift superset the
    fault schedule can ever use.  A shift ``s`` over ``m = n/k`` rows per
    shard reaches receiver shards ``(s // m) % k`` and — when it straddles a
    shard boundary (``s % m != 0``) — ``(s // m + 1) % k``.  Offset 0 is
    always included: fault renormalization puts dropped nodes on identity
    (diagonal) entries.
    """
    m = n // k
    offs = {0}
    for s in shifts:
        s = s % n
        offs.add((s // m) % k)
        if s % m:
            offs.add((s // m + 1) % k)
    return tuple(sorted(offs))


def _dense_shard_stacks(W: jax.Array, n: int, k: int, offsets):
    """Traced analogue of :func:`_shard_blocks` for a runtime dense W:
    gather each shard's ``(m, |offsets|·m)`` mixing factor and ``(m, 1)``
    self-diagonal from the (traced) matrix with jnp ops, so a new fault
    pattern is new *data*, not a new compile."""
    m = n // k
    Wj = jnp.asarray(W, jnp.float32)
    diag = jnp.diagonal(Wj)
    Mj = Wj - jnp.diag(diag)
    blocks = Mj.reshape(k, m, k, m)
    cols = (jnp.arange(k)[:, None] + jnp.asarray(offsets)[None, :]) % k
    # advanced indices split by a slice put the broadcast dims in front:
    # (k, |off|, m, m) → (k, m, |off|·m)
    picked = blocks[jnp.arange(k)[:, None], :, cols]
    Mstack = jnp.transpose(picked, (0, 2, 1, 3)).reshape(
        k, m, len(offsets) * m)
    return Mstack, diag.reshape(k, m, 1)


def _mix_dense_reference(params: PyTree, W: jax.Array, n: int,
                         comm_dtype=None) -> PyTree:
    """Reference dense round ``x ← d ⊙ x + M · cast(x)`` for a runtime W —
    the oracle the dense pallas/sharded paths are tested against.  Gossip
    wire semantics: only the off-diagonal (neighbor) term is wire-cast."""
    Wj = jnp.asarray(W, jnp.float32)
    dj = jnp.diagonal(Wj).reshape(n, 1)
    Mj = Wj - jnp.diag(jnp.diagonal(Wj))

    def one(x):
        x2 = x.reshape(n, -1).astype(jnp.float32)
        xw = x2.astype(comm_dtype).astype(jnp.float32) \
            if comm_dtype is not None else x2
        return (dj * x2 + Mj @ xw).reshape(x.shape).astype(x.dtype)

    return jax.tree.map(one, params)


def _compressed_round_dense(params: PyTree, q: PyTree, W: jax.Array,
                            n: int) -> PyTree:
    """Compensated compressed round ``x + (M·q − (1−d)⊙q)`` for a runtime
    dense W (reference oracle for ``compressed_step_mix_dense``)."""
    Wj = jnp.asarray(W, jnp.float32)
    dj = jnp.diagonal(Wj).reshape(n, 1)
    wj = 1.0 - dj
    Mj = Wj - jnp.diag(jnp.diagonal(Wj))

    def one(x, qq):
        x2 = x.reshape(n, -1).astype(jnp.float32)
        q2 = qq.reshape(n, -1).astype(jnp.float32)
        return (x2 + (Mj @ q2 - wj * q2)).reshape(x.shape).astype(x.dtype)

    return jax.tree.map(one, params, q)


def _push_sum_sharded(joint: PyTree, *, W: jax.Array, n_nodes: int,
                      offsets, comm_dtype, mesh: jax.sharding.Mesh,
                      node_axis: str, model_axis: str, block_d: int,
                      interpret: Optional[bool]) -> PyTree:
    """Sharded push-sum round: ppermute halo exchange over the *static*
    offset superset, per-shard factors gathered from the traced W.  The
    ppermute path is already directional (shard r receives from shard
    ``r+q``), so asymmetric W needs no new wiring — only the runtime
    Mstack/dstack (transpose-free: the weight column is mixed by the same
    per-shard kernel as the parameters, no Wᵀ ever forms)."""
    from jax.sharding import PartitionSpec as P
    from repro.kernels import mixing_pallas

    names = node_axis_names(mesh, node_axis)
    if not names:
        raise ValueError(f"mixing._push_sum_sharded: mesh "
                         f"{dict(mesh.shape)} has no axis for "
                         f"node_axis={node_axis!r}")
    k = node_shard_count(mesh, node_axis)
    n = n_nodes
    if n % k:
        raise ValueError(f"mixing._push_sum_sharded: n_nodes={n} not "
                         f"divisible by the {k} node-axis shards")
    offsets = tuple(range(k)) if offsets is None else tuple(offsets)
    mnames, km = _model_names_count(mesh, model_axis, names)

    xf, unflatten = mixing_pallas.flatten_nodes_sharded(joint, km)
    xspec = P(names, mnames) if mnames else P(names)
    Mstack, dstack = _dense_shard_stacks(W, n, k, offsets)
    perms = {q: tuple(((r + q) % k, r) for r in range(k))
             for q in offsets if q}

    def body(xb, Mr, dr):
        send = xb.astype(comm_dtype) if comm_dtype is not None else xb
        parts = [send if q == 0
                 else jax.lax.ppermute(send, names, perms[q])
                 for q in offsets]
        xs = jnp.concatenate(parts, axis=0).astype(jnp.float32)
        return mixing_pallas.shard_mix_block(
            xb, xs, dr[0], Mr[0], block_d=block_d, interpret=interpret)

    fn = jax.shard_map(body, mesh=auto_axes(mesh),
                       in_specs=(xspec, P(names), P(names)),
                       out_specs=xspec, check_vma=False)
    return unflatten(fn(xf, Mstack, dstack))


def communicate_push_sum(params: PyTree, weight: jax.Array, *,
                         W: jax.Array, n_nodes: int, comm_dtype=None,
                         backend: str = "reference",
                         mesh: Optional[jax.sharding.Mesh] = None,
                         node_axis: str = "data",
                         shard_mode: str = "auto",
                         model_axis: str = "model",
                         leaf_threshold: Optional[int] = None,
                         offsets=None, block_d: int = 2048,
                         interpret: Optional[bool] = None,
                         compressor=None,
                         ef_state: Optional[PyTree] = None, seed=0):
    """One push-sum round: ``(x, w) ← (W·x, W·w)`` for a **runtime**
    column-stochastic ``W`` (DESIGN.md §2.5).

    ``weight`` is the per-node push-sum scalar, shape ``(n, 1)``; readers
    de-bias with ``x/w`` (:func:`repro.train.state.debias`).  W is a traced
    ``(n, n)`` operand — fault drops and per-step resampling change the
    data, never the compiled program.  The weight column rides the same
    round as the parameters (packed into the pallas staging buffer /
    sharded row-blocks alongside them), so x and w experience bit-identical
    mixing arithmetic and the de-bias ratio is exact at consensus.

    Backends mirror :func:`communicate`: ``"reference"`` (dense jnp
    oracle), ``"pallas"`` stacked (:func:`fused_step_mix_dense`), and —
    when ``mesh``'s node axis is sharded — the ppermute path
    (:func:`_push_sum_sharded`), whose halo set comes from the *static*
    ``offsets`` superset (:func:`push_sum_shard_offsets`; default: all
    shard offsets, always safe).

    With a lossy ``compressor`` the parameters run the compensated
    compressed round while the weight is mixed **exactly** (dense ``W·w``
    outside the codec — the de-bias denominator must never be lossy);
    returns ``(mixed, new_weight, new_ef_state)``.  Without a compressor
    returns ``(mixed, new_weight)``.  Sharded + compressed push-sum is
    unsupported (raise) — fall back to the stacked backends.
    """
    _check_backend(backend, 0, caller="mixing.communicate_push_sum")
    n = n_nodes
    if weight.shape[0] != n:
        raise ValueError(f"communicate_push_sum: weight has {weight.shape[0]}"
                         f" rows for n_nodes={n}")
    w2 = weight.reshape(n, -1).astype(jnp.float32)
    sharded = use_sharded_backend(backend, mesh, node_axis, shard_mode)

    tel = _hub()
    if tel is not None:
        # push-sum rounds mix against a *runtime* W (fault patterns are
        # data, not programs — DESIGN.md §2.5), so the static shift/send
        # accounting does not apply: report one send's worth of payload
        # bytes from the live tree and flag sends as data-dependent (-1)
        from repro.obs import meters as obs_meters
        sizes = obs_meters.per_node_leaf_sizes(params, n)
        elem = (np.dtype(comm_dtype).itemsize
                if comm_dtype is not None else 4)
        leaves = jax.tree.leaves(params)
        tel.emit(
            "comm_round", phase="push_sum", role="round",
            topology="runtime", backend=backend, sharded=sharded,
            n_nodes=int(n), sends=-1,
            compression=(compressor.name if compressor is not None
                         else "none"),
            measured_bytes=int(sum(sizes)) * int(elem),
            analytic_bytes=None,
            staged_bytes=_staged_bytes(
                params, phase="push_sum", backend=backend, sharded=sharded,
                compressor=compressor, leaf_threshold=leaf_threshold,
                weight=weight),
            traced=bool(leaves)
            and isinstance(leaves[0], jax.core.Tracer))

    if compressor is not None and compressor.lossy:
        if sharded:
            raise ValueError(
                "mixing.communicate_push_sum: compressed push-sum has no "
                "sharded path (the fault-varying W would need runtime wire "
                "layouts); use comm_shard_mode='stacked'")
        # the weight is the de-bias denominator: mix it exactly, outside
        # the lossy codec — column-stochastic W keeps Σw = n to fp exactness
        Wj = jnp.asarray(W, jnp.float32)
        new_w = (Wj @ w2).astype(weight.dtype).reshape(weight.shape)
        if backend == "pallas":
            from repro.kernels import mixing_pallas
            mixed, new_ef = mixing_pallas.compressed_step_mix_dense(
                params, W=W, compressor=compressor, ef_state=ef_state,
                seed=seed, n_nodes=n, block_d=block_d, interpret=interpret)
            return mixed, new_w, new_ef
        from repro import compress as compress_mod
        q, new_ef = compress_mod.apply_tree(compressor, params, ef_state,
                                            seed)
        mixed = _compressed_round_dense(params, q, W, n)
        return mixed, new_w, new_ef

    joint = {"x": params, "w": weight}
    if sharded:
        out = _push_sum_sharded(joint, W=W, n_nodes=n, offsets=offsets,
                                comm_dtype=comm_dtype, mesh=mesh,
                                node_axis=node_axis, model_axis=model_axis,
                                block_d=block_d, interpret=interpret)
    elif backend == "pallas":
        from repro.kernels import mixing_pallas
        out = mixing_pallas.fused_step_mix_dense(
            joint, W, n_nodes=n, comm_dtype=comm_dtype, block_d=block_d,
            interpret=interpret, leaf_threshold=leaf_threshold)
    else:
        out = _mix_dense_reference(joint, W, n, comm_dtype=comm_dtype)
    # identity codec: exact path + EF pass-through
    if compressor is not None:
        return out["x"], out["w"], ef_state
    return out["x"], out["w"]


def _communicate_sharded_collective(params: PyTree, *, compressor, ef_state,
                                    seed, phase: str, n_nodes: int,
                                    n_pods: int, mesh: jax.sharding.Mesh,
                                    node_axis: str = "data",
                                    model_axis: str = "model",
                                    qblock: Optional[int] = None,
                                    caller: Optional[str] = None):
    """Compressed global/pod-averaging collective with the node axis
    sharded over ``mesh`` (DESIGN.md §2.3 "Compressed collectives").

    The chunked reduce-scatter runs as one ``all_to_all`` of the stage-1
    **wire arrays** (int8/fp8 codes + one *uint8 exponent byte* per
    power-of-two block scale — ``pow2_block_scale`` guarantees a pure
    exponent, so the fp32 scale word never crosses the ICI) — the
    compressed bytes are exactly what crosses the wire; each column
    segment's owner dequantizes, applies the anchored accumulate, and
    re-quantizes the (per-pod) mean chunk, which returns via an
    ``all_gather`` of stage-2 codes+exponents.  Stage-1 quantization, the
    EF residual ``e' = y − q₁``, and the local emulation ``ρ = Q₂(q₁)``
    are row-local and run *outside* the shard_map, so GSPMD keeps them
    collective-free; the compensated combine ``x + (r − ρ)`` is
    elementwise.  Returns ``(mixed, new_ef_state)``.

    On a 2-D ``(node, model)`` mesh the packed columns are sliced over
    the model axis: padding is to ``k_model · k · QBLOCK`` so every model
    shard's slice starts on a scale-block boundary (absolute-column
    randomness and block scales stay bit-stable under resharding), the
    reduce-scatter segments split ``D/k_model`` instead of ``D``, and the
    stage-2 column offset is ``model_slice + node_segment``.

    ``caller`` names the public entry point for validation errors; both
    dispatch paths (``communicate``/``communicate_sharded``) and direct
    callers get their own message instead of an opaque shard_map trace
    failure.
    """
    from jax.sharding import PartitionSpec as P
    from repro.compress import collective as ccol
    from repro.kernels import mixing_pallas

    who = caller or "mixing._communicate_sharded_collective"
    names = node_axis_names(mesh, node_axis)
    if not names:
        raise ValueError(f"{who}: mesh {dict(mesh.shape)} has no axis for "
                         f"node_axis={node_axis!r} — the compressed "
                         f"collective needs a sharded node axis (use the "
                         f"stacked path instead)")
    k = node_shard_count(mesh, node_axis)
    n = n_nodes
    if n % k:
        raise ValueError(f"{who}: n_nodes={n} not divisible by the {k} "
                         f"node-axis shards of mesh axes {names}")
    pods = n_pods if phase == "pod_avg" else 1
    _check_pods(n, pods, who)
    kind = compressor.name
    qb = ccol.QBLOCK if qblock is None else qblock
    mnames, km = _model_names_count(mesh, model_axis, names)

    xf, unflatten = mixing_pallas.flatten_nodes(params)
    ef2 = ef_unflatten = None
    if ef_state is not None:
        ef2, ef_unflatten = mixing_pallas.flatten_nodes(ef_state)
    D = xf.shape[1]
    # segment boundaries must land on scale blocks for every model slice:
    # pad to k_model·k·qblock (appended zero columns — real columns keep
    # their absolute indices, so scales and random bits are unchanged)
    xp = ccol.pad_cols(xf, km * k * qb)
    ep = ccol.pad_cols(ef2, km * k * qb)
    Dp = xp.shape[1]
    s1, s2 = ccol.stage_seeds(seed)

    y = xp if ep is None else xp + ep
    codes1, scales1, q1 = ccol.quantize_blocks(y, kind, s1, qb)
    new_ef = None if ep is None else (y - q1)[:, :D]
    _, _, rho = ccol.quantize_blocks(q1, kind, s2, qb)

    width = Dp // km          # columns per model slice
    seg = width // k          # columns per (node shard, model shard) owner
    axis_sizes = [mesh.shape[a] for a in names]
    msizes = [mesh.shape[a] for a in mnames]
    wspec = P(names, mnames) if mnames else P(names)

    def body(cb, eb):
        # reduce-scatter: the compressed wire arrays (codes + exponent
        # bytes) cross the ICI, node axis only — each model slice reduces
        # its own columns
        ac = jax.lax.all_to_all(cb, names, split_axis=1, concat_axis=0,
                                tiled=True)                     # (n, seg)
        ae = jax.lax.all_to_all(eb, names, split_axis=1, concat_axis=0,
                                tiled=True)                     # (n, seg/qb)
        q_seg = ccol.dequant_blocks(ac, ccol.exponent_scales(ae), qb)
        mbar = ccol.anchored_mean(q_seg, pods)                  # (p, seg)
        shard = 0
        for a, sz in zip(names, axis_sizes):
            shard = shard * sz + jax.lax.axis_index(a)
        mshard = 0
        for a, sz in zip(mnames, msizes):
            mshard = mshard * sz + jax.lax.axis_index(a)
        c2, sc2, _ = ccol.quantize_blocks(mbar, kind, s2, qb,
                                          col0=mshard * width + shard * seg)
        gc = jax.lax.all_gather(c2, names, axis=1, tiled=True)  # (p, width)
        ge = jax.lax.all_gather(ccol.scale_exponents(sc2), names, axis=1,
                                tiled=True)
        return ccol.dequant_blocks(gc, ccol.exponent_scales(ge), qb)

    fn = jax.shard_map(body, mesh=auto_axes(mesh), in_specs=(wspec, wspec),
                       out_specs=P(None, mnames) if mnames else P(),
                       check_vma=False)
    r = fn(codes1, ccol.scale_exponents(scales1))               # (p, Dp)
    per = n // pods
    r_rows = jnp.broadcast_to(r[:, None], (pods, per, Dp)).reshape(n, Dp)
    mixed = (xp + (r_rows - rho))[:, :D]
    return unflatten(mixed), (ef_unflatten(new_ef) if ep is not None
                              else None)
