"""Training-step builder: per-node forward/backward (vmapped over the node
axis) → per-node optimizer update → communication round (the paper's Alg. 1).

One compiled variant per communication phase — "gossip(shift)", "global",
"none", "slowmo" — dispatched host-side by the schedule (DESIGN.md §2.2), so
each HLO carries exactly the collectives of its phase and cost/collective
analysis per phase is exact.

There is exactly ONE step body (``_core`` below): algorithm-specific
behaviour enters through the ``repro.core.algo`` hooks (``pre_update`` /
``comm_payload`` / ``post_round``), and the execution-mode axes (sync /
overlap / push-sum / fused-consensus) parameterize how the round itself
runs.  The returned callable keeps the historical per-mode signature.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import TrainConfig
from repro.core import algo as algo_lib
from repro.core import mixing
from repro.core import topology as topo
from repro.core.algo import phases_for_algorithm  # noqa: F401  (re-export)
from repro.models.model import Model
from repro.optim import clip_by_global_norm, make_optimizer
from repro.train.state import TrainState, consensus_distance, debias

PyTree = Any


def _grad_global_norm(grads: PyTree) -> jax.Array:
    """Global L2 norm over all nodes' grads — a cheap on-device monitor
    (one reduction per leaf inside the step; no host sync).  Emitted as
    ``metrics["grad_norm"]`` when monitors are on (DESIGN.md §2.7)."""
    return jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                        for g in jax.tree.leaves(grads)))


def build_train_step(model: Model, tcfg: TrainConfig, n_nodes: int, *,
                     phase: str, shift_step: int = 0,
                     buf_shift: int = 0,
                     with_consensus: bool = False,
                     unroll: bool = False,
                     mesh: Optional[jax.sharding.Mesh] = None,
                     fault_hops: Optional[Tuple[int, ...]] = None
                     ) -> Callable:
    """Returns step(state, batch, lr) -> (state, metrics).

    ``phase``: one of ``phases_for_algorithm(dist.algorithm)``.
    batch leaves carry leading (n_nodes, per_node_batch, …).

    With ``DistConfig.comm_overlap`` the returned step has the 4-arg
    signature ``step(state, batch, lr, comm_buf) -> (state, metrics,
    comm_buf)`` (DESIGN.md §2.6): gossip phases *finish* the in-flight
    round primed one step ago — applying W with ``buf_shift``, the shift
    recorded when the buffer was primed — against the stale buffer, then
    *start* the next round from this step's half-step params; global /
    pod_avg / algorithm-owned phases run synchronously (the period
    boundary is the natural flush) and re-prime the buffer from their
    result; phase "none" passes the buffer through untouched.

    With a ``mesh`` whose node axis is sharded, the pallas comm backend
    routes through the shard_map-aware path (DESIGN.md §2.1 dispatch
    table) — per-shard fused kernels with ppermute halo exchange —
    honoring ``DistConfig.comm_shard_mode``.

    With ``DistConfig.push_sum`` the returned step has the 5-arg signature
    ``step(state, batch, lr, W, active)`` (DESIGN.md §2.5): ``W`` is the
    round's column-stochastic matrix as a **traced** ``(n, n)`` operand —
    fault drops and resampling are new data, never new compiles — and
    ``active`` the ``(n,)`` live mask; dropped nodes' grads are zeroed and
    their params/opt rows frozen.  ``fault_hops`` (from
    ``FaultSchedule.hop_superset``) statically bounds the sharded path's
    halo offsets.

    Algorithms with a comm payload (GT-PGA's tracker) ride it through the
    round as one joint tree ``{"params": ..., <slot>: ...}``, so every
    backend / compressor / overlap / push-sum combination above applies
    to the payload unchanged.
    """
    dist = tcfg.dist
    dist.validate_nodes(n_nodes)
    algo = algo_lib.get_algorithm(dist.algorithm, caller="build_train_step")
    sharded_comm = mixing.use_sharded_backend(
        dist.comm_backend, mesh, dist.node_axis, dist.comm_shard_mode)
    # the round-invariant knobs, captured once (DESIGN.md §2.1): every
    # communicate call below goes through this spec, so a knob added to
    # CommSpec is forwarded everywhere by construction
    spec = dist.comm_spec(n_nodes, mesh=mesh)
    spec_plain = spec.replace(compressor=None, global_compressor=None)
    # wire compressor (DESIGN.md §2.3): built once at step-build time; the
    # identity compressor routes to the exact uncompressed path inside
    # mixing.communicate, so only a *lossy* compressor changes the step
    compressor = spec.compressor
    lossy_comm = spec.lossy
    # compressed collective for the averaging phases (DESIGN.md §2.3
    # "Compressed collectives"): identity routes to the exact psum path
    # inside mixing, so only a lossy choice changes the step
    global_compressor = spec.global_compressor
    lossy_global = global_compressor is not None and global_compressor.lossy
    opt = make_optimizer(tcfg.optimizer, per_node=True)
    # DistConfig.remat/remat_policy -> blocks.make_remat policy string
    if dist.remat == "none":
        remat_policy = "none"
    elif dist.remat_policy == "dots":
        remat_policy = "dots"
    else:
        remat_policy = "default"

    mode = ("push" if dist.push_sum
            else "overlap" if dist.comm_overlap else "sync")
    owned = phase in algo.owned_phases

    def node_loss(params, batch):
        return model.loss(params, batch, remat=remat_policy,
                          z_loss=tcfg.z_loss, unroll=unroll)

    def total_loss(params, batch):
        losses, metrics = jax.vmap(node_loss)(params, batch)
        # sum over nodes => grads land per-node, unscaled (paper Alg. 1)
        return jnp.sum(losses), jax.tree.map(jnp.mean, metrics)

    grad_fn = jax.grad(total_loss, has_aux=True)

    def accum_grad_fn(params, batch):
        """Gradient accumulation: split the per-node batch into
        ``tcfg.microbatches`` slices and scan — activation memory drops ~m×
        at unchanged math (equal-size microbatch mean == full-batch mean)."""
        m = tcfg.microbatches

        def to_mb(t):
            n, b = t.shape[:2]
            return t.reshape((n, m, b // m) + t.shape[2:]).swapaxes(0, 1)

        mbs = jax.tree.map(to_mb, batch)

        def body(acc, mb):
            g, met = grad_fn(params, mb)
            acc = jax.tree.map(jnp.add, acc, g)
            return acc, met

        zeros = jax.tree.map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params)
        grads, mets = jax.lax.scan(body, zeros, mbs)
        grads = jax.tree.map(lambda g: g / m, grads)
        return grads, jax.tree.map(jnp.mean, mets)

    # -- push-sum round constants ------------------------------------------
    ps_offsets = None
    if mode == "push" and sharded_comm:
        # static halo superset for the sharded ppermute path: every shift
        # the topology (over its whole period) or the fault schedule's
        # resampling can ever emit — the runtime W only re-weights them
        k = mixing.node_shard_count(mesh, dist.node_axis)
        if phase == "global":
            ps_offsets = tuple(range(k))
        else:
            hops = set(fault_hops or ())
            period = max(1, topo.schedule_period(dist.topology, n_nodes))
            for s in range(period):
                hops |= set(topo.shift_weights(dist.topology, n_nodes, s))
            ps_offsets = mixing.push_sum_shard_offsets(n_nodes, k, hops)
    comm_dtype_ps = spec.comm_dtype

    def freeze_dropped(new: PyTree, old: PyTree,
                       active: jax.Array) -> PyTree:
        """Dropped nodes take no step: revert their node rows (params
        AND optimizer state — a zero grad still decays momentum, which
        would silently train the dead node).  Leaves without a node
        axis (shared counters) pass through."""
        a = active.astype(jnp.bool_)

        def one(nw, od):
            if not hasattr(nw, "ndim") or nw.ndim == 0 \
                    or nw.shape[0] != n_nodes:
                return nw
            m = a.reshape((n_nodes,) + (1,) * (nw.ndim - 1))
            return jnp.where(m, nw, od)

        return jax.tree.map(one, new, old)

    # -- per-mode round bodies ---------------------------------------------
    def _push_round(extras, params_half, step_seed, W, active):
        payload = algo.comm_payload(extras, params_half)
        has_payload = bool(payload)
        joint = algo_lib.join_payload(payload, params_half)
        w = extras["push_weight"]
        new_w = w
        if phase == "none" or n_nodes == 1:
            mixed = joint
        elif lossy_comm and phase == "gossip":
            mixed, new_w, new_ef = mixing.communicate_push_sum(
                joint, w, W=W, n_nodes=n_nodes,
                comm_dtype=comm_dtype_ps, backend=dist.comm_backend,
                mesh=mesh, node_axis=dist.node_axis,
                shard_mode=dist.comm_shard_mode,
                model_axis=dist.model_axis,
                leaf_threshold=dist.pallas_leaf_threshold,
                offsets=ps_offsets, compressor=compressor,
                ef_state=extras.get("ef_state"), seed=step_seed)
            if new_ef is not None:
                extras["ef_state"] = new_ef
        else:
            mixed, new_w = mixing.communicate_push_sum(
                joint, w, W=W, n_nodes=n_nodes,
                comm_dtype=comm_dtype_ps, backend=dist.comm_backend,
                mesh=mesh, node_axis=dist.node_axis,
                shard_mode=dist.comm_shard_mode,
                model_axis=dist.model_axis,
                leaf_threshold=dist.pallas_leaf_threshold,
                offsets=ps_offsets)
        if phase == "global":
            # a full-participation global round sets every w_i to
            # Σw/n = 1 in exact arithmetic; snap to it so the PGA
            # reset also washes out accumulated fp drift in w
            new_w = jnp.where(jnp.all(active > 0),
                              jnp.ones_like(new_w), new_w)
        extras["push_weight"] = new_w
        return algo_lib.wrap_mixed(mixed, has_payload)

    def _overlap_round(extras, params_half, step_seed, comm_buf, sctx):
        payload = algo.comm_payload(extras, params_half)
        has_payload = bool(payload)
        joint = algo_lib.join_payload(payload, params_half)
        new_buf = comm_buf
        if phase == "none" or n_nodes == 1:
            return algo_lib.wrap_mixed(joint, has_payload), new_buf, None
        if owned:
            # algorithm-owned phase (SlowMo outer step): no round to
            # finish — post_round consumes the half-step directly and its
            # result re-primes the in-flight buffer
            new_params, extras2 = algo.post_round(
                extras, algo_lib.wrap_mixed(joint, has_payload), phase, sctx)
            extras.clear()
            extras.update(extras2)
            reprime = algo_lib.join_payload(
                algo.comm_payload(extras, new_params), new_params)
            new_buf, new_ef = mixing.start_round(
                reprime, spec, ef_state=extras.get("ef_state"),
                seed=step_seed)
            # the dense buffer aliases new_params; copy so the jit
            # outputs (state, comm_buf) never share a buffer — both
            # are donated back to the next step
            new_buf = jax.tree.map(jnp.copy, new_buf)
            if new_ef is not None:
                extras["ef_state"] = new_ef
            return None, new_buf, new_params
        if phase == "gossip":
            # finish the round primed one step ago (its shift, not
            # this step's), then immediately issue the next one from
            # this half-step — x_{t+1} = y_t + (W(buf_shift) - I)·y_{t-1}
            mixed = mixing.finish_round(joint, comm_buf, spec,
                                        step=buf_shift)
            new_buf, new_ef = mixing.start_round(
                joint, spec, ef_state=extras.get("ef_state"),
                seed=step_seed)
        else:
            # global / pod_avg: synchronous flush + re-prime
            mixed, new_buf, new_ef = mixing.overlap_flush(
                joint, spec, phase=phase, step=shift_step,
                ef_state=extras.get("ef_state"), seed=step_seed)
            new_buf = jax.tree.map(jnp.copy, new_buf)
        if new_ef is not None:
            extras["ef_state"] = new_ef
        return algo_lib.wrap_mixed(mixed, has_payload), new_buf, None

    def _sync_round(extras, params_half, step_seed):
        payload = algo.comm_payload(extras, params_half)
        has_payload = bool(payload)
        joint = algo_lib.join_payload(payload, params_half)
        if owned:
            return algo_lib.wrap_mixed(joint, has_payload), None
        mixed = None
        fused_consensus = None
        lossy_round = (lossy_comm or
                       (lossy_global and phase in ("global", "pod_avg")))
        if (lossy_round and n_nodes > 1
                and phase in ("gossip", "global", "pod_avg")):
            # compressed round: the SR seed is the absolute step (so
            # rounding is unbiased across steps); consensus falls back
            # to consensus_distance below — residual fusion does not
            # compose with compression (DESIGN.md §2.3)
            mixed, new_ef = mixing.communicate(
                joint, spec, phase=phase, step=shift_step,
                axis=0, ef_state=extras.get("ef_state"), seed=step_seed)
            if new_ef is not None:
                extras["ef_state"] = new_ef
        elif (dist.comm_backend == "pallas" and with_consensus
                and n_nodes > 1 and not has_payload
                and phase in ("gossip", "global", "pod_avg")):
            # fused: the mixing kernel emits the consensus residual in
            # the same parameter pass instead of re-reading new_params
            # (bypasses communicate(), so meter the round explicitly)
            mixing.meter_round(params_half, spec_plain, phase=phase,
                               step=shift_step)
            if sharded_comm:
                mixed, _xbar, resid = mixing.communicate_sharded(
                    params_half, spec_plain, phase=phase,
                    step=shift_step, with_residual=True)
            else:
                from repro.kernels import mixing_pallas
                mixed, _, resid = mixing_pallas.mix_residual(
                    params_half, phase=phase, topology=dist.topology,
                    n_nodes=n_nodes, step=shift_step,
                    comm_dtype=spec.comm_dtype, n_pods=dist.n_pods,
                    with_xbar=False,
                    leaf_threshold=dist.pallas_leaf_threshold)
            fused_consensus = resid / n_nodes
        if mixed is None:
            mixed = mixing.communicate(
                joint, spec_plain, phase=phase, step=shift_step)
        return algo_lib.wrap_mixed(mixed, has_payload), fused_consensus

    # -- the one step body -------------------------------------------------
    # Every op of the step falls under one of four sibling named scopes —
    # fwd_bwd, optimizer, monitor, round — so a profiler trace splits the
    # step's device time by its HLO op_name (DESIGN.md §2.7).  A scope is
    # metadata only: the optimized program is the same without it.
    def _core(state: TrainState, batch: PyTree, lr: jax.Array,
              comm_buf=None, W=None, active=None
              ) -> Tuple[TrainState, Dict[str, jax.Array], Any]:
        extras = dict(state.extras)
        with jax.named_scope("fwd_bwd"):
            if tcfg.microbatches > 1:
                grads, metrics = accum_grad_fn(state.params, batch)
            else:
                grads, metrics = grad_fn(state.params, batch)
        if mode == "push":
            with jax.named_scope("optimizer"):
                af = active.astype(jnp.float32)
                grads = jax.tree.map(
                    lambda g: g * af.reshape(
                        (n_nodes,) + (1,) * (g.ndim - 1)), grads)
        if with_consensus:
            metrics = dict(metrics)
            with jax.named_scope("monitor"):
                metrics["grad_norm"] = _grad_global_norm(grads)
        with jax.named_scope("optimizer"):
            if tcfg.optimizer.grad_clip:
                grads = clip_by_global_norm(grads, tcfg.optimizer.grad_clip)
            upd, extras = algo.pre_update(extras, grads)
            extras = dict(extras)
            params_half, opt_state = opt.update(upd, state.opt_state,
                                                state.params, lr)
            if mode == "push":
                params_half = freeze_dropped(params_half, state.params,
                                             active)
                opt_state = freeze_dropped(opt_state, state.opt_state,
                                           active)
        sctx = algo_lib.StepContext(dist=dist, n_nodes=n_nodes, lr=lr)
        fused_consensus = None
        new_buf = comm_buf
        with jax.named_scope("round"):
            if mode == "push":
                mixed = _push_round(extras, params_half, state.step, W,
                                    active)
                new_params, extras = algo.post_round(extras, mixed, phase,
                                                     sctx)
            elif mode == "overlap":
                mixed, new_buf, owned_params = _overlap_round(
                    extras, params_half, state.step, comm_buf, sctx)
                if owned_params is not None:
                    new_params = owned_params
                else:
                    new_params, extras = algo.post_round(extras, mixed,
                                                         phase, sctx)
            else:
                mixed, fused_consensus = _sync_round(extras, params_half,
                                                     state.step)
                new_params, extras = algo.post_round(extras, mixed, phase,
                                                     sctx)
        metrics = dict(metrics)
        with jax.named_scope("monitor"):
            if mode == "push":
                # the checkable invariant: Σw = n for every
                # column-stochastic round, every fault pattern
                # (DESIGN.md §2.5)
                new_w = extras["push_weight"]
                metrics["mass"] = jnp.sum(new_w.astype(jnp.float32))
                if with_consensus:
                    metrics["consensus"] = consensus_distance(
                        debias(new_params, new_w))
            elif with_consensus:
                metrics["consensus"] = (fused_consensus
                                        if fused_consensus is not None
                                        else consensus_distance(new_params))
        with jax.named_scope("optimizer"):
            next_step = state.step + 1
        new_state = TrainState(params=new_params, opt_state=opt_state,
                               step=next_step, extras=extras)
        return new_state, metrics, new_buf

    # -- historical per-mode signatures ------------------------------------
    if mode == "push":
        def push_step(state: TrainState, batch: PyTree, lr: jax.Array,
                      W: jax.Array, active: jax.Array
                      ) -> Tuple[TrainState, Dict[str, jax.Array]]:
            new_state, metrics, _ = _core(state, batch, lr, W=W,
                                          active=active)
            return new_state, metrics

        return push_step

    if mode == "overlap":
        def overlap_step(state: TrainState, batch: PyTree, lr: jax.Array,
                         comm_buf
                         ) -> Tuple[TrainState, Dict[str, jax.Array], Any]:
            return _core(state, batch, lr, comm_buf=comm_buf)

        return overlap_step

    def step(state: TrainState, batch: PyTree, lr: jax.Array
             ) -> Tuple[TrainState, Dict[str, jax.Array]]:
        new_state, metrics, _ = _core(state, batch, lr)
        return new_state, metrics

    return step
