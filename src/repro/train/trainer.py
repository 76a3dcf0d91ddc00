"""Host training loop: schedule-driven phase dispatch, AGA feedback,
checkpoint hooks, metrics.

Works in two regimes:
  * No mesh (one device): n nodes as a stacked leading axis on that device.
  * Mesh execution (launch/train.py on several devices): state and batches
    are placed by the ``train_data``/``train_pod`` logical-axis rules
    (models/sharding.py), the node axis split over the mesh's node axes,
    and every step returns the state on that same placement.
"""
from __future__ import annotations

import time
from collections import deque
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import obs
from repro.configs.base import TrainConfig
from repro.core import topology as topo
from repro.core.mixing import auto_axes, node_axis_names, node_shard_count
from repro.core.schedule import make_schedule
from repro.data import make_stream
from repro.models import sharding as shd
from repro.models.model import Model, make_model
from repro.optim import make_optimizer, make_schedule as make_lr
from repro.core import algo as algo_lib
from repro.train.state import (TrainState, stack_for_nodes, stacked_axes,
                               state_axes)
from repro.train.step import build_train_step

PyTree = Any


class Trainer:
    def __init__(self, tcfg: TrainConfig, n_nodes: int, *,
                 mesh: Optional[jax.sharding.Mesh] = None,
                 with_consensus: bool = False,
                 fault_schedule=None,
                 telemetry: Optional[obs.Telemetry] = None,
                 measure_occupancy: Optional[bool] = None):
        tcfg.dist.validate().validate_nodes(n_nodes)
        if fault_schedule is not None:
            if not tcfg.dist.push_sum:
                raise ValueError(
                    "Trainer: fault injection requires DistConfig."
                    "push_sum=True — only the push-sum weight scalar keeps "
                    "the average unbiased when nodes drop (DESIGN.md §2.5)")
            if fault_schedule.n_nodes != n_nodes:
                raise ValueError(
                    f"Trainer: fault_schedule built for "
                    f"{fault_schedule.n_nodes} nodes, trainer has {n_nodes}")
        if mesh is not None:
            mesh = auto_axes(mesh)
        self.tcfg = tcfg
        self.n_nodes = n_nodes
        self.mesh = mesh
        self.model = make_model(tcfg.model)
        self.lr_fn = make_lr(tcfg.optimizer)
        self.schedule = make_schedule(tcfg.dist)
        self.period = topo.schedule_period(tcfg.dist.topology, n_nodes)
        self.with_consensus = with_consensus
        self.fault_schedule = fault_schedule
        self.stream = make_stream(tcfg.model, tcfg.data, n_nodes=n_nodes,
                                  global_batch=tcfg.global_batch,
                                  seq_len=tcfg.seq_len)
        self._compiled: Dict[Any, Any] = {}
        # async overlap (DESIGN.md §2.6): the in-flight round's double
        # buffer and the shift it was primed with — host-side trajectory
        # state, primed lazily at run() start (resume == flush: a fresh
        # process re-primes from the checkpointed params)
        self._overlap = tcfg.dist.comm_overlap
        self._comm_buf = None
        self._buf_shift = 0
        # telemetry hub (DESIGN.md §2.7): the default hub preserves the
        # legacy behavior — step records at log boundaries land in an
        # in-memory ring (the .history view) and print via PrettySink;
        # pass a hub with a JsonlSink (launch/train --telemetry-dir) for
        # a persistent stream.  run() installs it as the ambient hub so
        # the mixing-round meters self-report during compiles.
        if telemetry is None:
            telemetry = obs.Telemetry(
                sinks=[obs.RingSink(), obs.PrettySink()])
        elif telemetry.ring() is None:
            telemetry.sinks.append(obs.RingSink())
        telemetry.tags.setdefault("algorithm", tcfg.dist.algorithm)
        self.telemetry = telemetry
        # device-side monitor window: per-step (lr, metrics) DEVICE
        # scalars accumulate here and materialize in ONE batched
        # device_get at log boundaries — never a per-step host sync
        self._pending: deque = deque(maxlen=1024)
        self._phase_counts: Dict[str, int] = {}
        # one-shot occupancy calibration for overlapped runs: costs two
        # extra (non-donating) compiles, so default-on only when a
        # persistent stream is attached (launch/train --telemetry-dir);
        # pass True/False to force either way
        self.measure_occupancy = measure_occupancy
        self._occ_measured = False
        self._sched_live = False   # True once this process advanced the
                                   # schedule (guards the resume reload)
        self._faults_live = False  # same guard for the fault counters
        self.state_shardings = None
        self._batch_sharding = None
        if mesh is not None:
            self._place_on(mesh)

    def _place_on(self, mesh: jax.sharding.Mesh) -> None:
        """Shardings of the state (logical-axis rules) and of a batch (node
        axis over the mesh's node axes)."""
        dist = self.tcfg.dist
        k = node_shard_count(mesh, dist.node_axis)
        if self.n_nodes % k:
            raise ValueError(
                f"Trainer: {self.n_nodes} nodes do not split evenly over "
                f"the mesh's {k} node shards")
        axes_box: Dict[str, Any] = {}

        def init_axes(key):
            params, axes_box["axes"] = self.model.init(key)
            return self._init_tree(params)

        shapes = jax.eval_shape(init_axes, jax.random.PRNGKey(0))
        st_axes = stacked_axes(axes_box["axes"])
        tree = state_axes(st_axes, self.tcfg.optimizer.name,
                          extras=algo_lib.extras_axes(dist, st_axes,
                                                      axes_box["axes"]))
        mode = "train_data" if dist.node_axis == "data" else "train_pod"
        self.state_shardings = shd.shardings_for(tree, mode, mesh, shapes)
        names = node_axis_names(mesh, dist.node_axis)
        self._batch_sharding = NamedSharding(mesh, P(names) if names else P())

    @property
    def history(self) -> List[Dict[str, float]]:
        """Log-boundary step records — a view over the telemetry ring
        sink (same dicts the JSONL stream carries; the legacy keys
        ``step``/``phase``/``lr``/``time``/``loss``/... are preserved)."""
        ring = self.telemetry.ring()
        return ring.records("step") if ring is not None else []

    # ------------------------------------------------------------------
    def _init_tree(self, params: PyTree) -> TrainState:
        params = stack_for_nodes(params, self.n_nodes)
        opt = make_optimizer(self.tcfg.optimizer, per_node=True)
        opt_state = opt.init(params)
        # algorithm/mode slots (SlowMo anchors, GT tracker, EF memory,
        # push weights) come from the slot descriptors — no per-algorithm
        # branching here
        extras = algo_lib.init_extras(self.tcfg.dist, params, self.n_nodes)
        return TrainState(params=params, opt_state=opt_state,
                          step=jnp.zeros((), jnp.int32), extras=extras)

    def init_state(self, key: jax.Array) -> TrainState:
        if self.state_shardings is None:
            return self._init_tree(self.model.init(key)[0])
        # built in place: the stacked state never lands on one device
        return jax.jit(lambda k: self._init_tree(self.model.init(k)[0]),
                       out_shardings=self.state_shardings)(key)

    def _batch(self, k: int) -> PyTree:
        batch = self.stream.get_batch(k)
        if self._batch_sharding is None:
            return jax.tree.map(jnp.asarray, batch)
        return jax.device_put(batch, self._batch_sharding)

    # ------------------------------------------------------------------
    def _get_step_fn(self, phase: str, shift: int, buf_shift: int = 0):
        # buf_shift keys the compile cache only for overlapped gossip
        # steps, where it is baked in statically (the W of the round
        # being *finished* — DESIGN.md §2.6); 0 everywhere else.  The
        # algorithm name rides in the key so trainers sharing a cache dict
        # (or a future config swap) can never replay another algorithm's
        # compiled phase
        key = (self.tcfg.dist.algorithm, phase, shift, buf_shift)
        if key not in self._compiled:
            hops = (self.fault_schedule.hop_superset(self.tcfg.dist.topology)
                    if self.fault_schedule is not None else None)
            fn = build_train_step(self.model, self.tcfg, self.n_nodes,
                                  phase=phase, shift_step=shift,
                                  buf_shift=buf_shift,
                                  with_consensus=self.with_consensus,
                                  mesh=self.mesh, fault_hops=hops)
            if self.state_shardings is not None:
                fn = _keep_placement(fn, self.state_shardings)
            donate = (0, 3) if self._overlap else (0,)
            self._compiled[key] = jax.jit(fn, donate_argnums=donate)
        return self._compiled[key]

    # ------------------------------------------------------------------
    def _push_round(self, phase: str, k: int, shift: int):
        """Host-side (W, active) for the push-sum step at absolute step
        ``k`` — values only, the compiled step is W-agnostic.  ``advance``
        commits the fault counters (pure elsewhere)."""
        n = self.n_nodes
        if self.fault_schedule is not None:
            fs = self.fault_schedule
            active = fs.advance(k)
            if k in fs.drops:
                self.telemetry.emit("fault", step=k, kind="drop",
                                    nodes=list(fs.drops[k]))
            if k in fs.rejoins:
                self.telemetry.emit("fault", step=k, kind="rejoin",
                                    nodes=list(fs.rejoins[k]))
        else:
            active = np.ones(n, dtype=bool)
        if phase == "gossip":
            if self.fault_schedule is not None:
                W = self.fault_schedule.matrix(self.tcfg.dist.topology, k,
                                               shift_step=shift)
            else:
                W = topo.push_sum_matrix(self.tcfg.dist.topology, n,
                                         step=shift)
        elif phase == "global":
            W = topo.global_push_matrix(n, active)
        else:                       # "none": W is unused by the step
            W = np.eye(n)
        return (jnp.asarray(W, jnp.float32),
                jnp.asarray(active, jnp.float32))

    # ------------------------------------------------------------------
    def run(self, state: TrainState, steps: Optional[int] = None,
            log_every: Optional[int] = None) -> TrainState:
        # install the hub as the ambient one for the whole loop so the
        # mixing-round meters (core/mixing) self-report comm_round
        # records during compiles without plumbing
        with obs.telemetry_scope(self.telemetry):
            return self._run(state, steps, log_every)

    def _run(self, state: TrainState, steps: Optional[int],
             log_every: Optional[int]) -> TrainState:
        tcfg = self.tcfg
        steps = steps if steps is not None else tcfg.steps
        log_every = log_every if log_every is not None else tcfg.log_every
        t0 = time.time()
        # explicit transfer (allowed under a device->host transfer
        # guard); the hot loop below performs ZERO implicit syncs —
        # metrics stay on device until the batched log-boundary fetch
        # repro: allow(RPR001)
        start = int(jax.device_get(state.step))
        if self.state_shardings is not None:
            # a restored checkpoint arrives on the host: place it
            state = jax.device_put(state, self.state_shardings)
        # resume-aware: schedule/lr/data keyed on the
        if start > 0 and not self._sched_live:  # absolute step counter —
            # and a stateful schedule (AGA's period counter) is trajectory
            # state too: a fresh process resuming a checkpoint reloads the
            # sidecar written next to it (no-op for stateless schedules or
            # in-process continuation, where the live state is already
            # correct)
            self.load_schedule(step=start)
        self._sched_live = True
        if start > 0 and not self._faults_live \
                and self.fault_schedule is not None:
            self.load_faults(step=start)
        self._faults_live = True
        if self._overlap and self.n_nodes > 1 and self._comm_buf is None:
            # prime the double buffer from the current params (warm-up
            # round mixes x_0 with itself; on resume this is exactly the
            # flush semantics — the stale buffer is not checkpointed)
            from repro.core import mixing
            spec = tcfg.dist.comm_spec(self.n_nodes, mesh=self.mesh)
            impl = algo_lib.get_algorithm(tcfg.dist.algorithm,
                                          caller="Trainer")
            joint = algo_lib.join_payload(
                impl.comm_payload(state.extras, state.params), state.params)
            buf, ef = mixing.start_round(
                joint, spec, ef_state=state.ef_state, seed=start)
            # the dense buffer aliases state.params — copy so donating
            # both state and buffer never hands XLA the same buffer twice
            self._comm_buf = jax.tree.map(jnp.copy, buf)
            if ef is not state.ef_state:
                state = state.with_extras(ef_state=ef)
            self._buf_shift = self.schedule.gossip_shift_step(
                start, self.period)
        for k in range(start, start + steps):
            with self.telemetry.span("train/input", step=k):
                batch = self._batch(k)
            # advance() commits stateful schedules (AGA's period counter);
            # phase()/peek_phase() stay pure for dryrun/roofline/logging
            phase = (self.schedule.advance(k) if self.n_nodes > 1
                     else "none")
            shift = self.schedule.gossip_shift_step(k, self.period)
            lr = jnp.asarray(self.lr_fn(k), jnp.float32)
            with self.telemetry.span("train/step", step=k,
                                     phase=phase) as sp:
                if self._overlap:
                    bs = self._buf_shift if phase == "gossip" else 0
                    step_fn = self._get_step_fn(phase, shift, buf_shift=bs)
                    state, metrics, self._comm_buf = step_fn(
                        state, batch, lr, self._comm_buf)
                    if phase != "none":
                        # the buffer now in flight was primed at this
                        # step: record its shift for the finish_round
                        # that applies it
                        self._buf_shift = shift
                elif tcfg.dist.push_sum:
                    step_fn = self._get_step_fn(phase, shift)
                    W, active = self._push_round(phase, k, shift)
                    state, metrics = step_fn(state, batch, lr, W, active)
                else:
                    step_fn = self._get_step_fn(phase, shift)
                    state, metrics = step_fn(state, batch, lr)
                # --trace-fence: serialize the pipeline so the span is
                # device time, not async dispatch time.  Only this span
                # fences: a fenced span marks one step's completion
                sp.fence(metrics["loss"])
            # lazily: the schedule holds the DEVICE scalar and
            # materializes it only at period boundaries (explicit
            # device_get in schedule._as_float) — no per-step sync
            self.schedule.observe_loss(k, metrics["loss"])
            self._phase_counts[phase] = self._phase_counts.get(phase, 0) + 1
            self._pending.append((k, phase, lr, metrics))
            if self._overlap and phase not in ("gossip", "none"):
                # period boundary: the compiled step flushed the
                # in-flight round before its collective (DESIGN.md §2.6)
                self.telemetry.emit("flush", step=k, phase=phase)
            if log_every and (k % log_every == 0 or k == steps - 1):
                with self.telemetry.span("train/log", step=k):
                    self._log_boundary(k, phase, t0)
                mo = self.measure_occupancy
                if mo is None:
                    mo = any(isinstance(s, obs.JsonlSink)
                             for s in self.telemetry.sinks)
                if (mo and self._overlap and self.n_nodes > 1
                        and not self._occ_measured and k > start):
                    self._occ_measured = True
                    self._measure_occupancy(state, k)
            if tcfg.ckpt_every and (k + 1) % tcfg.ckpt_every == 0:
                from repro.checkpoint import save_checkpoint
                save_checkpoint(tcfg.ckpt_dir, state, k + 1)
                self._save_schedule(k + 1)
                self._save_faults(k + 1)
                self.telemetry.emit("ckpt", step=k + 1,
                                    path=tcfg.ckpt_dir)
        return state

    # ------------------------------------------------------------------
    def _log_boundary(self, k: int, phase: str, t0: float) -> None:
        """Materialize the device-side monitor window in ONE batched,
        explicit transfer (``Telemetry.fetch``) and emit the ``step``
        record — ring sink (``.history``), pretty print, JSONL."""
        window = list(self._pending)
        self._pending.clear()
        if not window:
            return
        _, _, lr, metrics = window[-1]
        host = self.telemetry.fetch({
            "lr": lr, "metrics": metrics,
            "window_loss": [w[3]["loss"] for w in window]})
        rec = {"step": k, "phase": phase, "lr": float(host["lr"]),
               "time": time.time() - t0}
        rec.update({m: float(v) for m, v in host["metrics"].items()})
        wl = [float(x) for x in host["window_loss"]]
        rec["loss_window_mean"] = sum(wl) / len(wl)
        rec["window"] = len(wl)
        # executed-round counts by phase: joins the traced comm_round
        # records (emitted once per compiled variant) back to reality
        rec["phase_counts"] = dict(self._phase_counts)
        self.telemetry.emit("step", **rec)

    # ------------------------------------------------------------------
    def _measure_occupancy(self, state: TrainState, k: int) -> None:
        """One-shot pipeline-occupancy calibration for overlapped runs
        (DESIGN.md §2.7): time the overlapped step, the comm-free step,
        and a synchronous issue+apply round, then report

            occupancy = clip(1 - max(0, t_overlap - t_compute) / t_sync,
                             0, 1)

        — the fraction of the synchronous round cost hidden under
        compute.  Uses fresh non-donating jits so ``state`` survives;
        runs with the ambient hub scoped out so the probe rounds do not
        spam ``comm_round`` records."""
        from repro.core import mixing
        tcfg = self.tcfg
        spec = tcfg.dist.comm_spec(self.n_nodes, mesh=self.mesh)
        shift = self.schedule.gossip_shift_step(k, self.period)
        batch = self._batch(k)
        lr = jnp.asarray(self.lr_fn(k), jnp.float32)

        def build(phase):
            fn = build_train_step(self.model, tcfg, self.n_nodes,
                                  phase=phase, shift_step=shift,
                                  buf_shift=shift,
                                  with_consensus=self.with_consensus,
                                  mesh=self.mesh)
            return jax.jit(fn)   # no donation: timing-only probes

        step_ov, step_cmp = build("gossip"), build("none")
        with obs.telemetry_scope(None):
            t_ov = obs.fenced_time(step_ov, state, batch, lr,
                                   self._comm_buf, iters=3, warmup=1)
            t_cmp = obs.fenced_time(step_cmp, state, batch, lr,
                                    self._comm_buf, iters=3, warmup=1)
            t_issue = obs.fenced_time(
                mixing.start_round, state.params, spec, iters=3,
                warmup=1, ef_state=state.ef_state, seed=k)
            rs, _ = mixing.start_round(state.params, spec,
                                       ef_state=state.ef_state, seed=k)
            t_apply = obs.fenced_time(
                mixing.finish_round, state.params, rs, spec, iters=3,
                warmup=1, step=shift)
        t_sync = t_issue + t_apply
        occ = obs.meters.occupancy(
            t_cmp * 1e-6, t_sync * 1e-6, t_ov * 1e-6)
        self.telemetry.emit(
            "comm_round", phase="gossip", role="occupancy",
            occupancy=occ, t_step_overlap_us=t_ov,
            t_step_compute_us=t_cmp, t_round_sync_us=t_sync,
            topology=tcfg.dist.topology, backend=tcfg.dist.comm_backend,
            n_nodes=self.n_nodes, step=k)

    # ------------------------------------------------------------------
    def _schedule_path(self, step: int) -> str:
        import os
        return os.path.join(self.tcfg.ckpt_dir,
                            f"schedule_{step:08d}.json")

    def _save_schedule(self, step: int) -> None:
        """Sidecar for stateful schedules: AGA's period counter and H
        adaptation are part of the training trajectory, so a resumed run
        must reload them (stateless schedules write nothing)."""
        sd = self.schedule.state_dict()
        if not sd:
            return
        import json
        with open(self._schedule_path(step), "w") as f:
            json.dump(sd, f)

    def load_schedule(self, step: Optional[int] = None) -> None:
        """Restore the schedule's internal state saved alongside the
        checkpoint at ``step`` (default: latest).  Call when resuming a
        stateful-schedule run (gossip_aga) after
        ``checkpoint.restore_checkpoint``; a missing sidecar is a no-op
        (stateless schedules, or checkpoints predating the sidecar)."""
        import json
        import os
        from repro.checkpoint import latest_step
        step = step if step is not None else latest_step(self.tcfg.ckpt_dir)
        if step is None:
            return
        path = self._schedule_path(step)
        if os.path.exists(path):
            with open(path) as f:
                self.schedule.load_state_dict(json.load(f))

    # ------------------------------------------------------------------
    def _faults_path(self, step: int) -> str:
        import os
        return os.path.join(self.tcfg.ckpt_dir, f"faults_{step:08d}.json")

    def _save_faults(self, step: int) -> None:
        """Sidecar for the fault schedule's bookkeeping counters: a
        resumed run must report the same drop/rejoin totals as an
        uninterrupted one (the schedule itself is a pure function of the
        step, so only the counters are trajectory state)."""
        if self.fault_schedule is None:
            return
        import json
        with open(self._faults_path(step), "w") as f:
            json.dump(self.fault_schedule.state_dict(), f)

    def load_faults(self, step: Optional[int] = None) -> None:
        """Restore the fault counters saved alongside the checkpoint at
        ``step`` (default: latest); missing sidecar is a no-op."""
        if self.fault_schedule is None:
            return
        import json
        import os
        from repro.checkpoint import latest_step
        step = step if step is not None else latest_step(self.tcfg.ckpt_dir)
        if step is None:
            return
        path = self._faults_path(step)
        if os.path.exists(path):
            with open(path) as f:
                self.fault_schedule.load_state_dict(json.load(f))


def _keep_placement(step_fn, state_shardings):
    """Pin the returned state to the input placement: left to itself the
    compiler may hand back a replicated node axis after a global average,
    and the next step would then recompile for the new layout."""
    def step(state, *args):
        out = step_fn(state, *args)
        return (jax.lax.with_sharding_constraint(out[0], state_shardings),
                *out[1:])
    return step


def quick_train(tcfg: TrainConfig, n_nodes: int, steps: int, *,
                seed: int = 0, with_consensus: bool = False) -> Trainer:
    """Convenience: build, init, run — returns the Trainer (with .history)."""
    tr = Trainer(tcfg, n_nodes, with_consensus=with_consensus)
    state = tr.init_state(jax.random.PRNGKey(seed))
    tr.run(state, steps)
    return tr
