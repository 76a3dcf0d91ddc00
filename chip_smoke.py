"""Smoke test of the Gossip-PGA trainer on a TPU at full pga-lm-100m width.

    python3 chip_smoke.py                # one chip: phases 1 and 2
    python3 chip_smoke.py --four-chips   # four chips: the sharded path only

Phase 1 drives the main path as ``repro.launch.train`` does (``Trainer`` ->
``build_train_step`` -> ``mixing.communicate``): gossip_pga over
one_peer_exp, 4 nodes stacked on the chip, H=4, AdamW, sequence 1024,
global batch 16, 8 steps (two PGA periods) on the reference comm backend.
Phase 2 runs the same trainer on the pallas backend for one gossip and one
global step, then checks on the chip that ``mixing.communicate`` under the
pallas backend matches the reference backend on one full-width stacked
state (gossip and global phases, and the fused consensus residual).

``--four-chips`` runs one node per chip on a ``("data",)`` mesh for one PGA
period on both backends, checks that every node-stacked parameter and
optimizer leaf is split over the 4 devices, and that the sharded pallas
round matches the reference round.

The weights are random (seed 0).  Without a TPU, or without the ``src/repro``
package next to this file, the script exits non-zero and prints no result.
The last line of standard output is the JSON result.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import statistics
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))

N_NODES = 4
H = 4
SEQ_LEN = 1024
GLOBAL_BATCH = 16
# fp32 parity of one round: per leaf, max |pallas - reference| against the
# leaf's max |reference|.  A gossip or global round sums at most n = 4
# scaled terms, so the two orders of summation differ by a few ulps (2^-23).
ROUND_RTOL = 1e-6
# the consensus residual sums ~5.5e8 squares in two different orders
RESID_RTOL = 1e-4
# consensus after a global round, against the squared norm of the params
GLOBAL_CONSENSUS_RTOL = 1e-6


def log(*parts) -> None:
    print("chip_smoke:", *parts, flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke: FAILED: {what}")


def train_config(backend: str, h: int = H):
    from repro.configs import (DataConfig, DistConfig, OptimizerConfig,
                               TrainConfig, get_model_config)
    steps = 2 * h
    # launch/train's defaults, apart from the node count, H and the widths
    return TrainConfig(
        model=get_model_config("pga-lm-100m"),
        dist=DistConfig(algorithm="gossip_pga", topology="one_peer_exp",
                        H=h, comm_backend=backend),
        optimizer=OptimizerConfig(name="adamw", lr=3e-3,
                                  schedule="warmup_cosine", warmup_steps=10,
                                  total_steps=steps),
        data=DataConfig(non_iid=True), global_batch=GLOBAL_BATCH,
        seq_len=SEQ_LEN, steps=steps, log_every=1)


class CompileClock:
    """Sums the backend compile time JAX reports while it is open."""

    def __init__(self):
        import jax
        from jax._src import dispatch
        self.event = dispatch.BACKEND_COMPILE_EVENT
        self.secs = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration_secs: float, **_) -> None:
        if event == self.event:
            self.secs += duration_secs

    def take(self) -> float:
        secs, self.secs = self.secs, 0.0
        return secs


def run_steps(tr, state, steps: int, clock: CompileClock):
    """Run ``steps`` trainer steps one at a time; returns the state and one
    record per step (the trainer's step record plus wall and compile time)."""
    import jax
    records = []
    for _ in range(steps):
        clock.take()
        t0 = time.perf_counter()
        state = tr.run(state, steps=1)
        jax.block_until_ready(state)
        wall = time.perf_counter() - t0
        rec = dict(tr.history[-1])
        rec["wall_s"] = wall
        rec["compile_s"] = clock.take()
        records.append(rec)
    return state, records


def params_sq_norm(params) -> float:
    import jax
    import jax.numpy as jnp
    # one node's squared norm: the scale a consensus distance is read against
    return float(jax.jit(lambda t: sum(
        jnp.sum(jnp.square(x[0].astype(jnp.float32)))
        for x in jax.tree.leaves(t)))(params))


def check_finite(records, what: str) -> None:
    for r in records:
        check(math.isfinite(r["loss"]),
              f"{what}: loss {r['loss']} at step {r['step']}")


def check_global_consensus(records, sq_norm: float, what: str) -> None:
    for r in records:
        if r["phase"] == "global":
            rel = r["consensus"] / sq_norm
            log(f"{what}: consensus after global step {r['step']}: "
                f"{r['consensus']!r} (relative {rel!r})")
            check(rel <= GLOBAL_CONSENSUS_RTOL,
                  f"{what}: consensus {rel!r} relative after global step "
                  f"{r['step']} exceeds {GLOBAL_CONSENSUS_RTOL}")


def report_steps(records, what: str) -> None:
    for r in records:
        log(f"{what}: step {r['step']} phase={r['phase']} "
            f"loss={r['loss']!r} consensus={r['consensus']!r} "
            f"wall_s={r['wall_s']:.3f} compile_s={r['compile_s']:.1f}")
    compiled = [r for r in records if r["compile_s"] > 0]
    for r in compiled:
        log(f"{what}: compile of the {r['phase']} variant first run at step "
            f"{r['step']}: {r['compile_s']:.1f} s")
    steady = {}
    for r in records:
        if r["compile_s"] == 0:
            steady.setdefault(r["phase"], []).append(r["wall_s"])
    for phase, times in sorted(steady.items()):
        log(f"{what}: {phase} step time (host clock, median of "
            f"{len(times)} warm steps): {statistics.median(times):.4f} s")


def noisy_stacked(model, n: int, key, shardings=None):
    """A full-width node-stacked params tree whose nodes differ (the model's
    random init plus 1e-2 node noise), built on the device."""
    import jax
    from repro.train.state import stack_for_nodes

    def make(key):
        params = stack_for_nodes(model.init(key)[0], n)
        leaves, treedef = jax.tree.flatten(params)
        noise = [1e-2 * jax.random.normal(jax.random.fold_in(key, i),
                                          x.shape, x.dtype)
                 for i, x in enumerate(leaves)]
        return jax.tree.unflatten(
            treedef, [x + e for x, e in zip(leaves, noise)])

    return jax.jit(make, out_shardings=shardings)(key)


def worst_rel(got, want) -> float:
    import jax
    import jax.numpy as jnp

    @jax.jit
    def per_leaf(a, b):
        return jax.tree.map(
            lambda x, y: jnp.max(jnp.abs(x - y)) / jnp.maximum(
                jnp.max(jnp.abs(y)), 1e-30), a, b)

    return max(float(v) for v in jax.tree.leaves(per_leaf(got, want)))


def compare_rounds(x, spec_ref, spec_pal, n: int, sharded: bool) -> None:
    """Pallas vs reference backend for the gossip and global rounds and the
    fused consensus residual, on the stacked state ``x``."""
    import jax
    from repro.core import mixing
    from repro.train.state import consensus_distance

    failures = []
    for phase, step in (("gossip", 0), ("gossip", 1), ("global", 0)):
        want = jax.jit(lambda t: mixing.communicate(
            t, spec_ref, phase=phase, step=step))(x)
        got = jax.jit(lambda t: mixing.communicate(
            t, spec_pal, phase=phase, step=step))(x)
        rel = worst_rel(got, want)
        log(f"round parity {phase}(shift step {step}): max relative "
            f"|pallas - reference| = {rel!r} (tolerance {ROUND_RTOL})")
        if not rel <= ROUND_RTOL:
            failures.append(f"{phase} round {rel!r}")
        del want, got

    if sharded:
        def fused(t):
            return mixing.communicate_sharded(t, spec_pal, phase="gossip",
                                              step=1, with_residual=True)
    else:
        from repro.kernels import mixing_pallas

        def fused(t):
            return mixing_pallas.mix_residual(
                t, phase="gossip", topology=spec_pal.topology, n_nodes=n,
                step=1, comm_dtype=spec_pal.comm_dtype,
                leaf_threshold=spec_pal.leaf_threshold)
    mixed, _xbar, resid = jax.jit(fused)(x)
    want = jax.jit(lambda t: mixing.communicate(
        t, spec_ref, phase="gossip", step=1))(x)
    want_resid = float(jax.jit(consensus_distance)(want)) * n
    rel = worst_rel(mixed, want)
    rel_r = abs(float(resid) - want_resid) / want_resid
    log(f"fused residual round: mixed max relative diff {rel!r}; residual "
        f"{float(resid)!r} vs reference {want_resid!r} (relative {rel_r!r}, "
        f"tolerance {RESID_RTOL})")
    if not rel <= ROUND_RTOL:
        failures.append(f"fused mixed {rel!r}")
    if not rel_r <= RESID_RTOL:
        failures.append(f"fused residual {rel_r!r}")
    check(not failures, "pallas != reference: " + ", ".join(failures))


def peak_bytes() -> int:
    import jax
    return max(d.memory_stats().get("peak_bytes_in_use", 0)
               for d in jax.local_devices())


def one_chip(clock: CompileClock) -> None:
    import gc

    import jax
    from repro.launch.mesh import node_mesh
    from repro.train import Trainer

    check(node_mesh(N_NODES) is None,
          f"one-chip phases need one device, found {len(jax.devices())}")
    # -- phase 1: the main path on the reference backend --------------------
    tcfg = train_config("reference")
    tr = Trainer(tcfg, n_nodes=N_NODES, with_consensus=True)
    t0 = time.perf_counter()
    state = tr.init_state(jax.random.PRNGKey(0))
    jax.block_until_ready(state)
    n_params = sum(x.size for x in jax.tree.leaves(state.params)) // N_NODES
    log(f"phase 1: pga-lm-100m full width, {n_params} params per node, "
        f"{N_NODES} nodes stacked, seq {SEQ_LEN}, global batch "
        f"{GLOBAL_BATCH}, H={H}, reference backend; init "
        f"{time.perf_counter() - t0:.1f} s")
    sq = params_sq_norm(state.params)
    state, recs = run_steps(tr, state, tcfg.steps, clock)
    report_steps(recs, "phase 1")
    check_finite(recs, "phase 1")
    check_global_consensus(recs, sq, "phase 1")
    first, last = recs[0]["loss"], recs[-1]["loss"]
    log(f"phase 1: loss first {first!r} last {last!r} "
        f"({'fell' if last < first else 'did not fall'} over "
        f"{len(recs)} steps; lr warms up over 10)")
    log(f"phase 1: peak_bytes_in_use {peak_bytes()}")
    del state, tr
    gc.collect()

    # -- phase 2: the pallas backend ----------------------------------------
    tcfg = train_config("pallas", h=2)
    tr = Trainer(tcfg, n_nodes=N_NODES, with_consensus=True)
    state = tr.init_state(jax.random.PRNGKey(0))
    sq = params_sq_norm(state.params)
    state, recs = run_steps(tr, state, 2, clock)
    report_steps(recs, "phase 2 (pallas trainer)")
    check([r["phase"] for r in recs] == ["gossip", "global"],
          f"phase 2 ran phases {[r['phase'] for r in recs]}")
    check_finite(recs, "phase 2")
    check_global_consensus(recs, sq, "phase 2")
    model = tr.model
    del state, tr
    gc.collect()
    x = noisy_stacked(model, N_NODES, jax.random.PRNGKey(1))
    spec = tcfg.dist.comm_spec(N_NODES).replace(compressor=None,
                                                global_compressor=None)
    compare_rounds(x, spec.replace(backend="reference"), spec, N_NODES,
                   sharded=False)
    log(f"phase 2: peak_bytes_in_use {peak_bytes()}")


def four_chips(clock: CompileClock) -> None:
    import jax
    import numpy as np
    from repro.launch.mesh import node_mesh
    from repro.train import Trainer

    check(len(jax.devices()) == 4,
          f"--four-chips needs 4 devices, found {len(jax.devices())}")
    mesh = node_mesh(N_NODES)
    finals = {}
    for backend in ("reference", "pallas"):
        tcfg = train_config(backend)
        tr = Trainer(tcfg, n_nodes=N_NODES, mesh=mesh, with_consensus=True)
        state = tr.init_state(jax.random.PRNGKey(0))
        sq = params_sq_norm(state.params)
        state, recs = run_steps(tr, state, H, clock)
        what = f"four chips, {backend}"
        report_steps(recs, what)
        check_finite(recs, what)
        check_global_consensus(recs, sq, what)
        for name, tree in (("params", state.params),
                           ("opt_state", state.opt_state)):
            for path, a in jax.tree_util.tree_flatten_with_path(tree)[0]:
                if a.ndim and a.shape[0] == N_NODES:
                    spans = len(a.sharding.device_set)
                    check(spans == 4 and a.sharding.spec[0] == "data",
                          f"{what}: {name}{jax.tree_util.keystr(path)} "
                          f"sharding {a.sharding}")
        log(f"{what}: every node-stacked params/opt_state leaf spans 4 "
            f"devices on its node axis")
        finals[backend] = (jax.device_get(state.params), recs)
        spec = tcfg.dist.comm_spec(N_NODES, mesh=mesh).replace(
            compressor=None, global_compressor=None)
        model, shardings = tr.model, tr.state_shardings.params
        del state, tr
    losses = {b: [r["loss"] for r in finals[b][1]] for b in finals}
    log(f"four chips: losses reference {losses['reference']} "
        f"pallas {losses['pallas']}")
    diff = max(float(np.max(np.abs(a - b))) for a, b in zip(
        jax.tree.leaves(finals["reference"][0]),
        jax.tree.leaves(finals["pallas"][0])))
    log(f"four chips: params after one period, max |pallas - reference| "
        f"{diff!r}")
    x = noisy_stacked(model, N_NODES, jax.random.PRNGKey(1), shardings)
    compare_rounds(x, spec.replace(backend="reference"), spec, N_NODES,
                   sharded=True)
    log(f"four chips: peak_bytes_in_use (max over devices) {peak_bytes()}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip sharded path")
    args = ap.parse_args()

    sys.path.insert(0, os.path.join(HERE, "src"))
    try:
        from repro.launch.mesh import use_compile_cache
    except ImportError as e:
        print(f"chip_smoke: the repro package is not in {HERE}/src ({e})",
              file=sys.stderr)
        return 2
    log(f"compile cache: {use_compile_cache()}")
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX platform {dev.platform!r})",
              file=sys.stderr)
        return 1
    log(f"jax {jax.__version__}, {len(jax.devices())} x {dev.device_kind}")
    clock = CompileClock()
    if args.four_chips:
        four_chips(clock)
    else:
        one_chip(clock)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
