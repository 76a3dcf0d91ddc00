"""One run of one cell: set-up, the timed (or traced) window, and the
comparison with the plain reference.

Set-up builds the trainer as ``repro.launch.train`` does, with the
benchmark's hub and tracer passed in and the program's own input stream
wrapped in an annotation, makes the weights on the device from the seed
in one jitted call, and drives the trainer from step ``H - 3``: its first
three steps (both gossip shifts of the one-peer graph, then the global
average) go through ``Trainer.run`` and are compared with the reference;
one more call of ``H + 1`` steps warms every phase variant after every
other.  The window is one ``Trainer.run`` call
of a whole number of periods, sized from the warm steps to last about
``--seconds``, and ends in ``block_until_ready``.
"""
from __future__ import annotations

import dataclasses
import gc
import math
import os
import shutil
import statistics
import sys
import tempfile
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from chip import cells, check, flops, hooks, tracered, traffic

HERE = os.path.dirname(os.path.abspath(__file__))
FIRST = 3                 # steps compared with the reference


def log(*parts) -> None:
    print("chipbench:", *parts, file=sys.stderr, flush=True)


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from a seed of any size (both 32-bit words count)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


def cell_mesh(chips: int):
    """``launch.mesh.node_mesh``'s mesh over the cell's chips: none for one
    chip, one ``("data",)`` axis over ``chips`` devices otherwise."""
    if chips == 1:
        return None
    devices = jax.devices()[:chips]
    return jax.make_mesh((chips,), ("data",), devices=devices,
                         axis_types=(jax.sharding.AxisType.Auto,))


class Setup:
    """The trainer, its state, and what set-up read of the first steps."""

    def __init__(self, cell: cells.Cell, seed: int):
        from repro.launch.mesh import node_mesh
        from repro.train import Trainer
        self.cell, self.seed = cell, int(seed)
        self.tcfg = cells.train_config(cell)
        self.tcfg = self.tcfg.replace(data=dataclasses.replace(
            self.tcfg.data, seed=self.seed))
        if not self.tcfg.ckpt_every:
            self.tcfg = self.tcfg.replace(
                ckpt_dir=os.path.join(HERE, ".ckpt"))
        self.H = self.tcfg.dist.H
        if self.tcfg.dist.algorithm != "gossip_pga" or self.H < FIRST:
            raise ValueError("the comparison follows gossip_pga with "
                             f"H >= {FIRST}")
        n = cell.n_nodes
        mesh = (node_mesh(n) if len(jax.devices()) == cell.chips
                else cell_mesh(cell.chips))
        self.ref = cells.reference_module(cell)
        self.hub, self.tracer = hooks.make_hub()
        self.tr = Trainer(self.tcfg, n, mesh=mesh, with_consensus=True,
                          telemetry=self.hub)
        self.stream = self.tr.stream            # the program's own
        self.tr.stream = hooks.AnnotatedStream(self.stream)
        self.ref_batches = traffic.ReferenceBatches(cell.traffic,
                                                    cell.model, n, self.seed)
        self.start = self.H - FIRST
        self.node_sharding = (NamedSharding(mesh, P("data"))
                              if mesh is not None else None)
        self.replicated = (NamedSharding(mesh, P())
                           if mesh is not None else None)

    # -- weights and state ----------------------------------------------
    def make_weights(self):
        cfg, key = self.cell.model, seed_key(self.seed)
        self.params0 = jax.jit(lambda k: self.ref.init_params(cfg, k),
                               out_shardings=self.replicated)(key)
        want = jax.eval_shape(lambda k: self.tr.model.init(k)[0], key)
        got = jax.tree.map(lambda x: (x.shape, x.dtype), self.params0)
        exp = jax.tree.map(lambda x: (x.shape, x.dtype), want)
        if got != exp:
            raise ValueError("the reference's parameter layout differs from "
                             "the program's")
        start = self.start

        def build(p):
            st = self.tr._init_tree(p)
            st.step = jnp.asarray(start, jnp.int32)
            return st

        self.state = jax.jit(build, out_shardings=self.tr.state_shardings
                             )(self.params0)

    def first_steps(self) -> Dict:
        """Run the first three steps through ``Trainer.run``; read their
        losses, the first gradient's norms, the change's norms after the
        two gossip steps and after all three, and how far the program's
        batches for them differ from the reference's own."""
        m_norms = g_norms = None
        changes = jax.jit(check.change_norms)
        for i in range(FIRST):
            self.state = self.tr.run(self.state, steps=1)
            if i == 0:
                m_norms = jax.jit(check.pair_norms)(
                    self.state.opt_state["m"])
            if i == FIRST - 2:
                g_norms = changes(self.state.params, self.params0)
        ch = changes(self.state.params, self.params0)
        self.tracer.drain()
        losses = [v for s, _, v in self.tracer.done[:FIRST]]
        gaps = [traffic.input_gap(self.stream.get_batch(k),
                                  self.ref_batches.get_batch(k))
                for k in range(self.start, self.start + FIRST)]
        return {"losses": [float(x) for x in jax.device_get(losses)],
                "m_norms": np.asarray(jax.device_get(m_norms)),
                "gossip_norms": np.asarray(jax.device_get(g_norms)),
                "change_norms": np.asarray(jax.device_get(ch)),
                "input_gap": max(gaps)}

    def finish_warmup(self) -> float:
        """The rest of the warm-up: one ``Trainer.run`` of ``H + 1`` steps,
        so that inside one call a gossip step follows the global one and
        the global one a gossip step.  A step program's first call on the
        layout another program's output has takes JAX's slow dispatch path
        (a trace and a lowering); the window must not pay that.  Returns
        the median interval between the warm steps' completions, in
        seconds, which those first calls do not skew."""
        first = len(self.tracer.done)
        self.state = self.tr.run(self.state, steps=self.H + 1)
        jax.block_until_ready(self.state)
        self.tracer.drain()
        stamps = [t for _, t, _ in self.tracer.done[first:]]
        return statistics.median(b - a for a, b in zip(stamps, stamps[1:]))

    def free_program(self) -> None:
        self.state = None
        self.tr._compiled.clear()
        self.tr = None
        gc.collect()


# ---------------------------------------------------------------------------
# The reference, following the first three steps
# ---------------------------------------------------------------------------
def reference_readings(setup: Setup, precision: str = "highest",
                       fault: Optional[str] = None,
                       lamb_per_layer: bool = False) -> Dict:
    cell, ref = setup.cell, setup.ref
    n, groups = cell.n_nodes, cell.chips
    opt = cell.workload["optimizer"]
    put = (lambda x: jax.device_put(x, setup.node_sharding)) \
        if setup.node_sharding is not None else jnp.asarray
    init = jax.jit(lambda p: ref.init_state(p, n))
    if setup.node_sharding is not None:
        shapes = jax.eval_shape(init, setup.params0)
        init = jax.jit(init, out_shardings=jax.tree.map(
            lambda x: setup.node_sharding if x.ndim else setup.replicated,
            shapes))
    state = init(setup.params0)
    log("device bytes in use with the reference's state: "
        f"{device_bytes('bytes_in_use', groups)}")
    step = ref.make_step(cell.model, opt, n, groups, precision=precision,
                         fault=fault, lamb_per_layer=lamb_per_layer)
    jitted = {}
    losses, m_norms, g_norms = [], None, None
    period = max(1, int(round(math.log2(n))))
    for i in range(FIRST):
        k = setup.start + i
        phase = "global" if (k + 1) % setup.H == 0 else "gossip"
        # without an exchange the peer is never read: one program
        hop = 0 if fault == "no_exchange" else ref.hop_of(phase, k % period,
                                                          n)
        if fault == "no_gossip" and hop:
            hop = 1
        if hop not in jitted:       # the state is updated in place
            jitted[hop] = jax.jit(lambda s, b, lr, hop=hop: step(s, b, lr,
                                                                 hop),
                                  donate_argnums=0)
        batch = jax.tree.map(put, setup.ref_batches.get_batch(k))
        state, node_losses = jitted[hop](
            state, batch, jnp.asarray(check.lr_at(opt, k), jnp.float32))
        losses.append(jnp.mean(node_losses))
        if i == 0:
            m_norms = jax.jit(check.pair_norms)(state["m"])
        if i == FIRST - 2:
            g_norms = jax.jit(check.change_norms)(state["params"],
                                                  setup.params0)
    ch = jax.jit(check.change_norms)(state["params"], setup.params0)
    out = {"losses": [float(x) for x in jax.device_get(losses)],
           "m_norms": np.asarray(jax.device_get(m_norms)),
           "gossip_norms": np.asarray(jax.device_get(g_norms)),
           "change_norms": np.asarray(jax.device_get(ch))}
    del state
    gc.collect()
    return out


# ---------------------------------------------------------------------------
# Windows
# ---------------------------------------------------------------------------
def device_bytes(stat: str, chips: int) -> int:
    """``memory_stats()[stat]`` of the fullest of the first ``chips``."""
    return max((d.memory_stats() or {}).get(stat, 0)
               for d in jax.devices()[:chips])


def percentile(values: List[float], q: float) -> float:
    """The ``q``-th percentile, interpolated between closest ranks."""
    xs = sorted(values)
    if len(xs) == 1:
        return xs[0]
    pos = (len(xs) - 1) * q / 100.0
    lo = int(math.floor(pos))
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def timed_window(setup: Setup, steps: int, clock: hooks.CompileClock
                 ) -> Dict:
    tracer = setup.tracer
    first = len(tracer.done)
    n_builds = clock.builds
    t0 = time.perf_counter()
    setup.state = setup.tr.run(setup.state, steps=steps)
    jax.block_until_ready(setup.state)
    t1 = time.perf_counter()
    tracer.drain()
    done = tracer.done[first:]
    stamps = [t for _, t, _ in done]
    intervals = [b - a for a, b in zip([t0] + stamps[:-1], stamps)]
    losses = np.asarray(jax.device_get([v for _, _, v in done]))
    return {"t0": t0, "t1": t1, "steps": len(done),
            "intervals": intervals, "done_steps": [k for k, _, _ in done],
            "losses": losses,
            "builds": clock.builds - n_builds}


def traced_window(setup: Setup, steps: int) -> Dict:
    """Run ``steps`` steps under the JAX profiler and reduce the trace."""
    tmp = tempfile.mkdtemp(prefix="chipbench-trace-")
    try:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        first = len(setup.tracer.done)
        jax.profiler.start_trace(tmp, profiler_options=opts)
        setup.state = setup.tr.run(setup.state, steps=steps)
        jax.block_until_ready(setup.state)
        jax.profiler.stop_trace()
        setup.tracer.drain()
        paths = [os.path.join(d, f) for d, _, fs in os.walk(tmp)
                 for f in fs if f.endswith(".xplane.pb")]
        if len(paths) != 1:
            raise RuntimeError(f"expected one trace file, found {paths}")
        tr = tracered.load_xplane(paths[0])
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    red = tracered.reduce_trace(tr)
    done = setup.tracer.done[first:]
    losses = np.asarray(jax.device_get([v for _, _, v in done]))
    return {"trace": tr, "red": red, "steps": len(done), "losses": losses}


def metric_context(setup: Setup, w: Dict, peaks: Dict) -> Dict:
    """What the per-layer readers (``metrics/<name>.py``) read."""
    cell, red = setup.cell, w["red"]
    lo, hi = red["window_ns"]
    steps = [int(s["args"].get("step", -1)) for s in red["steps"]]
    tokens = head = 0
    for k in steps:
        b = setup.stream.get_batch(k)
        tokens += int(b["inputs"].size)
        head += traffic.head_positions(b)
    comm = setup.tcfg.dist.comm_dtype
    return {
        "trace": w["trace"], "red": red,
        "window_s": (hi - lo) * 1e-9, "n_steps": len(steps),
        "chips": cell.chips, "n_nodes": cell.n_nodes, "peaks": peaks,
        "flops": flops.train_flops(cell.model, cell.traffic["seq_len"],
                                   tokens, head),
        "params_per_node": flops.param_count(cell.model),
        "comm_itemsize": 2 if comm == "bfloat16" else 4,
    }


def breakdown(w: Dict) -> Dict:
    red = w["red"]
    return {"device_ops": [[n, s] for n, s in
                           tracered.top_ops(w["trace"], red["window_ns"])],
            "idle_gaps": [[n, ns * 1e-9] for n, ns in
                          red["idle_gaps_ns"][:10]]}


# ---------------------------------------------------------------------------
# One run
# ---------------------------------------------------------------------------
def run_cell(cell: cells.Cell, seed: int, seconds: float, trace: bool,
             t_start: float, bench: Dict, *,
             peaks: Optional[Dict] = None) -> Dict:
    from repro.launch.mesh import use_compile_cache
    log(f"compile cache: {use_compile_cache()}")
    # every program the run uses goes to the cache, the small ones too
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    devs = jax.devices()
    kind = devs[0].device_kind
    peaks = peaks if peaks is not None else flops.load_peaks(kind)
    clock = hooks.CompileClock()
    setup = Setup(cell, seed)
    setup.make_weights()
    prog = setup.first_steps()
    per_step = setup.finish_warmup()
    log(f"{cell.name}: warm step {per_step!r} s; first losses "
        f"{prog['losses']}; set-up compiled {clock.count} programs in "
        f"{clock.secs!r} s")
    limits = cell.workload["limits"]
    result: Dict = {"correct": False, "attempted": 0, "failed": 0,
                    "metrics": {}, "device": {
                        "platform": devs[0].platform, "kind": kind,
                        "count": cell.chips}}
    entry_metrics = _cell_metrics(bench, cell.name, trace)
    if trace:
        steps = 2 * setup.H
        w = traced_window(setup, steps)
        ctx = metric_context(setup, w, peaks)
        busy = statistics.fmean(w["red"]["busy_ns"].values()) * 1e-9
        result["device"].update(busy_s=busy, window_s=ctx["window_s"])
        for m in entry_metrics:
            value = cells.metric_reader(m["name"])(ctx)
            if value is not None:
                result["metrics"][m["name"]] = {"value": float(value),
                                                "unit": m["unit"]}
        result["breakdown"] = breakdown(w)
        log(f"phase attribution of device ops: {w['red']['attribution']}")
    else:
        periods = max(1, int(round(seconds / (per_step * setup.H))))
        steps = periods * setup.H
        setup_s = time.perf_counter() - t_start
        w = timed_window(setup, steps, clock)
        window_s = w["t1"] - w["t0"]
        print("chipbench: programs traced, lowered or compiled inside the "
              f"window: {w['builds']}", flush=True)
        if w["builds"]:
            raise RuntimeError(f"{w['builds']} program build(s) inside the "
                               "measured window")
        tokens = steps * cell.traffic["global_batch"] * \
            cell.traffic["seq_len"]
        values = {"tokens_per_s": tokens / window_s,
                  "step_ms_p95": percentile(w["intervals"], 95) * 1e3,
                  "setup_s": setup_s}
        for m in entry_metrics:
            result["metrics"][m["name"]] = {"value": values[m["name"]],
                                            "unit": m["unit"]}
        log(f"window: {steps} steps in {window_s!r} s; step intervals "
            f"median {statistics.median(w['intervals']) * 1e3!r} ms")
        top = sorted(zip(w["intervals"], w["done_steps"]), reverse=True)
        log("longest step intervals, ms (step they end at): " + " ".join(
            f"{x * 1e3:.2f}({k})" for x, k in top[:20]))
    result["attempted"] = w["steps"]
    result["failed"] = int(np.sum(~np.isfinite(w["losses"])))
    result["device"]["memory_peak_bytes"] = device_bytes(
        "peak_bytes_in_use", cell.chips)
    setup.tracer.close()
    setup.free_program()
    ref = reference_readings(setup)
    nums = check.numbers(prog, ref)
    result["correct"] = bool(check.verdict(nums, limits)
                             and result["failed"] == 0)
    result["checks"] = check.report(nums, limits)
    for line in check.lines(nums, limits):
        log(line)
    return result


def _cell_metrics(bench: Dict, cell: str, trace: bool) -> List[Dict]:
    group = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]
