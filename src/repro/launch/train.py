"""Training launcher: ``python -m repro.launch.train --arch <id>``.

Runs the reduced config by default and the published widths with
``--full-config``.  On one device the ``--nodes`` nodes stack on it.  With
several devices (e.g. a four-chip TPU host) it builds a ``("data",)`` mesh
over all of them and each device holds ``nodes / device_count`` nodes
(``launch.mesh.node_mesh``).  Compiles go to the persistent cache that
``launch.mesh.use_compile_cache`` sets up.
"""
from __future__ import annotations

import argparse

import jax

from repro.configs import (ALGORITHMS, DataConfig, DistConfig,
                           OptimizerConfig, TrainConfig, get_model_config,
                           list_archs)
from repro.launch.mesh import node_mesh, use_compile_cache
from repro.train import Trainer


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=list(list_archs()))
    ap.add_argument("--algorithm", default="gossip_pga",
                    choices=list(ALGORITHMS),
                    help="registered algorithm (repro.core.algo), incl. "
                         "gt_pga: gradient tracking + periodic global "
                         "averaging for non-IID data")
    ap.add_argument("--topology", default="one_peer_exp")
    ap.add_argument("--H", type=int, default=6)
    ap.add_argument("--nodes", type=int, default=8)
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--global-batch", type=int, default=16)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--optimizer", default="adamw")
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--comm-backend", default="reference",
                    choices=("reference", "pallas"),
                    help="mixing implementation (DESIGN.md §2.1): roll-based "
                         "reference or fused Pallas kernels")
    ap.add_argument("--comm-shard-mode", default="auto",
                    choices=("auto", "stacked", "sharded"),
                    help="pallas backend under a mesh-sharded node axis: "
                         "auto-detect, force the local stacked kernels, or "
                         "require the shard_map path (DESIGN.md §2.1)")
    ap.add_argument("--leaf-threshold", type=int, default=262_144,
                    help="per-node elements at which a parameter leaf gets "
                         "its own pallas dispatch (skips the concat staging "
                         "buffer)")
    ap.add_argument("--comm-compression", default="none",
                    choices=("none", "identity", "int8", "fp8", "topk",
                             "randk"),
                    help="wire compressor for the communication round "
                         "(repro.compress, DESIGN.md §2.3); identity is "
                         "bit-identical to none")
    ap.add_argument("--comm-compression-k", type=int, default=32,
                    help="elements kept per node per leaf for topk/randk")
    ap.add_argument("--comm-global-compression", default="none",
                    choices=("none", "identity", "int8", "fp8"),
                    help="compressed collective for the global/pod-"
                         "averaging phases (DESIGN.md §2.3 Compressed "
                         "collectives); identity is bit-identical to none")
    ap.add_argument("--error-feedback", action="store_true",
                    help="per-node error-feedback memory: compression "
                         "error is fed back next round instead of dropped")
    ap.add_argument("--comm-overlap", action="store_true",
                    help="pipelined gossip (DESIGN.md §2.6): the mixing "
                         "round of step t overlaps the compute of step "
                         "t+1 via a one-step-stale double buffer; global/"
                         "PGA rounds stay synchronous")
    ap.add_argument("--push-sum", action="store_true",
                    help="push-sum gossip (DESIGN.md §2.5): column-"
                         "stochastic directed mixing with a per-node weight "
                         "scalar, de-biased at read time — required for "
                         "directed topologies and fault injection")
    ap.add_argument("--fault-drop", default="",
                    help="drop events as 'step:id,id[;step:id,...]', e.g. "
                         "'40:3,5;90:0' drops nodes 3,5 at step 40 and "
                         "node 0 at step 90 (requires --push-sum)")
    ap.add_argument("--fault-rejoin", default="",
                    help="rejoin events, same syntax as --fault-drop")
    ap.add_argument("--fault-resample", default="none",
                    choices=("none", "hop", "peer"),
                    help="re-draw the gossip wiring each step: 'hop' "
                         "resamples one shared power-of-two hop, 'peer' "
                         "gives every node its own draw")
    ap.add_argument("--fault-seed", type=int, default=0,
                    help="seed for the deterministic per-step fault/"
                         "resample RNG (counter-based; resume-stable)")
    ap.add_argument("--full-config", action="store_true",
                    help="full published dims (TPU-scale; default reduced)")
    ap.add_argument("--iid", action="store_true")
    ap.add_argument("--telemetry-dir", default="",
                    help="write the structured telemetry stream "
                         "(DESIGN.md §2.7) to <dir>/telemetry.jsonl: step "
                         "records, per-round comm byte/latency meters, "
                         "fault + checkpoint events")
    ap.add_argument("--trace", default="",
                    help="save a Chrome-trace-event timeline of the run's "
                         "host spans (train/step, train/input, train/log) "
                         "to this path (load in Perfetto / "
                         "chrome://tracing); on its own clock.  Under a "
                         "jax.profiler trace the same spans land on the "
                         "profiler's host plane, on the device clock, "
                         "beside the step's named scopes (fwd_bwd, "
                         "optimizer, monitor, round)")
    ap.add_argument("--trace-fence", action="store_true",
                    help="block_until_ready at span exits so spans measure "
                         "device time instead of async dispatch time "
                         "(serializes the pipeline it measures)")
    args = ap.parse_args()

    use_compile_cache()
    cfg = get_model_config(args.arch, reduced=not args.full_config)
    tcfg = TrainConfig(
        model=cfg,
        dist=DistConfig(algorithm=args.algorithm, topology=args.topology,
                        H=args.H, comm_backend=args.comm_backend,
                        comm_shard_mode=args.comm_shard_mode,
                        pallas_leaf_threshold=args.leaf_threshold,
                        comm_compression=args.comm_compression,
                        comm_compression_k=args.comm_compression_k,
                        comm_global_compression=args.comm_global_compression,
                        comm_error_feedback=args.error_feedback,
                        comm_overlap=args.comm_overlap,
                        push_sum=args.push_sum),
        optimizer=OptimizerConfig(name=args.optimizer, lr=args.lr,
                                  schedule="warmup_cosine", warmup_steps=10,
                                  total_steps=args.steps),
        data=DataConfig(non_iid=not args.iid),
        global_batch=args.global_batch, seq_len=args.seq_len,
        steps=args.steps, log_every=max(args.steps // 10, 1))
    fault_schedule = None
    if args.fault_drop or args.fault_rejoin or args.fault_resample != "none":
        from repro.core.faults import FaultSchedule, parse_fault_events
        fault_schedule = FaultSchedule(
            n_nodes=args.nodes,
            drops=parse_fault_events(args.fault_drop),
            rejoins=parse_fault_events(args.fault_rejoin),
            resample=args.fault_resample,
            seed=args.fault_seed)
    telemetry = None
    if args.telemetry_dir or args.trace or args.trace_fence:
        import os
        from repro import obs
        sinks = [obs.RingSink(), obs.PrettySink()]
        if args.telemetry_dir:
            os.makedirs(args.telemetry_dir, exist_ok=True)
            sinks.insert(0, obs.JsonlSink(
                os.path.join(args.telemetry_dir, "telemetry.jsonl")))
        telemetry = obs.Telemetry(sinks=sinks, fence=args.trace_fence)
    tr = Trainer(tcfg, n_nodes=args.nodes, mesh=node_mesh(args.nodes),
                 with_consensus=True, fault_schedule=fault_schedule,
                 telemetry=telemetry)
    state = tr.init_state(jax.random.PRNGKey(0))
    tr.run(state, steps=args.steps)
    if telemetry is not None:
        if args.trace:
            print("trace:", telemetry.tracer.save(args.trace))
        telemetry.close()


if __name__ == "__main__":
    main()
