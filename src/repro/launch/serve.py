"""Serving launcher: ``python -m repro.launch.serve --arch <id>`` — batched
greedy decoding against the reduced config (CPU) or full config (TPU)."""
from __future__ import annotations

import argparse

import jax
import numpy as np

from repro.configs import get_model_config, list_archs
from repro.launch.mesh import use_compile_cache
from repro.models import make_model
from repro.serve import BatchedServer, Engine, Request


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=list(list_archs()))
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--slots", type=int, default=2)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--s-max", type=int, default=64)
    ap.add_argument("--full-config", action="store_true")
    ap.add_argument("--telemetry-dir", default="",
                    help="write serve_req records (latency, tokens/s) to "
                         "<dir>/telemetry.jsonl")
    ap.add_argument("--trace", default="",
                    help="save a Chrome trace of serve/prefill + "
                         "serve/decode spans to this path")
    args = ap.parse_args()

    use_compile_cache()
    cfg = get_model_config(args.arch, reduced=not args.full_config)
    if not cfg.causal:
        raise SystemExit(f"{args.arch} is encoder-only: no decode serving")
    model = make_model(cfg)
    params, _ = model.init(jax.random.PRNGKey(0))
    telemetry = None
    if args.telemetry_dir or args.trace:
        import os
        from repro import obs
        sinks = [obs.PrettySink(types=("serve_req",))]
        if args.telemetry_dir:
            os.makedirs(args.telemetry_dir, exist_ok=True)
            sinks.insert(0, obs.JsonlSink(
                os.path.join(args.telemetry_dir, "telemetry.jsonl")))
        telemetry = obs.Telemetry(sinks=sinks)
    server = BatchedServer(Engine(model, s_max=args.s_max), params,
                           n_slots=args.slots, telemetry=telemetry)
    rng = np.random.default_rng(0)
    reqs = [Request(uid=i, prompt=rng.integers(0, cfg.vocab_size, size=6),
                    max_new=args.max_new) for i in range(args.requests)]
    for r in sorted(server.run(reqs), key=lambda r: r.uid):
        print(f"req {r.uid}: {list(r.prompt)} -> {r.generated}")
    if telemetry is not None:
        if args.trace:
            print("trace:", telemetry.tracer.save(args.trace))
        telemetry.close()


if __name__ == "__main__":
    main()
