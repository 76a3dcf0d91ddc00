"""Readings that the limits of ``check.py`` are set from.

    python3 benchmarks/chip/calibrate.py --workload <cell> \
        --seeds 1,2,3 [--stand-in-seeds 1,2,3] [--out FILE]

For every seed of ``--seeds``: the program's first three steps against
the reference (the sound readings).  For every seed of
``--stand-in-seeds``, the reference put in the program's place against
the reference: computed in float8 (the control), and with each fault a
training cell can have planted ("half_batch", "no_exchange", and
"no_gossip": the exchange left out of the gossip steps alone).  A state
left unchanged ("frozen") reads 1 by construction and needs no run.  For
a LAMB cell, also the reference with the paper's per-layer trust ratio
("lamb_per_layer"): the size of the departure the program and the
reference share.  One
process, so the trainer compiles once; no timed window.  Each reading is
one JSON line on standard output and in ``--out``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))

STAND_INS = (("control", "fp8", None),
             ("half_batch", "highest", "half_batch"),
             ("no_exchange", "highest", "no_exchange"),
             ("no_gossip", "highest", "no_gossip"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--stand-in-seeds", default="")
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    sys.path.insert(0, os.path.dirname(HERE))
    from chip import bench, cells, check
    from repro.launch.mesh import use_compile_cache
    import jax
    use_compile_cache()
    cell = cells.load_cell(args.workload, cells.benchmark(ROOT))
    if jax.devices()[0].platform != "tpu" or len(jax.devices()) < cell.chips:
        print("calibrate: needs the cell's TPUs", file=sys.stderr)
        return 1
    out = open(args.out, "a") if args.out else None

    t0 = time.perf_counter()

    def emit(rec):
        rec["elapsed_s"] = time.perf_counter() - t0
        line = json.dumps(rec)
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()

    seeds = [int(s) for s in args.seeds.split(",") if s]
    stand = [int(s) for s in args.stand_in_seeds.split(",") if s]
    trainer = None
    for seed in sorted(set(seeds) | set(stand), key=lambda s: (
            s not in seeds, seeds.index(s) if s in seeds else 0)):
        setup = bench.Setup(cell, seed)
        if trainer is not None:       # reuse the compiled phase variants
            setup.tr._compiled = trainer._compiled
        setup.make_weights()
        prog = None
        if seed in seeds:
            prog = setup.first_steps()
        trainer = setup.tr
        setup.state = None
        setup.tracer.close()
        ref = bench.reference_readings(setup)
        if prog is not None:
            emit({"cell": cell.name, "seed": seed, "kind": "program",
                  **check.numbers(prog, ref), "losses": prog["losses"],
                  "ref_losses": ref["losses"]})
        if seed in stand:
            for kind, precision, fault in STAND_INS:
                got = bench.reference_readings(setup, precision, fault)
                emit({"cell": cell.name, "seed": seed, "kind": kind,
                      **check.numbers(got, ref), "losses": got["losses"]})
            if cell.workload["optimizer"]["name"] == "lamb":
                got = bench.reference_readings(setup, lamb_per_layer=True)
                emit({"cell": cell.name, "seed": seed,
                      "kind": "lamb_per_layer", **check.numbers(got, ref)})
        del setup
    return 0


if __name__ == "__main__":
    sys.exit(main())
