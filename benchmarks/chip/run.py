"""On-chip benchmark of the Gossip-PGA trainer.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Runs from the root of a checkout.  ``BENCHMARK.json`` names the cell's
configuration and traffic; ``cells.py`` says which files hold them.  With
``--trace 0`` the last line of standard output is one JSON object with
the cell's end-to-end metrics; with ``--trace 1`` its per-layer metrics,
read from a profiler trace of two PGA periods.  Without a TPU, with fewer
chips than the cell asks for, or without the ``src/repro`` package beside
this directory, it exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def fail(msg: str, code: int = 1) -> int:
    print(f"chipbench: {msg}", file=sys.stderr, flush=True)
    return code


def check_devices(chips: int):
    """The TPUs JAX finds; raises when there are none or too few."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise RuntimeError(f"no TPU: JAX platform is {devs[0].platform!r}")
    if len(devs) < chips:
        raise RuntimeError(f"the cell needs {chips} chips, JAX finds "
                           f"{len(devs)}")
    return devs


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        return fail(f"the repro package is not in {src}", 2)
    sys.path.insert(0, src)
    sys.path.insert(0, os.path.dirname(HERE))
    from chip import bench, cells
    try:
        bench_json = cells.benchmark(ROOT)
        cell = cells.load_cell(args.workload, bench_json)
    except (OSError, KeyError, ValueError) as e:
        return fail(f"cannot load workload {args.workload!r}: {e}", 2)
    try:
        check_devices(cell.chips)
    except RuntimeError as e:
        return fail(str(e))
    result = bench.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                            T_START, bench_json)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
