"""``correct`` on the CPU at a tiny size: a sound run passes, the control
in float8 fails (the faults are in ``test_chip_bench_faults.py``)."""
import json
import os
import subprocess
import sys
import time

import pytest

from chip import bench, cells, check

HERE = os.path.dirname(os.path.abspath(__file__))
TESTDATA = os.path.join(HERE, "testdata")
SRC = os.path.join(os.path.dirname(os.path.dirname(HERE)), "src")
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
# (tiny stand-in, the benchmark cell whose limits it is held to)
LM_CELLS = [("tiny-lm-1chip", "lm100m-pga-1chip"),
            ("tiny-lm-pallas-1chip", "lm100m-pga-pallas-1chip")]
CELLS = LM_CELLS + [("tiny-mlm-1chip", "lm100m-pga-1chip")]


def tiny_cell(name, limits_of):
    with open(os.path.join(TESTDATA, "bench.json")) as f:
        tb = json.load(f)
    cell = cells.load_cell(name, tb, base=TESTDATA)
    real = cells.load_cell(limits_of, cells.benchmark())
    cell.workload = dict(cell.workload, limits=real.workload["limits"])
    return cell, tb


def run(cell, tb, seed=20240917):
    return bench.run_cell(cell, seed, 0.2, False, time.perf_counter(), tb,
                          peaks=PEAKS)


@pytest.mark.parametrize("name,limits_of", LM_CELLS)
def test_a_sound_run_is_correct(name, limits_of):
    cell, tb = tiny_cell(name, limits_of)
    r = run(cell, tb)
    assert r["correct"], r["checks"]
    assert list(r)[-1] == "checks"
    assert r["attempted"] >= cell.workload["dist"]["H"] and not r["failed"]


@pytest.mark.parametrize("name,limits_of", CELLS)
def test_the_control_in_float8_is_not_correct(name, limits_of):
    cell, _ = tiny_cell(name, limits_of)
    setup = bench.Setup(cell, 7)
    setup.make_weights()
    setup.free_program()
    ref = bench.reference_readings(setup)
    control = bench.reference_readings(setup, precision="fp8")
    nums = check.numbers(control, ref)
    assert not check.verdict(nums, cell.workload["limits"]), nums


def test_an_unknown_device_kind_fails_before_any_result():
    cell, tb = tiny_cell("tiny-lm-1chip", "lm100m-pga-1chip")
    with pytest.raises(KeyError, match="no peaks"):
        bench.run_cell(cell, 1, 0.2, False, time.perf_counter(), tb)


def test_the_four_chip_path_runs_on_four_host_devices():
    """The mesh path (one node per device, the reference's per-device
    nodes) end to end on four CPU devices, in a process of its own."""
    code = (
        "import json, sys, time\n"
        f"sys.path[:0] = [{os.path.dirname(HERE)!r}, {SRC!r}]\n"
        "from chip import bench, cells\n"
        f"tb = json.load(open({os.path.join(TESTDATA, 'bench.json')!r}))\n"
        f"cell = cells.load_cell('tiny-mlm-4chip', tb, base={TESTDATA!r})\n"
        "r = bench.run_cell(cell, 5, 0.2, False, time.perf_counter(), tb,\n"
        f"                   peaks={PEAKS!r})\n"
        "print(json.dumps(r))\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    r = json.loads(p.stdout.strip().splitlines()[-1])
    assert r["device"]["count"] == 4 and r["attempted"] >= 6
    assert all(v["value"] < 1 for v in r["checks"].values())
