"""Every cell resolves from its files alone; a new cell is new files."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from chip import cells, check

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
BENCH = cells.benchmark(ROOT)
CELLS = [w["name"] for w in BENCH["workloads"]]


def test_every_workload_file_is_a_cell_of_the_benchmark():
    files = sorted(f[:-5] for f in os.listdir(os.path.join(HERE, "workloads"))
                   if f.endswith(".json"))
    assert files == sorted(CELLS)


@pytest.mark.parametrize("name", CELLS)
def test_workload_resolves_to_a_train_config(name):
    from repro.configs import TrainConfig
    cell = cells.load_cell(name, BENCH)
    tcfg = cells.train_config(cell)
    assert isinstance(tcfg, TrainConfig)
    assert tcfg.global_batch == cell.traffic["global_batch"]
    assert tcfg.model.n_layers == cell.model["n_layers"]
    assert cell.n_nodes % cell.chips == 0
    assert cell.traffic["global_batch"] % cell.n_nodes == 0
    assert set(cell.workload["limits"]) == set(check.NUMBERS)
    ref = cells.reference_module(cell)
    assert ref.param_shapes(cell.model)


def test_every_metric_has_its_reader_and_every_config_its_file():
    for m in BENCH["per_layer"]:
        assert callable(cells.metric_reader(m["name"]))
    for c in BENCH["configs"]:
        assert os.path.isfile(os.path.join(ROOT, c["file"]))


def test_a_new_cell_is_only_new_files(tmp_path):
    """A later PR adds a configuration, a traffic mix and a workload as
    files and entries; the harness resolves them without an edit."""
    for sub in ("configs", "traffic", "workloads"):
        shutil.copytree(os.path.join(HERE, sub), tmp_path / sub)
    with open(tmp_path / "traffic" / "lm-s2048-b8.json", "w") as f:
        json.dump({"seq_len": 2048, "global_batch": 8}, f)
    wl = json.load(open(os.path.join(HERE, "workloads",
                                     "lm100m-pga-1chip.json")))
    wl["dist"]["comm_overlap"] = True
    wl["optimizer"]["lr"] = 1e-3
    with open(tmp_path / "workloads" / "lm100m-overlap-1chip.json",
              "w") as f:
        json.dump(wl, f)
    bench = dict(BENCH, workloads=BENCH["workloads"] + [
        {"name": "lm100m-overlap-1chip", "config": "pga-lm-100m",
         "traffic": "lm-s2048-b8", "chips": 1, "why": "a later cell"}])
    cell = cells.load_cell("lm100m-overlap-1chip", bench, base=str(tmp_path))
    tcfg = cells.train_config(cell)
    assert tcfg.dist.comm_overlap and tcfg.seq_len == 2048
    assert tcfg.optimizer.lr == 1e-3


def test_an_unknown_workload_field_is_refused(tmp_path):
    cell = cells.load_cell(CELLS[0], BENCH)
    cell.workload = dict(cell.workload, dist={"no_such_knob": 1})
    with pytest.raises(KeyError, match="no_such_knob"):
        cells.train_config(cell)


def test_without_a_tpu_the_command_fails_and_prints_nothing(capsys):
    from chip import run
    rc = run.main(["--workload", CELLS[0], "--seed", "1", "--seconds", "1",
                   "--trace", "0"])
    out = capsys.readouterr()
    assert rc != 0 and out.out == ""
    assert "no TPU" in out.err


def test_without_the_program_the_command_fails(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(HERE, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run([sys.executable, "benchmarks/chip/run.py",
                        "--workload", CELLS[0], "--seed", "1",
                        "--seconds", "1", "--trace", "0"],
                       cwd=tmp_path, capture_output=True, text=True,
                       timeout=120)
    assert p.returncode != 0 and p.stdout == ""
