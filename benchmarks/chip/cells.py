"""Cells found by name: ``BENCHMARK.json`` names a cell's configuration and
traffic, and each lives in a file of its own under this directory.

* ``configs/<config>.json``: the model's sizes as run (keys named as
  ``repro.configs.ModelConfig`` names them), the program's arch id
  (``repro_arch``), the source, ``reduced``, ``assumed``, the deployment,
  and the plain reference (``reference``: a module under ``references/``).
* ``traffic/<traffic>.json``: the batch, the sequence length and the
  non-IID settings; they set ``TrainConfig`` and ``DataConfig``, which the
  program's stream reads, and the reference's own batches
  (``traffic.py``).
* ``workloads/<cell>.json``: ``chips``, ``n_nodes``, the ``limits`` of
  the numbers ``check.py`` compares, and sections
  ``train``, ``dist``, ``optimizer`` and ``data`` whose keys map by name
  onto ``TrainConfig``, ``DistConfig``, ``OptimizerConfig`` and
  ``DataConfig``.

A later cell, configuration or traffic mix is new files and new
``BENCHMARK.json`` entries; nothing here changes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from typing import Dict

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))

# ModelConfig keys a configuration file pins; the reference reads them too
MODEL_KEYS = ("family", "n_layers", "d_model", "n_heads", "n_kv_heads",
              "head_dim", "d_ff", "vocab_size", "causal", "tie_embeddings",
              "norm_eps", "rope_theta", "dtype", "param_dtype")
WORKLOAD_SECTIONS = ("train", "dist", "optimizer", "data")


def _load(path: str) -> Dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: str = ROOT) -> Dict:
    return _load(os.path.join(root, "BENCHMARK.json"))


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    n_nodes: int
    model: Dict          # configs/<config>.json
    traffic: Dict        # traffic/<traffic>.json
    workload: Dict       # workloads/<cell>.json
    entry: Dict          # the cell's entry in BENCHMARK.json


def load_cell(name: str, bench: Dict = None, base: str = HERE) -> Cell:
    bench = bench if bench is not None else benchmark()
    entries = {w["name"]: w for w in bench["workloads"]}
    if name not in entries:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(known: {sorted(entries)})")
    entry = entries[name]
    wl = _load(os.path.join(base, "workloads", f"{name}.json"))
    if wl["chips"] != entry["chips"]:
        raise ValueError(f"{name}: workloads/{name}.json asks for "
                         f"{wl['chips']} chips, BENCHMARK.json for "
                         f"{entry['chips']}")
    model = _load(os.path.join(base, "configs", f"{entry['config']}.json"))
    traffic = _load(os.path.join(base, "traffic",
                                 f"{entry['traffic']}.json"))
    return Cell(name=name, chips=wl["chips"], n_nodes=wl["n_nodes"],
                model=model, traffic=traffic, workload=wl, entry=entry)


def _replace(obj, fields: Dict, what: str):
    known = {f.name for f in dataclasses.fields(obj)}
    unknown = sorted(set(fields) - known)
    if unknown:
        raise KeyError(f"{what}: unknown field(s) {unknown}")
    return dataclasses.replace(obj, **fields)


def model_config(cell: Cell):
    """The program's ``ModelConfig`` for the cell, every size pinned to the
    configuration file's."""
    from repro.configs import get_model_config
    base = get_model_config(cell.model["repro_arch"])
    sizes = {k: cell.model[k] for k in MODEL_KEYS}
    return _replace(base, sizes, f"configs/{cell.entry['config']}.json")


def train_config(cell: Cell):
    """``TrainConfig`` of the cell: each workload section's keys set the
    field of that name; the traffic sets the batch and sequence length."""
    from repro.configs import (DataConfig, DistConfig, OptimizerConfig,
                               TrainConfig)
    wl = cell.workload
    extra = sorted(set(wl) - set(WORKLOAD_SECTIONS)
                   - {"chips", "n_nodes", "limits"})
    if extra:
        raise KeyError(f"workloads/{cell.name}.json: unknown key(s) {extra}")
    dist = _replace(DistConfig(), wl.get("dist", {}), "dist").validate()
    opt = _replace(OptimizerConfig(), wl.get("optimizer", {}), "optimizer")
    data = _replace(DataConfig(), wl.get("data", {}), "data")
    data = dataclasses.replace(
        data, non_iid=bool(cell.traffic.get("non_iid", data.non_iid)),
        non_iid_alpha=float(cell.traffic.get("non_iid_alpha",
                                             data.non_iid_alpha)))
    tcfg = TrainConfig(model=model_config(cell), dist=dist, optimizer=opt,
                       data=data,
                       global_batch=int(cell.traffic["global_batch"]),
                       seq_len=int(cell.traffic["seq_len"]))
    return _replace(tcfg, wl.get("train", {}), "train")


def reference_module(cell: Cell):
    """The configuration's plain reference, ``references/<name>.py``."""
    path = os.path.join(HERE, "references", f"{cell.model['reference']}.py")
    spec = importlib.util.spec_from_file_location(
        f"chip_reference_{cell.model['reference']}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str):
    """The per-layer metric's reader, ``metrics/<name>.py``."""
    path = os.path.join(HERE, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        "chip_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
