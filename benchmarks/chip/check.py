"""The comparison that decides ``correct``.

The compared steps are a PGA period's last three: two gossip steps (the
one-peer graph's two shifts) and the global average.  The plain
reference follows them from the same weights and from batches it draws
itself; these numbers are read on the training path and on it:

* ``loss_gap``: the largest relative gap of a step's loss (the mean of
  the nodes' losses) over the three steps;
* ``grad_gap``: by the worst (leaf, node) pair, the gap between the norms
  of the first gradient as the optimizer got it, read from the optimizer
  state after one step (AdamW's and LAMB's first moment is then
  ``(1 - b1)`` times the clipped gradient);
* ``gossip_gap``: by the worst (leaf, node) pair, the gap between the
  norms of the parameters' change over the two gossip steps, read before
  the global average (which keeps no trace of a gossip round: it takes
  the node mean, and gossip keeps the node mean);
* ``change_gap``: by the worst (leaf, node) pair, the gap between the
  norms of the parameters' change over the three steps;
* ``input_gap``: the share of the elements of the program's batches for
  the three steps that differ from the reference's own.

A norm gap is ``|program - reference|`` over the reference's norm of that
pair or of the median pair, whichever is larger.  Pairs whose reference
gradient is below a thousandth of the median pair's move by round-off
alone and are left out of ``gossip_gap`` and ``change_gap``.
"""
from __future__ import annotations

import math
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

NUMBERS = ("loss_gap", "grad_gap", "gossip_gap", "change_gap",
           "input_gap")
STILL = 1e-3      # a reference gradient under this share of the median's


def lr_at(opt: Dict, step: int) -> float:
    """The learning rate of ``step`` under the schedule the workload
    states (linear warmup, then constant or half-cosine decay)."""
    base, warm = float(opt["lr"]), int(opt.get("warmup_steps", 0))
    if warm and step < warm:
        return base * (step + 1) / warm
    if opt["schedule"] == "constant":
        return base
    if opt["schedule"] != "warmup_cosine":
        raise ValueError(f"no plain schedule {opt['schedule']!r}")
    total = max(int(opt["total_steps"]), warm + 1)
    t = min(max((step - warm) / max(total - warm, 1), 0.0), 1.0)
    floor = float(opt.get("min_lr_ratio", 0.0)) * base
    return floor + (base - floor) * 0.5 * (1 + math.cos(math.pi * t))


def pair_norms(tree) -> jax.Array:
    """``(leaves, nodes)`` float32 norms of a node-stacked tree."""
    return jnp.stack([
        jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)).reshape(
            x.shape[0], -1), axis=1)) for x in jax.tree.leaves(tree)])


def change_norms(params, params0) -> jax.Array:
    """Norms of ``params - params0`` per (leaf, node); ``params0`` is one
    node's tree, broadcast over the node axis."""
    return pair_norms(jax.tree.map(
        lambda x, x0: x.astype(jnp.float32) - x0.astype(jnp.float32)[None],
        params, params0))


def _norm_gap(got: np.ndarray, want: np.ndarray,
              keep: np.ndarray = None) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    scale = np.maximum(want, np.median(want))
    gap = np.abs(got - want) / np.maximum(scale, 1e-30)
    if keep is not None:
        gap = gap[keep]
    return float(gap.max()) if gap.size else 0.0


def numbers(prog: Dict, ref: Dict) -> Dict[str, float]:
    """The compared numbers from two readings, each a dict with ``losses``
    (3,), ``m_norms``, ``gossip_norms`` and ``change_norms`` ((leaves,
    nodes)); the program's may carry ``input_gap`` (0 where absent)."""
    lp = np.asarray(prog["losses"], np.float64)
    lr = np.asarray(ref["losses"], np.float64)
    if not (np.all(np.isfinite(lp)) and np.all(np.isfinite(lr))):
        loss_gap = math.inf
    else:
        loss_gap = float(np.max(np.abs(lp - lr) / np.abs(lr)))
    g_ref = np.asarray(ref["m_norms"], np.float64)
    keep = g_ref >= STILL * np.median(g_ref)
    out = {"loss_gap": loss_gap,
           "grad_gap": _norm_gap(prog["m_norms"], g_ref),
           "gossip_gap": _norm_gap(prog["gossip_norms"],
                                   ref["gossip_norms"], keep),
           "change_gap": _norm_gap(prog["change_norms"],
                                   ref["change_norms"], keep),
           "input_gap": float(prog.get("input_gap", 0.0))}
    return {k: (v if math.isfinite(v) else math.inf) for k, v in out.items()}


def _ok(value: float, limit: Optional[float]) -> bool:
    """A number passes under its limit; one whose limit is None is read
    and reported but not compared (no fault or control separates it from
    sound runs), though it still has to be finite."""
    return math.isfinite(value) and (limit is None or value <= limit)


def verdict(nums: Dict[str, float], limits: Dict[str, float]) -> bool:
    missing = sorted(set(NUMBERS) - set(limits))
    if missing:
        raise KeyError(f"no limit for {missing}")
    return all(_ok(nums[k], limits[k]) for k in NUMBERS)


def report(nums: Dict[str, float], limits: Dict[str, float]) -> Dict:
    """The compared numbers beside their limits, for the result line; a
    number that is not finite is given as null (JSON has no infinity)."""
    return {k: {"value": nums[k] if math.isfinite(nums[k]) else None,
                "limit": limits[k]} for k in NUMBERS}


def lines(nums: Dict[str, float], limits: Dict[str, float]) -> List[str]:
    return [f"check {k}: {nums[k]!r} limit {limits[k]!r} "
            f"{'ok' if _ok(nums[k], limits[k]) else 'FAILED'}"
            for k in NUMBERS]
