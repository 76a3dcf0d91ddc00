"""The reference's own inputs, made independently of the program.

The timed path reads its batches from the program's stream
(``Trainer.stream``, built from ``DataConfig``: the traffic file's
``non_iid`` and ``non_iid_alpha`` and the run's ``--seed``).  The plain
reference must take nothing the program made, so this module draws the
same synthetic language batches by itself: per node, tokens from a
unigram that favours a node-specific band of the vocabulary (non-IID,
strength ``non_iid_alpha``); targets an affine map of the inputs with 15%
of them replaced at random; for an encoder, 15% of the positions masked.
``bench.py`` holds the program's batches of the compared steps to these,
element for element (``input_gap``).

Traffic file keys: ``seq_len``, ``global_batch``, ``non_iid``,
``non_iid_alpha``.
"""
from __future__ import annotations

from typing import Dict

import numpy as np

KEYS = ("seq_len", "global_batch", "non_iid", "non_iid_alpha")
NOISE = 0.15        # share of targets replaced at random
MASK = 0.15         # share of an encoder's positions masked


def check_traffic(traffic: Dict) -> Dict:
    unknown = sorted(set(traffic) - set(KEYS))
    if unknown:
        raise KeyError(f"traffic: unknown key(s) {unknown}")
    return traffic


class ReferenceBatches:
    """``get_batch(step)`` -> dict of ``(n_nodes, per_node, seq_len)``
    arrays: ``inputs`` and ``targets`` (int32) and, for an encoder,
    ``mask``."""

    def __init__(self, traffic: Dict, model: Dict, n_nodes: int, seed: int):
        check_traffic(traffic)
        if traffic["global_batch"] % n_nodes:
            raise ValueError(f"global batch {traffic['global_batch']} does "
                             f"not split over {n_nodes} nodes")
        self.vocab = int(model["vocab_size"])
        self.masked = model["family"] == "encoder"
        self.n_nodes = n_nodes
        self.per_node = traffic["global_batch"] // n_nodes
        self.seq_len = int(traffic["seq_len"])
        self.seed = int(seed)
        self._cdf = np.cumsum(self._node_probs(
            bool(traffic.get("non_iid", True)),
            float(traffic.get("non_iid_alpha", 0.5))), axis=-1)
        self._cdf /= self._cdf[:, -1:]

    def _node_probs(self, non_iid: bool, alpha: float) -> np.ndarray:
        n, v = self.n_nodes, self.vocab
        if not non_iid or n == 1:
            return np.full((n, v), 1.0 / v)
        rng = np.random.default_rng(self.seed)
        centers = rng.uniform(0, v, size=n)
        pos = np.arange(v)[None, :]
        dist = np.minimum(np.abs(pos - centers[:, None]),
                          v - np.abs(pos - centers[:, None]))
        logits = -alpha * (dist / (v / 4.0)) ** 2
        p = np.exp(logits - logits.max(-1, keepdims=True))
        return p / p.sum(-1, keepdims=True)

    def get_batch(self, step: int) -> Dict[str, np.ndarray]:
        rng = np.random.default_rng(np.random.SeedSequence([self.seed,
                                                            int(step)]))
        n, b, s, v = self.n_nodes, self.per_node, self.seq_len, self.vocab
        u = rng.random((n, b, s))
        toks = np.stack([np.searchsorted(self._cdf[i], u[i], side="right")
                         for i in range(n)]).astype(np.int32)
        tgt = (31 * toks.astype(np.int64) + 17) % v
        corrupt = rng.random(tgt.shape) < NOISE
        tgt = np.where(corrupt, rng.integers(0, v, tgt.shape), tgt)
        batch = {"inputs": toks, "targets": tgt.astype(np.int32)}
        if self.masked:
            batch["mask"] = rng.random(toks.shape) < MASK
        return batch


def input_gap(got: Dict[str, np.ndarray], want: Dict[str, np.ndarray]
              ) -> float:
    """Share of the elements of ``want`` that ``got`` differs in (1 where
    a key or a shape differs)."""
    if set(got) != set(want):
        return 1.0
    diff = total = 0
    for k, w in want.items():
        g = np.asarray(got[k])
        if g.shape != w.shape:
            return 1.0
        diff += int(np.sum(g != w))
        total += w.size
    return diff / max(total, 1)


def head_positions(batch: Dict[str, np.ndarray]) -> int:
    """Positions the output head is trained on in ``batch``."""
    if "mask" in batch:
        return int(np.sum(batch["mask"]))
    return int(np.asarray(batch["inputs"]).size)
