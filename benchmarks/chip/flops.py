"""Operation and byte counts from shapes, for MFU and roofline shares.

Counts are the model's own arithmetic: two operations per multiply-add of
every matrix product of the forward pass, times three for forward plus
backward.  Recomputation (remat) is not counted, and neither are norms,
softmax or element-wise work.  Causal attention counts only the positions
a query may see, ``(S + 1) / 2`` on average.  A masked-LM head counts only
the masked positions it is trained on.
"""
from __future__ import annotations

import json
import os
from typing import Dict

HERE = os.path.dirname(os.path.abspath(__file__))


def body_flops_per_token(cfg: Dict, seq_len: int) -> float:
    """Forward-pass operations per token of the transformer stack (no
    output head): projections, attention scores and values, and the
    gated MLP's three matrices."""
    d, nh, nkv = cfg["d_model"], cfg["n_heads"], cfg["n_kv_heads"]
    hd, ff, layers = cfg["head_dim"], cfg["d_ff"], cfg["n_layers"]
    proj = 2 * d * hd * (nh + 2 * nkv) + 2 * nh * hd * d
    mlp = 2 * 3 * d * ff
    seen = (seq_len + 1) / 2 if cfg["causal"] else seq_len
    attn = 2 * 2 * nh * hd * seen
    return float(layers * (proj + mlp + attn))


def head_flops_per_token(cfg: Dict) -> float:
    """Forward-pass operations per position the output head scores."""
    return float(2 * cfg["d_model"] * cfg["vocab_size"])


def train_flops(cfg: Dict, seq_len: int, tokens: int,
                head_tokens: int) -> float:
    """Forward plus backward operations for ``tokens`` positions of which
    ``head_tokens`` go through the output head."""
    fwd = (tokens * body_flops_per_token(cfg, seq_len)
           + head_tokens * head_flops_per_token(cfg))
    return 3.0 * fwd


def param_count(cfg: Dict) -> int:
    """Parameters per node of the dense transformer layout."""
    d, nh, nkv = cfg["d_model"], cfg["n_heads"], cfg["n_kv_heads"]
    hd, ff, layers, v = (cfg["head_dim"], cfg["d_ff"], cfg["n_layers"],
                         cfg["vocab_size"])
    per_layer = (2 * d + d * hd * (nh + 2 * nkv) + nh * hd * d
                 + 3 * d * ff)
    total = layers * per_layer + v * d + d
    if not cfg["tie_embeddings"]:
        total += d * v
    if not cfg["causal"]:
        total += d          # the mask embedding
    return total


def round_bytes(n_nodes: int, params_per_node: int,
                comm_itemsize: int) -> int:
    """Bytes one mixing round must move: read and write the node-stacked
    ``(n, D)`` state once, in the wire dtype."""
    return 2 * n_nodes * params_per_node * comm_itemsize


def load_peaks(device_kind: str, path: str = None) -> Dict[str, float]:
    """Peak rates of one chip of ``device_kind``; a kind missing from the
    table is an error, never a default."""
    with open(path or os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    kinds = table["kinds"]
    if device_kind not in kinds:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"peaks.json (known: {sorted(kinds)})")
    return {k: float(v) for k, v in kinds[device_kind].items()}
