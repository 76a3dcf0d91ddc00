"""Three-term roofline analysis from compiled dry-run artifacts.

  compute term    = HLO_FLOPs / peak_FLOP/s           (per chip)
  memory term     = HLO_bytes / HBM_bw                (per chip)
  collective term = collective_bytes / link_bw        (per chip)

``cost_analysis()`` runs on the post-SPMD per-device module, so FLOPs/bytes
are already per chip.  Collective bytes are not in cost_analysis — we parse
the optimized HLO and sum operand shard sizes of every all-gather /
all-reduce / reduce-scatter / all-to-all / collective-permute.

Hardware constants: TPU v5e — 197 TFLOP/s bf16, 819 GB/s HBM,
~50 GB/s/link ICI.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Any, Dict, Optional

PEAK_FLOPS = 197e12          # bf16 per chip
HBM_BW = 819e9               # bytes/s per chip
ICI_BW = 50e9                # bytes/s per link

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2,
    "s32": 4, "u32": 4, "s64": 8, "u64": 8, "f8e4m3fn": 1, "f8e5m2": 1,
    "bf16": 2, "f16": 2, "f32": 4, "f64": 8, "c64": 8, "c128": 16,
    "token": 0, "opaque": 0,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

# e.g.  %x = bf16[16,3584]{1,0} all-gather(...)
_OP_RE = re.compile(
    r"=\s*(?:\(([^)]*)\)|([a-z0-9\[\],{}\s]*?))\s*"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\(", re.IGNORECASE)

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")


def _shape_bytes(shape_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(shape_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                if d:
                    n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> Dict[str, Any]:
    """Per-collective-type byte counts (result shard sizes) + op counts.
    ``-start`` ops are counted, ``-done`` duplicates skipped."""
    per_type: Dict[str, int] = {c: 0 for c in _COLLECTIVES}
    counts: Dict[str, int] = {c: 0 for c in _COLLECTIVES}
    for line in hlo_text.splitlines():
        if "-done(" in line:
            continue
        m = _OP_RE.search(line)
        if not m:
            continue
        shape_str = m.group(1) or m.group(2) or ""
        kind = m.group(3).lower()
        b = _shape_bytes(shape_str)
        per_type[kind] += b
        counts[kind] += 1
    total = sum(per_type.values())
    return {"total_bytes": total, "per_type_bytes": per_type,
            "counts": counts}


@dataclasses.dataclass
class Roofline:
    flops: float
    hlo_bytes: float
    coll_bytes: float
    coll_detail: Dict[str, Any]
    compute_s: float
    memory_s: float
    collective_s: float
    model_flops: Optional[float] = None

    @property
    def dominant(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def bound_time_s(self) -> float:
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def useful_flops_ratio(self) -> Optional[float]:
        if self.model_flops is None or self.flops == 0:
            return None
        return self.model_flops / self.flops

    def to_dict(self) -> Dict[str, Any]:
        return {
            "flops": self.flops, "hlo_bytes": self.hlo_bytes,
            "coll_bytes": self.coll_bytes,
            "coll_per_type": self.coll_detail.get("per_type_bytes"),
            "coll_counts": self.coll_detail.get("counts"),
            "compute_s": self.compute_s, "memory_s": self.memory_s,
            "collective_s": self.collective_s, "dominant": self.dominant,
            "model_flops": self.model_flops,
            "useful_flops_ratio": self.useful_flops_ratio,
        }


def raw_costs(compiled) -> Dict[str, float]:
    cost = compiled.cost_analysis() or {}
    coll = collective_bytes(compiled.as_text())
    return {"flops": float(cost.get("flops", 0.0)),
            "bytes": float(cost.get("bytes accessed", 0.0)),
            "coll": float(coll["total_bytes"]),
            "coll_detail": coll}


def from_costs(costs: Dict[str, float], *,
               model_flops: Optional[float] = None) -> Roofline:
    return Roofline(
        flops=costs["flops"], hlo_bytes=costs["bytes"],
        coll_bytes=costs["coll"],
        coll_detail=costs.get("coll_detail", {}),
        compute_s=costs["flops"] / PEAK_FLOPS,
        memory_s=costs["bytes"] / HBM_BW,
        collective_s=costs["coll"] / ICI_BW,
        model_flops=model_flops,
    )


def from_compiled(compiled, *, model_flops: Optional[float] = None
                  ) -> Roofline:
    return from_costs(raw_costs(compiled), model_flops=model_flops)


def scan_corrected_costs(costs_1rep: Dict[str, float],
                         costs_2rep: Dict[str, float],
                         n_reps: int) -> Dict[str, float]:
    """XLA's cost_analysis counts a while-loop (lax.scan) body ONCE regardless
    of trip count, so scanned-layer programs under-report flops/bytes/
    collectives by ~n_reps.  Correct by lowering 1-rep and 2-rep depth
    variants: per-rep cost = c2 − c1; total = c1 + (R−1)·(c2 − c1)."""
    out = {}
    for k in ("flops", "bytes", "coll"):
        per_rep = max(costs_2rep[k] - costs_1rep[k], 0.0)
        out[k] = costs_1rep[k] + (n_reps - 1) * per_rep
    out["coll_detail"] = {
        "total_bytes": out["coll"],
        "per_type_bytes": {
            k: costs_1rep["coll_detail"]["per_type_bytes"].get(k, 0)
            + (n_reps - 1) * max(
                costs_2rep["coll_detail"]["per_type_bytes"].get(k, 0)
                - costs_1rep["coll_detail"]["per_type_bytes"].get(k, 0), 0)
            for k in costs_1rep["coll_detail"]["per_type_bytes"]},
        "counts": costs_1rep["coll_detail"]["counts"],
    }
    return out


# ---------------------------------------------------------------------------
# MODEL_FLOPS = 6·N·D (dense) or 6·N_active·D (MoE) per train step;
# 2·N·D forward-only (prefill); 2·N_active per token (decode).
# ---------------------------------------------------------------------------
def count_params(cfg, active_only: bool = False) -> int:
    """Analytic parameter count from the config (no allocation)."""
    d, V = cfg.d_model, cfg.vocab_size
    total = V * d  # embedding
    if not cfg.tie_embeddings:
        total += d * V
    for mixer, ffn in cfg.layers:
        total += 2 * d  # norms (approx; post-norms ignored)
        if mixer in ("attn", "attn_sw"):
            if cfg.mla is not None:
                m = cfg.mla
                qd = m.nope_head_dim + m.rope_head_dim
                total += d * cfg.n_heads * qd
                total += d * m.kv_lora_rank + d * m.rope_head_dim
                total += m.kv_lora_rank * cfg.n_heads * (
                    m.nope_head_dim + m.v_head_dim)
                total += cfg.n_heads * m.v_head_dim * d
            else:
                hd = cfg.resolved_head_dim
                total += d * cfg.n_heads * hd * 2  # q, o
                total += d * cfg.n_kv_heads * hd * 2  # k, v
        elif mixer == "mamba":
            s = cfg.ssm
            di = s.expand * d
            R = s.dt_rank or max(d // 16, 1)
            total += d * 2 * di + di * (R + 2 * s.d_state) + R * di \
                + di * s.d_state + 2 * di + di * d
        elif mixer == "mlstm":
            s = cfg.ssm
            di = s.mlstm_expand * d
            nh = max(di // (2 * s.mlstm_head_dim), 1)
            dk = s.mlstm_head_dim
            total += d * 2 * di + di * nh * dk * 2 + di * (di // nh) * nh \
                + 2 * di * nh + di * d
        elif mixer == "slstm":
            total += d * 4 * d + 4 * d * (d // cfg.ssm.slstm_heads) + d * d
        if ffn == "dense":
            total += 3 * d * cfg.d_ff
        elif ffn == "moe":
            m = cfg.moe
            n_e = m.top_k if active_only else m.n_routed
            total += d * m.n_routed  # router (always dense compute)
            total += n_e * 3 * d * m.d_ff_expert
            total += m.n_shared * 3 * d * m.d_ff_expert
    return int(total)


def model_flops_for(cfg, shape, n_chips: int) -> float:
    """Per-chip MODEL_FLOPS for one step of the given input shape."""
    n_active = count_params(cfg, active_only=True)
    tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode"
                                   else 1)
    mult = 6 if shape.kind == "train" else 2
    return mult * n_active * tokens / n_chips
