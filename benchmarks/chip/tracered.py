"""Reduction of a JAX profiler trace to the facts the per-layer metrics read.

``load_xplane`` keeps, from an ``.xplane.pb``, only what the reduction
needs, as plain dicts (the form ``testdata/`` records):

* ``ops``: each event of a TPU's ``XLA Ops`` line: device index, short
  name (the HLO instruction name), start and end in ns, whether it is a
  collective, and whether it is a Pallas kernel (``tpu_custom_call``) with
  its first result shape;
* ``modules``: each event of a TPU's ``XLA Modules`` line (one program
  execution): device, name, start, end;
* ``host``: the benchmark's own annotations (``train/step`` with its
  ``step`` and ``phase``, ``host.input``, ``host.fetch``) and the
  ``PjitFunction(<fn>)`` dispatch events, from the host's planes.

``reduce_trace`` maps the i-th execution of the train-step program on each
device to the i-th ``train/step`` annotation, so each device op gets the
phase of the step whose program it runs in.  Where the counts or the
programs disagree, collectives fall back to their op type
(collective-permute: gossip; all-reduce: global) and ``attribution`` says
so.
"""
from __future__ import annotations

import bisect
import re
from typing import Dict, List, Optional, Tuple

COLLECTIVE_RE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all"
    r"|collective-broadcast|send|recv)")
_SHAPE_RE = re.compile(r"=\s*\(?\s*([a-z0-9]+\[[0-9,]*\])")
_DEVICE_RE = re.compile(r"^/device:TPU:(\d+)$")
HOST_ANNOTATIONS = ("train/step", "host.input", "host.fetch")


def _short(text: str) -> str:
    name = text.split(" = ", 1)[0].strip()
    return name[1:] if name.startswith("%") else name


def op_record(dev: int, text: str, start: float, end: float) -> Dict:
    name = _short(text)
    rec = {"dev": dev, "name": name, "start": start, "end": end,
           "coll": bool(COLLECTIVE_RE.match(name))}
    if 'custom_call_target="tpu_custom_call"' in text:
        m = _SHAPE_RE.search(text)
        rec["kernel"] = m.group(1) if m else ""
    return rec


def load_xplane(path: str) -> Dict[str, List[Dict]]:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ops, modules, host = [], [], []
    for plane in pd.planes:
        m = _DEVICE_RE.match(plane.name)
        if m:
            dev = int(m.group(1))
            for line in plane.lines:
                if line.name == "XLA Ops":
                    for e in line.events:
                        ops.append(op_record(dev, e.name, e.start_ns,
                                             e.end_ns))
                elif line.name == "XLA Modules":
                    for e in line.events:
                        modules.append({"dev": dev, "name": e.name,
                                        "start": e.start_ns,
                                        "end": e.end_ns})
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in HOST_ANNOTATIONS:
                        args = {k: v for k, v in e.stats
                                if k in ("step", "phase")}
                        host.append({"name": e.name, "start": e.start_ns,
                                     "end": e.end_ns, "args": args})
                    elif e.name.startswith("PjitFunction("):
                        host.append({"name": e.name, "start": e.start_ns,
                                     "end": e.end_ns, "args": {}})
    return {"ops": ops, "modules": modules, "host": host}


# ---------------------------------------------------------------------------
# Interval arithmetic
# ---------------------------------------------------------------------------
def union(intervals) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: float, hi: float) -> List[Tuple[float, float]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def total(intervals) -> float:
    return sum(e - s for s, e in intervals)


def subtract(a: Tuple[float, float], covered: List[Tuple[float, float]],
             starts: List[float]) -> float:
    """Length of interval ``a`` not covered by the sorted disjoint
    ``covered`` (``starts`` are their start points)."""
    s, e = a
    left = e - s
    i = max(bisect.bisect_right(starts, s) - 1, 0)
    while i < len(covered) and covered[i][0] < e:
        cs, ce = covered[i]
        left -= max(0.0, min(e, ce) - max(s, cs))
        i += 1
    return left


def gaps(busy: List[Tuple[float, float]], lo: float, hi: float
         ) -> List[Tuple[float, float]]:
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


# ---------------------------------------------------------------------------
# The reduction
# ---------------------------------------------------------------------------
def _step_program_names(host: List[Dict]) -> set:
    """Module names of the train-step program: ``jit_<fn>`` for every
    ``PjitFunction(<fn>)`` dispatched inside a ``train/step`` span."""
    spans = [h for h in host if h["name"] == "train/step"]
    names = set()
    for p in host:
        if not p["name"].startswith("PjitFunction("):
            continue
        if any(s["start"] <= p["start"] <= s["end"] for s in spans):
            names.add("jit_" + p["name"][len("PjitFunction("):-1])
    return names or {"jit_step"}


def reduce_trace(tr: Dict[str, List[Dict]]) -> Dict:
    """Steps, window, per-device busy time, idle gaps and per-phase
    collective exposure of one traced stretch of training steps."""
    steps = sorted((h for h in tr["host"] if h["name"] == "train/step"),
                   key=lambda h: h["start"])
    if not steps:
        raise ValueError("trace holds no train/step annotation")
    prog = _step_program_names(tr["host"])
    devices = sorted({o["dev"] for o in tr["ops"]}
                     | {m["dev"] for m in tr["modules"]})
    if not devices:
        raise ValueError("trace holds no TPU device events")
    attribution = "program"
    step_mods: Dict[int, List[Dict]] = {}
    for d in devices:
        mods = sorted((m for m in tr["modules"] if m["dev"] == d
                       and m["name"].split("(", 1)[0] in prog),
                      key=lambda m: m["start"])
        step_mods[d] = mods
        if len(mods) != len(steps):
            attribution = "op_type"
    if attribution == "program":
        by_prog: Dict[str, str] = {}
        for d in devices:
            for m, s in zip(step_mods[d], steps):
                ph = str(s["args"].get("phase", ""))
                if by_prog.setdefault(m["name"], ph) != ph:
                    attribution = "op_type"
    lo = steps[0]["start"]
    hi = max((m["end"] for d in devices for m in step_mods[d]),
             default=max(o["end"] for o in tr["ops"]))
    busy, exposed, idle = {}, {}, []
    for d in devices:
        ops = [o for o in tr["ops"] if o["dev"] == d]
        b = union(clip([(o["start"], o["end"]) for o in ops], lo, hi))
        busy[d] = total(b)
        compute = union((o["start"], o["end"]) for o in ops
                        if not o["coll"])
        cstarts = [c[0] for c in compute]
        per_phase: Dict[str, float] = {}
        for o in ops:
            if not o["coll"] or o["end"] <= lo or o["start"] >= hi:
                continue
            ph = _phase_of(o, step_mods[d], steps, attribution)
            per_phase[ph] = per_phase.get(ph, 0.0) + subtract(
                (o["start"], o["end"]), compute, cstarts)
        exposed[d] = per_phase
        if d == devices[0]:
            idle = gaps(b, lo, hi)
    labelled = sorted(((_host_label(tr["host"], (s + e) / 2), e - s)
                       for s, e in idle), key=lambda x: -x[1])
    phases = [str(s["args"].get("phase", "")) for s in steps]
    return {"devices": devices, "window_ns": (lo, hi),
            "busy_ns": busy, "idle_gaps_ns": labelled,
            "exposed_coll_ns": exposed, "attribution": attribution,
            "step_phases": phases, "step_modules": step_mods,
            "steps": steps}


def _phase_of(op: Dict, mods: List[Dict], steps: List[Dict],
              attribution: str) -> str:
    if attribution == "program":
        starts = [m["start"] for m in mods]
        i = bisect.bisect_right(starts, op["start"]) - 1
        if 0 <= i < len(mods) and op["start"] < mods[i]["end"]:
            return str(steps[i]["args"].get("phase", ""))
        return "outside"
    if op["name"].startswith("collective-permute"):
        return "gossip"
    if op["name"].startswith("all-reduce"):
        return "global"
    return "other"


def _host_label(host: List[Dict], t: float) -> str:
    """The innermost benchmark annotation open at ``t``, or "none"."""
    best = None
    for h in host:
        if h["name"] in HOST_ANNOTATIONS and h["start"] <= t < h["end"]:
            if best is None or h["start"] >= best["start"]:
                best = h
    return best["name"] if best else "none"


def top_ops(tr: Dict[str, List[Dict]], window: Tuple[float, float],
            k: int = 10) -> List[Tuple[str, float]]:
    """The ``k`` device ops with the most time in ``window``, in seconds
    per device; only ops not nested inside another op are counted, so a
    loop counts once and not again through its body."""
    lo, hi = window
    devices = sorted({o["dev"] for o in tr["ops"]}) or [0]
    acc: Dict[str, float] = {}
    for d in devices:
        end = -1.0
        for o in sorted((o for o in tr["ops"] if o["dev"] == d),
                        key=lambda o: (o["start"], -o["end"])):
            if o["start"] < end:
                continue
            end = o["end"]
            s, e = max(o["start"], lo), min(o["end"], hi)
            if e > s:
                acc[o["name"]] = acc.get(o["name"], 0.0) + (e - s)
    return sorted(((n, v * 1e-9 / len(devices)) for n, v in acc.items()),
                  key=lambda x: -x[1])[:k]


def step_kernel_time(tr: Dict[str, List[Dict]], red: Dict,
                     match) -> Tuple[float, int]:
    """Summed device time (ns, over devices) of Pallas kernel ops inside
    train-step programs for which ``match(op)`` holds, and the number of
    (device, step) programs that ran at least one."""
    t, rounds = 0.0, 0
    for d in red["devices"]:
        mods = red["step_modules"][d]
        starts = [m["start"] for m in mods]
        hit = set()
        for o in tr["ops"]:
            if o["dev"] != d or "kernel" not in o or not match(o):
                continue
            i = bisect.bisect_right(starts, o["start"]) - 1
            if 0 <= i < len(mods) and o["start"] < mods[i]["end"]:
                t += o["end"] - o["start"]
                hit.add(i)
        rounds += len(hit)
    return t, rounds


def exposed_ms(red: Dict, phase: str) -> Optional[float]:
    """Exposed collective time of ``phase``'s steps in ms per such step,
    averaged over the chips; None where no collective ran in that phase."""
    n_steps = sum(1 for p in red["step_phases"] if p == phase)
    per_dev = [e[phase] for e in red["exposed_coll_ns"].values()
               if phase in e]
    if not n_steps or not per_dev:
        return None
    return sum(per_dev) / len(red["exposed_coll_ns"]) * 1e-6 / n_steps
