"""Split a traced train step's device time by the program's named scopes.

The train step (``repro.train.step``) runs each op under one of four
sibling ``jax.named_scope`` s: ``fwd_bwd``, ``optimizer``, ``monitor`` and
``round``.  A scope is the op's HLO ``op_name`` metadata only.  The
trainer's host loop opens the program spans ``train/input`` and
``train/log``, which ``repro.obs.Tracer`` also writes as profiler
annotations.

``load_xplane`` reads a profiler trace as ``tracered.load_xplane`` does and
adds two things: each op record gets ``scope``, the first of the four
scopes in its ``op_name`` (``""`` for none), and the host records gain the
program spans.  On a TPU v5e an ``XLA Ops`` event carries no ``op_name``
of its own (its name is the HLO text without metadata, its stats only
offset and duration): the ``op_name`` is the ``tf_op`` stat of the
event's *metadata*, which ``jax.profiler.ProfileData`` does not expose,
so ``op_names`` reads it from the XSpace proto.

``scope_time`` sums one scope's device time inside the train-step programs
and ``split`` gives the per-step numbers that per-layer metrics would
report: ``fwd_bwd_ms``, ``optimizer_ms``, ``monitor_ms``,
``round_ms.gossip``, ``round_ms.global``, ``unscoped_ms`` and
``train_input_ms_per_step``.  Each reads ``None`` where no op of the step
programs carries a scope, as when the executable came from a persistent
compile-cache entry written before the scopes existed (the cache key
leaves debug information out).

Run as a script, it measures one cell on the chip: the benchmark's set-up,
an untraced window of two PGA periods, then a traced one, and prints one
JSON line with the split, the step programs' busy time, the largest ops of
each scope, and the median step interval of both windows::

    python3 benchmarks/chip/scopes.py --workload <cell> --seed <n> \\
        [--record <path>]

``--record`` writes one PGA period of the traced window (its top-level
ops, programs and host records) as a recorded trace for the tests.
"""
from __future__ import annotations

import bisect
import os
import re
import sys
from typing import Dict, List, Optional, Tuple

if __package__ in (None, ""):
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from chip import tracered  # noqa: E402

SCOPES = ("fwd_bwd", "optimizer", "monitor", "round")
PROGRAM_SPANS = ("train/input", "train/log")
_SCOPE_SPLIT_RE = re.compile(r"[/;]")


def op_scope(op_name: str) -> str:
    """The first of ``SCOPES`` among the path components of ``op_name``
    (an op XLA merged from several carries them joined by ``;``)."""
    for part in _SCOPE_SPLIT_RE.split(op_name):
        if part in SCOPES:
            return part
    return ""


# ---------------------------------------------------------------------------
# Event metadata: ``ProfileData`` gives an event's own stats only, and an
# XLA op's ``tf_op`` is a stat of its event *metadata*.  The XSpace proto
# is read here at the wire level, skipping every line's events.
# ---------------------------------------------------------------------------
def _varint(buf, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """(field number, value) of one serialized message: an int for a
    varint, the bytes of anything else."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"protobuf wire type {wire} not supported")
        yield key >> 3, value


def _map_value(entry):
    return next((v for f, v in _fields(entry) if f == 2), b"")


def op_names(xspace: bytes) -> Dict[str, Dict[str, str]]:
    """For each plane (by name), each event metadata's name and display
    name mapped to the value of its ``tf_op`` stat, the op's ``op_name``."""
    buf = memoryview(xspace)
    out: Dict[str, Dict[str, str]] = {}
    for f, plane in _fields(buf):
        if f != 1:                                  # XSpace.planes
            continue
        name, stat_names, events = "", {}, []
        for pf, pv in _fields(plane):
            if pf == 2:                             # XPlane.name
                name = bytes(pv).decode()
            elif pf == 5:                           # XPlane.stat_metadata
                md = dict(_fields(_map_value(pv)))
                stat_names[md.get(1, 0)] = bytes(md.get(2, b"")).decode()
            elif pf == 4:                           # XPlane.event_metadata
                events.append(_map_value(pv))
        want = {i for i, n in stat_names.items() if n == "tf_op"}
        if not want:
            continue
        found: Dict[str, str] = {}
        for em in events:
            names, value = [], None
            for ef, ev in _fields(em):
                if ef in (2, 4):                    # name, display_name
                    names.append(bytes(ev).decode())
                elif ef == 5:                       # XEventMetadata.stats
                    st = dict(_fields(ev))
                    if st.get(1) not in want:
                        continue
                    if 5 in st:                     # str_value
                        value = bytes(st[5]).decode()
                    elif 7 in st:                   # ref_value
                        value = stat_names.get(st[7], "")
            if value:
                for n in names:
                    found[n] = value
        out[name] = found
    return out


def load_xplane(path: str) -> Dict[str, List[Dict]]:
    from jax.profiler import ProfileData
    with open(path, "rb") as f:
        raw = f.read()
    pd = ProfileData.from_serialized_xspace(raw)
    named_by_plane = op_names(raw)
    keep = tracered.HOST_ANNOTATIONS + PROGRAM_SPANS
    ops, modules, host = [], [], []
    for plane in pd.planes:
        m = tracered._DEVICE_RE.match(plane.name)
        if m:
            dev = int(m.group(1))
            named = named_by_plane.get(plane.name, {})
            for line in plane.lines:
                if line.name == "XLA Ops":
                    for e in line.events:
                        rec = tracered.op_record(dev, e.name, e.start_ns,
                                                 e.end_ns)
                        rec["scope"] = op_scope(named.get(e.name, ""))
                        ops.append(rec)
                elif line.name == "XLA Modules":
                    for e in line.events:
                        modules.append({"dev": dev, "name": e.name,
                                        "start": e.start_ns,
                                        "end": e.end_ns})
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in keep:
                        args = {k: v for k, v in e.stats
                                if k in ("step", "phase")}
                        host.append({"name": e.name, "start": e.start_ns,
                                     "end": e.end_ns, "args": args})
                    elif e.name.startswith("PjitFunction("):
                        host.append({"name": e.name, "start": e.start_ns,
                                     "end": e.end_ns, "args": {}})
    return {"ops": ops, "modules": modules, "host": host}


# ---------------------------------------------------------------------------
# The split
# ---------------------------------------------------------------------------
def step_ops(trace: Dict, red: Dict, dev: int
             ) -> List[Tuple[Dict, int, str]]:
    """Device ``dev``'s top-level ops (an op nested inside another, such as
    a loop's body, is left out, as ``tracered.top_ops`` does) that start
    inside a train-step program, each with the index of that program in
    ``red["step_modules"][dev]`` and its scope.  A top-level op without a
    scope of its own takes the scope with the most time among the ops
    nested in it: on a TPU v5e a loop's own event carries no ``op_name``
    while its body's ops do."""
    mods = red["step_modules"][dev]
    starts = [m["start"] for m in mods]
    tops: List[Tuple[Dict, Dict[str, float]]] = []
    end = -1.0
    for o in sorted((o for o in trace["ops"] if o["dev"] == dev),
                    key=lambda o: (o["start"], -o["end"])):
        if o["start"] < end:
            nested = tops[-1][1]
            sc = o.get("scope", "")
            nested[sc] = nested.get(sc, 0.0) + o["end"] - o["start"]
            continue
        end = o["end"]
        tops.append((o, {}))
    out = []
    for o, nested in tops:
        i = bisect.bisect_right(starts, o["start"]) - 1
        if not (0 <= i < len(mods) and o["start"] < mods[i]["end"]):
            continue
        scope = o.get("scope", "")
        if not scope:
            nested.pop("", None)
            scope = max(nested, key=nested.get, default="")
        out.append((o, i, scope))
    return out


def scope_time(trace: Dict, red: Dict, scope: str,
               phase: Optional[str] = None) -> float:
    """Device time in ns of the top-level ops of ``scope`` (``""``: no
    scope) inside the train-step programs, clipped to the window and
    averaged over the chips; with ``phase``, only in the programs of that
    phase's steps (program attribution only)."""
    lo, hi = red["window_ns"]
    phases = red["step_phases"]
    t = 0.0
    for d in red["devices"]:
        for o, i, sc in step_ops(trace, red, d):
            if sc != scope:
                continue
            if phase is not None and phases[i] != phase:
                continue
            t += max(0.0, min(o["end"], hi) - max(o["start"], lo))
    return t / len(red["devices"])


def step_busy_time(trace: Dict, red: Dict) -> float:
    """Union of the device op intervals inside the train-step programs,
    clipped to the window, in ns averaged over the chips."""
    lo, hi = red["window_ns"]
    t = 0.0
    for d in red["devices"]:
        t += tracered.total(tracered.union(tracered.clip(
            [(o["start"], o["end"]) for o, _, _ in step_ops(trace, red,
                                                            d)],
            lo, hi)))
    return t / len(red["devices"])


def has_scopes(trace: Dict, red: Dict) -> bool:
    return any(sc for d in red["devices"]
               for _, _, sc in step_ops(trace, red, d))


def split(trace: Dict, red: Dict) -> Dict[str, Optional[float]]:
    """The per-step numbers, in ms, keyed by metric name."""
    names = ("fwd_bwd_ms", "optimizer_ms", "monitor_ms", "round_ms.gossip",
             "round_ms.global", "unscoped_ms", "train_input_ms_per_step")
    out: Dict[str, Optional[float]] = dict.fromkeys(names)
    if not has_scopes(trace, red):
        return out
    n = len(red["steps"])
    for scope, name in (("fwd_bwd", "fwd_bwd_ms"),
                        ("optimizer", "optimizer_ms"),
                        ("monitor", "monitor_ms"), ("", "unscoped_ms")):
        out[name] = scope_time(trace, red, scope) * 1e-6 / n
    if red["attribution"] == "program":
        for phase in ("gossip", "global"):
            k = red["step_phases"].count(phase)
            if k:
                out[f"round_ms.{phase}"] = scope_time(
                    trace, red, "round", phase) * 1e-6 / k
    lo, hi = red["window_ns"]
    spans = [h for h in trace["host"] if h["name"] == "train/input"
             and lo <= h["start"] < hi]
    if spans:
        out["train_input_ms_per_step"] = sum(
            h["end"] - h["start"] for h in spans) * 1e-6 / n
    return out


def host_label(host: List[Dict], t: float) -> str:
    """The innermost benchmark annotation open at ``t``; where none is, the
    innermost program span; else ``"none"``."""
    for names in (tracered.HOST_ANNOTATIONS, PROGRAM_SPANS):
        best = None
        for h in host:
            if h["name"] in names and h["start"] <= t < h["end"]:
                if best is None or h["start"] >= best["start"]:
                    best = h
        if best is not None:
            return best["name"]
    return "none"


def top_scope_ops(trace: Dict, red: Dict, k: int = 8
                  ) -> Dict[str, List[Tuple[str, float]]]:
    """Each scope's ``k`` top-level ops with the most time, in ms per
    step."""
    lo, hi = red["window_ns"]
    n, nd = len(red["steps"]), len(red["devices"])
    acc: Dict[str, Dict[str, float]] = {}
    for d in red["devices"]:
        for o, _, sc in step_ops(trace, red, d):
            t = max(0.0, min(o["end"], hi) - max(o["start"], lo))
            by = acc.setdefault(sc, {})
            by[o["name"]] = by.get(o["name"], 0.0) + t
    return {s: sorted(((name, v * 1e-6 / n / nd) for name, v in by.items()),
                      key=lambda x: -x[1])[:k]
            for s, by in acc.items()}


def period_record(trace: Dict, red: Dict, H: int, source: str) -> Dict:
    """The first ``H`` steps (one PGA period) of a traced window: their
    programs, their top-level ops (each with the scope ``step_ops`` gives
    it) and the host records in that stretch.  The window opens on an idle
    device, so its i-th step program is its i-th step; the host runs
    ahead, so its records stop where step ``H`` begins.  The device's
    clock may read a little behind the host's: the first program can
    start before the first ``train/step`` annotation."""
    steps, mods = red["steps"], red["step_modules"]
    lo = steps[0]["start"]
    hi = max(mods[d][H - 1]["end"] for d in red["devices"])
    host_hi = steps[H]["start"] if H < len(steps) else hi
    ops = [dict(o, scope=sc) for d in red["devices"]
           for o, i, sc in step_ops(trace, red, d) if i < H]
    first = min([lo] + [mods[d][0]["start"] for d in red["devices"]])
    modules = [m for m in trace["modules"] if first <= m["start"] < hi]
    host = [h for h in trace["host"] if lo <= h["start"] < host_hi]
    return {"source": source, "ops": ops, "modules": modules, "host": host}


# ---------------------------------------------------------------------------
# One cell on the chip
# ---------------------------------------------------------------------------
def measure(workload: str, seed: int, record: Optional[str]) -> Dict:
    import json
    import statistics

    import jax

    from chip import bench, cells, hooks
    from repro.launch.mesh import use_compile_cache
    use_compile_cache()
    cell = cells.load_cell(workload)
    setup = bench.Setup(cell, seed)
    setup.make_weights()
    setup.first_steps()
    setup.finish_warmup()
    steps = 2 * setup.H
    plain = bench.timed_window(setup, steps, hooks.CompileClock())
    # the benchmark's traced window, read with this module's loader
    first = len(setup.tracer.done)
    loader, tracered.load_xplane = tracered.load_xplane, load_xplane
    try:
        traced = bench.traced_window(setup, steps)
    finally:
        tracered.load_xplane = loader
    trace, red = traced["trace"], traced["red"]
    n = len(red["steps"])
    values = split(trace, red)
    busy = step_busy_time(trace, red) * 1e-6 / n
    parts = [values[k] for k in ("fwd_bwd_ms", "optimizer_ms", "monitor_ms",
                                 "unscoped_ms")]
    rounds = sum(scope_time(trace, red, "round", p)
                 for p in ("gossip", "global")) * 1e-6 / n
    stamps = [t for _, t, _ in setup.tracer.done[first:]]
    lo, hi = red["window_ns"]
    gaps = sorted(((host_label(trace["host"], (s + e) / 2), e - s)
                   for s, e in tracered.gaps(
                       tracered.union(tracered.clip(
                           [(o["start"], o["end"]) for o in trace["ops"]
                            if o["dev"] == red["devices"][0]], lo, hi)),
                       lo, hi)), key=lambda x: -x[1])
    out = {
        "workload": workload, "seed": seed,
        "device": jax.devices()[0].device_kind,
        "attribution": red["attribution"], "steps": n,
        "step_phases": red["step_phases"],
        "split_ms": values,
        "step_busy_ms": busy,
        "sum_ms": (sum(parts) + rounds) if None not in parts else None,
        "window_ms_per_step": (hi - lo) * 1e-6 / n,
        "top_ops_ms": top_scope_ops(trace, red),
        "idle_gaps_ms": [[k, v * 1e-6] for k, v in gaps[:10]],
        "median_interval_ms": {
            "untraced": statistics.median(plain["intervals"][1:]) * 1e3,
            "traced": statistics.median(
                b - a for a, b in zip(stamps, stamps[1:])) * 1e3},
    }
    if record:
        rec = period_record(
            trace, red, setup.H,
            f"{jax.devices()[0].device_kind}: {workload}, one PGA period "
            "of a traced window, top-level ops only")
        with open(record, "w") as f:
            json.dump(rec, f, separators=(",", ":"))
    setup.tracer.close()
    return out


def main(argv=None) -> int:
    import argparse
    import json
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--record", default="")
    args = ap.parse_args(argv)
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    sys.path.insert(0, os.path.join(root, "src"))
    print(json.dumps(measure(args.workload, args.seed, args.record or None)),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
