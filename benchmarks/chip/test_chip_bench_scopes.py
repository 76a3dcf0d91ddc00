"""The split of a traced step by the program's named scopes, and the
program spans on the profiler's host plane."""
import copy
import json
import os

import pytest

from chip import hooks, scopes, tracered

HERE = os.path.dirname(os.path.abspath(__file__))


def _op(dev, name, start, end, scope):
    rec = tracered.op_record(dev, f"%{name} = f32[4,8]{{1,0}} op()", start,
                             end)
    rec["scope"] = scope
    return rec


def scoped_trace():
    """A gossip step, then a global step, on two devices, in ns.  Device
    0's first op is a loop whose body ops are nested inside it."""
    host = [
        {"name": "train/input", "start": 0, "end": 3, "args": {"step": 0}},
        {"name": "train/step", "start": 4, "end": 10,
         "args": {"step": 0, "phase": "gossip"}},
        {"name": "PjitFunction(step)", "start": 5, "end": 6, "args": {}},
        {"name": "train/input", "start": 12, "end": 14,
         "args": {"step": 1}},
        {"name": "train/step", "start": 15, "end": 20,
         "args": {"step": 1, "phase": "global"}},
        {"name": "PjitFunction(step)", "start": 16, "end": 17, "args": {}},
        {"name": "train/log", "start": 190, "end": 220, "args": {"step": 1}},
        {"name": "host.fetch", "start": 195, "end": 215, "args": {}},
    ]
    modules = []
    for d in (0, 1):
        modules += [{"dev": d, "name": "jit_step(111)", "start": 100,
                     "end": 200},
                    {"dev": d, "name": "jit_convert(9)", "start": 205,
                     "end": 206},
                    {"dev": d, "name": "jit_step(222)", "start": 210,
                     "end": 300}]
    ops = [
        _op(0, "while.1", 100, 150, "fwd_bwd"),
        _op(0, "fusion.9", 110, 125, "fwd_bwd"),       # the loop's body
        _op(0, "fusion.8", 125, 130, "optimizer"),     # the loop's body
        _op(0, "fusion.2", 150, 160, "optimizer"),
        _op(0, "fusion.3", 160, 165, "monitor"),
        _op(0, "_mix_flat.1", 165, 190, "round"),
        _op(0, "copy.1", 190, 195, ""),
        _op(0, "convert.1", 205, 206, ""),             # not a step program
        _op(0, "fusion.4", 210, 260, "fwd_bwd"),
        _op(0, "all-reduce.1", 260, 280, "round"),
        _op(0, "fusion.5", 280, 290, "optimizer"),
        _op(0, "fusion.6", 290, 300, "monitor"),
        _op(1, "while.1", 100, 160, "fwd_bwd"),
        _op(1, "fusion.2", 160, 170, "optimizer"),
        _op(1, "_mix_flat.1", 170, 200, "round"),
        _op(1, "fusion.4", 210, 250, "fwd_bwd"),
        _op(1, "all-reduce.1", 250, 290, "round"),
        _op(1, "fusion.6", 290, 300, "monitor"),
    ]
    return {"ops": ops, "modules": modules, "host": host}


@pytest.mark.parametrize("op_name,scope", [
    ("jit(step)/fwd_bwd/transpose(jvp(vmap()))/dot_general", "fwd_bwd"),
    ("jit(step)/round/round/roll", "round"),
    ("jit(overlap_step)/round/apply/pallas_call", "round"),
    ("jit(step)/optimizer/round/mul", "optimizer"),
    ("jit(step)/le;jit(step)/fwd_bwd/jvp(vmap())/broadcast_in_dim",
     "fwd_bwd"),
    ("jit(step)/monitors/sqrt", ""),
    ("jit(step)/sin", ""),
    ("", ""),
])
def test_op_scope_is_the_first_scope_component(op_name, scope):
    assert scopes.op_scope(op_name) == scope


XSPACE = """
planes {
  id: 1 name: "/device:TPU:0"
  lines {
    id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 5000 }
    events { metadata_id: 2 offset_ps: 6000 duration_ps: 3000 }
    events { metadata_id: 4 offset_ps: 9000 duration_ps: 1000 }
  }
  lines {
    id: 2 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 3 offset_ps: 0 duration_ps: 10000 }
  }
  event_metadata { key: 1 value { id: 1 display_name: "fusion.1"
    name: "%fusion.1 = f32[4]{0} fusion(%p), kind=kLoop"
    stats { metadata_id: 7 str_value: "jit(step)/fwd_bwd/mul" } } }
  event_metadata { key: 2 value { id: 2 display_name: "copy.1"
    name: "%copy.1 = f32[4]{0} copy(%p)"
    stats { metadata_id: 7 ref_value: 8 } } }
  event_metadata { key: 3 value { id: 3 name: "jit_step(1)" } }
  event_metadata { key: 4 value { id: 4 name: "%copy.2 = f32[4]{0} copy(%p)" } }
  stat_metadata { key: 7 value { id: 7 name: "tf_op" } }
  stat_metadata { key: 8 value { id: 8 name: "jit(step)/round/copy" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines {
    id: 1 name: "python" timestamp_ns: 900
    events { metadata_id: 1 offset_ps: 0 duration_ps: 50000 }
  }
  event_metadata { key: 1 value { id: 1 name: "train/input" } }
}
"""


def test_load_xplane_reads_op_names_from_the_event_metadata(tmp_path):
    from jax.profiler import ProfileData
    raw = ProfileData.text_proto_to_serialized_xspace(XSPACE)
    assert scopes.op_names(raw)["/device:TPU:0"] == {
        "fusion.1": "jit(step)/fwd_bwd/mul",
        "%fusion.1 = f32[4]{0} fusion(%p), kind=kLoop":
            "jit(step)/fwd_bwd/mul",
        "copy.1": "jit(step)/round/copy",
        "%copy.1 = f32[4]{0} copy(%p)": "jit(step)/round/copy"}
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(raw)
    tr = scopes.load_xplane(str(path))
    assert [(o["name"], o["scope"]) for o in tr["ops"]] == [
        ("fusion.1", "fwd_bwd"), ("copy.1", "round"), ("copy.2", "")]
    assert tr["modules"] == [{"dev": 0, "name": "jit_step(1)",
                              "start": 1000, "end": 1010}]
    assert [h["name"] for h in tr["host"]] == ["train/input"]


def test_split_counts_a_loop_once_and_averages_over_chips():
    tr = scoped_trace()
    red = tracered.reduce_trace(tr)
    assert red["attribution"] == "program"
    assert red["window_ns"] == (4, 300)
    # the loop counts 50 ns on device 0, its body's 5 ns optimizer op not
    # at all; devices 0 and 1 are averaged
    assert scopes.scope_time(tr, red, "fwd_bwd") == 100.0
    assert scopes.scope_time(tr, red, "optimizer") == 15.0
    assert scopes.scope_time(tr, red, "round", "gossip") == 27.5
    assert scopes.scope_time(tr, red, "round", "global") == 30.0
    got = scopes.split(tr, red)
    assert got == pytest.approx({
        "fwd_bwd_ms": 50e-6, "optimizer_ms": 7.5e-6, "monitor_ms": 6.25e-6,
        "round_ms.gossip": 27.5e-6, "round_ms.global": 30e-6,
        "unscoped_ms": 1.25e-6,
        # the first step's input lies before the window opens
        "train_input_ms_per_step": 1e-6})
    parts = sum(got[k] for k in ("fwd_bwd_ms", "optimizer_ms",
                                 "monitor_ms", "unscoped_ms"))
    rounds = (got["round_ms.gossip"] + got["round_ms.global"]) / 2
    assert scopes.step_busy_time(tr, red) * 1e-6 / 2 == pytest.approx(
        parts + rounds)


def test_a_scopeless_loop_takes_the_scope_of_its_body():
    tr = scoped_trace()
    want = scopes.split(tr, tracered.reduce_trace(tr))
    for o in tr["ops"]:
        if o["name"] == "while.1" and o["dev"] == 0:
            o["scope"] = ""
    red = tracered.reduce_trace(tr)
    assert scopes.split(tr, red) == want
    rec = scopes.period_record(tr, red, 2, "hand")
    assert [o["scope"] for o in rec["ops"] if o["name"] == "while.1"] == [
        "fwd_bwd", "fwd_bwd"]


def test_split_reads_nothing_without_scopes():
    tr = scoped_trace()
    for o in tr["ops"]:
        o["scope"] = ""
    red = tracered.reduce_trace(tr)
    assert set(scopes.split(tr, red).values()) == {None}


def test_round_by_phase_needs_program_attribution():
    tr = scoped_trace()
    tr["modules"] = [m for m in tr["modules"]
                     if not (m["dev"] == 1 and m["name"] == "jit_step(222)")]
    red = tracered.reduce_trace(tr)
    assert red["attribution"] == "op_type"
    got = scopes.split(tr, red)
    assert got["round_ms.gossip"] is None and got["round_ms.global"] is None
    assert got["fwd_bwd_ms"] is not None


def test_idle_gap_falls_back_to_the_program_span():
    host = scoped_trace()["host"]
    assert scopes.host_label(host, 200) == "host.fetch"
    assert scopes.host_label(host, 217) == "train/log"
    assert scopes.host_label(host, 11) == "none"


def test_top_scope_ops_and_the_period_record():
    tr = scoped_trace()
    red = tracered.reduce_trace(tr)
    top = scopes.top_scope_ops(tr, red)
    assert top["round"][0] == ("all-reduce.1", pytest.approx(15e-6))
    assert "fusion.8" not in dict(top["optimizer"])
    rec = scopes.period_record(tr, red, 2, "hand")
    assert [h["name"] for h in rec["host"]].count("train/step") == 2
    assert "fusion.9" not in {o["name"] for o in rec["ops"]}
    assert scopes.split(rec, tracered.reduce_trace(rec)) == \
        scopes.split(tr, red)
    # the first program starts before the first step's annotation on the
    # host's clock: the record keeps it, and with it the attribution
    skewed = copy.deepcopy(tr)
    for h in skewed["host"]:
        h["start"] += 120
        h["end"] += 120
    red = tracered.reduce_trace(skewed)
    rec = scopes.period_record(skewed, red, 1, "hand")
    assert [m["name"] for m in rec["modules"]] == ["jit_step(111)"] * 2
    assert tracered.reduce_trace(rec)["attribution"] == "program"


# ---------------------------------------------------------------------------
# The program's spans in a CPU profiler trace
# ---------------------------------------------------------------------------
def _traced_trainer_run(tmp_path, telemetry, stream_wrap=None):
    import jax

    from repro.configs import (DataConfig, DistConfig, OptimizerConfig,
                               TrainConfig, get_model_config)
    from repro.train import Trainer
    tcfg = TrainConfig(
        model=get_model_config("pga-lm-100m", reduced=True),
        dist=DistConfig(algorithm="gossip_pga", topology="ring", H=4),
        optimizer=OptimizerConfig(name="sgd", lr=0.05, schedule="constant",
                                  warmup_steps=0),
        data=DataConfig(), global_batch=4, seq_len=16, log_every=1)
    tr = Trainer(tcfg, n_nodes=2, telemetry=telemetry)
    if stream_wrap is not None:
        tr.stream = stream_wrap(tr.stream)
    state = tr.init_state(jax.random.PRNGKey(0))
    jax.profiler.start_trace(str(tmp_path))
    try:
        jax.block_until_ready(tr.run(state, steps=2))
    finally:
        jax.profiler.stop_trace()
    paths = [os.path.join(d, f) for d, _, fs in os.walk(tmp_path)
             for f in fs if f.endswith(".xplane.pb")]
    assert len(paths) == 1
    return [h["name"] for h in scopes.load_xplane(paths[0])["host"]]


def test_benchmark_hub_annotates_each_span_once(tmp_path):
    hub, tracer = hooks.make_hub()
    try:
        names = _traced_trainer_run(tmp_path, hub, hooks.AnnotatedStream)
    finally:
        tracer.close()
    assert names.count("train/step") == 2
    assert names.count("host.input") == 2
    assert names.count("train/input") == 2
    assert names.count("train/log") == 2
    assert names.count("host.fetch") == 2
    # only the fenced train/step spans were stamped as steps
    assert tracer.sent == 2


def test_default_hub_spans_land_on_the_host_plane(tmp_path):
    names = _traced_trainer_run(tmp_path, None)
    for name in ("train/step", "train/input", "train/log"):
        assert names.count(name) == 2


def test_recorded_one_chip_period_split():
    """The first PGA period of a traced window on a TPU v5e (pallas
    backend, 4 stacked nodes; top-level ops only, each with its scope):
    the split, and the four scopes plus the unscoped ops summing to the
    step programs' busy time."""
    with open(os.path.join(HERE, "testdata",
                           "trace-lm100m-pallas-scopes-1chip.json")) as f:
        tr = json.load(f)
    red = tracered.reduce_trace(tr)
    assert red["attribution"] == "program"
    assert red["step_phases"] == ["gossip"] * 4 + ["global", "gossip"]
    assert {h["name"] for h in tr["host"]} >= {"train/input", "train/log"}
    times = {sc: scopes.scope_time(tr, red, sc)
             for sc in scopes.SCOPES + ("",)}
    assert times == {"fwd_bwd": 370426923.0, "optimizer": 134896024.0,
                     "monitor": 0.0, "round": 237967679.0,
                     "": 302531756.0}
    busy = scopes.step_busy_time(tr, red)
    assert busy == 1045822382.0 == sum(times.values())
    got = scopes.split(tr, red)
    assert got == pytest.approx({
        "fwd_bwd_ms": 370426923e-6 / 6, "optimizer_ms": 134896024e-6 / 6,
        "monitor_ms": 0.0, "round_ms.gossip": 198301038e-6 / 5,
        "round_ms.global": 39666641e-6, "unscoped_ms": 302531756e-6 / 6,
        "train_input_ms_per_step": 4.987909833333333})
    rounds = (5 * got["round_ms.gossip"] + got["round_ms.global"]) / 6
    assert sum(got[k] for k in ("fwd_bwd_ms", "optimizer_ms", "monitor_ms",
                                "unscoped_ms")) + rounds == pytest.approx(
        busy * 1e-6 / 6)
