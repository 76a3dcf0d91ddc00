"""The reduction from a profiler trace to the per-layer metrics."""
import copy
import json
import os

import pytest

from chip import cells, tracered

HERE = os.path.dirname(os.path.abspath(__file__))


def _op(dev, name, start, end, kernel=None):
    text = f"%{name} = f32[4,8]{{1,0}} op()"
    if kernel:
        text = (f"%{name} = ({kernel}{{1,0}}, f32[1,1]) custom-call(), "
                'custom_call_target="tpu_custom_call"')
    return tracered.op_record(dev, text, start, end)


def hand_trace():
    """Two steps (gossip, then global) on two devices, in ns."""
    host = [
        {"name": "train/step", "start": 0, "end": 10,
         "args": {"step": 0, "phase": "gossip"}},
        {"name": "host.input", "start": 1, "end": 3, "args": {}},
        {"name": "PjitFunction(step)", "start": 5, "end": 6, "args": {}},
        {"name": "train/step", "start": 12, "end": 20,
         "args": {"step": 1, "phase": "global"}},
        {"name": "host.input", "start": 12, "end": 16, "args": {}},
        {"name": "PjitFunction(step)", "start": 17, "end": 18, "args": {}},
        {"name": "host.fetch", "start": 190, "end": 215, "args": {}},
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
        _op(0, "fusion.1", 100, 150),
        _op(0, "fusion.9", 110, 120),                 # nested in fusion.1
        _op(0, "collective-permute-done.1", 140, 170),
        _op(0, "fusion.2", 170, 200, kernel="f32[4,1024]"),
        _op(0, "all-reduce.1", 210, 260),
        _op(0, "fusion.3", 250, 300),
        _op(1, "fusion.1", 100, 200),
        _op(1, "collective-permute-done.1", 180, 205),
        _op(1, "fusion.3", 210, 300),
        _op(1, "all-reduce.1", 260, 280),
    ]
    return {"ops": ops, "modules": modules, "host": host}


def test_busy_union_window_and_idle_gaps():
    red = tracered.reduce_trace(hand_trace())
    assert red["window_ns"] == (0, 300)
    assert red["busy_ns"] == {0: 190.0, 1: 195.0}
    # device 0 idles before its first op and between the two programs,
    # while the host drains the log boundary
    assert red["idle_gaps_ns"] == [("none", 100), ("host.fetch", 10)]


def test_collective_exposure_by_the_phase_of_the_program():
    red = tracered.reduce_trace(hand_trace())
    assert red["attribution"] == "program"
    assert red["step_phases"] == ["gossip", "global"]
    # device 0: the permute overlaps compute for 10 of its 30 ns; the
    # all-reduce for 10 of 50; device 1's permute runs 5 ns past its
    # compute and its all-reduce is hidden
    assert red["exposed_coll_ns"] == {0: {"gossip": 20.0, "global": 40.0},
                                      1: {"gossip": 5.0, "global": 0.0}}
    ctx = {"red": red, "trace": hand_trace(), "n_steps": 2}
    gossip = cells.metric_reader("comm_exposed_ms.gossip")(ctx)
    glob = cells.metric_reader("comm_exposed_ms.global")(ctx)
    assert gossip == pytest.approx(12.5e-6)
    assert glob == pytest.approx(20e-6)
    idle = cells.metric_reader("device_idle_share")(ctx)
    assert idle == pytest.approx(100 * (1 - 192.5 / 300))
    inp = cells.metric_reader("input_ms_per_step")(ctx)
    assert inp == pytest.approx(6e-6 / 2)


def test_phase_falls_back_to_op_type_when_programs_do_not_match():
    tr = hand_trace()
    tr["modules"] = [m for m in tr["modules"]
                     if not (m["dev"] == 1 and m["name"] == "jit_step(222)")]
    red = tracered.reduce_trace(tr)
    assert red["attribution"] == "op_type"
    assert set(red["exposed_coll_ns"][0]) == {"gossip", "global"}


def test_no_collective_reads_nothing():
    tr = hand_trace()
    tr["ops"] = [o for o in tr["ops"] if not o["coll"]]
    red = tracered.reduce_trace(tr)
    ctx = {"red": red, "trace": tr, "n_steps": 2}
    assert cells.metric_reader("comm_exposed_ms.gossip")(ctx) is None


def test_top_ops_count_a_nested_op_once_and_kernels_by_step():
    tr = hand_trace()
    red = tracered.reduce_trace(tr)
    top = dict(tracered.top_ops(tr, red["window_ns"]))
    assert "fusion.9" not in top
    assert top["fusion.1"] == pytest.approx((50 + 100) * 1e-9 / 2)
    t, rounds = tracered.step_kernel_time(
        tr, red, lambda o: o["kernel"].startswith("f32[4,"))
    assert (t, rounds) == (30.0, 1)


def test_mix_round_roofline_from_kernel_time():
    tr = hand_trace()
    red = tracered.reduce_trace(tr)
    ctx = {"red": red, "trace": tr, "n_nodes": 4, "params_per_node": 1024,
           "comm_itemsize": 4, "peaks": {"hbm_bytes_per_s": 819e9}}
    got = cells.metric_reader("mix_round_roofline")(ctx)
    least = 2 * 4 * 1024 * 4 / 819e9
    assert got == pytest.approx(100 * least / 30e-9)
    no_kernel = copy.deepcopy(tr)
    for o in no_kernel["ops"]:
        o.pop("kernel", None)
    red = tracered.reduce_trace(no_kernel)
    assert cells.metric_reader("mix_round_roofline")(
        dict(ctx, red=red, trace=no_kernel)) is None


def _recorded(name):
    with open(os.path.join(HERE, "testdata", name)) as f:
        return json.load(f)


def test_recorded_one_chip_period():
    """One PGA period traced on a TPU v5e (pallas backend, 4 stacked
    nodes): each step's program and phase, the busy union, the idle gaps
    and the mixing kernels' time per round."""
    tr = _recorded("trace-lm100m-pallas-1chip.json")
    red = tracered.reduce_trace(tr)
    assert red["attribution"] == "program"
    assert red["step_phases"] == ["gossip"] * 5 + ["global"]
    lo, hi = red["window_ns"]
    assert (lo, hi) == (43515416, 926556952)
    assert red["busy_ns"] == {0: 882034199.0}
    assert red["idle_gaps_ns"][0] == ("train/step", 864708)
    assert sum(g for _, g in red["idle_gaps_ns"]) == (hi - lo) - 882034199
    assert red["exposed_coll_ns"] == {0: {}}      # one chip: no collective
    t, rounds = tracered.step_kernel_time(
        tr, red, lambda o: o["kernel"].startswith("f32[4,"))
    assert (t, rounds) == (185265549.0, 6)
    ctx = {"red": red, "trace": tr, "n_nodes": 4,
           "params_per_node": 138431232, "comm_itemsize": 4,
           "peaks": {"hbm_bytes_per_s": 819e9}, "n_steps": 6}
    share = cells.metric_reader("mix_round_roofline")(ctx)
    assert share == pytest.approx(
        100 * (2 * 4 * 138431232 * 4 / 819e9) / (185265549e-9 / 6))
    assert 0 < share < 100
    assert cells.metric_reader("device_idle_share")(ctx) == pytest.approx(
        100 * (1 - 882034199 / (hi - lo)))
    assert cells.metric_reader("comm_exposed_ms.gossip")(ctx) is None


def test_recorded_four_chip_steps():
    """Two chips of three steps traced on a 2x2 TPU v5e (bert-large, one
    node per chip): collectives attributed to the phase of their program,
    their exposed time, and the idle gap of a slow dispatch."""
    tr = _recorded("trace-bert-large-4chip.json")
    red = tracered.reduce_trace(tr)
    assert red["attribution"] == "program"
    assert red["step_phases"] == ["gossip", "gossip", "global"]
    assert red["window_ns"] == (162922457, 768958712)
    assert red["busy_ns"] == {0: 440097176.0, 1: 440091811.0}
    # the global step's first call in the window took JAX's slow dispatch
    # path: the chips idled 164 ms while the host was inside train/step
    assert red["idle_gaps_ns"][0] == ("train/step", 164322778)
    assert red["exposed_coll_ns"] == {
        0: {"gossip": 123578191.0, "global": 32478231.0},
        1: {"gossip": 123535849.0, "global": 32470709.0}}
    ctx = {"red": red, "trace": tr, "n_steps": 3}
    assert cells.metric_reader("comm_exposed_ms.gossip")(ctx) == \
        pytest.approx((123578191 + 123535849) / 2 / 2 * 1e-6)
    assert cells.metric_reader("comm_exposed_ms.global")(ctx) == \
        pytest.approx((32478231 + 32470709) / 2 * 1e-6)
    permutes = [o for o in tr["ops"] if o["name"].startswith(
        "collective-permute")]
    assert permutes and all(o["coll"] for o in permutes)
