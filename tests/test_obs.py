"""Telemetry subsystem (DESIGN.md §2.7): sink schema round-trip, Chrome
trace export, comm-round byte meters vs the analytic cost model, overlap
issue/apply accounting, fault events in the stream, and the
zero-per-step-host-sync regression on the Trainer hot path."""
import contextlib
import json
import os
import re
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.compress import round_wire_bytes
from repro.configs import (DataConfig, DistConfig, OptimizerConfig,
                           TrainConfig, get_model_config)
from repro.core import mixing
from repro.core.algorithms import simulate
from repro.core.faults import FaultSchedule
from repro.train import Trainer

CFG = get_model_config("pga-lm-100m", reduced=True)


def _tcfg(algorithm="gossip_pga", H=4, **dist_kw):
    return TrainConfig(
        model=CFG,
        dist=DistConfig(algorithm=algorithm, topology="ring", H=H,
                        **dist_kw),
        optimizer=OptimizerConfig(name="sgd", lr=0.05, schedule="constant",
                                  warmup_steps=0, grad_clip=1.0),
        data=DataConfig(non_iid=True), global_batch=8, seq_len=32,
        log_every=0)


def _quadratic(d=6, m=48):
    A = jax.random.normal(jax.random.PRNGKey(11), (m, d))
    b = jax.random.normal(jax.random.PRNGKey(12), (m,))

    def loss_fn(x):
        return 0.5 * jnp.mean((A @ x - b) ** 2)

    def grad_fn(xs, key, k):
        return jax.vmap(jax.grad(loss_fn))(xs)

    return loss_fn, grad_fn, d


# ---------------------------------------------------------------------------
# Hub + sinks
# ---------------------------------------------------------------------------
def test_sink_schema_roundtrip(tmp_path):
    path = str(tmp_path / "t.jsonl")
    tel = obs.Telemetry(sinks=[obs.JsonlSink(path), obs.RingSink()],
                        tags={"algorithm": "unit"})
    tel.emit("step", step=3, phase="gossip", loss=1.25)
    tel.emit("comm_round", phase="global", role="round",
             measured_bytes=128)
    tel.emit("ckpt", step=4)
    tel.close()
    recs = [json.loads(ln) for ln in open(path)]
    assert [r["type"] for r in recs] == ["step", "comm_round", "ckpt"]
    for r in recs:
        assert r["schema"] == obs.SCHEMA_VERSION
        assert r["algorithm"] == "unit"      # hub tags stamped on every rec
        assert isinstance(r["ts"], float)
    assert recs[0]["loss"] == 1.25
    # the ring sink saw the identical records
    ring = tel.ring()
    assert [r["type"] for r in ring.records()] == [r["type"] for r in recs]
    assert ring.records("step")[0]["step"] == 3


def test_emit_unknown_type_and_missing_fields_raise():
    tel = obs.Telemetry()
    with pytest.raises(ValueError, match="unknown record type"):
        tel.emit("nonsense", step=0)
    with pytest.raises(ValueError, match="missing required"):
        tel.emit("step", step=0)             # no phase


def test_pretty_sink_matches_legacy_format():
    import io
    buf = io.StringIO()
    tel = obs.Telemetry(sinks=[obs.PrettySink(stream=buf)],
                        tags={"algorithm": "gossip_pga"})
    tel.emit("step", step=7, phase="gossip", loss=6.5, consensus=1e-3)
    tel.emit("comm_round", phase="gossip", role="round")  # not printed
    out = buf.getvalue()
    assert out == ("[gossip_pga] step     7 loss=6.5000 phase=gossip"
                   " consensus=1.000e-03\n")


def test_telemetry_scope_nesting():
    a, b = obs.Telemetry(), obs.Telemetry()
    assert obs.get_telemetry() is None
    with obs.telemetry_scope(a):
        assert obs.get_telemetry() is a
        with obs.telemetry_scope(b):
            assert obs.get_telemetry() is b
        assert obs.get_telemetry() is a
    assert obs.get_telemetry() is None


# ---------------------------------------------------------------------------
# Chrome trace export
# ---------------------------------------------------------------------------
def test_chrome_trace_valid_and_nested(tmp_path):
    tr = obs.Tracer()
    with tr.span("train/step", step=0):
        with tr.span("comm/issue"):
            pass
        with tr.span("comm/apply"):
            pass
    path = tr.save(str(tmp_path / "trace.json"))
    doc = json.load(open(path))            # valid JSON round-trip
    evs = doc["traceEvents"]
    assert {e["name"] for e in evs} == {"train/step", "comm/issue",
                                        "comm/apply"}
    for e in evs:
        assert e["ph"] == "X" and e["ts"] >= 0 and e["dur"] >= 0
    outer = next(e for e in evs if e["name"] == "train/step")
    for e in evs:
        if e is outer:
            continue
        # child spans nest inside the parent by time containment
        assert e["ts"] >= outer["ts"]
        assert e["ts"] + e["dur"] <= outer["ts"] + outer["dur"] + 1e-3
    assert outer["args"] == {"step": 0}


def test_fenced_time_records_spans():
    tr = obs.Tracer()
    x = jnp.arange(8.0)
    us = obs.fenced_time(jnp.sum, x, iters=3, warmup=1,
                         name="bench/sum", tracer=tr)
    assert us > 0
    assert [e["name"] for e in tr.events] == ["bench/sum"] * 3


# ---------------------------------------------------------------------------
# Comm meters: measured == analytic on the reference backend
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("compression", ["identity", "int8"])
@pytest.mark.parametrize("phase", ["gossip", "global"])
def test_comm_round_measured_matches_analytic(compression, phase):
    n, shapes = 8, [(32,), (7,)]
    params = [jnp.ones((n,) + s, jnp.float32) for s in shapes]
    per_node = sum(int(np.prod(s)) for s in shapes)
    spec = DistConfig(algorithm="gossip_pga", topology="ring",
                      comm_backend="reference",
                      comm_compression=compression).comm_spec(n)
    tel = obs.Telemetry(sinks=[obs.RingSink()])
    with obs.telemetry_scope(tel):
        mixing.communicate(params, spec, phase=phase, step=0)
    recs = tel.ring().records("comm_round")
    assert len(recs) == 1
    r = recs[0]
    assert r["phase"] == phase and r["role"] == "round"
    assert r["compression"] == compression
    want = round_wire_bytes(phase, "ring", n, per_node,
                            compression=compression,
                            leaf_sizes=[int(np.prod(s)) for s in shapes])
    assert r["analytic_bytes"] == want
    assert r["measured_bytes"] == want     # packed-buffer bytes agree


@pytest.mark.parametrize("round_,backend,want", [
    ("phase", "pallas", 7 * 4), ("phase", "reference", 0),
    ("push_sum", "pallas", 7 * 4 + 4), ("push_sum", "reference", 0)])
def test_comm_round_staged_bytes(round_, backend, want):
    """A pallas round reports the per-node bytes that still ride its
    packed staging buffer: the leaves below the dispatch threshold (the
    (n, 32) leaf is mixed in its own layout), and in a push-sum round the
    weight column beside them; the reference round none."""
    n = 4
    params = [jnp.ones((n, 32), jnp.float32), jnp.ones((n, 7), jnp.float32)]
    spec = DistConfig(algorithm="gossip_pga", topology="ring",
                      comm_backend=backend,
                      pallas_leaf_threshold=16).comm_spec(n)
    tel = obs.Telemetry(sinks=[obs.RingSink()])
    with obs.telemetry_scope(tel):
        if round_ == "phase":
            for phase in ("gossip", "global"):
                mixing.communicate(params, spec, phase=phase, step=0)
        else:
            for _ in range(2):
                mixing.communicate_push_sum(
                    params, jnp.ones((n, 1), jnp.float32),
                    W=jnp.full((n, n), 1.0 / n), n_nodes=n, backend=backend,
                    leaf_threshold=16)
    recs = tel.ring().records("comm_round")
    assert [r["staged_bytes"] for r in recs] == [want, want]


def test_comm_round_meter_noop_without_hub():
    n = 4
    params = [jnp.ones((n, 8), jnp.float32)]
    spec = DistConfig(algorithm="gossip_pga",
                      topology="ring").comm_spec(n)
    assert obs.get_telemetry() is None
    out = mixing.communicate(params, spec, phase="gossip", step=0)
    assert jax.tree.leaves(out)[0].shape == (n, 8)


# ---------------------------------------------------------------------------
# Overlap: issue/apply records iff comm_overlap; occupancy reported
# ---------------------------------------------------------------------------
def _dumped_scopes(path) -> set:
    """Every name-stack component of the ops in the modules JAX dumped to
    ``path`` (``jax_dump_ir_to``)."""
    names = set()
    for f in os.listdir(path):
        with open(os.path.join(path, f)) as fh:
            for loc in re.findall(r'loc\("(jit\([^"]*)"', fh.read()):
                names.update(loc.split("/"))
    return names


@pytest.mark.parametrize("overlap", [False, True])
def test_overlap_issue_apply_iff_comm_overlap(overlap, tmp_path):
    loss_fn, grad_fn, d = _quadratic()
    tel = obs.Telemetry(sinks=[obs.RingSink()])
    prev = jax.config.values["jax_dump_ir_to"]
    jax.config.update("jax_dump_ir_to", str(tmp_path))
    try:
        simulate(algorithm="gossip_pga", grad_fn=grad_fn, loss_fn=loss_fn,
                 x0=jnp.zeros(d), n=4, steps=8, lr=0.05, topology="ring",
                 H=4, eval_every=4, overlap=overlap, telemetry=tel)
    finally:
        jax.config.update("jax_dump_ir_to", prev)
    roles = {r["role"] for r in tel.ring().records("comm_round")}
    scopes = _dumped_scopes(tmp_path)
    if overlap:
        assert {"issue", "apply"} <= roles
        # the float32 issue captures the iterate itself and emits no op;
        # the apply and the period boundary's flush do
        assert {"apply", "flush"} <= scopes
    else:
        assert "issue" not in roles and "apply" not in roles
        assert not {"issue", "apply", "flush"} & scopes
        assert "round" in roles and "round" in scopes


# ---------------------------------------------------------------------------
# Named scopes of the train step: every device op under one of four
# ---------------------------------------------------------------------------
STEP_SCOPES = ("fwd_bwd", "optimizer", "monitor", "round")
STEP_MODES = {"sync": {}, "overlap": {"comm_overlap": True},
              "push_sum": {"push_sum": True},
              "pallas_fused": {"comm_backend": "pallas"}}


def _scope_of(op_name: str) -> str:
    return next((p for p in re.split(r"[/;]", op_name)
                 if p in STEP_SCOPES), "")


def _step_hlo(mode: str, phase: str) -> str:
    """Optimized HLO text of the trainer's compiled step.  A bfloat16 wire
    makes the overlapped issue a cast, so it emits ops of its own."""
    tcfg = _tcfg(comm_dtype="bfloat16", **STEP_MODES[mode])
    tr = Trainer(tcfg, n_nodes=4, with_consensus=True)
    state = tr.init_state(jax.random.PRNGKey(0))
    args = [state, tr._batch(0), jnp.asarray(0.05, jnp.float32)]
    if mode == "overlap":
        spec = tcfg.dist.comm_spec(4)
        args.append(mixing.start_round(state.params, spec)[0])
    if mode == "push_sum":
        args.extend(tr._push_round(phase, 0, 0))
    step = tr._get_step_fn(phase, 0, buf_shift=0)
    return step.lower(*args).compile().as_text()


@pytest.mark.parametrize("mode", list(STEP_MODES))
@pytest.mark.parametrize("phase", ["gossip", "global"])
def test_train_step_ops_carry_the_step_scopes(phase, mode):
    ops = re.findall(r'^\s*(?:ROOT )?%\S+ = .*op_name="(jit\(\w+\)/[^"]*)"',
                     _step_hlo(mode, phase), re.M)
    scopes = {_scope_of(n) for n in ops}
    assert {"fwd_bwd", "optimizer", "round"} <= scopes
    if mode != "pallas_fused":
        # the fused kernel emits the consensus residual inside the round,
        # and XLA merges the grad-norm monitor with the clip's equal norm
        assert "monitor" in scopes
    parts = {p for n in ops for p in re.split(r"[/;]", n)}
    if mode == "overlap" and phase == "gossip":
        assert {"issue", "apply"} <= parts
    elif mode == "overlap":
        assert "flush" in parts and "apply" not in parts
    else:
        assert not {"issue", "apply", "flush"} & parts
    # a backward op sits under the scope of its forward
    assert any(n.split("/")[1] == "fwd_bwd" and "transpose(" in n
               for n in ops)


def test_step_scopes_leave_the_optimized_program_unchanged(monkeypatch):
    def strip(text):
        return [re.sub(r", metadata=\{[^}]*\}", "", line)
                for line in text.splitlines()
                if re.match(r"\s*(ROOT |%|ENTRY|HloModule|\})", line)]

    scoped = strip(_step_hlo("sync", "gossip"))
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    plain = strip(_step_hlo("sync", "gossip"))
    assert scoped == plain


def test_trainer_overlap_occupancy_record():
    tcfg = _tcfg(comm_overlap=True)
    tr = Trainer(tcfg, n_nodes=4, measure_occupancy=True)
    state = tr.init_state(jax.random.PRNGKey(0))
    tr.run(state, steps=4, log_every=2)
    occ = [r for r in tr.telemetry.ring().records("comm_round")
           if r.get("role") == "occupancy"]
    assert len(occ) == 1
    assert 0.0 <= occ[0]["occupancy"] <= 1.0
    assert occ[0]["t_round_sync_us"] > 0
    # period boundaries emitted pipeline-flush records
    assert tr.telemetry.ring().records("flush")


# ---------------------------------------------------------------------------
# Fault events appear in the stream
# ---------------------------------------------------------------------------
def test_fault_events_in_stream():
    loss_fn, grad_fn, d = _quadratic()
    fs = FaultSchedule(n_nodes=4, drops={3: (1,)}, rejoins={6: (1,)})
    tel = obs.Telemetry(sinks=[obs.RingSink()])
    simulate(algorithm="gossip_pga", grad_fn=grad_fn, loss_fn=loss_fn,
             x0=jnp.zeros(d), n=4, steps=8, lr=0.05,
             topology="directed_ring", H=4, eval_every=4,
             push_sum=True, fault_schedule=fs, telemetry=tel)
    faults = tel.ring().records("fault")
    assert [(f["step"], f["kind"], f["nodes"]) for f in faults] == \
        [(3, "drop", [1]), (6, "rejoin", [1])]
    # push-sum rounds still meter their wire traffic (runtime-W record)
    comm = tel.ring().records("comm_round")
    assert comm and all(c["phase"] == "push_sum" for c in comm)
    steps = tel.ring().records("step")
    assert steps and "mass" in steps[-1]


# ---------------------------------------------------------------------------
# Zero per-step host syncs on the no-logging hot path (regression)
# ---------------------------------------------------------------------------
@pytest.mark.repro_guards
def test_trainer_hot_path_zero_per_step_host_syncs(monkeypatch):
    """log_every=0 gossip_aga run crossing a global boundary: the loop
    must never implicitly sync (float()/np.asarray on device values) —
    enforced by the transfer guard, which permits only the *explicit*
    ``jax.device_get`` transfers; those must stay O(boundaries), not
    O(steps)."""
    tcfg = _tcfg(algorithm="gossip_aga")
    tr = Trainer(tcfg, n_nodes=4)
    state = tr.init_state(jax.random.PRNGKey(0))

    calls = {"n": 0}
    real = jax.device_get

    def counting(tree):
        calls["n"] += 1
        return real(tree)

    monkeypatch.setattr(jax, "device_get", counting)
    steps = 10    # AGA H_init=4 -> crosses two global boundaries
    with jax.transfer_guard_device_to_host("disallow"):
        state = tr.run(state, steps=steps, log_every=0)
    # start-step read + one lazy materialization per global boundary;
    # strictly fewer transfers than steps == no per-step sync
    assert calls["n"] < steps
    assert int(real(state.step)) == steps
    # the schedule did adapt (the lazy loss signal arrived)
    assert len(tr.schedule.history) >= 2


def test_trainer_log_boundary_batched_fetch():
    """With logging on, host materialization is ONE counted fetch per
    log boundary (not per step), and history keeps the legacy keys."""
    tcfg = _tcfg()
    tr = Trainer(tcfg, n_nodes=4, with_consensus=True)
    state = tr.init_state(jax.random.PRNGKey(0))
    tr.run(state, steps=8, log_every=4)        # boundaries: k=0, 4, 7
    assert tr.telemetry.host_fetches == 3
    assert len(tr.history) == 3
    for rec in tr.history:
        for key in ("step", "phase", "lr", "time", "loss", "consensus"):
            assert key in rec
    assert tr.history[-1]["phase_counts"].get("gossip", 0) >= 1


# ---------------------------------------------------------------------------
# Serving telemetry
# ---------------------------------------------------------------------------
def test_serve_req_records():
    from repro.models import make_model
    from repro.serve import BatchedServer, Engine, Request
    model = make_model(CFG)
    params, _ = model.init(jax.random.PRNGKey(0))
    tel = obs.Telemetry(sinks=[obs.RingSink()])
    server = BatchedServer(Engine(model, s_max=32), params, n_slots=2,
                           telemetry=tel)
    rng = np.random.default_rng(0)
    reqs = [Request(uid=i,
                    prompt=rng.integers(0, CFG.vocab_size, size=4),
                    max_new=3) for i in range(3)]
    done = server.run(reqs)
    assert len(done) == 3
    recs = tel.ring().records("serve_req")
    assert sorted(r["uid"] for r in recs) == [0, 1, 2]
    for r in recs:
        assert r["latency_s"] > 0
        assert r["new_tokens"] == 3 and r["prompt_tokens"] == 4
        assert r["tokens_per_s"] > 0
    names = {e["name"] for e in tel.tracer.events}
    assert {"serve/prefill", "serve/decode"} <= names


# ---------------------------------------------------------------------------
# report.py integration
# ---------------------------------------------------------------------------
def test_telemetry_table_smoke(tmp_path, capsys):
    import sys
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from benchmarks.report import telemetry_table
    path = str(tmp_path / "t.jsonl")
    tel = obs.Telemetry(sinks=[obs.JsonlSink(path)])
    tel.emit("comm_round", phase="gossip", role="round", topology="ring",
             backend="reference", compression="none", sends=2,
             analytic_bytes=312, measured_bytes=312)
    tel.emit("comm_round", phase="gossip", role="occupancy",
             occupancy=0.75, t_step_overlap_us=10.0,
             t_step_compute_us=8.0, t_round_sync_us=8.0)
    tel.emit("step", step=0, phase="gossip", loss=2.0, consensus=1e-2,
             phase_counts={"gossip": 9})
    tel.emit("step", step=9, phase="global", loss=1.0, consensus=1e-4)
    tel.emit("fault", step=3, kind="drop", nodes=[1])
    tel.emit("serve_req", uid=0, latency_s=0.01, tokens_per_s=100.0)
    tel.close()
    telemetry_table(path)
    out = capsys.readouterr().out
    assert "per-round communication" in out
    assert "| gossip | round | ring | reference | none | 2 | 312 | 312" \
        in out
    assert "pipeline occupancy: **0.75**" in out
    assert "loss: 2.0000 @ step 0 -> 1.0000 @ step 9" in out
    assert "step 3 drop [1]" in out
    assert "latency p50 10.0ms" in out


def test_trend_table_skips_unknown_schema(tmp_path, capsys):
    import sys
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from benchmarks.report import HISTORY_SCHEMA, trend_table
    path = str(tmp_path / "hist.jsonl")
    rows = [
        {"sha": "aaaaaaa", "rows": [{"name": "mix", "ratio": 1.1}]},
        {"sha": "bbbbbbb", "schema": HISTORY_SCHEMA,
         "rows": [{"name": "mix", "ratio": 1.2}]},
        {"sha": "ccccccc", "schema": HISTORY_SCHEMA + 99,
         "future_field": [{"whatever": 1}]},
    ]
    with open(path, "w") as f:
        for r in rows:
            f.write(json.dumps(r) + "\n")
    trend_table(path)                      # must not raise
    cap = capsys.readouterr()
    assert "1.10 | 1.20" in cap.out        # v1 + v2 rows rendered
    assert "ccccccc" not in cap.out        # unknown schema skipped...
    assert "unknown schema" in cap.err     # ...with a warning
