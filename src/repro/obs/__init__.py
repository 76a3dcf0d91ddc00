"""Observability layer (DESIGN.md §2.7): structured telemetry records,
span tracing with Chrome-trace export, and comm-round byte meters.

    from repro import obs

    tel = obs.Telemetry(sinks=[obs.JsonlSink("run.jsonl"), obs.RingSink()])
    with obs.telemetry_scope(tel):
        ...                         # mixing rounds self-report comm_round
        tel.emit("step", step=k, phase="gossip", loss=0.7)
        with tel.span("train/step", step=k) as sp:
            sp.fence(step_fn(state, batch, lr))
    tel.tracer.save("trace.json")   # load in Perfetto
"""
from repro.obs import meters
from repro.obs.telemetry import (RECORD_TYPES, SCHEMA_VERSION,
                                 JsonlSink, PrettySink, RingSink, Sink,
                                 Telemetry, get_telemetry, set_telemetry,
                                 telemetry_scope)
from repro.obs.trace import Tracer, fenced_time

__all__ = [
    "JsonlSink", "PrettySink", "RingSink", "RECORD_TYPES",
    "SCHEMA_VERSION", "Sink", "Telemetry", "Tracer", "fenced_time",
    "get_telemetry", "meters", "set_telemetry",
    "telemetry_scope",
]
