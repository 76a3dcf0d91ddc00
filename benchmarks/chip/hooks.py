"""The benchmark's hooks into the trainer, all passed in from outside.

* ``WatchTracer`` is given to ``Telemetry(tracer=...)``.  Each span opens
  a ``jax.profiler.TraceAnnotation`` of its name (and its ``step`` and
  ``phase``).  The value the trainer registers with ``sp.fence(loss)`` is
  handed to a watcher thread that waits for it and stamps when it is
  ready: one completion time per step, without blocking dispatch.
* ``Hub`` is that ``Telemetry``: its ``fetch`` (the trainer's log-boundary
  drain) runs inside an annotation named ``host.fetch``, and first waits
  until the watcher has stamped every step sent so far.  The fetch blocks
  on the newest of them anyway; waiting for the stamps first keeps the
  host loop, which resumes with the fetch, from holding the interpreter
  while the watcher stamps that step late.
* ``AnnotatedStream`` wraps the program's own ``Trainer.stream``: its
  ``get_batch`` runs inside an annotation named ``host.input``.
* ``CompileClock`` counts the backend compilations JAX reports.
"""
from __future__ import annotations

import contextlib
import queue
import threading
import time
from typing import Any, List, Optional, Tuple

import jax

from repro import obs
from repro.obs.trace import Tracer


class WatchTracer(Tracer):
    def __init__(self):
        super().__init__(fence=False)
        self._q: "queue.Queue" = queue.Queue()
        self._stamped = threading.Condition()
        self.done: List[Tuple[int, float, Any]] = []
        self.sent = 0
        self._thread = threading.Thread(target=self._watch, daemon=True)
        self._thread.start()

    def _watch(self) -> None:
        while True:
            item = self._q.get()
            if item is None:
                return
            step, value = item
            try:
                jax.block_until_ready(value)
            except Exception:       # a failed step is stamped too; reading
                pass                # its value raises where it is fetched
            stamp = time.perf_counter()
            with self._stamped:
                self.done.append((step, stamp, value))
                self._stamped.notify_all()

    @contextlib.contextmanager
    def span(self, name: str, **args):
        ann = {k: v for k, v in args.items() if k in ("step", "phase")}
        with jax.profiler.TraceAnnotation(name, **ann):
            with super().span(name, **args) as handle:
                yield handle
        if handle.value is not None:
            self.sent += 1
            self._q.put((args.get("step", -1), handle.value))

    def drain(self) -> None:
        """Wait until every registered value has been stamped."""
        with self._stamped:
            self._stamped.wait_for(lambda: len(self.done) >= self.sent)

    def close(self) -> None:
        self._q.put(None)
        self._thread.join(timeout=60)


class Hub(obs.Telemetry):
    def fetch(self, tree: Any) -> Any:
        with jax.profiler.TraceAnnotation("host.fetch"):
            self.tracer.drain()
            return super().fetch(tree)


class AnnotatedStream:
    """Wraps ``Trainer.stream``: the program's ``get_batch`` runs inside a
    ``host.input`` annotation."""

    def __init__(self, stream):
        self.stream = stream

    def get_batch(self, step: int):
        with jax.profiler.TraceAnnotation("host.input", step=step):
            return self.stream.get_batch(step)


class CompileClock:
    """Counts the programs JAX builds: backend compilations (``count``,
    with their seconds in ``secs``) and, in ``builds``, also every trace
    and lowering to MLIR, which a persistent-cache hit still pays."""

    def __init__(self):
        from jax._src import dispatch
        self.compile_event = dispatch.BACKEND_COMPILE_EVENT
        self.build_events = (dispatch.JAXPR_TRACE_EVENT,
                             dispatch.JAXPR_TO_MLIR_MODULE_EVENT,
                             dispatch.BACKEND_COMPILE_EVENT)
        self.count = 0
        self.builds = 0
        self.secs = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration_secs: float, **_) -> None:
        if event in self.build_events:
            self.builds += 1
        if event == self.compile_event:
            self.count += 1
            self.secs += duration_secs


def make_hub(tracer: Optional[WatchTracer] = None) -> Tuple[Hub,
                                                            WatchTracer]:
    tracer = tracer or WatchTracer()
    return Hub(sinks=[obs.RingSink()], tracer=tracer), tracer
