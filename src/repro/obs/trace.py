"""Span tracing: host-side timed spans with optional device fencing and
Chrome-trace-event export (DESIGN.md §2.7).

A :class:`Tracer` collects ``"X"`` (complete) events from ``with
tracer.span("train/step")`` blocks.  Spans measure *host* wall-clock by
default — under JAX's async dispatch that is dispatch time, not device
time.  Fencing closes the gap: ``span(...)`` yields a handle whose
``fence(value)`` registers a jax value to ``block_until_ready`` at span
exit, either always (``fence="always"``) or only when the tracer was
built with ``fence=True`` (the ``--trace-fence`` flag; ``fence="auto"``,
the default).  Unfenced spans are nearly free; fenced spans serialize
the pipeline they measure — that trade is the point of the flag.

Each span is also a ``jax.profiler.TraceAnnotation`` of the same name and
args, so under a ``jax.profiler`` trace it lands on the host plane, on
the device trace's clock.  Outside a profiler trace an annotation records
nothing.

:func:`to_chrome` emits the Chrome trace-event JSON format
(``{"traceEvents": [{"ph": "X", "ts": µs, "dur": µs, ...}]}``), which
loads directly in Perfetto / ``chrome://tracing``.  Nesting is implied
by time containment per (pid, tid) track, so properly nested host spans
render as a flame graph with no extra bookkeeping.

:func:`fenced_time` is the one fenced-timer helper shared by
``benchmarks/common.time_fn`` and the telemetry layer, so BENCH rows and
telemetry spans are the same numbers.
"""
from __future__ import annotations

import contextlib
import json
import threading
import time
from collections import deque
from typing import Any, Callable, Dict, Iterator, List, Optional

MAX_EVENTS = 1 << 16   # ring-bounded: long runs keep the newest spans


class _SpanHandle:
    """Yielded by :meth:`Tracer.span`; lets the block attach result
    values to fence on and extra args recorded into the event."""

    __slots__ = ("value", "mode", "args")

    def __init__(self, args: Dict[str, Any]):
        self.value = None
        self.mode = "auto"
        self.args = args

    def fence(self, value: Any, mode: str = "auto") -> Any:
        """Register ``value`` to ``jax.block_until_ready`` at span exit.
        ``mode``: "auto" fences only when the tracer has fencing on
        (``--trace-fence``); "always" fences unconditionally; "never"
        drops a previously registered value.  Returns ``value``."""
        self.value = value if mode != "never" else None
        self.mode = mode
        return value


class Tracer:
    """Collects timed span events; thread-safe; export via
    :meth:`to_chrome` / :meth:`save`.  A subclass that overrides
    :meth:`span` owns its profiler annotation: the base span then opens
    none, so a span is never annotated twice."""

    def __init__(self, fence: bool = False, max_events: int = MAX_EVENTS):
        self.fence = fence
        self._annotate = type(self).span is Tracer.span
        self.events: deque = deque(maxlen=max_events)
        self._origin = time.perf_counter()
        self._lock = threading.Lock()
        self._tids: Dict[int, int] = {}

    # ------------------------------------------------------------------
    def _tid(self) -> int:
        ident = threading.get_ident()
        with self._lock:
            if ident not in self._tids:
                self._tids[ident] = len(self._tids)
            return self._tids[ident]

    @contextlib.contextmanager
    def span(self, name: str, **args) -> Iterator[_SpanHandle]:
        """Time a block as one complete ("X") event.  ``args`` become the
        event's ``args`` payload (shown on click in Perfetto) and the
        profiler annotation's."""
        handle = _SpanHandle(dict(args))
        annotation = contextlib.nullcontext()
        if self._annotate:
            import jax
            annotation = jax.profiler.TraceAnnotation(name, **args)
        t0 = time.perf_counter()
        with annotation:
            try:
                yield handle
            finally:
                if handle.value is not None and (
                        handle.mode == "always" or self.fence):
                    import jax
                    jax.block_until_ready(handle.value)
                t1 = time.perf_counter()
                self.events.append({
                    "name": name,
                    "t0": t0 - self._origin,
                    "dur": t1 - t0,
                    "tid": self._tid(),
                    "args": handle.args,
                })

    def add_event(self, name: str, t0: float, dur: float,
                  **args) -> None:
        """Record an externally timed span (``t0`` in perf_counter
        seconds — e.g. from :func:`fenced_time`'s inner loop)."""
        self.events.append({"name": name, "t0": t0 - self._origin,
                            "dur": dur, "tid": self._tid(),
                            "args": dict(args)})

    # ------------------------------------------------------------------
    def to_chrome(self) -> Dict[str, Any]:
        """Chrome trace-event JSON (Perfetto / about:tracing loadable)."""
        return {
            "displayTimeUnit": "ms",
            "traceEvents": [
                {"name": e["name"], "ph": "X", "pid": 0, "tid": e["tid"],
                 "ts": round(e["t0"] * 1e6, 3),
                 "dur": round(e["dur"] * 1e6, 3),
                 "cat": e["name"].split("/", 1)[0],
                 "args": e["args"]}
                for e in self.events],
        }

    def save(self, path: str) -> str:
        with open(path, "w") as f:
            json.dump(self.to_chrome(), f)
        return path


# ---------------------------------------------------------------------------
# The shared fenced timer (benchmarks/common.time_fn delegates here)
# ---------------------------------------------------------------------------
def fenced_time(fn: Callable, *args, iters: int = 10, warmup: int = 2,
                name: Optional[str] = None,
                tracer: Optional[Tracer] = None, **kwargs) -> float:
    """Median wall-clock **microseconds** per call, each call fenced with
    ``jax.block_until_ready`` — the one timing loop benchmarks and the
    telemetry layer share.  With ``tracer`` (and ``name``) every timed
    call is also recorded as a span, so BENCH rows and trace timelines
    come from the same measurements."""
    import jax
    for _ in range(max(warmup, 0)):
        jax.block_until_ready(fn(*args, **kwargs))
    times: List[float] = []
    for i in range(max(iters, 1)):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args, **kwargs))
        dt = time.perf_counter() - t0
        times.append(dt)
        if tracer is not None and name is not None:
            tracer.add_event(name, t0, dt, iter=i)
    times.sort()
    return times[len(times) // 2] * 1e6
