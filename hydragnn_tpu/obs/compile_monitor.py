"""Compile-event monitor: count XLA compiles as they happen.

Serving already proves "zero unexpected recompiles" because its bucket
cache counts compiles explicitly; training had no equivalent — a
silently recompiling train step (shape drift, weak-type flip, donation
mismatch) just reads as a mysteriously slow epoch. This hooks
``jax.monitoring``'s duration-event stream, on which jax records every
backend compile (``/jax/core/compile/backend_compile_duration``), so
the train loop can record per-epoch compile counts in the flight
record and assert "no recompile after step 1" the way serving does.

jax has no listener-unregister API in all supported versions, so ONE
process-wide dispatcher is registered lazily and forwards to whatever
monitors are currently active — starting/stopping a monitor never
mutates jax's listener list. On jax builds without ``jax.monitoring``
(or without the duration-listener hook) the monitor degrades to
``available=False``: counts stay 0 and callers treat the assertion as
unavailable rather than vacuously true; the fallback state is recorded
into the metrics registry so a flight record never silently claims
"0 compiles" from a monitor that could not listen.
"""

from __future__ import annotations

import threading
import time
from typing import Dict, List, Optional, Tuple

from hydragnn_tpu.utils import syncdebug

# the event jax's dispatch layer records around every backend compile
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

# graftsync: guarded-by=compile_monitor._active_lock
_active: List["CompileMonitor"] = []
_active_lock = syncdebug.maybe_wrap(
    threading.Lock(), "compile_monitor._active_lock"
)
_dispatcher_registered = False  # graftsync: guarded-by=compile_monitor._active_lock


def _dispatch(event: str, duration_secs: float, **kwargs) -> None:
    with _active_lock:
        monitors = list(_active)
    for m in monitors:
        m._on_event(event, duration_secs)


def _monitoring_available() -> bool:
    try:
        import jax.monitoring as mon

        return hasattr(mon, "register_event_duration_secs_listener")
    except Exception:
        return False


def _ensure_dispatcher() -> bool:
    global _dispatcher_registered
    # check-and-register under the lock: two monitors starting
    # concurrently must not both register the dispatcher, or every
    # compile would be counted twice forever (jax has no unregister)
    if not _monitoring_available():
        return False
    with _active_lock:
        if _dispatcher_registered:
            return True
        import jax.monitoring as mon

        mon.register_event_duration_secs_listener(_dispatch)
        _dispatcher_registered = True
        return True


class CompileMonitor:
    """Counts matching duration events while active.

    ``marks`` give windowed assertions: ``mark("warm")`` after the
    first step, then ``count_since("warm") == 0`` is the steady-state
    no-recompile contract. Use as a context manager or via
    start()/stop().
    """

    def __init__(
        self,
        events: Tuple[str, ...] = (BACKEND_COMPILE_EVENT,),
        registry=None,
    ):
        self._events = frozenset(events)
        self._lock = syncdebug.maybe_wrap(
            threading.Lock(), "compile_monitor.CompileMonitor._lock"
        )
        self.count = 0  # graftsync: guarded-by=compile_monitor.CompileMonitor._lock
        self.total_duration_s = 0.0  # graftsync: guarded-by=compile_monitor.CompileMonitor._lock
        # graftsync: guarded-by=compile_monitor.CompileMonitor._lock
        self.records: List[Tuple[float, str, float]] = []  # (t, event, dur)
        # graftsync: guarded-by=compile_monitor.CompileMonitor._lock
        self._marks: Dict[str, Tuple[int, float]] = {}  # (count, seconds)
        # graftsync: thread-safe=written only from the lifecycle-owning thread in start(); the dispatch thread only reads
        self.available = False
        # graftsync: thread-safe=written only from the lifecycle-owning thread in start()/stop()
        self._started = False
        if registry is not None:
            registry.gauge("obs.compile_monitor_available")
        self._registry = registry

    # -- lifecycle ---------------------------------------------------------

    def start(self) -> "CompileMonitor":
        if self._started:
            return self
        self.available = _ensure_dispatcher()
        if self.available:
            with _active_lock:
                _active.append(self)
        if self._registry is not None:
            self._registry.gauge("obs.compile_monitor_available").set(
                1 if self.available else 0
            )
        self._started = True
        return self

    def stop(self) -> None:
        if not self._started:
            return
        with _active_lock:
            if self in _active:
                _active.remove(self)
        self._started = False

    def __enter__(self) -> "CompileMonitor":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()

    # -- event sink --------------------------------------------------------

    def _on_event(self, event: str, duration_secs: float) -> None:
        if event not in self._events:
            return
        with self._lock:
            self.count += 1
            self.total_duration_s += duration_secs
            self.records.append((time.time(), event, duration_secs))

    # -- windowed queries --------------------------------------------------

    def mark(self, name: str) -> int:
        """Snapshot the current count under ``name``; returns it."""
        with self._lock:
            self._marks[name] = (self.count, self.total_duration_s)
            return self.count

    def count_since(self, name: str) -> int:
        with self._lock:
            return self.count - self._marks.get(name, (0, 0.0))[0]

    def seconds_since(self, name: str) -> float:
        with self._lock:
            return self.total_duration_s - self._marks.get(name, (0, 0.0))[1]

    def snapshot(self) -> dict:
        with self._lock:
            return {
                "available": self.available,
                "count": self.count,
                "total_duration_s": round(self.total_duration_s, 6),
            }
