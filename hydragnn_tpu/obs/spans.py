"""Host-side span tracing: where does a train step's wall time go?

Decomposes each epoch's steps into the three places wall time hides:

  - **data-wait** — time the consumer blocks on the loader (host
    batching + H2D that the prefetch thread failed to hide);
  - **host-dispatch** — time inside the jitted call before it returns
    (async: tracing/arg handling; on step 0 this includes the compile);
  - **device-execute** — sampled: for a small window of steps per epoch
    the step's outputs are ``block_until_ready``-ed and the extra wait
    beyond dispatch is recorded. Only the window pays the sync; every
    steady-state step stays fully async, so instrumented training keeps
    the device-sync discipline the train loop documents.

This makes the "wall is several times device time" class of gap a
measured, per-epoch number: ``epoch_snapshot`` feeds the
flight recorder (``hydragnn_tpu/obs/flight.py``) and tensorboard.

:func:`span` is the program's ONE span primitive (docs/OBSERVABILITY.md
"Program spans"): every phase of set-up and of an epoch, and in
per-step mode every loader wait and step, runs under one. A span is a
``jax.profiler.TraceAnnotation`` — under any live capture it lies on the
device trace's clock — and an entry (seconds, count, parent) in an
in-memory table that the train loop flushes into the flight record at
the end of set-up and at each epoch boundary. :func:`count` keeps
counters beside it, flushed at the same boundaries.
"""

from __future__ import annotations

import contextlib
import threading
import time
from typing import Any, Dict, Iterable, Iterator, Optional

import jax

from hydragnn_tpu.obs.registry import telemetry_enabled
from hydragnn_tpu.utils import syncdebug

_LOCK = syncdebug.maybe_wrap(threading.Lock(), "spans._LOCK")
_PHASES: Dict[str, list] = {}  # graftsync: guarded-by=spans._LOCK
_COUNTS: Dict[str, float] = {}  # graftsync: guarded-by=spans._LOCK
_OPEN = threading.local()  # .stack: names of this thread's open spans
_NULL_SPAN = contextlib.nullcontext()


class _Span:
    __slots__ = ("name", "_annotation", "_parent", "_t0")

    def __init__(self, name: str, attrs: Dict[str, Any]):
        self.name = name
        self._annotation = jax.profiler.TraceAnnotation(name, **attrs)

    def __enter__(self) -> "_Span":
        stack = getattr(_OPEN, "stack", None)
        if stack is None:
            stack = _OPEN.stack = []
        self._parent = stack[-1] if stack else None
        stack.append(self.name)
        self._annotation.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        seconds = time.perf_counter() - self._t0
        self._annotation.__exit__(*exc)
        _OPEN.stack.pop()
        with _LOCK:
            entry = _PHASES.setdefault(self.name, [0.0, 0, self._parent])
            entry[0] += seconds
            entry[1] += 1


def span(name: str, **attrs):
    """Context manager around one phase of the program. Adds no host
    sync. With ``HYDRAGNN_TELEMETRY=0`` it is one shared null context."""
    if not telemetry_enabled():
        return _NULL_SPAN
    return _Span(name, attrs)


def span_iter(iterable: Iterable, name: str) -> Iterator:
    """Yield from ``iterable`` with each wait for its next item (a
    loader's host batching and transfer) under the span ``name``."""
    it = iter(iterable)
    while True:
        with span(name):
            try:
                item = next(it)
            except StopIteration:
                return
        yield item


def count(name: str, n: float) -> None:
    """Add ``n`` to the counter ``name`` (``graphs``, ``steps``,
    ``diagnosed_steps``)."""
    if telemetry_enabled():
        with _LOCK:
            _COUNTS[name] = _COUNTS.get(name, 0) + n


def drain() -> Dict[str, Dict[str, Any]]:
    """The spans closed since the last drain, ``{name: {"s", "n",
    "parent"}}``, and an empty table after it."""
    with _LOCK:
        phases = {
            k: {"s": round(s, 6), "n": n, "parent": parent}
            for k, (s, n, parent) in _PHASES.items()
        }
        _PHASES.clear()
    return phases


def drain_counts() -> Dict[str, float]:
    with _LOCK:
        counts = dict(_COUNTS)
        _COUNTS.clear()
    return counts


class StepSpans:
    """Per-epoch span accumulator for the per-step training path.

    Usage (the train loop's shape):

        spans.epoch_start(epoch)
        for batch in spans.timed_iter(loader):
            out = spans.step(train_step, state, batch)
        record = spans.epoch_snapshot()

    ``sample_steps`` steps per epoch (after ``skip_first``, which skips
    the compile step) are synchronously fenced to sample device time.
    Use :meth:`disabled` for the inert variant — ``timed_iter`` returns
    its argument unchanged and ``step`` is a direct call, so the off
    path adds no per-step timing syscalls or allocations.
    """

    def __init__(self, sample_steps: int = 3, skip_first: int = 1, tracer=None):
        self.sample_steps = sample_steps
        self.skip_first = skip_first
        self.enabled = True
        self.epoch = -1
        # optional obs/trace.py Tracer: each sampled sync step is also
        # emitted as a one-span trace keyed (epoch, step), joining the
        # train timeline with serve request traces
        self.tracer = tracer
        # deterministic straggler injection (HYDRAGNN_INJECT_STRAGGLER=
        # "HOST:MS"): when this process IS the named podview host, every
        # step sleeps MS — inflating its host_epoch summary so the
        # rank-0 SkewMonitor's step_skew rule has a real signal
        self._straggle_s = 0.0
        from hydragnn_tpu.obs import podview

        spec = podview.straggler_spec()
        if spec is not None and spec[0] == podview.host_identity()[0]:
            self._straggle_s = spec[1]
        # (process_index, process_count) stamped into epoch snapshots;
        # resolved lazily so construction never forces backend init
        self._host_identity: Optional[tuple] = None
        self._reset()

    @staticmethod
    def disabled() -> "_NullSpans":
        return _NULL_SPANS

    def _reset(self) -> None:
        self.steps = 0
        self.data_wait_s = 0.0
        self.dispatch_s = 0.0
        self.first_step_s = 0.0
        self.sampled = 0
        self.device_wait_s = 0.0
        self.sync_step_s = 0.0

    def epoch_start(self, epoch: int) -> None:
        self.epoch = epoch
        self._reset()

    # -- recording ---------------------------------------------------------

    def timed_iter(self, iterable: Iterable) -> Iterator:
        """Yield from ``iterable``, accumulating the time this consumer
        spends blocked waiting for the next batch."""
        it = span_iter(iterable, "train.loader_wait")
        while True:
            t0 = time.perf_counter()
            try:
                item = next(it)
            except StopIteration:
                return
            self.data_wait_s += time.perf_counter() - t0
            yield item

    def step(self, fn, *args) -> Any:
        """Run one train step, recording dispatch time; inside the
        sampling window, fence the outputs and record device wait."""
        with span("train.step", step=self.steps):
            return self._step(fn, *args)

    def _step(self, fn, *args) -> Any:
        t0 = time.perf_counter()
        if self._straggle_s:
            time.sleep(self._straggle_s)
        sampling = (
            self.skip_first <= self.steps < self.skip_first + self.sample_steps
        )
        if sampling:
            from hydragnn_tpu.utils.profile import capture_active

            # a live profiler capture (incident or epoch-gated) must
            # see the step as it actually runs: the sync fence would
            # serialize the very window being profiled, so the sample
            # is skipped outright, not deferred
            sampling = not capture_active()
        if sampling:
            with span("obs.sampled_sync_step"):
                out = fn(*args)
                t1 = time.perf_counter()
                jax.block_until_ready(out)
            t2 = time.perf_counter()
            self.dispatch_s += t1 - t0
            self.device_wait_s += t2 - t1
            self.sync_step_s += t2 - t0
            self.sampled += 1
            if self.tracer is not None:
                tr = self.tracer.begin(seq=self.steps, epoch=self.epoch)
                if tr is not None:
                    now = time.time()
                    tr.add_span(
                        "train.sampled_step",
                        now - (t2 - t0),
                        now,
                        epoch=self.epoch,
                        step=self.steps,
                        dispatch_ms=round((t1 - t0) * 1e3, 3),
                        device_wait_ms=round((t2 - t1) * 1e3, 3),
                    )
                    self.tracer.finish(tr)
        else:
            out = fn(*args)
            dt = time.perf_counter() - t0
            self.dispatch_s += dt
            if self.steps == 0:
                self.first_step_s = dt  # includes trace + compile
        self.steps += 1
        return out

    # -- export ------------------------------------------------------------

    def epoch_snapshot(self) -> dict:
        """One epoch's breakdown, flight-record-ready. Millisecond
        per-step means; seconds for the epoch totals."""
        sampled = max(self.sampled, 1) if self.sampled else 0
        if self._host_identity is None:
            from hydragnn_tpu.obs import podview

            self._host_identity = podview.host_identity()
        return {
            "steps": self.steps,
            "process_index": self._host_identity[0],
            "process_count": self._host_identity[1],
            "data_wait_s": round(self.data_wait_s, 6),
            "dispatch_s": round(self.dispatch_s, 6),
            "first_step_s": round(self.first_step_s, 6),
            "sampled_steps": self.sampled,
            "device_wait_ms_mean": (
                round(self.device_wait_s / sampled * 1e3, 3) if sampled else None
            ),
            "sync_step_ms_mean": (
                round(self.sync_step_s / sampled * 1e3, 3) if sampled else None
            ),
        }


class _NullSpans(StepSpans):
    """Telemetry-off spans: structurally a StepSpans (callers need no
    gate) but every hook is free — ``timed_iter`` IS the identity and
    ``step`` a direct call, pinned by tests/test_obs.py."""

    def __init__(self):
        super().__init__(sample_steps=0)
        self.enabled = False

    def epoch_start(self, epoch: int) -> None:
        self.epoch = epoch

    def timed_iter(self, iterable: Iterable) -> Iterable:
        return iterable

    def step(self, fn, *args) -> Any:
        return fn(*args)

    def epoch_snapshot(self) -> Optional[dict]:
        return None


_NULL_SPANS = _NullSpans()
