"""Epoch-gated profiler over ``jax.profiler`` tensorboard traces.

Reference: hydragnn/utils/profile.py:9-70 — a torch.profiler subclass with
schedule wait=5/warmup=3/active=3 gated to one target epoch, writing
tensorboard traces, configured from ``NeuralNetwork.Profile``
({"enable": 1, "target_epoch": E}) and driven by the train loop
(set_current_epoch / context manager around the epoch / step per batch).

The JAX profiler traces a time window rather than a step schedule, so the
schedule is emulated: within the target epoch, tracing starts after
``wait + warmup`` steps and stops after ``active`` more. Traces land in
``<prefix>/plugins/profile`` and open in TensorBoard / XProf (including
TPU HLO timelines when run on TPU).
"""

from __future__ import annotations

import os
import threading
from typing import Optional

import jax

from hydragnn_tpu.utils import syncdebug

# jax allows ONE active profiler trace per process; this slot is the
# arbiter between the epoch-gated Profiler below and incident captures
# (obs/triggers.py), and the signal obs/spans.py uses to suppress its
# sampled block_until_ready fence while a capture is live (the fence
# would serialize the very step being profiled). The slot is
# tri-state — "idle" / "active" / "stopping" — because both
# start_trace and stop_trace block (device sync) and must run OUTSIDE
# the lock, yet the slot has to stay busy through them: a two-state
# flag cleared before stop_trace() returns would let a concurrent
# try_start_capture start a trace the old owner's stop then kills.
_CAPTURE_LOCK = syncdebug.maybe_wrap(threading.Lock(), "profile._CAPTURE_LOCK")
_CAPTURE_STATE = "idle"  # graftsync: guarded-by=profile._CAPTURE_LOCK


def capture_active() -> bool:
    """Whether a jax profiler trace is being captured (or torn down)."""
    with _CAPTURE_LOCK:
        return _CAPTURE_STATE != "idle"


def try_start_capture(prefix: str) -> bool:
    """Start a jax profiler trace into ``prefix`` if no capture is
    live; returns whether this caller now owns the capture. Refusal
    (not an exception) is the contract — an incident firing during the
    epoch-gated profiler's window simply captures nothing."""
    global _CAPTURE_STATE
    with _CAPTURE_LOCK:
        if _CAPTURE_STATE != "idle":
            return False
        _CAPTURE_STATE = "active"
    try:
        os.makedirs(prefix, exist_ok=True)
        jax.profiler.start_trace(prefix)
    except Exception:
        with _CAPTURE_LOCK:
            _CAPTURE_STATE = "idle"
        return False
    return True


def stop_capture() -> None:
    """Stop the live capture (no-op when none is). The slot stays busy
    ("stopping") until stop_trace returns, so a concurrent
    try_start_capture cannot start a trace this teardown would kill."""
    global _CAPTURE_STATE
    with _CAPTURE_LOCK:
        if _CAPTURE_STATE != "active":
            return
        _CAPTURE_STATE = "stopping"
    try:
        jax.profiler.stop_trace()
    finally:
        with _CAPTURE_LOCK:
            _CAPTURE_STATE = "idle"


class Profiler:
    def __init__(
        self,
        prefix: str = "",
        enable: bool = False,
        target_epoch: int = 0,
        wait: int = 5,
        warmup: int = 3,
        active: int = 3,
    ):
        self.prefix = prefix or "./logs/profile"
        self.enable = enable
        self.target_epoch = target_epoch
        self.current_epoch = -1
        self.wait = wait
        self.warmup = warmup
        self.active = active
        self.done = False
        self._step_in_epoch = 0
        self._tracing = False
        # observer hook: called as on_trace(prefix, epoch) when a trace
        # window closes — the train loop points it at the run flight
        # recorder so the trace artifact is discoverable from the run's
        # event log (hydragnn_tpu/obs/flight.py "profile_trace" events)
        self.on_trace = None

    def setup(self, config: dict) -> None:
        """Configure from the ``Profile`` config section (reference keys:
        ``enable``, ``target_epoch``; profile.py:32-42). ``enable``
        accepts 1/"1"/True (JSON configs vary)."""
        self.enable = str(config.get("enable", 0)).lower() in ("1", "true")
        self.target_epoch = int(config.get("target_epoch", 0))

    def set_current_epoch(self, current_epoch: int) -> None:
        self.current_epoch = current_epoch
        self._step_in_epoch = 0

    @property
    def _armed(self) -> bool:
        return (
            self.enable
            and not self.done
            and self.current_epoch == self.target_epoch
        )

    def step(self) -> None:
        """Call once per training batch (reference: profiler.step() in the
        hot loop, train_validate_test.py:362)."""
        if not self._armed:
            return
        self._step_in_epoch += 1
        start_at = self.wait + self.warmup
        if not self._tracing and self._step_in_epoch == start_at:
            self._tracing = try_start_capture(self.prefix)
        elif self._tracing and self._step_in_epoch >= start_at + self.active:
            self._stop()

    def _stop(self) -> None:
        if self._tracing:
            stop_capture()
            self._tracing = False
            self.done = True
            print(f"Profiler trace written to {self.prefix} (epoch {self.target_epoch})")
            if self.on_trace is not None:
                self.on_trace(self.prefix, self.target_epoch)

    def __enter__(self) -> "Profiler":
        return self

    def __exit__(self, exc_type, exc_value, tb) -> bool:
        # end of the epoch: close an in-flight trace even if the epoch had
        # fewer steps than wait+warmup+active
        self._stop()
        return False

    def reset(self) -> None:
        self._step_in_epoch = 0
        self.done = False


def trace_annotation(name: str):
    """Named span inside jitted/host code for the profiler timeline — the
    analog of torch.profiler.record_function spans
    (reference: train_validate_test.py:349-358) and the gptl4py/nvtx shim
    (reference: hydragnn/utils/gptl4py_dummy.py). One more name for
    ``obs/spans.py:span``, the one place that opens an annotation."""
    from hydragnn_tpu.obs.spans import span

    return span(name)


def scan_slope_ms(make_chain, k1: int, k2: int) -> float:
    """Per-iteration time (ms) of a K-chained computation by the
    scan-slope protocol: time the chain at two lengths and take the
    slope — cancels the per-dispatch overhead, which would otherwise
    swamp sub-ms ops. ``make_chain(k)`` returns a zero-arg callable that
    runs the k-chained computation and blocks on a D2H readback
    (``np.asarray`` of a chain-dependent value). The caller must treat a
    non-positive slope as noise, not data."""
    import time

    times = {}
    for k in (k1, k2):
        run = make_chain(k)
        run()  # compile + warmup
        t0 = time.perf_counter()
        run()
        times[k] = time.perf_counter() - t0
    return (times[k2] - times[k1]) / (k2 - k1) * 1e3
