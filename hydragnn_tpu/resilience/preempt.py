"""Preemption handling and the process exit-code contract.

A preemptible TPU slice gets a SIGTERM and a short grace window before
the evictor sends SIGKILL. :class:`PreemptionHandler` converts that
signal into a graceful-stop flag the train loop checks at batch
granularity; the loop then writes a final checkpoint + meta pair,
records ``preempt`` / ``run_end{status:"preempted"}`` flight events,
and raises :class:`TrainingPreempted`. :func:`run_guard` maps the
typed exceptions onto the exit codes the restart supervisor
(:mod:`hydragnn_tpu.resilience.supervisor`) classifies.

Exit codes follow sysexits where one fits (75 = EX_TEMPFAIL: retry is
reasonable; 78 = EX_CONFIG: retry is pointless):

  ===========================  ====  =========================================
  EXIT_OK                         0  run completed
  EXIT_PREEMPTED                 75  graceful SIGTERM/SIGINT stop, resumable
  EXIT_ROLLBACK_EXHAUSTED        76  non-finite sentry gave up (data/model bug)
  EXIT_CONFIG_ERROR              78  config/shape error — fail fast
  EXIT_HUNG                      79  hang watchdog aborted the process
  anything else / signal exits       crash — retried with backoff
  ===========================  ====  =========================================
"""

from __future__ import annotations

import contextlib
import os
import signal
import sys
import threading
import traceback
from typing import Optional
from hydragnn_tpu.utils import knobs

EXIT_OK = 0
EXIT_PREEMPTED = 75
EXIT_ROLLBACK_EXHAUSTED = 76
EXIT_CONFIG_ERROR = 78
EXIT_HUNG = 79


class TrainingPreempted(Exception):
    """The run was gracefully stopped by SIGTERM/SIGINT after writing a
    resumable checkpoint; re-invoking the same config resumes it."""

    exit_code = EXIT_PREEMPTED

    def __init__(self, signum: int, epoch: int):
        self.signum = int(signum)
        self.epoch = int(epoch)
        try:
            name = signal.Signals(signum).name
        except ValueError:
            name = str(signum)
        super().__init__(
            f"training preempted by {name} at epoch {epoch}; "
            "checkpoint written, resume with the same config"
        )


class NonFiniteRollbackExhausted(RuntimeError):
    """The non-finite sentry rolled back as many times as allowed (or
    had no checkpoint to roll back to) and the run still produces
    non-finite steps — deterministic data/model problem, not worth a
    restart."""

    exit_code = EXIT_ROLLBACK_EXHAUSTED


class PodHostLost(Exception):
    """A peer host of the pod was declared lost from the heartbeat view
    (resilience/podckpt.py:PodSignaler) — typically mid-commit, where
    waiting longer cannot help: the missing host's manifest will never
    arrive. Exits with the PREEMPTED code: the run is resumable from
    the last committed generation and the pod supervisor should
    restart it promptly, not burn the crash backoff budget."""

    exit_code = EXIT_PREEMPTED

    def __init__(self, lost, epoch: int):
        self.lost = sorted(int(h) for h in lost)
        self.epoch = int(epoch)
        super().__init__(
            f"pod host(s) {self.lost} declared lost at epoch {epoch}; "
            "restart from the last committed generation"
        )


class PreemptionHandler:
    """Installable SIGTERM/SIGINT -> graceful-stop flag.

    The signal handler only sets an event (async-signal-safe) and arms
    a hard-exit timer for ``grace_s`` seconds: if the graceful path
    (finish the batch, write the checkpoint, flush the flight record)
    overruns the window the evictor would enforce anyway, the process
    self-exits with :data:`EXIT_PREEMPTED` rather than dying
    checkpoint-less to the follow-up SIGKILL.

    Installation is best-effort: off the main thread (e.g. a serve
    worker driving training) ``signal.signal`` raises and the handler
    stays inert (``available`` False). ``uninstall`` restores the
    previous handlers and cancels the timer — REQUIRED before the
    process outlives the run (the train loop does this on every exit
    path).
    """

    def __init__(
        self,
        signals=(signal.SIGTERM, signal.SIGINT),
        grace_s: float = 30.0,
        hard_exit: bool = True,
    ):
        self.grace_s = float(grace_s)
        self.hard_exit = bool(hard_exit)
        # graftsync: thread-safe=written only by the signal handler, which CPython runs on the main thread; GIL-atomic int
        self.signum: Optional[int] = None
        # graftsync: thread-safe=written only from the owning thread in install()/uninstall(); the timer thread never touches it
        self.available = False
        self._signals = tuple(signals)
        self._stop = threading.Event()
        # pod coordination (resilience/podckpt.py): when the train loop
        # attaches a PodSignaler + keeps proposed_gen current, the
        # SIGTERM handler announces the preemption to peer hosts so the
        # whole pod cuts the SAME generation inside the grace window
        # graftsync: thread-safe=written by the main thread (loop setup / per-epoch update); read by the main-thread signal handler
        self.signaler = None
        self.proposed_gen = 0
        # graftsync: thread-safe=install()/uninstall() run on the owning (main) thread only
        self._old: dict = {}
        # graftsync: thread-safe=written by the main-thread signal handler and uninstall(); CPython delivers signals on the main thread
        self._timer: Optional[threading.Timer] = None

    def install(self) -> "PreemptionHandler":
        try:
            for sig in self._signals:
                self._old[sig] = signal.signal(sig, self._handle)
            self.available = True
        except ValueError:
            # not the main thread: restore whatever we managed to set
            self.uninstall()
            self.available = False
        return self

    def uninstall(self) -> None:
        for sig, old in self._old.items():
            try:
                signal.signal(sig, old)
            except ValueError:
                pass
        self._old.clear()
        self.available = False
        if self._timer is not None:
            self._timer.cancel()
            self._timer = None

    def _handle(self, signum, frame) -> None:
        self.signum = signum
        self._stop.set()
        if self.signaler is not None:
            # never raises (PodSignaler.post_preempt is exception-safe)
            self.signaler.post_preempt(self.proposed_gen, signum)
        if self.hard_exit and self._timer is None:
            t = threading.Timer(self.grace_s, self._force_exit)
            t.daemon = True
            t.start()
            self._timer = t

    # graftsync: thread-root
    def _force_exit(self) -> None:
        # runs on the timer thread after the grace window: plain write
        # (no logging machinery) then immediate exit — the evictor's
        # SIGKILL is due any moment
        try:
            os.write(
                2,
                (
                    f"PreemptionHandler: grace window ({self.grace_s}s) "
                    "exceeded; hard-exiting\n"
                ).encode(),
            )
        except OSError:
            pass
        os._exit(EXIT_PREEMPTED)

    def should_stop(self) -> bool:
        return self._stop.is_set()

    def __enter__(self) -> "PreemptionHandler":
        return self.install()

    def __exit__(self, *exc) -> None:
        self.uninstall()


@contextlib.contextmanager
def run_guard():
    """Map the typed training exceptions onto the supervisor's exit-code
    contract — wrap a driver's ``run_training`` call::

        with run_guard():
            run_training(cfg, samples=samples)

    ``ValueError`` / ``KeyError`` / ``TypeError`` / ``FileNotFoundError``
    are classified as config errors (the dominant class for a mis-built
    config or dataset path; deterministic, so the supervisor fail-fasts
    instead of burning its restart budget). Every other exception
    propagates as the generic crash the supervisor retries.
    """
    try:
        yield
    except TrainingPreempted as exc:
        raise SystemExit(exc.exit_code)
    except PodHostLost as exc:
        print(f"run_guard: {exc}", file=sys.stderr)
        raise SystemExit(exc.exit_code)
    except NonFiniteRollbackExhausted as exc:
        print(f"run_guard: {exc}", file=sys.stderr)
        raise SystemExit(exc.exit_code)
    except RuntimeError as exc:
        from hydragnn_tpu.utils.checkpoint import CheckpointFormatError
        from hydragnn_tpu.utils.platform import BackendInitError

        if isinstance(exc, BackendInitError):
            # a chip belongs to one process at a time: a backend that
            # does not come up is held by another process (a sibling of
            # this supervised child, or its parent) or misconfigured —
            # restarting into the same state cannot help
            print(
                f"run_guard: {exc} — one process per chip; fail-fast",
                file=sys.stderr,
            )
            raise SystemExit(EXIT_CONFIG_ERROR)
        if isinstance(exc, CheckpointFormatError):
            # an upgrade refusal is deterministic — retrying cannot help
            traceback.print_exc()
            print(
                "run_guard: checkpoint format refusal (fail-fast)",
                file=sys.stderr,
            )
            raise SystemExit(EXIT_CONFIG_ERROR)
        raise
    except (ValueError, KeyError, TypeError, FileNotFoundError):
        traceback.print_exc()
        print("run_guard: classified as config error (fail-fast)", file=sys.stderr)
        raise SystemExit(EXIT_CONFIG_ERROR)


def auto_resume_config(training: dict, log_name: str, log_dir: str) -> bool:
    """Supervisor resume wiring: when ``HYDRAGNN_AUTO_RESUME=1`` (set by
    the restart supervisor for every restarted child) and the run's
    checkpoint already exists, flip the config to
    ``Training.continue=1`` / ``startfrom=<log_name>`` so the restarted
    process continues instead of starting over. Returns True when the
    config was mutated."""
    if knobs.raw("HYDRAGNN_AUTO_RESUME") != "1":
        return False
    from hydragnn_tpu.utils.checkpoint import checkpoint_exists

    if not checkpoint_exists(log_name, log_dir):
        return False
    training["continue"] = 1
    training.setdefault("startfrom", log_name)
    return True
