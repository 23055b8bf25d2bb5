"""Fault-tolerant training: the machinery that keeps the north-star
workload alive on preemptible hardware without a human in the loop
(docs/RESILIENCE.md).

The repo already had exact resume and crash-consistent meta repair
(train/loop.py, utils/checkpoint.py); this package DRIVES that
machinery when a run is dying:

  - :mod:`~hydragnn_tpu.resilience.preempt` — SIGTERM/SIGINT ->
    graceful-stop flag checked at batch granularity; final checkpoint
    + ``run_end{status:"preempted"}`` within a grace window; the
    process exit-code contract (``EXIT_*``) and :func:`run_guard`.
  - :mod:`~hydragnn_tpu.resilience.sentry` — host-side policy over the
    on-device non-finite guard folded into the jitted train step
    (``make_train_step(guard_nonfinite=True)``): skipped-batch
    accounting and the roll-back-to-last-good-checkpoint decision.
  - :mod:`~hydragnn_tpu.resilience.watchdog` — heartbeat thread that
    dumps every Python thread's stack into the flight record and
    aborts when the loop stalls (stuck dispatch / collective /
    data-wait).
  - :mod:`~hydragnn_tpu.resilience.supervisor` — bounded restart
    supervisor (``tools/supervise.py``): exponential backoff,
    exit-cause classification, fail-fast on config errors; the
    pod-level variant (``PodSupervisor``, ``tools/supervise.py
    --pod N``) supervises N simulated hosts as one unit with
    ``host_lost`` classed for prompt restart and optional elastic
    N-1 recovery.
  - :mod:`~hydragnn_tpu.resilience.podckpt` — sharded pod checkpoints
    with a generation commit protocol (per-host shard + sha sidecar +
    manifest, rank-0 ``gen<N>.COMMIT`` written LAST), filesystem
    heartbeats/preemption coordination (``PodSignaler``), and elastic
    restore that re-shards a committed generation across a different
    host count.
  - :mod:`~hydragnn_tpu.resilience.pod` — ``PodPlane``: everything one
    training run does because it is one host of several (podview's
    shards and skew, podckpt's heartbeats, cuts and generations), behind
    the one object ``train/loop.py`` calls at its epoch boundaries.
  - :mod:`~hydragnn_tpu.resilience.inject` — env-gated deterministic
    fault injection (NaN batch, SIGTERM, SIGKILL mid-checkpoint,
    stalled producer) so every path above is testable, not decorative.
  - :mod:`~hydragnn_tpu.resilience.hooks` — the small per-batch hook
    bundle ``train/loop.py`` threads through the hot loop.

Everything flows into the existing flight recorder
(:mod:`hydragnn_tpu.obs.flight`); ``tools/obs_report.py --faults``
narrates a run's fault history.
"""

from hydragnn_tpu.resilience.preempt import (
    EXIT_CONFIG_ERROR,
    EXIT_HUNG,
    EXIT_OK,
    EXIT_PREEMPTED,
    EXIT_ROLLBACK_EXHAUSTED,
    NonFiniteRollbackExhausted,
    PodHostLost,
    PreemptionHandler,
    TrainingPreempted,
    auto_resume_config,
    run_guard,
)
from hydragnn_tpu.resilience.sentry import NonFiniteSentry
from hydragnn_tpu.resilience.watchdog import HangWatchdog, dump_thread_stacks
from hydragnn_tpu.resilience.supervisor import (
    FAIL_FAST_CAUSES,
    PodSupervisor,
    Supervisor,
    SupervisorPolicy,
    classify_exit,
    classify_pod_exit,
)
from hydragnn_tpu.resilience.hooks import TrainHooks

__all__ = [
    "EXIT_OK",
    "EXIT_PREEMPTED",
    "EXIT_ROLLBACK_EXHAUSTED",
    "EXIT_CONFIG_ERROR",
    "EXIT_HUNG",
    "TrainingPreempted",
    "NonFiniteRollbackExhausted",
    "PreemptionHandler",
    "auto_resume_config",
    "run_guard",
    "NonFiniteSentry",
    "HangWatchdog",
    "dump_thread_stacks",
    "Supervisor",
    "SupervisorPolicy",
    "PodSupervisor",
    "PodHostLost",
    "FAIL_FAST_CAUSES",
    "classify_exit",
    "classify_pod_exit",
    "TrainHooks",
]
