"""Env-gated deterministic fault injection.

Every resilience path is only trustworthy if it can be driven on
demand; these hooks make each failure mode a reproducible test case
(tests/test_resilience.py, ci.sh fault-injection smoke stage) instead
of a production anecdote. All hooks are no-ops unless their env var is
set, and the restart supervisor strips ``HYDRAGNN_INJECT_*`` from
restarted children by default so an injected fault fires exactly once
per supervised run.

  =================================  ==========================================
  HYDRAGNN_INJECT_NAN_STEP=N[:M]     replace the batch's node features with
                                     NaN for train steps N..N+M-1 (M=1)
  HYDRAGNN_INJECT_SIGTERM_STEP=N     SIGTERM self-signal before train step N
  HYDRAGNN_INJECT_SIGTERM_EPOCH=E    SIGTERM self-signal at the start of
                                     epoch E (the epoch-boundary case)
  HYDRAGNN_INJECT_KILL_CHECKPOINT=K  during the K-th (1-indexed) checkpoint
                                     save of this process: write a TRUNCATED
                                     checkpoint file in place (simulating a
                                     torn write on a filesystem without
                                     atomic replace) and SIGKILL the process
  HYDRAGNN_INJECT_STALL_LOADER=B:S   the loader's producer sleeps S seconds
                                     before building batch B of an epoch
                                     (drives the hang watchdog)
  HYDRAGNN_INJECT_DONATION_CHECK_    force the persistent executable cache's
  FAIL=1                             donation round-trip gate to report
                                     failure (checked directly in
                                     utils/exec_cache.py:donation_roundtrip_ok)
                                     — a donated cached executable is then
                                     EVICTED with a ``donation_check_failed``
                                     miss and the consumer live-compiles
  HYDRAGNN_INJECT_TRIGGER=RULE       force-fire the named SLO trigger rule
                                     once at the next TriggerEngine.evaluate
                                     (obs/triggers.py) — drives the incident
                                     capture path without waiting for a real
                                     anomaly
  =================================  ==========================================

Serving-side faults (docs/RESILIENCE.md "Serving resilience"; request
numbers are the server's admission sequence, 0-based, so an injection
follows its request through batch coalescing AND the retry-as-singles
poison hunt):

  =====================================  ======================================
  HYDRAGNN_INJECT_SERVE_RAISE=N          the forward raises for any batch
                                         containing request N (poison request)
  HYDRAGNN_INJECT_SERVE_NAN=N            the forward's outputs are replaced
                                         with NaN for any batch containing
                                         request N (silent-corruption poison)
  HYDRAGNN_INJECT_SERVE_WEDGE=N:S        the dispatch thread sleeps S seconds
                                         (default 5) inside the forward of the
                                         batch containing request N (wedged
                                         dispatch — drives the serve watchdog)
  HYDRAGNN_INJECT_SERVE_KILL_DISPATCH=K  the K-th (1-indexed) dispatched batch
                                         raises OUTSIDE request isolation,
                                         killing the dispatch thread (drives
                                         the dispatch supervisor restart)
  HYDRAGNN_INJECT_SERVE_TORN_RELOAD=1    ModelServer.reload corrupts the
                                         candidate weights to NaN before the
                                         canary (the canary must fail and the
                                         old weights must keep serving)
  HYDRAGNN_INJECT_DRIFT=SHIFT            add a deterministic covariate shift
                                         of SHIFT (a float) to every incoming
                                         request's node features at admission
                                         (drives the feature_drift trigger +
                                         spool path; obs/drift.py)
  =====================================  ======================================

Retrain-pilot faults (hydragnn_tpu/pilot, docs/RESILIENCE.md "Closed
loop") — one per pilot stage, each proving the loop degrades to "old
weights keep serving" instead of making serving worse:

  =====================================  ======================================
  HYDRAGNN_INJECT_PILOT_TRAIN_CRASH=N    the pilot's first N fine-tune attempts
                                         exit nonzero before training (N=1:
                                         retry-with-backoff then success; N >=
                                         the attempt budget: failed cycle)
  HYDRAGNN_INJECT_PILOT_HUNG_TUNE=S      the fine-tune job wedges S seconds
                                         before any work (the supervisor
                                         wall-clock kill classifies hung/79)
  HYDRAGNN_INJECT_PILOT_CANARY_REGRESS   inflate the candidate's canary scores
  =1                                     so the gate rejects it (cooldown on
                                         the old weights, never a reload)
  HYDRAGNN_INJECT_PILOT_TORN_RELOAD=1    corrupt the candidate's weights
                                         between the pilot canary and the
                                         reload (the server's own reload
                                         canary must reject them)
  =====================================  ======================================

Pod faults (resilience/podckpt.py, docs/RESILIENCE.md "Pod recovery")
— each anchored to a (host, step-like) pair so exactly one simulated
host misbehaves at exactly one point, and provable both in-process
(tests/test_podckpt.py) and end-to-end (ci.sh pod-recovery smoke):

  =====================================  ======================================
  HYDRAGNN_INJECT_POD_KILL_HOST=H:G      host H SIGKILLs itself during the
                                         generation-G pod checkpoint save,
                                         AFTER its shard bytes land but BEFORE
                                         its manifest — generation G can never
                                         commit (the torn-generation case)
  HYDRAGNN_INJECT_POD_TORN_SHARD=H:G     host H's generation-G shard is
                                         written truncated while its sha256
                                         sidecar carries the good digest —
                                         restore must reject the shard by
                                         checksum and fall back a generation
  HYDRAGNN_INJECT_POD_LOST_HEARTBEAT=    host H stops writing heartbeat files
  H:E                                    from epoch E on (alive but silent —
                                         what a wedged host looks like from
                                         outside; drives host_lost detection)
  HYDRAGNN_INJECT_POD_BARRIER_STALL=H:S  host H sleeps S seconds before
                                         entering any pod_barrier (once per
                                         process) — peers must time out,
                                         proceed, and record the stall
  =====================================  ======================================

Step numbers are process-local dispatch counts (0-based, counted by
``TrainHooks``), so injections are deterministic regardless of resume
state.
"""

from __future__ import annotations

import os
import signal
import time
from typing import Optional, Tuple

from hydragnn_tpu.utils import knobs


def _spec(name: str) -> Optional[str]:
    v = knobs.raw(name)
    return v if v else None


def _two_ints(spec: str, default_second: int) -> Tuple[int, int]:
    parts = spec.split(":")
    a = int(parts[0])
    b = int(parts[1]) if len(parts) > 1 and parts[1] else default_second
    return a, b


def maybe_nan_batch(batch, step: int):
    """Return ``batch`` with NaN node features when step is inside the
    injected window, else the batch unchanged."""
    spec = _spec("HYDRAGNN_INJECT_NAN_STEP")
    if spec is None:
        return batch
    start, count = _two_ints(spec, 1)
    if not start <= step < start + count:
        return batch
    import numpy as np

    nodes = np.full_like(np.asarray(batch.nodes), np.nan)
    return batch.replace(nodes=nodes)


def maybe_sigterm(step: Optional[int] = None, epoch: Optional[int] = None) -> None:
    """Self-SIGTERM at the injected step or epoch boundary."""
    if step is not None:
        spec = _spec("HYDRAGNN_INJECT_SIGTERM_STEP")
        if spec is not None and step == int(spec):
            os.kill(os.getpid(), signal.SIGTERM)
    if epoch is not None:
        spec = _spec("HYDRAGNN_INJECT_SIGTERM_EPOCH")
        if spec is not None and epoch == int(spec):
            os.kill(os.getpid(), signal.SIGTERM)


# graftsync: thread-safe=fault-injection counter bumped only by the single checkpoint-writing thread
_CHECKPOINT_SAVES = 0


def maybe_kill_checkpoint(path: str, payload: str) -> None:
    """During the K-th checkpoint save: leave ``path`` TRUNCATED (half
    the payload, the file ``payload`` holds whole, written directly —
    deliberately bypassing the normal tmp-file + atomic-replace
    discipline, like a filesystem that tears writes on power loss) and
    SIGKILL the process. The restart must then reject the truncated file
    and restore the previous good one — the integrity-validation path
    this exists to prove."""
    spec = _spec("HYDRAGNN_INJECT_KILL_CHECKPOINT")
    if spec is None:
        return
    global _CHECKPOINT_SAVES
    _CHECKPOINT_SAVES += 1
    if _CHECKPOINT_SAVES != int(spec):
        return
    with open(payload, "rb") as f:
        data = f.read()
    with open(path, "wb") as f:
        f.write(data[: max(len(data) // 2, 1)])
        f.flush()
        os.fsync(f.fileno())
    os.kill(os.getpid(), signal.SIGKILL)


def maybe_stall_loader(batch_index: int) -> None:
    """Sleep in the loader's producer before building the injected
    batch index (per epoch)."""
    spec = _spec("HYDRAGNN_INJECT_STALL_LOADER")
    if spec is None:
        return
    b, seconds = _two_ints(spec, 3600)
    if batch_index == b:
        time.sleep(seconds)


def maybe_serve_raise(seqs) -> None:
    """Raise inside the serving forward when the batch holds the
    injected request number — the poison the retry-as-singles hunt must
    localize (the fault follows request N into its retry single)."""
    spec = _spec("HYDRAGNN_INJECT_SERVE_RAISE")
    if spec is not None and int(spec) in seqs:
        raise RuntimeError(
            f"injected serve fault: raise-in-forward at request {int(spec)}"
        )


def maybe_serve_nan(outputs, seqs):
    """Replace the forward's outputs with NaN when the batch holds the
    injected request number (silent corruption: no exception, just
    non-finite results the finite-output check must catch)."""
    spec = _spec("HYDRAGNN_INJECT_SERVE_NAN")
    if spec is None or int(spec) not in seqs:
        return outputs
    import numpy as np

    return [np.full_like(np.asarray(o), np.nan) for o in outputs]


# graftsync: thread-safe=GIL-atomic one-way False->True latch; only the single dispatch thread writes it
_SERVE_WEDGED = False


def maybe_serve_wedge(seqs) -> None:
    """Sleep inside the serving forward (wedged dispatch) for the batch
    holding the injected request number. Fires once per process."""
    spec = _spec("HYDRAGNN_INJECT_SERVE_WEDGE")
    if spec is None:
        return
    n, seconds = _two_ints(spec, 5)
    global _SERVE_WEDGED
    if n in seqs and not _SERVE_WEDGED:
        _SERVE_WEDGED = True
        time.sleep(seconds)


def maybe_serve_kill_dispatch(batch_count: int) -> None:
    """Raise OUTSIDE the per-request isolation at the K-th (1-indexed)
    dispatched batch — the dispatch thread dies and the in-process
    supervisor must restart it."""
    spec = _spec("HYDRAGNN_INJECT_SERVE_KILL_DISPATCH")
    if spec is not None and batch_count == int(spec):
        raise RuntimeError(
            f"injected serve fault: dispatch thread killed at batch {batch_count}"
        )


# graftsync: thread-safe=GIL-atomic one-way False->True latch; only the single trigger-evaluating thread writes it
_TRIGGER_FIRED = False


def injected_trigger(known_rules=None) -> Optional[str]:
    """The SLO rule name ``HYDRAGNN_INJECT_TRIGGER`` names, returned
    ONCE per process (the engine force-fires that rule at its next
    evaluate). ``known_rules`` filters: an injected name no engine rule
    carries is left un-consumed so the engine that DOES know it (train
    vs serve run in one process) gets the shot."""
    spec = _spec("HYDRAGNN_INJECT_TRIGGER")
    if spec is None:
        return None
    global _TRIGGER_FIRED
    if _TRIGGER_FIRED:
        return None
    if known_rules is not None and spec not in known_rules:
        return None
    _TRIGGER_FIRED = True
    return spec


def maybe_drift_shift(x):
    """Return the request's node features with the injected covariate
    shift applied (``x + SHIFT``), or unchanged when no drift is
    injected. Deterministic: every admitted request shifts identically,
    so the drift sketches see a clean mean/histogram displacement."""
    spec = _spec("HYDRAGNN_INJECT_DRIFT")
    if spec is None:
        return x
    import numpy as np

    return np.asarray(x) + float(spec)


def serve_torn_reload() -> bool:
    """Whether ModelServer.reload should corrupt the candidate weights
    before the canary (torn-reload injection)."""
    return _spec("HYDRAGNN_INJECT_SERVE_TORN_RELOAD") is not None


def pilot_train_crashes() -> int:
    """How many of the pilot's fine-tune attempts must crash before one
    is allowed to run (0 = none injected). Consumed per ATTEMPT by the
    pilot's tune launcher, which counts attempts itself — the child
    process may never even start, so a module latch cannot work here."""
    spec = _spec("HYDRAGNN_INJECT_PILOT_TRAIN_CRASH")
    return int(spec) if spec is not None else 0


def maybe_pilot_hang() -> None:
    """Wedge the fine-tune job for the injected number of seconds
    before it does any work — the supervisor-level wall clock (not the
    in-process watchdog, which never sees a pre-work hang) must kill
    and classify it."""
    spec = _spec("HYDRAGNN_INJECT_PILOT_HUNG_TUNE")
    if spec is not None:
        time.sleep(float(spec))


def pilot_canary_regress() -> bool:
    """Whether the pilot's canary scorer should inflate the CANDIDATE's
    scores so the gate rejects it."""
    return _spec("HYDRAGNN_INJECT_PILOT_CANARY_REGRESS") is not None


def pilot_torn_reload() -> bool:
    """Whether the pilot should corrupt the candidate weights between
    its canary gate and the hot reload (the server's own reload canary
    is then the last line of defense, and must hold)."""
    return _spec("HYDRAGNN_INJECT_PILOT_TORN_RELOAD") is not None


def maybe_pod_kill_host(host: int, gen) -> None:
    """SIGKILL this process when it is the injected host saving the
    injected pod-checkpoint generation. Called between the shard write
    and the manifest write, so the death always leaves a torn
    (uncommittable) generation behind."""
    spec = _spec("HYDRAGNN_INJECT_POD_KILL_HOST")
    if spec is None or gen is None:
        return
    h, g = _two_ints(spec, 1)
    if int(host) == h and int(gen) == g:
        os.kill(os.getpid(), signal.SIGKILL)


def maybe_pod_torn_shard(host: int, gen) -> bool:
    """Whether the injected host must write its injected generation's
    shard TRUNCATED while the sha256 sidecar keeps the good digest —
    the checksum-mismatch case restore's generation fallback exists
    for."""
    spec = _spec("HYDRAGNN_INJECT_POD_TORN_SHARD")
    if spec is None or gen is None:
        return False
    h, g = _two_ints(spec, 1)
    return int(host) == h and int(gen) == g


def maybe_pod_lost_heartbeat(host: int, epoch) -> bool:
    """Whether the injected host must SUPPRESS its heartbeat writes
    (from the injected epoch on). The host keeps training — only its
    liveness signal dies, so peers must declare it lost on evidence,
    not on exit codes."""
    spec = _spec("HYDRAGNN_INJECT_POD_LOST_HEARTBEAT")
    if spec is None or epoch is None:
        return False
    h, e = _two_ints(spec, 0)
    return int(host) == h and int(epoch) >= e


# graftsync: thread-safe=GIL-atomic one-way False->True latch; only the single barrier-entering main thread writes it
_BARRIER_STALLED = False


def maybe_pod_barrier_stall(host: int) -> None:
    """Sleep the injected host before it enters a pod_barrier (once
    per process) — its peers must hit the barrier timeout, proceed,
    and record the missing host rather than hang."""
    spec = _spec("HYDRAGNN_INJECT_POD_BARRIER_STALL")
    if spec is None:
        return
    h, seconds = _two_ints(spec, 5)
    global _BARRIER_STALLED
    if int(host) == h and not _BARRIER_STALLED:
        _BARRIER_STALLED = True
        time.sleep(seconds)


def strip_injection_env(env: dict) -> dict:
    """Copy of ``env`` without any injection knobs — what the restart
    supervisor hands to restarted children so injected faults fire
    exactly once. The removal set is DERIVED from the central knob
    registry's view of the environment (``knobs.active_injections``)
    rather than a hand-maintained list here, so every injection family
    — including ones added after this function — is stripped; the
    prefix filter backstops names a future build sets but this one's
    registry predates."""
    drop = set(knobs.active_injections(env=env))
    return {
        k: v
        for k, v in env.items()
        if k not in drop and not k.startswith(knobs.INJECT_PREFIX)
    }
