"""Run flight recorder: a crash-safe, append-only JSONL event log.

One file per run tells the whole story in machine-readable form: a
``run_start`` manifest (resolved config, jax/backend versions, mesh
shape, pad plans), one ``epoch`` record per epoch (losses, the
data-wait / dispatch / device step-time decomposition, compile counts),
``compile`` / ``retry`` / ``error`` events as they happen, and a
``run_end`` summary. Training writes it alongside checkpoints
(``<log_dir>/<log_name>/flight.jsonl``); ``bench.py`` / ``bench_serve.py``
write one next to their JSON records — the self-contained evidence
artifact a round verdict can parse instead of a builder anecdote (a
run that died mid-way still has every event up to the crash: each line
is written and flushed atomically-enough that the tail is at worst one
truncated line, which the reader skips).

Every ``run_start`` manifest additionally carries a ``graftcheck``
block — the compiled-IR contract audit (docs/LINT.md CC rules) stamped
by the emitter at run start: ``{"schema": .., "contracts": {CC001:
pass|fail|not_checked + why, ...}, "violations": [..]}``. Emitters that
never lower an executable (the restart supervisor) stamp an honest
all-``not_checked`` block so the key is universal.

Schema (``SCHEMA_VERSION``): every event is one JSON object per line
with ``v`` (schema version), ``kind``, ``t`` (unix seconds), ``rank``;
kind-specific required fields are in ``_REQUIRED``. Validate with
:func:`validate_flight_record` (ci.sh runs it on a tiny training run;
``tools/obs_report.py`` pretty-prints and diffs records).
"""

from __future__ import annotations

import json
import os
import time
from typing import Any, Dict, List, Optional, Union

# v2 (model-introspection PR): epoch events gain name-keyed per-task
# losses (``train_tasks``/``val_tasks`` as dicts), a ``heads`` block
# (per-head grad norms, conflict matrix, MAE/RMSE) and a ``hw`` block
# (achieved TFLOP/s, MFU, memory watermark); run_start manifests gain
# ``hw_cost`` (compiled-step FLOPs/bytes + chip peak) and
# ``diagnostics``. All new fields are OPTIONAL: the validator accepts
# every version in SUPPORTED_SCHEMA_VERSIONS, so v1 records (and v1
# writers) keep validating unchanged.
SCHEMA_VERSION = 2
SUPPORTED_SCHEMA_VERSIONS = (1, 2)

# kind -> fields every event of that kind must carry (beyond the
# envelope v/kind/t/rank). Unknown kinds are allowed (forward compat);
# unknown extra fields always are.
_REQUIRED: Dict[str, tuple] = {
    "run_start": ("manifest",),
    # the program's set-up spans (obs/spans.py:span), flushed once before
    # the first epoch: {name: {"s": seconds, "n": count, "parent": name}}
    "setup": ("phases",),
    "epoch": ("epoch", "train_loss", "val_loss"),
    "compile": ("count",),
    "retry": ("attempt", "error"),
    "error": ("error", "error_type"),
    "profile_trace": ("path",),
    "run_end": ("status",),
    # fault-tolerance events (hydragnn_tpu/resilience, docs/RESILIENCE.md)
    "preempt": ("signal", "epoch"),
    "resumed": ("epoch",),
    "rollback": ("epoch", "consec"),
    "watchdog": ("stall_s", "stacks"),
    "restart": ("attempt", "cause"),
    # serving-resilience events (hydragnn_tpu/serve, docs/RESILIENCE.md
    # "Serving resilience"): a quarantined poison request, an in-process
    # dispatch-thread restart, and hot-reload outcomes
    "quarantine": ("seq", "reason"),
    "dispatch_restart": ("attempt", "cause"),
    "reload": ("source",),
    "reload_failed": ("source", "error"),
    # persistent AOT executable cache (hydragnn_tpu/utils/exec_cache.py):
    # one event per cache interaction — hit / miss (with reason) /
    # store / evict / store_failed
    "exec_cache": ("event",),
    # incident-grade tracing (hydragnn_tpu/obs/trace.py, obs/triggers.py):
    # a sampled request/step trace (span list) and an SLO-trigger
    # incident bundle opened under logs/<run>/incidents/<id>/
    "trace_capture": ("trace_id", "spans"),
    "incident": ("id", "rule", "path"),
    # runtime lock-order witness (hydragnn_tpu/utils/syncdebug.py,
    # HYDRAGNN_LOCK_DEBUG=1): an observed acquisition order that
    # contradicts the static graftsync lock-order graph, with every
    # thread's stack at the moment of the inversion
    "lock_order": ("locks", "stacks"),
    # bench evidence events: one per measured config (bench.py) and one
    # per gate verdict (bench_serve.py warm-start check) — required here
    # so graftlint --artifacts can hold the committed BENCH_*.jsonl
    # records to the same schema bar as training flight logs
    "bench_config": ("name", "result"),
    "bench_result": ("record", "passed"),
    # serving-fleet events (hydragnn_tpu/fleet, docs/FLEET.md): every
    # autoscaler decision (up / down / replace / hold / up_failed, with
    # the trigger rule or quiet-timer reason and the resulting replica
    # count) and every per-replica step of a fleet-wide rolling reload
    "fleet_scale": ("action", "reason", "replicas"),
    "fleet_reload": ("model", "replica", "ok"),
    # served-traffic spool shard finalization (obs/spool.py): every
    # rotation names the shard, its sample/byte footprint, and any
    # LRU-evicted shards — the spool's disk-bound audit trail
    "spool_rotate": ("shard", "samples", "total_bytes"),
    # a drift trigger breached (obs/drift.py + the feature_drift /
    # pred_drift / error_drift rule kinds): which rule, what the sketch
    # observed vs the threshold, and where the offending spool window is
    "drift": ("rule", "observed", "threshold"),
    # retrain-pilot transitions (hydragnn_tpu/pilot, docs/RESILIENCE.md
    # "Closed loop"): every state-machine edge of the continual-learning
    # loop — which state the pilot entered, in which recovery cycle, and
    # why — so one flight timeline narrates incident -> fine-tune ->
    # canary -> reload end to end
    "pilot": ("state", "cycle"),
    # pod-visibility plane (obs/podview.py, docs/OBSERVABILITY.md "Pod
    # visibility"): a per-host epoch summary written into that host's
    # flight shard (the join unit merge_host_flights stitches on
    # ``(run_id, epoch)``), and the rank-0 SkewMonitor's per-epoch skew
    # verdict over all hosts' summaries
    "host_epoch": ("epoch", "host", "run_id", "epoch_s"),
    "podview": ("epoch", "skew_frac", "slowest_host"),
    # pod fault tolerance (resilience/podckpt.py, docs/RESILIENCE.md
    # "Pod recovery"): a peer host declared lost from the heartbeat
    # view (exactly one event per lost host per run), and the lineage
    # stamp of a run restored from a committed pod generation
    "host_lost": ("host",),
    "pod_resume": ("gen",),
}

# the fault-history subset tools/obs_report.py --faults narrates
FAULT_KINDS = (
    "preempt",
    "resumed",
    "rollback",
    "watchdog",
    "restart",
    "retry",
    "error",
    "quarantine",
    "dispatch_restart",
    "reload",
    "reload_failed",
    "incident",
    "lock_order",
    "drift",
    "fleet_scale",
    "fleet_reload",
    "pilot",
    "host_lost",
    "pod_resume",
)

_MANIFEST_REQUIRED = ("jax_version", "backend", "num_processes")


def _jsonable(obj: Any, depth: int = 0) -> Any:
    """Best-effort conversion to JSON-serializable structures: numpy
    scalars/arrays to python, unknown leaves to repr — a flight record
    write must never take the run down."""
    if depth > 8:
        return repr(obj)
    if obj is None or isinstance(obj, (bool, int, float, str)):
        return obj
    if isinstance(obj, dict):
        return {str(k): _jsonable(v, depth + 1) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [_jsonable(v, depth + 1) for v in obj]
    if hasattr(obj, "item") and getattr(obj, "ndim", None) == 0:
        return obj.item()  # numpy scalar
    if hasattr(obj, "tolist"):
        try:
            return obj.tolist()
        except Exception:
            return repr(obj)
    return repr(obj)


class FlightRecorder:
    """Append-only JSONL writer for one run.

    Each :meth:`record` opens nothing (the fd stays open), writes one
    line, and flushes — crash-safe in the sense that every completed
    event survives the process dying right after it. Disabled
    recorders (``enabled=False``) are inert: no file is created, every
    method is a no-op, so call sites never need their own gate.
    """

    def __init__(
        self,
        path: Optional[str],
        enabled: bool = True,
        host: Optional[int] = None,
    ):
        import threading

        from hydragnn_tpu.utils import syncdebug

        self.path = path
        # pod-visibility host identity: when set, every event's ``rank``
        # envelope field is stamped with this value instead of
        # jax.process_index() — how simulated hosts (HYDRAGNN_PODVIEW_HOST)
        # and real multihost shards both get distinguishable tracks in
        # the merged timeline (obs/podview.py)
        self.host = host
        # graftsync: thread-safe=GIL-atomic bool gate; a record() racing close() re-checks _f under the lock, worst case one event is dropped
        self.enabled = bool(enabled and path)
        self._f = None  # graftsync: guarded-by=flight.FlightRecorder._lock
        # the watchdog and preemption grace timer record from their own
        # threads; one lock keeps lines whole
        self._lock = syncdebug.maybe_wrap(
            threading.Lock(), "flight.FlightRecorder._lock"
        )
        if self.enabled:
            d = os.path.dirname(os.path.abspath(path))
            os.makedirs(d, exist_ok=True)
            self._f = open(path, "a", buffering=1)
            syncdebug.register_flight(self)

    # -- core --------------------------------------------------------------

    def record(self, kind: str, **payload) -> None:
        if not self.enabled:
            return
        event = {
            "v": SCHEMA_VERSION,
            "kind": kind,
            "t": round(time.time(), 3),
            "rank": self.host if self.host is not None else _rank(),
        }
        event.update({k: _jsonable(v) for k, v in payload.items()})
        try:
            with self._lock:
                if self._f is None:
                    return  # closed concurrently after the enabled gate
                self._f.write(json.dumps(event) + "\n")
                self._f.flush()
        except (OSError, ValueError):
            # a full disk or closed fd must not take the run down;
            # stop recording rather than raise per-event
            self.enabled = False

    # -- typed convenience wrappers ---------------------------------------

    def start_run(self, manifest: Dict[str, Any]) -> None:
        """The run's identity card. Callers pass what they know
        (resolved config, pad plans, mesh); the environment fields the
        schema requires are filled in here."""
        manifest = dict(manifest)
        manifest.setdefault("jax_version", _jax_version())
        manifest.setdefault("backend", _backend_name())
        manifest.setdefault("num_processes", _num_processes())
        self.record("run_start", manifest=manifest)

    def epoch(self, epoch: int, **payload) -> None:
        self.record("epoch", epoch=epoch, **payload)

    def compile_event(self, count: int, **payload) -> None:
        self.record("compile", count=count, **payload)

    def retry(self, attempt: int, error: str, **payload) -> None:
        self.record("retry", attempt=attempt, error=str(error)[-400:], **payload)

    def error(self, error: BaseException | str, **payload) -> None:
        self.record(
            "error",
            error=str(error)[-400:],
            error_type=type(error).__name__
            if isinstance(error, BaseException)
            else "str",
            **payload,
        )

    def end_run(self, status: str, **payload) -> None:
        self.record("run_end", status=status, **payload)

    def close(self) -> None:
        # detach under the lock so a concurrent record() either wins the
        # race (its line lands before the close) or sees _f gone — never
        # a write to a closed fd; the actual close happens outside
        with self._lock:
            f = self._f
            self._f = None
            self.enabled = False
        if f is not None:
            try:
                f.close()
            except OSError:
                pass

    def __enter__(self) -> "FlightRecorder":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


def _rank() -> int:
    try:
        import jax

        return jax.process_index()
    except Exception:
        return 0


def _jax_version() -> str:
    try:
        import jax

        return jax.__version__
    except Exception:
        return "unavailable"


def _backend_name() -> str:
    try:
        import jax

        return jax.default_backend()
    except Exception:
        return "unavailable"


def _num_processes() -> int:
    try:
        import jax

        return jax.process_count()
    except Exception:
        return 1


def read_flight_record(path: str) -> List[dict]:
    """Parse a flight record, tolerating a truncated final line (the
    crash case the recorder exists for). Raises FileNotFoundError when
    the file is absent; malformed INTERIOR lines are kept as
    ``{"kind": "_unparseable", "line": ...}`` so validation can flag
    them without losing the rest."""
    events: List[dict] = []
    with open(path) as f:
        lines = f.read().split("\n")
    for i, line in enumerate(lines):
        if not line.strip():
            continue
        try:
            events.append(json.loads(line))
        except json.JSONDecodeError:
            if i == len(lines) - 1 or (i == len(lines) - 2 and not lines[-1]):
                continue  # truncated tail: expected for a crashed run
            events.append({"kind": "_unparseable", "line": line[:200]})
    return events


def epoch_phases(events: List[dict]) -> Dict[int, Dict[str, dict]]:
    """Each epoch's program spans (``obs/spans.py:span``), ``{epoch:
    {name: {"s", "n", "parent"}}}``: the ``phases`` of its own ``epoch``
    event, and the spans that closed after that event was written
    (``epoch.record``, ``epoch.checkpoint``, ``epoch`` itself), which the
    next event carries under their epoch number as ``phases_late``."""
    out: Dict[int, Dict[str, dict]] = {}
    for ev in events:
        if ev.get("kind") == "epoch" and isinstance(ev.get("phases"), dict):
            out.setdefault(ev["epoch"], {}).update(ev["phases"])
        for late in ev.get("phases_late") or []:
            if late.get("epoch") is not None:
                out.setdefault(late["epoch"], {}).update(late.get("phases") or {})
    return out


def validate_flight_record(
    record: Union[str, List[dict]], require_complete: bool = False
) -> List[str]:
    """Schema check; returns a list of problems (empty = valid).

    ``require_complete=True`` additionally demands the happy-path
    shape: exactly one ``run_start`` first, at least one ``epoch``,
    and a terminal ``run_end`` — what ci.sh asserts of a tiny run.
    Without it, a crashed run (no run_end) still validates as long as
    every event it DID write is well-formed.
    """
    events = read_flight_record(record) if isinstance(record, str) else record
    problems: List[str] = []
    if not events:
        return ["empty flight record"]
    for i, ev in enumerate(events):
        where = f"event[{i}]"
        if ev.get("kind") == "_unparseable":
            problems.append(f"{where}: unparseable line {ev.get('line')!r}")
            continue
        for field in ("v", "kind", "t", "rank"):
            if field not in ev:
                problems.append(f"{where}: missing envelope field {field!r}")
        v = ev.get("v")
        if v is not None and v not in SUPPORTED_SCHEMA_VERSIONS:
            if isinstance(v, int) and v > SCHEMA_VERSION:
                pass  # newer writer: forward-compat, surfaced as a warning
            else:
                problems.append(
                    f"{where}: schema version {v!r} not in "
                    f"{SUPPORTED_SCHEMA_VERSIONS}"
                )
        kind = ev.get("kind")
        for field in _REQUIRED.get(kind, ()):
            if field not in ev:
                problems.append(f"{where} ({kind}): missing field {field!r}")
        if kind == "run_start":
            man = ev.get("manifest")
            if not isinstance(man, dict):
                problems.append(f"{where}: manifest is not a dict")
            else:
                for field in _MANIFEST_REQUIRED:
                    if field not in man:
                        problems.append(
                            f"{where}: manifest missing field {field!r}"
                        )
    kinds = [e.get("kind") for e in events]
    if require_complete:
        if kinds.count("run_start") != 1:
            problems.append(
                f"expected exactly one run_start, got {kinds.count('run_start')}"
            )
        elif kinds[0] != "run_start":
            problems.append(f"first event is {kinds[0]!r}, expected run_start")
        if "epoch" not in kinds:
            problems.append("no epoch events")
        if kinds[-1] != "run_end":
            problems.append(f"last event is {kinds[-1]!r}, expected run_end")
    return problems


def flight_record_warnings(record: Union[str, List[dict]]) -> List[str]:
    """Forward-compat advisories that must NOT fail validation: event
    kinds this reader does not know (a newer writer's events — still
    structurally fine) and events stamped with a schema version newer
    than this reader supports. ``tools/obs_report.py --validate/--diff``
    print these as warnings and exit 0."""
    events = read_flight_record(record) if isinstance(record, str) else record
    warnings: List[str] = []
    for i, ev in enumerate(events):
        kind = ev.get("kind")
        if kind is not None and kind != "_unparseable" and kind not in _REQUIRED:
            warnings.append(f"event[{i}]: unknown event kind {kind!r}")
        v = ev.get("v")
        if isinstance(v, int) and v > SCHEMA_VERSION:
            warnings.append(
                f"event[{i}]: schema version {v} is newer than this "
                f"reader (supports {SUPPORTED_SCHEMA_VERSIONS}) — fields "
                "may be missing from views"
            )
    return warnings
