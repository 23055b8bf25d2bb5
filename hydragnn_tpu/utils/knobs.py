"""Central registry of every ``HYDRAGNN_*`` environment knob.

Nine PRs scattered ``os.environ.get("HYDRAGNN_...")`` reads across
ops/, train/, serve/, data/, resilience/ and utils/ with no single
place that says what exists, what type each value is, what the default
is, or who consumes it. This module is that place: every knob is
declared here once (name, type, default, consumer module, one doc
line), every library read goes through the typed accessors below, and
two enforcement arms keep it honest:

  - **Static**: graftlint rule HG006 (``hydragnn_tpu/lint/rules.py``)
    fails CI on any ``HYDRAGNN_*`` string literal in the tree that is
    not declared here — a new knob cannot ship undocumented — and on
    any declared knob no longer referenced anywhere (stale registry).
  - **Runtime**: the accessors raise :class:`UndeclaredKnobError` for
    names missing from the registry, so a typo'd read fails loudly at
    the call site instead of silently returning the default forever.

``docs/KNOBS.md`` is GENERATED from this registry
(``python -m hydragnn_tpu.utils.knobs --write docs/KNOBS.md``);
tests/test_graftlint.py asserts the committed file matches, so the
docs cannot drift from the code.

This module must stay stdlib-only: the linter and the docs generator
load it without initializing jax or the rest of the package.
"""

from __future__ import annotations

import os
from typing import Dict, List, NamedTuple, Optional


class Knob(NamedTuple):
    name: str
    type: str  # "str" | "int" | "float" | "bool" | "flag" | "spec" | "path"
    default: Optional[str]  # None = unset means disabled/absent
    consumer: str  # the module that reads it
    doc: str


_K = Knob  # registry-entry marker the linter's AST parser keys on

#: Every ``HYDRAGNN_*`` env knob the tree reads, alphabetical. A
#: ``spec``-typed knob carries a structured value (``N``, ``N:M``, a
#: path, ...) documented in its consumer; a ``flag`` is significant
#: merely by being set non-empty.
KNOBS: Dict[str, Knob] = {
    k.name: k
    for k in (
        _K("HYDRAGNN_AUTO_RESUME", "flag", None, "resilience/preempt.py",
           "Set to 1 by the restart supervisor: resume from the run's own "
           "checkpoint instead of starting over."),
        _K("HYDRAGNN_BCAST_CE", "int", "1024", "ops/segment_pallas.py",
           "Edges per DMA chunk for the CSR-broadcast gather kernel "
           "(multiple of 16; overrides the TUNE_TILES.json table). The "
           "gathers' table window (BW, 128 rows) does not follow it."),
        _K("HYDRAGNN_BENCH_GATE_TOL", "float", "0.15", "tools/bench_gate.py",
           "Fractional regression tolerance for the CI perf gate's "
           "graphs/sec, MFU, and traffic arms."),
        _K("HYDRAGNN_BN", "int", "128", "ops/segment_pallas.py",
           "Output rows (nodes) per grid step in the segment kernels "
           "(multiple of 16; overrides the TUNE_TILES.json table)."),
        _K("HYDRAGNN_CE", "int", "512", "ops/segment_pallas.py",
           "Edges DMA'd per inner chunk in the segment-sum kernels "
           "(multiple of 16; overrides the TUNE_TILES.json table). Since "
           "PR 32 it does not move the gathers' table window (BW, 128 rows)."),
        _K("HYDRAGNN_DEBUG_BATCH", "bool", "0", "data/loader.py",
           "Validate layout contracts (sorted receivers, masked-edge "
           "targeting, window coverage) on every host batch."),
        _K("HYDRAGNN_DEVICE_KIND", "str", "default", "ops/segment_pallas.py",
           "Row selector into TUNE_TILES.json for block/chunk defaults "
           "(never read from jax.devices(): import must not init a backend)."),
        _K("HYDRAGNN_DIAGNOSTICS", "bool", "1", "train/run.py",
           "Force-disable model introspection (per-head grad norms, MFU "
           "ledger) regardless of config; the tier-1 suite sets 0."),
        _K("HYDRAGNN_DRIFT_REF", "path", None, "serve/server.py",
           "Drift reference window: a training flight.jsonl (the "
           "run_start.manifest stats block) or a bare stats JSON. Arms "
           "the DriftMonitor + drift trigger rules on server start."),
        _K("HYDRAGNN_EXEC_CACHE", "path", None, "utils/exec_cache.py",
           "Directory of the persistent AOT executable cache; unset = "
           "inert. Deliberately survives supervisor restart env-strips."),
        _K("HYDRAGNN_EXEC_CACHE_MAX_MB", "float", "512", "utils/exec_cache.py",
           "LRU size bound for the executable cache directory."),
        _K("HYDRAGNN_FLEET_COOLDOWN_S", "float", "30", "fleet/controller.py",
           "Minimum seconds between autoscaler scale decisions (up, down, "
           "or replace each re-arm it)."),
        _K("HYDRAGNN_FLEET_EVAL_EVERY_S", "float", "1.0", "fleet/controller.py",
           "Period of the fleet controller's background evaluation loop."),
        _K("HYDRAGNN_FLEET_MAX_REPLICAS", "int", "4", "fleet/controller.py",
           "Upper replica bound: a breach verdict at the cap records a "
           "fleet_scale hold event instead of spawning."),
        _K("HYDRAGNN_FLEET_MIN_REPLICAS", "int", "1", "fleet/controller.py",
           "Lower replica bound the quiet-fleet scale-down never crosses."),
        _K("HYDRAGNN_FLEET_QUIET_S", "float", "60", "fleet/controller.py",
           "Seconds the fleet queue must stay below the quiet threshold "
           "before the controller retires a replica."),
        _K("HYDRAGNN_FLEET_TENANT_BURST", "float", "32", "fleet/router.py",
           "Default per-tenant token-bucket burst capacity (tokens; one "
           "admission costs one token)."),
        _K("HYDRAGNN_FLEET_TENANT_RATE", "float", "0", "fleet/router.py",
           "Default per-tenant admission refill rate in requests/s for "
           "tenants without an explicit quota; 0 = unlimited."),
        _K("HYDRAGNN_FULL_MATRIX", "flag", None, "tests/test_train_matrix.py",
           "Opt into the full 7-model acceptance matrix (~15 min)."),
        _K("HYDRAGNN_GRAFTCHECK", "bool", "1", "train/run.py",
           "Stamp the compiled-IR contract block (lint/ir.py CC001-CC006) "
           "into every run_start flight manifest; 0 skips the lowering."),
        _K("HYDRAGNN_GRAFTCHECK_LAYOUTS", "str", "dp,fsdp2",
           "tools/graftcheck.py",
           "Comma-separated named Partitioner layouts the graftcheck CLI "
           "audits by default (dp = pure data parallel, fsdp2 = fsdp=2)."),
        _K("HYDRAGNN_INCIDENT_COOLDOWN_S", "float", "300",
           "obs/triggers.py",
           "Minimum seconds between admitted SLO trigger firings (the "
           "engine's rate limit against incident storms)."),
        _K("HYDRAGNN_INCIDENT_MAX", "int", "5", "obs/triggers.py",
           "Incident count cap per engine per run; further verdicts are "
           "suppressed (counted in the run_end triggers block)."),
        _K("HYDRAGNN_INCIDENT_OVERHEAD_PCT", "float", "5",
           "obs/triggers.py",
           "Profiler-capture overhead budget as a percent of run wall "
           "time; a new incident that would exceed it is suppressed."),
        _K("HYDRAGNN_INCIDENT_PROFILE_S", "float", "10", "obs/triggers.py",
           "Wall-time bound on one incident's profiler capture (whichever "
           "of steps/seconds trips first stops the trace)."),
        _K("HYDRAGNN_INCIDENT_PROFILE_STEPS", "int", "3", "obs/triggers.py",
           "Step-count bound on one incident's profiler capture "
           "(ticks of the capturing loop, train steps or serve batches)."),
        _K("HYDRAGNN_INJECT_DONATION_CHECK_FAIL", "flag", None,
           "utils/exec_cache.py",
           "Force the donation round-trip gate to report failure: the "
           "cached donated executable is evicted and live-compiled."),
        _K("HYDRAGNN_INJECT_DRIFT", "spec", None, "resilience/inject.py",
           "SHIFT: add a deterministic covariate shift of SHIFT to every "
           "incoming request's node features at admission (drives the "
           "feature_drift trigger end to end)."),
        _K("HYDRAGNN_INJECT_GRAFTCHECK", "spec", None, "lint/ir.py",
           "cc001..cc006 (comma-separated): plant one real compiled-IR "
           "violation per named contract for the graftcheck self-test."),
        _K("HYDRAGNN_INJECT_KILL_CHECKPOINT", "spec", None,
           "resilience/inject.py",
           "K: during the K-th checkpoint save, write a torn file and "
           "SIGKILL the process (integrity-validation drill)."),
        _K("HYDRAGNN_INJECT_LOCK_ORDER", "spec", None, "utils/syncdebug.py",
           "LOCKA,LOCKB: once both named locks register with the runtime "
           "witness, synthesize an A->B acquisition then the B->A "
           "inversion (one-shot; bookkeeping only, no real lock taken) "
           "to drive the lock_order violation path end to end."),
        _K("HYDRAGNN_INJECT_NAN_STEP", "spec", None, "resilience/inject.py",
           "N[:M]: replace node features with NaN for train steps "
           "N..N+M-1 (drives the non-finite sentry)."),
        _K("HYDRAGNN_INJECT_PILOT_CANARY_REGRESS", "flag", None,
           "resilience/inject.py",
           "Inflate the retrain candidate's canary scores so the gate "
           "rejects it (the pilot must cool down on the old weights)."),
        _K("HYDRAGNN_INJECT_PILOT_HUNG_TUNE", "spec", None,
           "resilience/inject.py",
           "S: the pilot's fine-tune job wedges for S seconds before "
           "doing any work (drives the supervisor wall-clock kill)."),
        _K("HYDRAGNN_INJECT_PILOT_TORN_RELOAD", "flag", None,
           "resilience/inject.py",
           "Corrupt the retrain candidate's weights between canary and "
           "reload (the server's own reload canary must reject them)."),
        _K("HYDRAGNN_INJECT_PILOT_TRAIN_CRASH", "spec", None,
           "resilience/inject.py",
           "N: the pilot's first N fine-tune attempts exit nonzero "
           "before training (N=1 proves retry-with-backoff; N >= the "
           "attempt budget proves the failed-cycle path)."),
        _K("HYDRAGNN_INJECT_POD_BARRIER_STALL", "spec", None,
           "resilience/inject.py",
           "H:S: simulated host H sleeps S seconds before entering any "
           "pod_barrier (once per process) — peers must time out, "
           "proceed, and record the missing host."),
        _K("HYDRAGNN_INJECT_POD_KILL_HOST", "spec", None,
           "resilience/inject.py",
           "H:G: host H SIGKILLs itself during the generation-G pod "
           "checkpoint save, after its shard bytes but before its "
           "manifest (the torn-generation drill)."),
        _K("HYDRAGNN_INJECT_POD_LOST_HEARTBEAT", "spec", None,
           "resilience/inject.py",
           "H:E: host H stops writing liveness heartbeats from epoch E "
           "on while continuing to train (drives host_lost detection)."),
        _K("HYDRAGNN_INJECT_POD_TORN_SHARD", "spec", None,
           "resilience/inject.py",
           "H:G: host H writes its generation-G pod shard truncated "
           "while the sha256 sidecar keeps the good digest (restore "
           "must reject by checksum and fall back a generation)."),
        _K("HYDRAGNN_INJECT_SERVE_KILL_DISPATCH", "spec", None,
           "resilience/inject.py",
           "K: the K-th dispatched serve batch raises outside request "
           "isolation, killing the dispatch thread."),
        _K("HYDRAGNN_INJECT_SERVE_NAN", "spec", None, "resilience/inject.py",
           "N: serve outputs become NaN for any batch holding request N "
           "(silent-corruption poison)."),
        _K("HYDRAGNN_INJECT_SERVE_RAISE", "spec", None, "resilience/inject.py",
           "N: the serving forward raises for any batch holding request "
           "N (poison request)."),
        _K("HYDRAGNN_INJECT_SERVE_TORN_RELOAD", "flag", None,
           "resilience/inject.py",
           "Corrupt reload candidate weights before the canary (the "
           "canary must fail and the old weights keep serving)."),
        _K("HYDRAGNN_INJECT_SERVE_WEDGE", "spec", None,
           "resilience/inject.py",
           "N[:S]: the dispatch thread sleeps S seconds (default 5) in "
           "the forward of the batch holding request N."),
        _K("HYDRAGNN_INJECT_SIGTERM_EPOCH", "spec", None,
           "resilience/inject.py",
           "E: SIGTERM self-signal at the start of epoch E."),
        _K("HYDRAGNN_INJECT_SIGTERM_STEP", "spec", None,
           "resilience/inject.py",
           "N: SIGTERM self-signal before train step N."),
        _K("HYDRAGNN_INJECT_STALL_LOADER", "spec", None,
           "resilience/inject.py",
           "B:S: the loader's producer sleeps S seconds before building "
           "batch B of an epoch (drives the hang watchdog)."),
        _K("HYDRAGNN_INJECT_STRAGGLER", "spec", None, "obs/spans.py",
           "HOST:MS: when this process's podview host index equals HOST, "
           "sleep MS milliseconds inside every train step's span path — a "
           "deterministic straggler that drives the step_skew trigger "
           "(being an INJECT knob it also forces per-step dispatch)."),
        _K("HYDRAGNN_INJECT_TRIGGER", "spec", None, "resilience/inject.py",
           "RULE: force-fire the named SLO trigger rule once at the next "
           "TriggerEngine.evaluate (drives incident capture on demand)."),
        _K("HYDRAGNN_LOCAL_MIN_ROWS", "int", "200000", "ops/segment_pallas.py",
           "Row threshold below which the local-window kernel family "
           "falls back (its fixed per-call cost needs large operands)."),
        _K("HYDRAGNN_LOCK_DEBUG", "bool", "0", "utils/syncdebug.py",
           "Wrap every declared lock in the runtime lock-order witness: "
           "observed acquisition order is checked against graftsync's "
           "static lock-order graph; a violation dumps all thread stacks "
           "into the flight record as a lock_order event (never raises)."),
        _K("HYDRAGNN_MATRIX_REPORT", "path", None, "tests/test_train_e2e.py",
           "Write the acceptance-matrix JSON report to this path."),
        _K("HYDRAGNN_NUM_PREFETCH", "int", "2", "data/loader.py",
           "Default loader prefetch depth (an explicit constructor "
           "argument wins)."),
        _K("HYDRAGNN_PALLAS", "str", "auto", "ops/segment_pallas.py",
           "Kernel dispatch: auto = Pallas on TPU for sorted 128-lane "
           "data; 1 = force on TPU; interpret = interpret mode anywhere "
           "(CPU tests); 0 = force XLA."),
        _K("HYDRAGNN_PILOT_CANARY_SAMPLES", "int", "16", "pilot/pilot.py",
           "Per-slice sample bound for the canary eval (reference slice "
           "and drifted window each score at most this many samples)."),
        _K("HYDRAGNN_PILOT_CANARY_TOL", "float", "0.2", "pilot/pilot.py",
           "Allowed fractional MAE regression of the retrain candidate "
           "vs the serving weights on EACH canary slice; worse than "
           "baseline*(1+tol) on either slice rejects the candidate."),
        _K("HYDRAGNN_PILOT_COOLDOWN_S", "float", "60", "pilot/pilot.py",
           "Hysteresis window after any retrain cycle (success or "
           "failure) during which new drift incidents are counted but "
           "never start another cycle — the anti-storm belt."),
        _K("HYDRAGNN_PILOT_MAX_WALL_S", "float", "600", "pilot/pilot.py",
           "Hard wall clock per fine-tune attempt; a hung job is killed "
           "and classified hung/79 by the supervisor wall-clock runner."),
        _K("HYDRAGNN_PILOT_STUCK_AFTER", "int", "3", "pilot/pilot.py",
           "Consecutive failed recovery cycles before the pilot stops "
           "flapping and escalates a terminal pilot_stuck incident."),
        _K("HYDRAGNN_PILOT_TUNE_ATTEMPTS", "int", "2", "pilot/pilot.py",
           "Crash-class restart budget for one cycle's fine-tune job "
           "(the supervisor's max_restarts)."),
        _K("HYDRAGNN_PILOT_TUNE_BACKOFF_S", "float", "1.0", "pilot/pilot.py",
           "Base of the exponential backoff between fine-tune restart "
           "attempts within one cycle."),
        _K("HYDRAGNN_PILOT_TUNE_EPOCHS", "int", "2", "pilot/tune.py",
           "Epochs the incremental fine-tune runs over the pinned spool "
           "window (starting from the serving checkpoint)."),
        _K("HYDRAGNN_PODVIEW", "bool", "0", "obs/podview.py",
           "Force-enable the pod-visibility plane (per-host flight "
           "shards + SkewMonitor) even in a single-process run — the "
           "simulated-host mode ci.sh and the tests use. Real multihost "
           "runs (jax.process_count() > 1) enable it automatically."),
        _K("HYDRAGNN_PODVIEW_HOST", "int", "-1", "obs/podview.py",
           "Override this process's podview host index (simulated hosts "
           "on one machine); -1/unset = use jax.process_index()."),
        _K("HYDRAGNN_PODVIEW_HOSTS", "int", "0", "obs/podview.py",
           "Override the expected host count the SkewMonitor and the "
           "merge reader wait for; 0/unset = jax.process_count()."),
        _K("HYDRAGNN_PODVIEW_RUN_ID", "str", None, "obs/podview.py",
           "Shared run id stamped into host_epoch events — the merge "
           "join key across host shards; unset = the run's log name."),
        _K("HYDRAGNN_PODVIEW_SKEW", "float", "0", "train/loop.py",
           "step_skew trigger threshold on podview.skew_frac; 0/unset = "
           "derive from the committed scaling model's skew_tolerance "
           "block (fallback 0.25)."),
        _K("HYDRAGNN_PODVIEW_STALL_S", "float", "120", "resilience/pod.py",
           "host_stall trigger threshold: seconds since the least-recent "
           "host's last flight event before the stall incident fires."),
        _K("HYDRAGNN_POD_BARRIER_TIMEOUT_S", "float", "60",
           "resilience/podckpt.py",
           "Bounded-wait limit for pod_barrier rendezvous; on expiry "
           "the host PROCEEDS and records the missing peers (a pod "
           "must degrade to evidence, never to a hang)."),
        _K("HYDRAGNN_POD_CKPT", "bool", "1", "resilience/pod.py",
           "Pod-sharded generation checkpointing (resilience/podckpt.py) "
           "whenever the run spans more than one podview host; 0 keeps "
           "only the single-host msgpack path."),
        _K("HYDRAGNN_POD_COMMIT_TIMEOUT_S", "float", "120",
           "resilience/podckpt.py",
           "How long rank 0 waits for every host's shard manifest "
           "before giving up on committing a generation (the COMMIT "
           "marker is only ever written after all manifests validate)."),
        _K("HYDRAGNN_POD_HEARTBEAT_S", "float", "1.0",
           "resilience/podckpt.py",
           "Write period of each host's liveness heartbeat file in the "
           "pod sync dir."),
        _K("HYDRAGNN_POD_KEEP_GENS", "int", "3", "resilience/podckpt.py",
           "Committed pod checkpoint generations retained; older ones "
           "are pruned (marker first, then shards) after each commit."),
        _K("HYDRAGNN_POD_LOST_AFTER_S", "float", "0",
           "resilience/podckpt.py",
           "Declare a peer host lost when its newest heartbeat is older "
           "than this many seconds (host_lost flight event + trigger). "
           "0/unset = detection off — required for the sequential "
           "simulated-host CI mode where stale beats are normal."),
        _K("HYDRAGNN_RESIDENCY_VMEM_MB", "float", "12", "ops/fused_conv.py",
           "VMEM budget the cross-layer resident conv-stack kernel may "
           "claim (a TPU core has ~16 MB; the pipeline needs headroom)."),
        _K("HYDRAGNN_SPOOL", "bool", "0", "serve/server.py",
           "Enable the served-traffic request spool (obs/spool.py): "
           "sampled requests + predictions appended to rotating HGC "
           "shards under <log_dir>/serve/spool."),
        _K("HYDRAGNN_SPOOL_MAX_MB", "float", "64", "serve/server.py",
           "Disk bound for the request spool; once finalized shards "
           "exceed it, the oldest shards are LRU-evicted."),
        _K("HYDRAGNN_SPOOL_SAMPLE", "int", "8", "serve/server.py",
           "Spool every Nth answered request (1 = every request)."),
        _K("HYDRAGNN_TELEMETRY", "bool", "1", "obs/registry.py",
           "Process-wide telemetry gate: 0/false/off disables the "
           "registry, flight recorder, spans, and compile monitor."),
        _K("HYDRAGNN_TILE_SHAPE", "str", "default", "ops/segment_pallas.py",
           "Shape-tag selector into TUNE_TILES.json for block/chunk "
           "defaults."),
        _K("HYDRAGNN_TRACE", "bool", "1", "obs/trace.py",
           "Per-request/step distributed tracing gate (within the "
           "process-wide HYDRAGNN_TELEMETRY gate): 0 disables tracing."),
        _K("HYDRAGNN_TRACE_SAMPLE", "int", "100", "obs/trace.py",
           "Record every Nth finished trace into the flight record as a "
           "trace_capture event (the first trace is always recorded)."),
        _K("HYDRAGNN_WATCHDOG_S", "float", "0", "train/loop.py",
           "Hang-watchdog stall threshold in seconds; 0/unset = off. "
           "Must be sized above the worst expected compile time."),
    )
}

#: The injection family prefix: the restart supervisor strips matching
#: vars from restarted children, and the scan-epoch eligibility check
#: refuses whole-epoch dispatch while any non-serve member is set.
INJECT_PREFIX = "HYDRAGNN_INJECT_"
_FALSE_WORDS = ("0", "false", "off")


class UndeclaredKnobError(KeyError):
    """A ``HYDRAGNN_*`` name was read that the registry does not
    declare — add a :class:`Knob` entry (and regenerate docs/KNOBS.md)
    before wiring a new knob into code."""


def _check_declared(name: str) -> None:
    if name not in KNOBS:
        raise UndeclaredKnobError(
            f"{name} is not declared in hydragnn_tpu/utils/knobs.py; "
            "register it (and regenerate docs/KNOBS.md) before reading it"
        )


def raw(name: str, default: Optional[str] = None) -> Optional[str]:
    """The raw env string (or ``default`` when unset). The one
    registry-validated primitive every other accessor goes through."""
    _check_declared(name)
    return os.environ.get(name, default)


def get_str(name: str, default: Optional[str] = None) -> Optional[str]:
    return raw(name, default)


def get_int(name: str, default: int) -> int:
    v = raw(name)
    return default if v is None or v == "" else int(v)


def get_float(name: str, default: float) -> float:
    v = raw(name)
    return default if v is None or v == "" else float(v)


def get_bool(name: str, default: bool) -> bool:
    """The repo's boolean-knob convention: any of 0/false/off (any
    case) is False, everything else set is True."""
    v = raw(name)
    if v is None:
        return default
    return v.lower() not in _FALSE_WORDS


def is_set(name: str) -> bool:
    """Flag semantics: set to any non-empty value."""
    return bool(raw(name))


def active_injections(
    include_serve: bool = True, env: Optional[Dict[str, str]] = None
) -> List[str]:
    """Sorted ``HYDRAGNN_INJECT_*`` names currently set in the
    environment (or in ``env`` when given — the restart supervisor
    passes a CHILD's environment to derive its strip set from the same
    registry view everything else uses). ``include_serve=False`` drops
    the serve-side family — what the scan-epoch eligibility check cares
    about (train-side injections are step-indexed and need per-step
    dispatch)."""
    src = os.environ if env is None else env
    return sorted(
        k
        for k in src
        if k.startswith(INJECT_PREFIX)
        and (include_serve or not k.startswith("HYDRAGNN_INJECT_SERVE"))
    )


def generate_docs() -> str:
    """docs/KNOBS.md, rendered from the registry."""
    lines = [
        "# Environment knobs",
        "",
        "GENERATED from `hydragnn_tpu/utils/knobs.py` — edit the registry,",
        "then `python -m hydragnn_tpu.utils.knobs --write docs/KNOBS.md`.",
        "`tests/test_graftlint.py` asserts this file matches the registry,",
        "and lint rule HG006 (docs/LINT.md) fails CI on any `HYDRAGNN_*`",
        "read the registry does not declare.",
        "",
        "A `flag` knob is significant merely by being set non-empty; a",
        "`spec` knob carries a structured value documented below; `bool`",
        "knobs treat 0/false/off (any case) as false and anything else",
        "set as true.",
        "",
        "| Knob | Type | Default | Consumer | What it does |",
        "|---|---|---|---|---|",
    ]
    for k in sorted(KNOBS.values()):
        default = "*(unset)*" if k.default is None else f"`{k.default}`"
        lines.append(
            f"| `{k.name}` | {k.type} | {default} | `{k.consumer}` | {k.doc} |"
        )
    lines += [
        "",
        "The `HYDRAGNN_INJECT_*` family is deterministic fault injection",
        "(`hydragnn_tpu/resilience/inject.py`, docs/RESILIENCE.md): every",
        "member is a no-op unless set, and the restart supervisor strips",
        "the whole family from restarted children so each injected fault",
        "fires exactly once per supervised run.",
        "",
    ]
    return "\n".join(lines)


def _main(argv: List[str]) -> int:
    if argv[:1] == ["--write"] and len(argv) == 2:
        with open(argv[1], "w") as f:
            f.write(generate_docs())
        print(f"wrote {argv[1]} ({len(KNOBS)} knobs)")
        return 0
    if argv[:1] == ["--check"] and len(argv) == 2:
        try:
            with open(argv[1]) as f:
                committed = f.read()
        except OSError:
            committed = ""
        if committed != generate_docs():
            print(
                f"{argv[1]} is stale: regenerate with "
                "python -m hydragnn_tpu.utils.knobs --write " + argv[1]
            )
            return 1
        print(f"{argv[1]} matches the registry ({len(KNOBS)} knobs)")
        return 0
    print(generate_docs(), end="")
    return 0


if __name__ == "__main__":
    import sys

    raise SystemExit(_main(sys.argv[1:]))
