"""``graftlint --artifacts``: schema-validate committed machine artifacts.

The repo commits bench evidence in two shapes, and both are validated
here so a malformed committed artifact fails CI instead of a later tool
run:

* **Flight JSONL records** (``BENCH_FLIGHT.jsonl``,
  ``BENCH_SERVE_WARM_FLIGHT.jsonl``) — their schema lives in
  ``obs/flight.py`` (``_REQUIRED``), so drift between the tables and
  the checked-in records is exactly the static-vs-runtime gap the
  linter exists to close: this mode runs the real
  ``validate_flight_record`` over each and reports problems as
  findings. ``flight.py`` is stdlib-only by design, so it is loaded
  standalone (``importlib``, no package import, no jax init).

* **Machine JSON artifacts** (``SCALING_*.json``, ``TUNE_TILES.json``,
  ``BENCH_CI_BASELINE.json``, ``BENCH_FLEET.json``) — per-kind schemas
  below (``MACHINE_SCHEMAS``), derived from the writers (
  tools/estimate_scaling.py, tools/tune_tiles.py, tools/bench_ci.py).
  The checks pin the fields downstream tools actually read; extra keys
  stay legal so a writer can grow its record without a lint dance.
"""

from __future__ import annotations

import fnmatch
import importlib.util
import json
import os
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

from .core import Finding

#: committed flight artifacts validated by the CI stage, repo-relative.
#: BENCH_FLIGHT.jsonl (the device bench's flight) is deliberately NOT
#: listed: it is rewritten per driver round on the TPU host and is not
#: a committed artifact — listing it made the gate fail on every
#: checkout without device-round evidence.
DEFAULT_ARTIFACTS = (
    "BENCH_SERVE_WARM_FLIGHT.jsonl",
    "BENCH_FLEET_FLIGHT.jsonl",
)


def _require(data: Any, fields: Dict[str, tuple]) -> List[str]:
    """Missing/mistyped required top-level fields of a dict artifact."""
    if not isinstance(data, dict):
        return [f"expected a JSON object, got {type(data).__name__}"]
    problems = []
    for name, types in fields.items():
        if name not in data:
            problems.append(f"missing required field '{name}'")
        elif not isinstance(data[name], types):
            want = "/".join(t.__name__ for t in types)
            problems.append(
                f"field '{name}' is {type(data[name]).__name__}, expected {want}"
            )
    return problems


_NUM = (int, float)


#: the five scenario rows bench_serve.py --fleet always records — a
#: missing one means a chaos scenario silently did not run.
_FLEET_SCENARIOS = (
    "baseline_n1",
    "sustained_n2",
    "replica_kill",
    "scale_up_under_load",
    "rolling_reload",
)


def _check_fleet(data: Any) -> List[str]:
    """BENCH_FLEET.json: the fleet chaos acceptance record
    (bench_serve.py --fleet, docs/FLEET.md): scale-out efficiency
    headline plus one row per chaos scenario."""
    problems = _require(
        data,
        {
            "metric": (str,),
            "value": _NUM,
            "unit": (str,),
            "replicas": (int,),
            "qps_n1": _NUM,
            "qps_n2": _NUM,
            "scaleout_efficiency": _NUM,
            "warm_replica_aot_compiles": (int,),
            "lost_futures": (int,),
            "slo_p99_ms": _NUM,
            "scenarios": (dict,),
            "failures": (list,),
        },
    )
    if problems:
        return problems
    for name in _FLEET_SCENARIOS:
        row = data["scenarios"].get(name)
        if not isinstance(row, dict):
            problems.append(f"scenarios.{name} missing (chaos scenario not run)")
    return problems


def _check_scaling(data: Any) -> List[str]:
    """SCALING_*.json: either a measured sweep (``sizes`` per device
    count, SCALING_cpu8) or an analytic estimate (``mesh`` +
    per-step collective model, SCALING_est_*)."""
    if not isinstance(data, dict):
        return [f"expected a JSON object, got {type(data).__name__}"]
    if "sizes" in data:  # measured sweep
        problems = _require(
            data, {"metric": (str,), "unit": (str,), "steps": (int,), "sizes": (dict,)}
        )
        if problems:
            return problems
        if not data["sizes"]:
            return ["'sizes' sweep is empty"]
        for n, row in data["sizes"].items():
            problems += [
                f"sizes[{n}].{p}" for p in _require(
                    row, {"step_ms": _NUM, "graphs_per_sec": _NUM}
                )
            ]
        return problems
    if "mesh" in data:  # analytic estimate
        problems = _require(
            data, {"mesh": (str,), "step_ms_device_single_chip": _NUM}
        )
        widths = data.get("widths")
        if widths is not None:
            if not isinstance(widths, dict) or not widths:
                problems.append("'widths' must be a non-empty object")
            else:
                for w, row in widths.items():
                    problems += [
                        f"widths[{w}].{p}"
                        for p in _require(row, {"n_devices": (int,)})
                    ]
        return problems
    return ["neither 'sizes' (measured sweep) nor 'mesh' (estimate) present"]


def _check_tune_tiles(data: Any) -> List[str]:
    """TUNE_TILES.json: {shape_tag: {device_kind: {BN, CE, BCAST_CE}}}
    — the committed tile sweep ops/segment_pallas.py reads its
    import-time defaults from."""
    problems = _require(data, {"_doc": (str,)})
    if problems:
        return problems
    tags = {k: v for k, v in data.items() if k != "_doc"}
    if not tags:
        return ["no shape-tag entries (only _doc)"]
    for tag, kinds in tags.items():
        if not isinstance(kinds, dict) or not kinds:
            problems.append(f"'{tag}' must be a non-empty object of device kinds")
            continue
        for kind, tiles in kinds.items():
            problems += [
                f"{tag}.{kind}.{p}" for p in _require(
                    tiles, {"BN": (int,), "CE": (int,), "BCAST_CE": (int,)}
                )
            ]
    return problems


def _check_ci_baseline(data: Any) -> List[str]:
    """BENCH_CI_BASELINE.json: {"backend:device_kind": perf row} — the
    regression reference tools/bench_ci.py compares against."""
    if not isinstance(data, dict):
        return [f"expected a JSON object, got {type(data).__name__}"]
    if not data:
        return ["no 'backend:device_kind' entries"]
    problems = []
    for key, row in data.items():
        if ":" not in key:
            problems.append(f"key '{key}' is not 'backend:device_kind'")
        problems += [
            f"{key}.{p}" for p in _require(
                row,
                {"step_ms_median": _NUM, "graphs_per_sec": _NUM, "steps": (int,)},
            )
        ]
    return problems


#: rule kinds obs/triggers.py:RULE_KINDS declares — duplicated here
#: because this module must stay loadable without the package (and
#: without jax); tests/test_triggers.py pins the two tuples equal.
_INCIDENT_RULE_KINDS = (
    "latency_p99",
    "queue_depth",
    "queue_age",
    "feature_drift",
    "pred_drift",
    "error_drift",
    "mfu_drop",
    "loss_spike",
    "nonfinite_burst",
    "pilot_stuck",
    "step_skew",
    "host_stall",
    "host_lost",
)


def _check_incident_manifest(data: Any) -> List[str]:
    """incident_manifest.json: one incident bundle's closing manifest
    (obs/triggers.py:Incident.close — the runtime validator there is
    validate_incident_manifest; this mirrors it for jax-free lint)."""
    problems = _require(
        data,
        {
            "schema_version": (int,),
            "id": (str,),
            "rule": (str,),
            "kind": (str,),
            "status": (str,),
            "trigger": (dict,),
            "files": (dict,),
            "profile": (dict,),
        },
    )
    if problems:
        return problems
    problems += [
        f"trigger.{p}" for p in _require(
            data["trigger"],
            {"rule": (str,), "kind": (str,), "observed": _NUM, "threshold": _NUM},
        )
    ]
    problems += [
        f"profile.{p}" for p in _require(
            data["profile"],
            {"captured": (bool,), "steps": (int,), "duration_s": _NUM,
             "nonempty": (bool,)},
        )
    ]
    if data["kind"] not in _INCIDENT_RULE_KINDS:
        problems.append(f"unknown rule kind {data['kind']!r}")
    return problems


#: machine-JSON artifact kinds: glob pattern -> (label, validator).
#: Patterns with ZERO committed matches are themselves findings — these
#: artifacts are evidence, and losing one silently is the failure mode.
MACHINE_SCHEMAS: Dict[str, Tuple[str, Callable[[Any], List[str]]]] = {
    "SCALING_*.json": ("scaling sweep/estimate", _check_scaling),
    "TUNE_TILES.json": ("kernel tile sweep", _check_tune_tiles),
    "BENCH_CI_BASELINE.json": ("CI perf baseline", _check_ci_baseline),
    "BENCH_FLEET.json": ("fleet chaos acceptance record", _check_fleet),
}

def _check_drift_report(data: Any) -> List[str]:
    """Drift report sidecar an incident bundle carries for the drift
    rule kinds (obs/drift.py:DriftMonitor.report()); the richer
    ``validate_drift_report`` lives there — this duplicates the fields
    downstream tools read so the linter stays package-free."""
    problems = _require(
        data,
        {"schema": (int,), "counts": (dict,), "feature": (dict,),
         "heads": (dict,), "error": (dict,)},
    )
    if problems:
        return problems
    if data["schema"] != 1:
        problems.append(f"unsupported drift report schema {data['schema']!r}")
    problems += [
        f"counts.{p}" for p in _require(
            data["counts"],
            {"feature_rows": _NUM, "pred_rows": _NUM, "labeled_rows": _NUM},
        )
    ]
    problems += [
        f"feature.{p}" for p in _require(
            data["feature"],
            {"psi_max": _NUM, "qshift_max": _NUM, "channels": (list,)},
        )
    ]
    return problems


def _check_podview_report(data: Any) -> List[str]:
    """Podview skew report sidecar a ``step_skew`` / ``host_stall``
    incident bundle carries (obs/podview.py:SkewMonitor.report()); the
    runtime validator there is ``validate_podview_report`` — this
    mirrors the fields downstream tools read so the linter stays
    package-free."""
    problems = _require(
        data,
        {"schema": (int,), "host": (int,), "hosts": (int,),
         "threshold": _NUM, "history": (list,), "attribution": (dict,)},
    )
    if problems:
        return problems
    if data["schema"] != 1:
        problems.append(f"unsupported podview report schema {data['schema']!r}")
    sh = data.get("slowest_host")
    if sh is not None and not isinstance(sh, int):
        problems.append("field 'slowest_host' must be an int or null")
    return problems


def _check_spool_manifest(data: Any) -> List[str]:
    """Per-shard manifest the request spool writes next to each HGC
    shard (obs/spool.py); pins the fields drift_report / retraining
    tooling read to pick a spool window."""
    problems = _require(
        data,
        {"schema": (int,), "shard": (str,), "num_samples": (int,),
         "model_fingerprint": (str,), "sample_every": (int,),
         "tenants": (list,), "seq_range": (list,), "t_range": (list,)},
    )
    if problems:
        return problems
    if data["schema"] != 1:
        problems.append(f"unsupported spool manifest schema {data['schema']!r}")
    if data["num_samples"] < 1:
        problems.append("spool shard manifest with num_samples < 1")
    if len(data["seq_range"]) != 2:
        problems.append("seq_range must be a [first, last] pair")
    return problems


def _check_pod_shard_manifest(data: Any) -> List[str]:
    """Per-host pod checkpoint shard manifest
    (resilience/podckpt.py:save_pod_shard) — the restore side trusts
    exactly these fields to reassemble leaves across layouts, so the
    linter holds them to the same bar as committed artifacts."""
    problems = _require(
        data,
        {"format_version": (int,), "gen": (int,), "host": (int,),
         "hosts": (int,), "shard": (str,), "sha256": (str,),
         "leaves": (list,)},
    )
    if problems:
        return problems
    if not (0 <= data["host"] < data["hosts"]):
        problems.append(
            f"host {data['host']} outside [0, hosts={data['hosts']})"
        )
    for i, leaf in enumerate(data["leaves"]):
        problems += [
            f"leaves[{i}].{p}" for p in _require(
                leaf, {"path": (str,), "key": (str,), "shape": (list,),
                       "dtype": (str,)},
            )
        ]
    return problems


def _check_pod_commit(data: Any) -> List[str]:
    """Generation COMMIT marker (resilience/podckpt.py) — written LAST
    by rank 0; a reader treats its presence as "this generation is
    complete", so its few fields must always be whole."""
    return _require(
        data,
        {"format_version": (int,), "gen": (int,), "hosts": (int,)},
    )


#: runtime-artifact kinds: produced by RUNS (never committed at the
#: repo root), so they dispatch by name for explicit paths but are
#: exempt from the zero-committed-matches scan above.
RUNTIME_SCHEMAS: Dict[str, Tuple[str, Callable[[Any], List[str]]]] = {
    "incident_manifest.json": (
        "incident bundle manifest", _check_incident_manifest,
    ),
    "drift_report.json": (
        "drift incident report", _check_drift_report,
    ),
    "spool_manifest.json": (
        "request spool shard manifest", _check_spool_manifest,
    ),
    "podview_report.json": (
        "podview skew report", _check_podview_report,
    ),
    "ckpt.gen*.host*.manifest.json": (
        "pod checkpoint shard manifest", _check_pod_shard_manifest,
    ),
    "gen*.COMMIT": (
        "pod checkpoint generation commit marker", _check_pod_commit,
    ),
}


def _machine_kind(name: str) -> Optional[Tuple[str, Callable[[Any], List[str]]]]:
    for pattern, spec in MACHINE_SCHEMAS.items():
        if fnmatch.fnmatch(name, pattern):
            return spec
    for pattern, spec in RUNTIME_SCHEMAS.items():
        if fnmatch.fnmatch(name, pattern):
            return spec
    return None


def validate_machine_artifact(path: str, rel_display: str) -> List[Finding]:
    """Validate ONE committed machine JSON artifact against its kind's
    schema (kind resolved from the file name)."""
    spec = _machine_kind(os.path.basename(path))
    if spec is None:
        return [
            Finding(
                rule="HGART",
                path=rel_display,
                line=1,
                col=1,
                message=(
                    "no schema registered for this artifact name "
                    "(known kinds: "
                    f"{', '.join(sorted({**MACHINE_SCHEMAS, **RUNTIME_SCHEMAS}))})"
                ),
            )
        ]
    label, check = spec
    try:
        with open(path) as fh:
            data = json.load(fh)
    except (OSError, ValueError) as exc:
        return [
            Finding(
                rule="HGART",
                path=rel_display,
                line=1,
                col=1,
                message=f"unreadable {label}: {exc}",
            )
        ]
    return [
        Finding(
            rule="HGART",
            path=rel_display,
            line=1,
            col=1,
            message=f"invalid {label}: {problem}",
            snippet=problem,
        )
        for problem in check(data)
    ]


def validate_machine_artifacts(repo_root: str) -> List[Finding]:
    """Validate every committed machine JSON artifact in the repo root;
    a kind with no matches at all is reported (lost evidence)."""
    findings: List[Finding] = []
    names = sorted(os.listdir(repo_root))
    for pattern, (label, _) in MACHINE_SCHEMAS.items():
        matches = [n for n in names if fnmatch.fnmatch(n, pattern)]
        if not matches:
            findings.append(
                Finding(
                    rule="HGART",
                    path=pattern,
                    line=1,
                    col=1,
                    message=f"no committed {label} matches '{pattern}'",
                )
            )
        for name in matches:
            findings.extend(
                validate_machine_artifact(os.path.join(repo_root, name), name)
            )
    return findings


def _load_flight_module(repo_root: str):
    path = os.path.join(repo_root, "hydragnn_tpu", "obs", "flight.py")
    spec = importlib.util.spec_from_file_location("_graftlint_flight", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def validate_artifacts(
    repo_root: str, paths: Optional[Sequence[str]] = None
) -> List[Finding]:
    """Validate each artifact; returns findings (empty = all valid).

    ``require_complete`` stays False: serve artifacts legitimately hold
    several run_start/run_end pairs (cold + warm passes) and no epoch
    events — every event must still be individually well-formed, and a
    kind absent from ``_REQUIRED`` has no required-field coverage at
    all, so unregistered kinds in a committed artifact are reported
    here too.

    With no explicit ``paths``, the committed machine JSON artifacts
    (``MACHINE_SCHEMAS``) are validated too; an explicit ``.json`` path
    is dispatched to its kind's schema by file name.
    """
    flight = _load_flight_module(repo_root)
    registered = set(flight._REQUIRED) | set(flight.FAULT_KINDS)
    findings: List[Finding] = []
    if paths is None:
        findings.extend(validate_machine_artifacts(repo_root))
    for rel in paths or DEFAULT_ARTIFACTS:
        path = rel if os.path.isabs(rel) else os.path.join(repo_root, rel)
        rel_display = rel.replace(os.sep, "/")
        if rel_display.endswith(".json"):
            findings.extend(validate_machine_artifact(path, rel_display))
            continue
        if not os.path.exists(path):
            findings.append(
                Finding(
                    rule="HGART",
                    path=rel_display,
                    line=1,
                    col=1,
                    message="flight artifact missing",
                )
            )
            continue
        for problem in flight.validate_flight_record(path):
            findings.append(
                Finding(
                    rule="HGART",
                    path=rel_display,
                    line=1,
                    col=1,
                    message=problem,
                    snippet=problem,
                )
            )
        for i, ev in enumerate(flight.read_flight_record(path)):
            kind = ev.get("kind")
            if kind and kind != "_unparseable" and kind not in registered:
                findings.append(
                    Finding(
                        rule="HGART",
                        path=rel_display,
                        line=i + 1,
                        col=1,
                        message=(
                            f"event[{i}] kind '{kind}' is not registered "
                            "in obs/flight.py _REQUIRED/FAULT_KINDS"
                        ),
                        snippet=str(kind),
                    )
                )
    return findings
