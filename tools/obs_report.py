"""Flight-record reporter: pretty-print, validate, and diff runs.

The flight record (hydragnn_tpu/obs/flight.py) is the machine-readable
artifact; this is the human view over it:

    python tools/obs_report.py run/flight.jsonl             # summary
    python tools/obs_report.py --validate run/flight.jsonl  # schema gate
    python tools/obs_report.py --diff a/flight.jsonl b/flight.jsonl

``--validate`` exits 1 on schema problems (``--require-complete`` also
demands run_start/epoch/run_end — what ci.sh asserts of its smoke run);
``--diff`` is the round-over-round tool: manifest drift (config,
backend, pad plans) and per-epoch loss/step-time deltas between two
runs — e.g. two rounds' BENCH flight records.
"""

from __future__ import annotations

import json
import sys
from typing import Dict, List, Optional

_REPO = __file__.rsplit("/", 2)[0]
if _REPO not in sys.path:  # runnable as `python tools/obs_report.py`
    sys.path.insert(0, _REPO)

from hydragnn_tpu.obs.flight import (  # noqa: E402
    FAULT_KINDS,
    epoch_phases,
    flight_record_warnings,
    read_flight_record,
    validate_flight_record,
)
from hydragnn_tpu.obs.introspect import (  # noqa: E402
    collect_head_series,
    flag_anomalies,
)
from hydragnn_tpu.obs.podview import (  # noqa: E402
    host_epoch_table,
    merge_host_flights,
)


def _fmt(v, nd: int = 6) -> str:
    if isinstance(v, float):
        return f"{v:.{nd}g}"
    return str(v)


def _flatten(d: dict, prefix: str = "") -> Dict[str, object]:
    out: Dict[str, object] = {}
    for k, v in sorted(d.items()):
        key = f"{prefix}.{k}" if prefix else str(k)
        if isinstance(v, dict):
            out.update(_flatten(v, key))
        else:
            out[key] = v
    return out


def _first(events: List[dict], kind: str) -> Optional[dict]:
    for e in events:
        if e.get("kind") == kind:
            return e
    return None


def _exec_cache_summary(events: List[dict]) -> Optional[str]:
    """One line over the run's ``exec_cache`` events (persistent AOT
    executable cache, hydragnn_tpu/utils/exec_cache.py): hit / miss /
    store / evict counts with the miss-reason breakdown. None when the
    record has no cache traffic (cache disabled or pre-r09 record)."""
    counts: Dict[str, int] = {}
    reasons: Dict[str, int] = {}
    for e in events:
        if e.get("kind") != "exec_cache":
            continue
        ev = str(e.get("event"))
        counts[ev] = counts.get(ev, 0) + 1
        if ev in ("miss", "evict"):
            r = str(e.get("reason") or "absent")
            reasons[r] = reasons.get(r, 0) + 1
    if not counts:
        return None
    parts = [f"{counts.get(k, 0)} {k}" for k in ("hit", "miss", "store", "evict")]
    line = " / ".join(parts)
    if reasons:
        line += " (" + ", ".join(
            f"{k}={v}" for k, v in sorted(reasons.items())
        ) + ")"
    ready = [
        e
        for e in events
        if e.get("kind") == "exec_cache" and e.get("event") == "train_ready"
    ]
    if ready:
        r = ready[-1]
        line += (
            f"; train_ready hit={r.get('hit')} compiles={r.get('compiles')} "
            f"build_s={r.get('build_s')} mode={r.get('mode')}"
        )
    return line


def _phase_tree(phases: Dict[str, dict], scale: float, unit: str) -> List[str]:
    """Program spans as an indented tree, children under their parent."""

    def walk(name: str, depth: int) -> List[str]:
        p = phases[name]
        row = f"  {'  ' * depth + name:32s} {p['s'] * scale:10.3f} {unit}  x{p['n']}"
        below = sorted((k for k, q in phases.items() if q.get("parent") == name), key=lambda k: -phases[k]["s"])
        return [row] + [r for k in below for r in walk(k, depth + 1)]

    roots = sorted((k for k, p in phases.items() if p.get("parent") not in phases), key=lambda k: -phases[k]["s"])
    return [r for k in roots for r in walk(k, 0)]


def render_phases(events: List[dict]) -> List[str]:
    """The program's spans (``obs/spans.py:span``): where set-up went,
    each epoch's phases, and what lies inside them on average."""
    lines: List[str] = []
    setup = _first(events, "setup")
    if setup and setup.get("phases"):
        lines.append("== setup phases ==")
        lines += _phase_tree(setup["phases"], 1.0, "s ")
    by_epoch = epoch_phases(events)
    if not by_epoch:
        return lines
    counts = {e["epoch"]: e for e in events if e.get("kind") == "epoch"}
    cols = sorted(
        {k for ph in by_epoch.values() for k, p in ph.items() if p.get("parent") == "epoch"},
        key=lambda k: -sum(ph.get(k, {}).get("s", 0.0) for ph in by_epoch.values()),
    )
    lines.append("== epoch phases (ms) ==")
    lines.append("  " + " ".join([f"{'ep':>4}", f"{'epoch':>9}"] + [f"{c.split('.', 1)[1][:13]:>13}" for c in cols]
                                 + [f"{'graphs':>8}", f"{'steps':>6}", f"{'diagnosed':>9}"]))
    for ep in sorted(by_epoch):
        ph = by_epoch[ep]
        cells = [f"{ep:>4}", f"{_ms(ph.get('epoch')):>9}"] + [f"{_ms(ph.get(c)):>13}" for c in cols]
        ev = counts.get(ep, {})
        lines.append("  " + " ".join(cells + [f"{ev.get('graphs', '-'):>8}", f"{ev.get('steps', '-'):>6}",
                                              f"{ev.get('diagnosed_steps', '-'):>9}"]))
    # whole epochs only: one cut short by a graceful stop has no ``epoch`` span
    whole = [ph for _, ph in sorted(by_epoch.items()) if "epoch" in ph]
    steady = whole[1:] or whole or list(by_epoch.values())
    mean: Dict[str, dict] = {}
    for ph in steady:
        for k, p in ph.items():
            m = mean.setdefault(k, {"s": 0.0, "n": 0, "parent": p.get("parent")})
            m["s"] += p["s"] / len(steady)
            m["n"] = max(m["n"], p["n"])
    lines.append(f"== inside an epoch (mean of {len(steady)} whole epoch(s) after the first, max count) ==")
    lines += _phase_tree(mean, 1e3, "ms")
    return lines


def _ms(phase: Optional[dict]) -> str:
    return "-" if not phase else f"{phase['s'] * 1e3:.1f}"


def render_report(events: List[dict]) -> str:
    """One run's story as text: manifest, set-up and epoch phases, epoch
    table, incidents, summary."""
    lines: List[str] = []
    start = _first(events, "run_start")
    if start:
        man = start.get("manifest", {})
        lines.append("== manifest ==")
        for key in (
            "run",
            "mode",
            "metric",
            "jax_version",
            "backend",
            "device_kind",
            "num_processes",
            "mesh",
            "num_epoch",
            "start_epoch",
            "scan_epoch",
            "mixed_precision",
        ):
            if key in man:
                lines.append(f"  {key}: {_fmt(man[key])}")
        par = man.get("parallel")
        if isinstance(par, dict) and par.get("available"):
            from hydragnn_tpu.parallel.partitioner import parallel_manifest_summary

            lines.append(f"  parallel: {parallel_manifest_summary(par)}")
        for split, plan in (man.get("pad_plans") or {}).items():
            lines.append(f"  pad[{split}]: {plan}")
    epochs = [e for e in events if e.get("kind") == "epoch"]
    if epochs:
        lines.append("== epochs ==")
        lines.append(
            "  ep    train_loss      val_loss        lr      steps  "
            "data_wait_s  dispatch_s  device_ms  compiles"
        )
        for e in epochs:
            st = e.get("step_time") or {}
            comp = e.get("compiles") or {}
            flag = " RECOMPILE!" if comp.get("unexpected") else ""
            lines.append(
                f"  {e.get('epoch', '?'):>4} "
                f"{_fmt(e.get('train_loss'), 6):>13} "
                f"{_fmt(e.get('val_loss'), 6):>13} "
                f"{_fmt(e.get('lr'), 4):>9} "
                f"{st.get('steps', e.get('steps', '-')):>6} "
                f"{_fmt(st.get('data_wait_s', '-'), 4):>12} "
                f"{_fmt(st.get('dispatch_s', '-'), 4):>11} "
                f"{_fmt(st.get('device_wait_ms_mean', '-'), 4):>10} "
                f"{comp.get('count', '-'):>8}{flag}"
            )
    lines += render_phases(events)
    ecache = _exec_cache_summary(events)
    if ecache:
        lines.append("== exec cache ==")
        lines.append(f"  {ecache}")
    incidents = [
        e for e in events if e.get("kind") in ("retry", "error", "_unparseable")
    ]
    if incidents:
        lines.append("== incidents ==")
        for e in incidents:
            lines.append(
                f"  [{e.get('kind')}] {e.get('error') or e.get('line') or ''}"
            )
    for kind in ("bench_config", "bench_result", "profile_trace"):
        for e in events:
            if e.get("kind") == kind:
                name = e.get("name") or e.get("path") or ""
                lines.append(f"== {kind} {name} ==")
                payload = {
                    k: v
                    for k, v in e.items()
                    if k not in ("v", "kind", "t", "rank", "name")
                }
                lines.append("  " + json.dumps(payload)[:400])
    end = _first(events, "run_end")
    if end is None:
        lines.append("== run_end: MISSING (crashed or still running) ==")
    else:
        lines.append("== run_end ==")
        for k, v in end.items():
            if k in ("v", "kind", "t", "rank", "metrics", "timers", "phases_late"):
                continue
            lines.append(f"  {k}: {_fmt(v)}")
        for k, t in (end.get("timers") or {}).items():
            lines.append(f"  timer {k}: {t}")
    return "\n".join(lines)


def render_heads(events: List[dict]) -> str:
    """The multi-task health view (``--heads``): per-head loss /
    grad-norm / MAE trajectories, the mean task-conflict matrix, the
    hardware-efficiency ledger, and the anomaly flags
    (``hydragnn_tpu/obs/introspect.py:flag_anomalies``) — the diagnosis
    a human or CI reads, not just the data."""
    series = collect_head_series(events)
    names = series["names"]
    lines: List[str] = []
    if not names:
        return "== heads: no per-head data in this record =="
    lines.append(f"== heads ({len(names)}): {', '.join(names)} ==")

    lines.append("== per-head trajectories ==")
    for n in names:
        lines.append(f"  head {n!r}:")
        lines.append(
            "      ep   train_loss    grad_norm          mae         rmse"
        )
        for i, ep in enumerate(series["epochs"]):
            row = [
                _fmt(series[key][n][i] if series[key][n][i] is not None else "-", 5)
                for key in ("train_loss", "grad_norm", "mae", "rmse")
            ]
            lines.append(
                f"    {ep!s:>4} {row[0]:>12} {row[1]:>12} {row[2]:>12} {row[3]:>12}"
            )

    mats = [m for m in series["cosine"] if m is not None]
    if mats:
        import numpy as np

        h = len(names)
        good = [np.asarray(m, float) for m in mats]
        good = [m for m in good if m.shape == (h, h)]
        if good:
            mean = np.mean(good, axis=0)
            lines.append(
                f"== task-conflict matrix (mean gradient cosine over "
                f"{len(good)} sampled epoch(s)) =="
            )
            short = [n[:12] for n in names]
            lines.append("  " + " " * 14 + " ".join(f"{s:>12}" for s in short))
            for i, s in enumerate(short):
                lines.append(
                    f"  {s:>14}"
                    + " ".join(f"{mean[i, j]:>+12.3f}" for j in range(h))
                )
    ratios = [r for r in series["update_ratio"] if r is not None]
    if ratios:
        lines.append(
            "== update/param norm ratio (sampled): "
            + ", ".join(f"{r:.3g}" for r in ratios)
            + " =="
        )

    hw_rows = [
        (e.get("epoch"), e.get("hw"))
        for e in events
        if e.get("kind") == "epoch" and isinstance(e.get("hw"), dict)
    ]
    if hw_rows:
        lines.append("== hardware-efficiency ledger ==")
        lines.append("      ep        mfu   achieved_tflops   mem_peak_bytes")
        for ep, hw in hw_rows:
            mem = (hw.get("memory") or {}).get("peak_bytes_in_use", "-")
            mfu = hw.get("mfu")
            tfl = hw.get("achieved_tflops")
            lines.append(
                f"    {ep!s:>4} {_fmt(mfu if mfu is not None else '-', 4):>10} "
                f"{_fmt(tfl if tfl is not None else '-', 6):>17} {mem!s:>16}"
            )

    flags = flag_anomalies(series)
    lines.append(f"== anomalies ({len(flags)}) ==")
    if flags:
        lines.extend(f"  ! {f}" for f in flags)
    else:
        lines.append("  (none — multi-task optimization looks healthy)")
    return "\n".join(lines)


def render_faults(events: List[dict]) -> str:
    """A run's fault history: chronological preemption / rollback /
    watchdog / restart / retry / error timeline — plus the serving-side
    kinds (quarantine, dispatch_restart, reload/reload_failed) — and
    non-completed run_end statuses: the view a post-mortem starts from.
    Handles MERGED records (several run_start..run_end segments in one
    file, the append-mode artifact of a supervised run)."""
    t0 = events[0].get("t") if events and isinstance(events[0].get("t"), (int, float)) else None

    def _rel(e) -> str:
        t = e.get("t")
        if t0 is None or not isinstance(t, (int, float)):
            return "     ?"
        return f"{t - t0:+9.2f}s"

    interesting = [
        e
        for e in events
        if e.get("kind") in FAULT_KINDS
        or e.get("kind") == "_unparseable"
        or (e.get("kind") == "run_end" and e.get("status") != "completed")
    ]
    counts = {
        "runs": sum(1 for e in events if e.get("kind") == "run_start"),
        "completed": sum(
            1
            for e in events
            if e.get("kind") == "run_end" and e.get("status") == "completed"
        ),
        "preempted": sum(
            1
            for e in events
            if e.get("kind") == "run_end" and e.get("status") == "preempted"
        ),
        "resumed": sum(1 for e in events if e.get("kind") == "resumed"),
        "rollbacks": sum(1 for e in events if e.get("kind") == "rollback"),
        "watchdog": sum(1 for e in events if e.get("kind") == "watchdog"),
        "restarts": sum(1 for e in events if e.get("kind") == "restart"),
        "host_lost": sum(1 for e in events if e.get("kind") == "host_lost"),
        "pod_resumes": sum(1 for e in events if e.get("kind") == "pod_resume"),
        "errors": sum(1 for e in events if e.get("kind") == "error"),
        "quarantined": sum(1 for e in events if e.get("kind") == "quarantine"),
        "dispatch_restarts": sum(
            1 for e in events if e.get("kind") == "dispatch_restart"
        ),
        "reloads": sum(1 for e in events if e.get("kind") == "reload"),
        "reload_failed": sum(
            1 for e in events if e.get("kind") == "reload_failed"
        ),
        "incidents": sum(1 for e in events if e.get("kind") == "incident"),
        "drift": sum(1 for e in events if e.get("kind") == "drift"),
        "pilot_cycles": sum(
            1
            for e in events
            if e.get("kind") == "pilot" and e.get("state") == "drift_confirmed"
        ),
        "pilot_stuck": sum(
            1
            for e in events
            if e.get("kind") == "pilot" and e.get("state") == "stuck"
        ),
        "spool_rotations": sum(
            1 for e in events if e.get("kind") == "spool_rotate"
        ),
        "nonfinite_skipped": sum(
            (e.get("nonfinite") or {}).get("skipped", 0)
            for e in events
            if e.get("kind") == "epoch"
        ),
    }
    lines = ["== fault summary =="]
    lines.append("  " + " ".join(f"{k}={v}" for k, v in counts.items()))
    if not interesting:
        lines.append("  (no fault events — a clean run)")
        return "\n".join(lines)
    lines.append("== fault timeline (t relative to first event) ==")
    for e in interesting:
        kind = e.get("kind")
        if kind == "preempt":
            detail = f"signal={e.get('signal')} epoch={e.get('epoch')} step={e.get('step')}"
        elif kind == "resumed":
            detail = f"epoch={e.get('epoch')}"
        elif kind == "rollback":
            detail = (
                f"epoch={e.get('epoch')} consec={e.get('consec')} "
                f"rollbacks={e.get('rollbacks')} lr={_fmt(e.get('lr'))}"
            )
        elif kind == "watchdog":
            stacks = e.get("stacks") or {}
            detail = f"stall_s={e.get('stall_s')} threads={sorted(stacks)}"
        elif kind == "restart":
            detail = (
                f"attempt={e.get('attempt')} cause={e.get('cause')} "
                f"exit_code={e.get('exit_code')} delay_s={e.get('delay_s')}"
            )
        elif kind == "host_lost":
            # a pod peer's heartbeats lapsed (or the supervisor saw its
            # signal death): the run restarts from the last committed
            # generation (docs/RESILIENCE.md 'Pod recovery')
            extras = [
                f"{k}={e[k]}"
                for k in ("epoch", "lost_after_s", "exit_code", "attempt")
                if e.get(k) is not None
            ]
            detail = f"host {e.get('host')} declared lost" + (
                " (" + " ".join(extras) + ")" if extras else ""
            )
        elif kind == "pod_resume":
            # the restarted run says which committed generation it rose
            # from and the pod layout that generation was cut under
            detail = (
                f"resumed from committed gen {e.get('gen')} "
                f"(prior_hosts={e.get('prior_hosts')}"
                + (
                    f", fallbacks={e.get('fallbacks')}"
                    if e.get("fallbacks")
                    else ""
                )
                + ")"
            )
        elif kind == "quarantine":
            detail = (
                f"seq={e.get('seq')} reason={e.get('reason')} "
                f"bucket={e.get('bucket')} error={str(e.get('error') or '')[:80]}"
            )
        elif kind == "dispatch_restart":
            detail = (
                f"attempt={e.get('attempt')} cause={e.get('cause')} "
                f"delay_s={e.get('delay_s')}"
            )
        elif kind == "reload":
            detail = f"source={e.get('source')} swap_s={e.get('swap_s')}"
        elif kind == "reload_failed":
            detail = (
                f"source={e.get('source')} rolled_back={e.get('rolled_back')} "
                f"error={str(e.get('error') or '')[:80]}"
            )
        elif kind == "incident":
            # SLO trigger fired; the bundle at `path` holds the evidence
            # (render it with tools/incident_report.py)
            detail = f"id={e.get('id')} rule={e.get('rule')} path={e.get('path')}"
        elif kind == "drift":
            # served traffic left the training reference; the incident
            # bundle's drift_report.json + the spool window hold the
            # evidence (render with tools/drift_report.py)
            window = e.get("spool_window") or {}
            detail = (
                f"rule={e.get('rule')} observed={_fmt(e.get('observed'))} "
                f"threshold={_fmt(e.get('threshold'))} "
                f"spool={window.get('dir') or '<off>'}"
            )
        elif kind == "pilot":
            # the retrain pilot's state machine (hydragnn_tpu/pilot):
            # drift_confirmed -> fine_tuning -> canary -> reloading ->
            # cooldown, or stuck when the recovery budget is spent
            extras = [
                f"{k}={e[k]}"
                for k in ("reason", "candidate", "rule")
                if e.get(k) is not None
            ]
            detail = (
                f"state={e.get('state')} cycle={e.get('cycle')} "
                f"failed_cycles={e.get('failed_cycles')}"
                + ("".join(" " + x for x in extras))
            )
        elif kind == "run_end":
            detail = f"status={e.get('status')}"
        else:
            detail = str(e.get("error") or e.get("line") or "")[:160]
        lines.append(f"  {_rel(e)} [{kind}] {detail}")
    return "\n".join(lines)


_SPARK = "▁▂▃▄▅▆▇█"


def _sparkline(values: List[object], lo: float = 0.0, hi: Optional[float] = None) -> str:
    """Unicode block sparkline; non-numeric entries render as spaces."""
    nums = [v for v in values if isinstance(v, (int, float))]
    if not nums:
        return ""
    if hi is None:
        hi = max(nums)
    span = max(hi - lo, 1e-9)
    out = []
    for v in values:
        if not isinstance(v, (int, float)):
            out.append(" ")
            continue
        idx = int((v - lo) / span * (len(_SPARK) - 1) + 0.5)
        out.append(_SPARK[min(len(_SPARK) - 1, max(0, idx))])
    return "".join(out)


def render_hosts(merged) -> str:
    """The pod view (``--hosts``, over a run directory of per-host
    flight shards): a per-epoch table with one row per host (epoch wall
    time, data-wait, nonfinite skips, MFU), the merge reader's advisory
    problems, and the rank-0 SkewMonitor's verdicts as a skew-fraction
    sparkline across epochs (docs/OBSERVABILITY.md 'Pod visibility')."""
    lines: List[str] = []
    lines.append(
        f"== hosts ({len(merged.hosts)}): "
        f"{', '.join(str(h) for h in merged.hosts) or '(none)'} =="
    )
    for prob in merged.problems:
        lines.append(f"  note: {prob}")
    table = host_epoch_table(merged.events)
    if not table:
        lines.append(
            "  (no host_epoch events — single-host record or podview off)"
        )
    else:
        lines.append(
            "    ep  host     epoch_s  data_wait_s  nonfinite          mfu"
        )
        for ep in sorted(table):
            rows = sorted(table[ep].items())
            slowest = (
                max(rows, key=lambda kv: kv[1].get("epoch_s") or 0.0)[0]
                if len(rows) > 1
                else None
            )
            for h, ev in rows:
                mark = "  <- slowest" if h == slowest else ""
                lines.append(
                    f"  {ep!s:>4} {h!s:>5} "
                    f"{_fmt(ev.get('epoch_s', '-'), 5):>11} "
                    f"{_fmt(ev.get('data_wait_s', '-'), 4):>12} "
                    f"{ev.get('nonfinite_skipped', 0)!s:>10} "
                    f"{_fmt(ev.get('mfu', '-'), 4):>12}{mark}"
                )
    verdicts = [e for e in merged.events if e.get("kind") == "podview"]
    if verdicts:
        vals = [e.get("skew_frac") for e in verdicts]
        nums = [v for v in vals if isinstance(v, (int, float))]
        last = verdicts[-1]
        thr = last.get("threshold")
        lines.append("== skew (rank-0 SkewMonitor) ==")
        lines.append(
            f"  skew_frac {_sparkline(vals, 0.0, max(nums + [thr or 0.0, 1e-9]))} "
            f"(epochs {verdicts[0].get('epoch')}..{last.get('epoch')}, "
            f"threshold {_fmt(thr, 4)})"
        )
        lines.append(
            f"  last: skew_frac={_fmt(last.get('skew_frac'), 4)} "
            f"slowest_host={last.get('slowest_host')} "
            f"cause={last.get('cause')}"
        )
    return "\n".join(lines)


def fault_schema_problems(events: List[dict]) -> List[str]:
    """Schema problems affecting the fault-history subset (what
    ``--faults`` gates on: a fault event that cannot be parsed is
    evidence lost exactly when it matters)."""
    watched = set(FAULT_KINDS) | {"run_end"}
    out = []
    for p in validate_flight_record(events):
        if "unparseable" in p or any(f"({k})" in p for k in watched):
            out.append(p)
    return out


def render_diff(a_events: List[dict], b_events: List[dict]) -> str:
    """What changed between two runs: manifest drift + per-epoch and
    summary deltas."""
    lines: List[str] = []
    a_start, b_start = _first(a_events, "run_start"), _first(b_events, "run_start")
    a_man = _flatten((a_start or {}).get("manifest") or {})
    b_man = _flatten((b_start or {}).get("manifest") or {})
    drift = []
    for key in sorted(set(a_man) | set(b_man)):
        va, vb = a_man.get(key, "<absent>"), b_man.get(key, "<absent>")
        if va != vb:
            drift.append(f"  {key}: {_fmt(va)} -> {_fmt(vb)}")
    lines.append(f"== manifest drift ({len(drift)} keys) ==")
    lines.extend(drift or ["  (identical)"])

    a_ep = {e.get("epoch"): e for e in a_events if e.get("kind") == "epoch"}
    b_ep = {e.get("epoch"): e for e in b_events if e.get("kind") == "epoch"}
    common = sorted(set(a_ep) & set(b_ep))
    if common:
        lines.append("== per-epoch deltas (B - A) ==")
        for ep in common:
            ea, eb = a_ep[ep], b_ep[ep]
            parts = [f"  ep {ep}:"]
            for field in ("train_loss", "val_loss"):
                va, vb = ea.get(field), eb.get(field)
                if isinstance(va, (int, float)) and isinstance(vb, (int, float)):
                    parts.append(f"{field} {vb - va:+.6g}")
            sa = (ea.get("step_time") or {}).get("data_wait_s")
            sb = (eb.get("step_time") or {}).get("data_wait_s")
            if isinstance(sa, (int, float)) and isinstance(sb, (int, float)):
                parts.append(f"data_wait_s {sb - sa:+.4g}")
            lines.append(" ".join(parts))
    only_a, only_b = sorted(set(a_ep) - set(b_ep)), sorted(set(b_ep) - set(a_ep))
    if only_a:
        lines.append(f"  epochs only in A: {only_a}")
    if only_b:
        lines.append(f"  epochs only in B: {only_b}")

    a_end, b_end = _first(a_events, "run_end"), _first(b_events, "run_end")
    lines.append("== run_end ==")
    for name, end in (("A", a_end), ("B", b_end)):
        if end is None:
            lines.append(f"  {name}: MISSING")
        else:
            brief = {
                k: v
                for k, v in end.items()
                if k in ("status", "epochs", "best_val_loss", "value", "metric")
            }
            lines.append(f"  {name}: {json.dumps(brief)}")
    return "\n".join(lines)


def main(argv=None) -> int:
    import argparse

    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument(
        "records",
        nargs="+",
        help="flight-record .jsonl path(s), or run directories of "
        "per-host shards (flight.jsonl + flight.host<k>.jsonl)",
    )
    p.add_argument(
        "--validate",
        action="store_true",
        help="schema-check instead of printing; exit 1 on problems",
    )
    p.add_argument(
        "--require-complete",
        action="store_true",
        help="with --validate: also require run_start + epoch(s) + run_end",
    )
    p.add_argument(
        "--diff",
        action="store_true",
        help="diff exactly two records (A B)",
    )
    p.add_argument(
        "--faults",
        action="store_true",
        help="fault-history view: preemption / rollback / watchdog / "
        "restart timeline (handles merged multi-run records); exits 1 "
        "when any fault event fails its schema",
    )
    p.add_argument(
        "--hosts",
        action="store_true",
        help="pod view over merged per-host flight shards: per-host "
        "epoch table (wall, data-wait, nonfinite skips, MFU) and the "
        "SkewMonitor skew sparkline; accepts a run directory",
    )
    p.add_argument(
        "--heads",
        action="store_true",
        help="multi-task health view: per-head loss/grad-norm/MAE "
        "trajectories, the gradient-cosine conflict matrix, the "
        "hardware-efficiency ledger, and anomaly flags "
        "(docs/OBSERVABILITY.md 'Model-level diagnostics')",
    )
    args = p.parse_args(argv)

    def _print_warnings(events) -> None:
        # forward-compat advisories (unknown kinds, newer schema
        # versions): surfaced, never fatal
        for w in flight_record_warnings(events):
            print(f"  WARNING: {w}")

    if args.hosts:
        for path in args.records:
            merged = merge_host_flights(path)
            if len(args.records) > 1:
                print(f"===== {path} =====")
            print(render_hosts(merged))
            _print_warnings(merged.events)
        return 0

    if args.heads:
        for path in args.records:
            events = read_flight_record(path)
            if len(args.records) > 1:
                print(f"===== {path} =====")
            print(render_heads(events))
            _print_warnings(events)
        return 0

    if args.faults:
        rc = 0
        for path in args.records:
            events = read_flight_record(path)
            if len(args.records) > 1:
                print(f"===== {path} =====")
            print(render_faults(events))
            problems = fault_schema_problems(events)
            for prob in problems:
                rc = 1
                print(f"  SCHEMA: {prob}")
        return rc

    if args.diff:
        if len(args.records) != 2:
            p.error("--diff needs exactly two records")
        a, b = (read_flight_record(r) for r in args.records)
        print(render_diff(a, b))
        _print_warnings(a)
        _print_warnings(b)
        return 0

    import os

    rc = 0
    for path in args.records:
        if args.validate and os.path.isdir(path):
            # a run directory of per-host shards: the merged timeline
            # must be schema-valid, but shard-level trouble (torn
            # tails, missing hosts) is advisory — the surviving hosts'
            # evidence still merges and must not fail the gate
            merged = merge_host_flights(path)
            problems = list(validate_flight_record(merged.events))
            if args.require_complete:
                # completeness is per shard: the merged timeline
                # legitimately interleaves one run_start per host
                from hydragnn_tpu.obs.podview import list_host_shards

                for h, shard in sorted(list_host_shards(path).items()):
                    for prob in validate_flight_record(
                        shard, require_complete=True
                    ):
                        problems.append(f"host{h}: {prob}")
            if problems:
                rc = 1
                print(f"{path}: INVALID ({len(problems)} problem(s))")
                for prob in problems:
                    print(f"  - {prob}")
            else:
                print(
                    f"{path}: OK ({len(merged.events)} merged events from "
                    f"{len(merged.hosts)} host shard(s))"
                )
                # pod-checkpoint posture: the newest committed
                # generation a restart would rise from, and — when a
                # run in this record DID rise from one — its lineage
                from hydragnn_tpu.resilience.podckpt import latest_commit_info

                commit = latest_commit_info(path)
                if commit is not None:
                    print(
                        f"  podckpt: last committed gen {commit.get('gen')}"
                        f" (step={commit.get('step')}"
                        f" hosts={commit.get('hosts')})"
                    )
                for e in merged.events:
                    if e.get("kind") != "run_start":
                        continue
                    lineage = (e.get("manifest") or {}).get("pod_resume")
                    if lineage:
                        print(
                            "  pod_resume: from gen "
                            f"{lineage.get('resumed_from_gen')} "
                            f"(prior_hosts={lineage.get('prior_hosts')}, "
                            f"prior_layout={lineage.get('prior_layout')})"
                        )
            for prob in merged.problems:
                print(f"  WARNING: {prob}")
            _print_warnings(merged.events)
            continue
        events = read_flight_record(path)
        if args.validate:
            problems = validate_flight_record(
                events, require_complete=args.require_complete
            )
            if problems:
                rc = 1
                print(f"{path}: INVALID ({len(problems)} problem(s))")
                for prob in problems:
                    print(f"  - {prob}")
            else:
                print(f"{path}: OK ({len(events)} events)")
                # surface the parallelism story alongside the verdict:
                # which mesh ran this record and how its state sharded
                start = _first(events, "run_start")
                par = ((start or {}).get("manifest") or {}).get("parallel")
                if isinstance(par, dict) and par.get("available"):
                    from hydragnn_tpu.parallel.partitioner import (
                        parallel_manifest_summary,
                    )

                    print(f"  parallel: {parallel_manifest_summary(par)}")
                ecache = _exec_cache_summary(events)
                if ecache:
                    print(f"  exec_cache: {ecache}")
                lineage = ((start or {}).get("manifest") or {}).get("pod_resume")
                if lineage:
                    print(
                        "  pod_resume: from gen "
                        f"{lineage.get('resumed_from_gen')} "
                        f"(prior_hosts={lineage.get('prior_hosts')}, "
                        f"prior_layout={lineage.get('prior_layout')})"
                    )
                # drift-observability posture: was the spool/drift plane
                # armed for the serve run(s) this record holds? (a serve
                # bench artifact with drift off is a monitoring gap, not
                # a schema error — surfaced, never fatal)
                serves = [
                    (e.get("manifest") or {})
                    for e in events
                    if e.get("kind") == "run_start"
                    and (e.get("manifest") or {}).get("mode") == "serve"
                ]
                if serves:
                    armed = sum(
                        1 for m in serves if (m.get("drift") or {}).get("armed")
                    )
                    spooled = sum(
                        1 for m in serves if (m.get("spool") or {}).get("enabled")
                    )
                    print(
                        f"  drift: armed on {armed}/{len(serves)} serve run(s),"
                        f" spool on {spooled}/{len(serves)}"
                    )
            _print_warnings(events)
        else:
            if len(args.records) > 1:
                print(f"===== {path} =====")
            print(render_report(events))
    return rc


if __name__ == "__main__":
    raise SystemExit(main())
