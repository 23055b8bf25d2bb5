"""The benchmark's entry point: one cell, one run, one result line.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process (a chip belongs to one process). It makes the cell's samples
and weights from ``--seed``, calls ``hydragnn_tpu.api.run_training`` ONCE
with the cell's configuration, and carves the measured window out of that
call between two epoch boundaries (``taps.py``): loader, dispatch, model
step, optimizer, validation, test, diagnostics, flight record and
checkpoint all run inside it, at the program's defaults. When the window
is full the program's own graceful stop ends the run. Then the peak memory
is read, the program's state is freed, the plain reference follows the
first steps the timed program made, and ``compare.py`` decides ``correct``.

The last line of standard output is the result. It fails (non-zero exit,
no result) unless JAX finds a TPU with exactly the chips the cell asks
for. ``--rehearse`` runs the same control flow at the tiny sizes the
workload file gives, on whatever backend JAX finds, writes its numbers
under ``rehearsal_metrics`` (never under ``metrics``) and exits 3.
"""

from __future__ import annotations

import time

_WALL0, _PC0 = time.time(), time.perf_counter()

import argparse
import gc
import glob
import importlib.util
import json
import os
import shutil
import sys
from typing import Any, Dict, List

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for p in (ROOT, HERE):
    if p not in sys.path:
        sys.path.insert(0, p)

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def process_start_wall() -> float:
    """Wall-clock time this process was created (Linux), else the time
    this module began to load."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        started = time.time() - uptime + ticks / os.sysconf("SC_CLK_TCK")
        return min(started, _WALL0)
    except (OSError, ValueError, IndexError):
        return _WALL0


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--keep-trace", default=None, help="copy the .xplane.pb here (for looking at one by hand)")
    return ap.parse_args(argv)


def read_flight(log_dir: str) -> List[Dict[str, Any]]:
    events = []
    for path in glob.glob(os.path.join(log_dir, "*", "flight.jsonl")):
        with open(path) as f:
            for line in f:
                line = line.strip()
                if line:
                    events.append(json.loads(line))
    return events


def load_metric_reader(name: str):
    path = os.path.join(HERE, "metrics", f"{name}.py")
    if not os.path.isfile(path):
        return None
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def benchmark_json() -> Dict[str, Any]:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def wanted_metrics(spec: Dict[str, Any], group: str, cell_name: str) -> List[Dict[str, Any]]:
    return [m for m in spec[group] if "workloads" not in m or cell_name in m["workloads"]]


def _host_facts() -> Dict[str, Any]:
    """Where this process sits on its host (a one-chip machine shares its
    host's cores): for reading run-to-run differences in the host's part."""
    facts: Dict[str, Any] = {"cpus_allowed": len(os.sched_getaffinity(0)), "cpu_count": os.cpu_count()}
    try:
        with open("/proc/self/stat") as f:
            facts["last_cpu"] = int(f.read().rsplit(")", 1)[1].split()[36])
    except (OSError, ValueError, IndexError):
        pass
    return facts


def _spread(values: List[float]) -> Dict[str, float]:
    v = sorted(values)
    return {"min": v[0], "median": v[len(v) // 2], "max": v[-1]} if v else {}


def _phase_means(flight, first: int, last: int) -> Dict[str, float]:
    """Host seconds per window epoch under each child of the program's
    ``epoch`` span, from the flight record's ``phases``."""
    import program_spans

    phases = program_spans.epoch_phases(flight)
    sums: Dict[str, float] = {}
    for i in range(first, last):
        for name, row in (phases.get(i) or {}).items():
            if row.get("parent") == program_spans.ROOT:
                sums[name] = sums.get(name, 0.0) + float(row["s"])
    return {k: v / (last - first) for k, v in sorted(sums.items())}


def run_cell(args, check_device: bool = True) -> Dict[str, Any]:
    """Everything but argument parsing and printing. Tests call it with
    ``check_device=False`` (and ``args.rehearse`` true) to drive a run on
    the CPU with the timed path broken underneath."""
    import cell as cellmod

    cell = cellmod.load_cell(args.workload, rehearse=args.rehearse)
    work = os.path.join(HERE, "_work", cell.name + ("-rehearse" if args.rehearse else ""))
    shutil.rmtree(work, ignore_errors=True)
    log_dir = os.path.join(work, "logs")
    os.makedirs(log_dir, exist_ok=True)
    if args.rehearse and cell.chips > 1 and "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "") + f" --xla_force_host_platform_device_count={cell.chips}"
        ).strip()

    import jax

    cellmod.place_compile_cache(args.rehearse)

    try:
        import hydragnn_tpu  # noqa: F401  the system under test
        from hydragnn_tpu.api import run_training
        from hydragnn_tpu.resilience import TrainingPreempted
    except ImportError as exc:
        print(f"benchmark: the program is not importable from {ROOT}: {exc}", file=sys.stderr)
        raise SystemExit(4)

    devices = jax.devices()
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind, "count": len(devices)}
    if check_device and not args.rehearse:
        if device["platform"] != "tpu" or device["count"] != cell.chips:
            print(f"benchmark: cell {cell.name} needs {cell.chips} TPU chip(s), JAX found {device}", file=sys.stderr)
            raise SystemExit(2)
        import peaks

        peaks.lookup(device["kind"])  # an unknown device ends the run here

    compiles: List[tuple] = []  # (perf_counter at the end of the compile, seconds)

    def on_duration(event: str, secs: float, **_):
        if event == COMPILE_EVENT:
            compiles.append((time.perf_counter(), float(secs)))

    jax.monitoring.register_event_duration_secs_listener(on_duration)

    t_gen0 = time.perf_counter()
    raw = cell.fam.generate(cell.traffic, args.seed)
    samples = cell.fam.program_samples(raw)
    gen_s = time.perf_counter() - t_gen0

    trace_dir = os.path.join(work, "trace") if (args.trace and not args.rehearse) else None
    from taps import Taps

    taps = Taps(cell, args.seed, args.seconds, samples, trace_dir=trace_dir)
    ended = None
    with taps:
        try:
            run_training(cell.run_config, samples=samples, log_dir=log_dir)
            ended = "the run ended before the window was full"
        except TrainingPreempted:
            pass
    t_end = time.perf_counter()
    if ended or taps.window_last is None:
        raise RuntimeError(ended or "the run was stopped before the window closed")

    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    device["memory_peak_bytes"] = peak

    flight = read_flight(log_dir)
    first, last = taps.window_first, taps.window_last
    t_open, t_close = taps.epoch_t[first], taps.epoch_t[last]
    window_s = t_close - t_open
    epochs = {e["epoch"]: e for e in flight if e.get("kind") == "epoch"}
    manifest = next((e["manifest"] for e in flight if e.get("kind") == "run_start"), {})
    in_window = [epochs[i] for i in range(first, last) if i in epochs]
    steps = taps.steps_per_epoch * (last - first)
    bad_epochs = sum(1 for e in in_window if (e.get("compiles") or {}).get("count", 0) > 0)
    skipped = sum((e.get("nonfinite") or {}).get("skipped", 0) for e in in_window)
    late_compiles = [c for c in compiles if t_open < c[0] <= t_close]
    failed = skipped
    if bad_epochs or late_compiles or len(in_window) != last - first:
        failed = steps  # a compile inside the window: the run is void, not slow

    ctx: Dict[str, Any] = {
        "cell": cell, "taps": taps, "flight": flight, "manifest": manifest, "epochs": epochs,
        "window": {"first": first, "last": last, "seconds": window_s, "steps": steps,
                   "graphs": taps.graphs_per_epoch * (last - first)},
        "setup": {"seconds": (t_open - _PC0) + (_WALL0 - process_start_wall()),
                  "compile_s": sum(s for t, s in compiles if t <= t_open),
                  "generate_s": gen_s},
        "device": device, "trace": None, "rehearse": args.rehearse,
    }
    # epochs of the window that ran without the profiler: the host-clock
    # per-layer metrics are taken over these (the epoch that stops the
    # trace pays for writing it)
    t_a, t_b = taps.traced if taps.traced else (None, None)
    ctx["epoch_seconds"] = {i: taps.epoch_t[i + 1] - taps.epoch_t[i] for i in range(first, last)}
    ctx["quiet_epochs"] = [i for i in range(first, last) if t_a is None or not (t_a <= i <= t_b)]
    ctx["traced_epochs"] = (t_b - t_a) if t_a is not None else 0
    ctx["traced_steps"] = ctx["traced_epochs"] * taps.steps_per_epoch

    # free the program's state before anything else uses the device
    del samples
    gc.collect()

    if trace_dir is not None:
        import trace_reduce

        pb = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))
        if not pb:
            raise RuntimeError(f"the profiler wrote no .xplane.pb under {trace_dir}")
        if args.keep_trace:
            os.makedirs(os.path.dirname(os.path.abspath(args.keep_trace)), exist_ok=True)
            shutil.copy(pb[-1], args.keep_trace)
        ctx["trace"] = trace_reduce.reduce(pb[-1])
        device["busy_s"] = ctx["trace"]["busy_s"]
        device["window_s"] = ctx["trace"]["window_s"]

    import compare

    checks, correct, notes = compare.decide(cell, taps, raw)
    ctx["reference_s"] = time.perf_counter() - t_end
    ctx["real"] = notes["real"]

    spec = benchmark_json()
    group = "per_layer" if args.trace else "end_to_end"
    metrics: Dict[str, Dict[str, Any]] = {}
    for m in wanted_metrics(spec, group, cell.name):
        reader = load_metric_reader(m["name"])
        value = reader.read(ctx) if reader is not None else None
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}

    result: Dict[str, Any] = {
        "correct": bool(correct), "attempted": int(steps), "failed": int(failed),
        "metrics": {} if args.rehearse else metrics, "device": device,
    }
    if args.rehearse:
        result["rehearsal_metrics"] = metrics
    if ctx["trace"] is not None:
        result["breakdown"] = ctx["trace"]["breakdown"]
    result["run"] = {
        "workload": cell.name, "seed": args.seed, "dispatch_mode": taps.mode,
        "window_epochs": last - first, "window_s": window_s, "epochs_run": len(taps.epoch_t),
        "reference_s": ctx["reference_s"], "compiles_in_window": len(late_compiles) + bad_epochs,
        "epoch_s": _spread(list(ctx["epoch_seconds"].values())),
        "train_wall_s": _spread([float(((epochs.get(i) or {}).get("hw") or {}).get("train_wall_s") or 0.0)
                                 for i in range(first, last)]),
        # host seconds per window epoch by the program's own spans (flight
        # record); "tail" is what is left of an epoch after the train
        # dispatch (validate, test, diagnostics, flight record, tensorboard,
        # every second epoch a checkpoint)
        "phase_s_per_epoch": {
            **_phase_means(flight, first, last),
            "tail": (window_s - sum(float(((epochs.get(i) or {}).get("hw") or {}).get("train_wall_s") or 0.0)
                                    for i in range(first, last))) / (last - first),
        },
        "host": _host_facts(),
        "compare": notes,
        # where the set-up went, seconds since this process began to load
        "setup_marks_s": {
            "imports_done": t_gen0 - _PC0, "samples_made": t_gen0 + gen_s - _PC0,
            **{k: v - _PC0 for k, v in taps.marks.items()},
            **{f"epoch{i}_start": taps.epoch_t[i] - _PC0 for i in range(min(first + 1, len(taps.epoch_t)))},
        },
    }
    result["checks"] = checks  # each number compared, beside its limit: last
    shutil.rmtree(os.path.join(work, "logs"), ignore_errors=True)
    return result


def main(argv=None) -> int:
    args = parse_args(argv)
    result = run_cell(args)
    for name, c in result["checks"].items():
        print(f"check {name}: value {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 3 if args.rehearse else 0


if __name__ == "__main__":
    sys.exit(main())
