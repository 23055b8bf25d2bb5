"""Serving benchmark: synthetic online traffic through the ModelServer.

Prints ONE JSON line. Headline: steady-state serving throughput
(graphs/sec) through the bucketed micro-batching path, plus the serving
metrics the subsystem exists to bound — request latency percentiles,
per-bucket occupancy, and ``compile_misses_after_warmup`` (MUST be 0:
every steady-state request routes to an AOT-compiled bucket; a nonzero
value means the ladder no longer covers the traffic and requests are
paying XLA compiles on the serving path).

Two phases after startup AOT warmup:
  1. a short warmup burst (stabilizes jit/allocator state; its requests
     are excluded from the timed window);
  2. the timed load phase — ``SERVE_THREADS`` concurrent closed-loop
     clients submitting ``SERVE_REQUESTS`` graphs sampled from the
     dataset size distribution.

CPU mode (``JAX_PLATFORMS=cpu python bench_serve.py``) runs a smoke-
sized model; the same knobs scale it to a real chip. Knobs:
SERVE_REQUESTS, SERVE_THREADS, SERVE_MAX_BATCH, SERVE_DELAY_MS,
SERVE_BUCKETS, SERVE_SAMPLES, SERVE_HIDDEN, SERVE_LAYERS.

Cold-vs-warm mode (``python bench_serve.py --cold-warm``, or
SERVE_COLD_WARM=1): the r09 cold-start headline. Starts TWO sequential
servers against the same persistent executable cache directory
(utils/exec_cache.py; SERVE_EXEC_CACHE overrides the default fresh temp
dir): the first (cold) pays the AOT bucket-ladder compiles and stores
every executable, the second (warm) must deserialize the whole ladder
from disk — ``compile_warmup == 0`` is asserted, the record reports
``startup_cold_s`` / ``startup_warm_s`` plus compile and exec-cache
counts, and both servers prove the ladder actually serves traffic.

Chaos mode (``python bench_serve.py --chaos``, or SERVE_CHAOS=1): the
committed self-healing acceptance run (docs/RESILIENCE.md "Serving
resilience"). Against live traffic it injects a raise-in-forward poison
request, a wedged dispatch (forward sleeps past the watchdog
threshold), a dispatch-thread death, and performs one hot reload —
then asserts the server ends the run READY, every submitted request
resolved (result or typed RequestFailed: ZERO lost/hanging futures),
the quarantine/restart/reload counts match the injection plan in both
the metrics and the flight record, and post-recovery traffic paid 0
new compile misses. The headline value is the worst not-ready gap
(recovery time); exit 1 on any violated invariant. The mid-traffic
hot reload here is the same canary + atomic-swap path the retrain
pilot (``hydragnn_tpu/pilot``, docs/RESILIENCE.md "Closed loop")
drives as the final stage of every retrain cycle, so this number is
also the serving-impact bound for a pilot-initiated reload.

Fleet mode (``python bench_serve.py --fleet``, or SERVE_FLEET=1): the
fleet chaos acceptance run (docs/FLEET.md). Measures sustained QPS at
fixed p99 through an N=2 replica fleet (vs an N=1 baseline — the
scale-out efficiency headline), then runs the three fleet chaos
scenarios against live traffic: replica-kill mid-traffic (controller
reaps + replaces, router death-retry absorbs in-flights), scale-up
under sustained queue breach (trigger verdict spawns a replica), and a
fleet-wide rolling reload. Every scenario asserts p99 under
FLEET_SLO_P99_MS and zero lost futures; every post-first replica must
warm-start from the shared exec cache with 0 AOT compiles. Writes the
committed, schema-validated BENCH_FLEET.json.
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time


def main() -> None:
    from bench import init_device_with_flight, open_bench_flight

    metric = "serve_bucketed_throughput"
    # backend init (compile cache placed, no retry) + a fresh flight
    # record: the serving bench leaves the same self-contained JSONL
    # evidence artifact training and bench.py do (BENCH_FLIGHT overrides
    # the path for both benches; default name differs so one round can
    # keep both artifacts)
    flight = open_bench_flight("BENCH_SERVE_FLIGHT.jsonl")
    device = init_device_with_flight(metric, flight)

    import numpy as np

    from hydragnn_tpu.flagship import build_flagship
    from hydragnn_tpu.serve import ModelRegistry, ModelServer, ServeConfig

    n_requests = int(os.environ.get("SERVE_REQUESTS", 96))
    n_threads = int(os.environ.get("SERVE_THREADS", 2))
    max_batch = int(os.environ.get("SERVE_MAX_BATCH", 8))
    delay_ms = float(os.environ.get("SERVE_DELAY_MS", 5.0))
    num_buckets = int(os.environ.get("SERVE_BUCKETS", 3))
    n_samples = int(os.environ.get("SERVE_SAMPLES", 64))
    hidden = int(os.environ.get("SERVE_HIDDEN", 16))
    layers = int(os.environ.get("SERVE_LAYERS", 2))

    # Random-init flagship (PNA multi-head): serving cost does not depend
    # on the weights, and skipping the train/checkpoint round-trip keeps
    # the bench self-contained. The checkpoint path is covered by
    # tests/test_serve.py's run_prediction-equivalence test.
    _, model, variables, loader = build_flagship(
        n_samples=n_samples,
        hidden_dim=hidden,
        num_conv_layers=layers,
        batch_size=max(max_batch, 2),
        unit_cells=(2, 4),
    )
    registry = ModelRegistry()
    served = registry.register("bench_serve", model, variables)

    requests = list(loader.all_samples)
    server = ModelServer(
        served,
        requests,
        ServeConfig(
            max_batch=max_batch,
            num_buckets=num_buckets,
            max_delay_ms=delay_ms,
            max_pending=max(4 * max_batch * n_threads, 64),
        ),
        flight=flight,
    )
    t0 = time.perf_counter()
    server.start()  # AOT-compiles the whole bucket ladder
    warmup_s = time.perf_counter() - t0

    # phase 1: warmup burst (excluded from the timed window)
    for s in requests[: min(2 * max_batch, len(requests))]:
        server.predict(s, timeout=60)
    snap_warm = server.metrics_snapshot()
    misses_at_warmup = snap_warm["compile_misses"]

    # phase 2: timed closed-loop clients over the dataset distribution
    rng = np.random.default_rng(0)
    order = rng.integers(0, len(requests), size=n_requests)
    per_thread = np.array_split(order, n_threads)
    errors: list = []

    # graftsync: thread-root
    def client(idx_list) -> None:
        try:
            for i in idx_list:
                server.predict(requests[int(i)], timeout=120)
        except BaseException as exc:  # pragma: no cover - surfaced in record
            errors.append(repr(exc))

    # graftsync: disable=HS004 -- every element is joined in the loop below
    threads = [threading.Thread(target=client, args=(ix,)) for ix in per_thread]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0

    snap = server.metrics_snapshot()
    server.stop()
    misses_after_warmup = snap["compile_misses"] - misses_at_warmup
    occ = {
        name: round(b["occupancy_mean"], 2)
        for name, b in snap["buckets"].items()
        if b["batches"]
    }
    record = {
        "metric": metric,
        "value": round(n_requests / wall, 2),
        "unit": "graphs/sec",
        "requests": n_requests,
        "threads": n_threads,
        "max_batch": max_batch,
        "max_delay_ms": delay_ms,
        "buckets": len(server.buckets),
        "bucket_plans": [
            [b.cap_nodes, b.cap_edges, b.node_pad, b.edge_pad] for b in server.buckets
        ],
        "warmup_compile_s": round(warmup_s, 2),
        "compile_warmup": snap["compile_warmup"],
        "compile_misses_after_warmup": misses_after_warmup,
        "latency": {k: round(v, 2) for k, v in snap["latency"].items()},
        "occupancy_mean": occ,
        "queue_depth_peak": snap["queue_depth_peak"],
        "rejected_overload": snap["rejected_overload"],
        # whether drift monitoring / the request spool was armed for
        # this bench (obs_report --validate surfaces the same from the
        # flight manifest)
        "observability": server.obs_arming,
        "errors": errors[:3],
    }
    # server.stop() already logged its run_end (metrics snapshot); the
    # bench's own verdict rides a final event, then the file closes
    flight.record(
        "bench_result",
        record=record,
        passed=bool(not errors and misses_after_warmup == 0),
    )
    flight.close()
    print(json.dumps(record))
    if errors:
        raise SystemExit(1)
    if misses_after_warmup != 0:
        print(
            f"FAIL: {misses_after_warmup} compile-cache misses after warmup — "
            "steady-state traffic recompiled",
            file=sys.stderr,
        )
        raise SystemExit(1)


def cold_warm() -> None:
    """Cold vs warm serve startup against one persistent executable
    cache dir (see module docstring). Exit 1 if the warm start paid ANY
    live warmup compile — the zero-compile second replica is the
    acceptance bar, not an aspiration."""
    from bench import init_device_with_flight, open_bench_flight

    metric = "serve_cold_vs_warm_startup"
    flight = open_bench_flight("BENCH_SERVE_WARM_FLIGHT.jsonl")
    device = init_device_with_flight(metric, flight)

    import tempfile

    from hydragnn_tpu.flagship import build_flagship
    from hydragnn_tpu.serve import ModelRegistry, ModelServer, ServeConfig

    max_batch = int(os.environ.get("SERVE_MAX_BATCH", 8))
    num_buckets = int(os.environ.get("SERVE_BUCKETS", 3))
    n_samples = int(os.environ.get("SERVE_SAMPLES", 64))
    hidden = int(os.environ.get("SERVE_HIDDEN", 16))
    layers = int(os.environ.get("SERVE_LAYERS", 2))
    cache_dir = os.environ.get("SERVE_EXEC_CACHE") or tempfile.mkdtemp(
        prefix="serve_exec_cache_"
    )

    _, model, variables, loader = build_flagship(
        n_samples=n_samples,
        hidden_dim=hidden,
        num_conv_layers=layers,
        batch_size=max(max_batch, 2),
        unit_cells=(2, 4),
    )
    requests = list(loader.all_samples)
    registry = ModelRegistry()

    def one_start(tag: str) -> dict:
        # a fresh registration per start = a fresh jitted forward, so
        # the warm server cannot lean on the cold server's in-process
        # jit cache — its zero-compile startup is the DISK cache's work
        served = registry.register(f"bench_serve_{tag}", model, variables)
        server = ModelServer(
            served,
            requests,
            ServeConfig(
                max_batch=max_batch,
                num_buckets=num_buckets,
                exec_cache_dir=cache_dir,
            ),
            flight=flight,
        )
        t0 = time.perf_counter()
        server.start()
        startup_s = time.perf_counter() - t0
        # the deserialized ladder must actually serve, not just load
        for s in requests[: min(max_batch, len(requests))]:
            server.predict(s, timeout=60)
        snap = server.metrics_snapshot()
        ladder = len(server.buckets)
        server.stop()
        return {
            "startup_s": round(startup_s, 3),
            "buckets": ladder,
            "compile_warmup": snap["compile_warmup"],
            "compile_misses": snap["compile_misses"],
            "exec_cache_hits": snap["exec_cache_hits"],
            "exec_cache_misses": snap["exec_cache_misses"],
            "exec_cache_miss_reasons": snap["exec_cache_miss_reasons"],
            "observability": server.obs_arming,
        }

    cold = one_start("cold")
    warm = one_start("warm")

    failures = []
    if warm["compile_warmup"] != 0:
        failures.append(
            f"warm start paid {warm['compile_warmup']} live warmup "
            "compiles — the persistent cache did not cover the ladder"
        )
    if warm["exec_cache_hits"] < warm["buckets"]:
        failures.append(
            f"warm exec_cache_hits={warm['exec_cache_hits']} below the "
            f"ladder size {warm['buckets']} — some bucket recompiled"
        )
    record = {
        "metric": metric,
        "value": warm["startup_s"],
        "unit": "s_warm_startup",
        "startup_cold_s": cold["startup_s"],
        "startup_warm_s": warm["startup_s"],
        "warm_over_cold": round(
            warm["startup_s"] / max(cold["startup_s"], 1e-9), 3
        ),
        "cache_dir": cache_dir,
        "cold": cold,
        "warm": warm,
        "failures": failures,
    }
    flight.record("bench_result", record=record, passed=not failures)
    flight.close()
    print(json.dumps(record))
    if failures:
        for f in failures:
            print(f"FAIL: {f}", file=sys.stderr)
        raise SystemExit(1)


def chaos() -> None:
    """The serving-resilience acceptance run (see module docstring)."""
    from bench import init_device_with_flight, open_bench_flight

    metric = "serve_chaos_recovery"
    flight = open_bench_flight("BENCH_SERVE_CHAOS_FLIGHT.jsonl")
    device = init_device_with_flight(metric, flight)

    import numpy as np

    from hydragnn_tpu.flagship import build_flagship
    from hydragnn_tpu.serve import (
        ModelRegistry,
        ModelServer,
        RequestFailed,
        ServeConfig,
    )

    n_requests = int(os.environ.get("SERVE_REQUESTS", 96))
    max_batch = int(os.environ.get("SERVE_MAX_BATCH", 8))
    n_samples = int(os.environ.get("SERVE_SAMPLES", 64))
    hidden = int(os.environ.get("SERVE_HIDDEN", 16))
    layers = int(os.environ.get("SERVE_LAYERS", 2))

    # the injection plan: one poison raise, one wedged forward past the
    # watchdog threshold, one dispatch-thread death, one hot reload
    seq_raise = n_requests // 4
    seq_wedge = (2 * n_requests) // 3
    kill_batch = 3
    wedge_s = 1
    os.environ["HYDRAGNN_INJECT_SERVE_RAISE"] = str(seq_raise)
    os.environ["HYDRAGNN_INJECT_SERVE_WEDGE"] = f"{seq_wedge}:{wedge_s}"
    os.environ["HYDRAGNN_INJECT_SERVE_KILL_DISPATCH"] = str(kill_batch)

    _, model, variables, loader = build_flagship(
        n_samples=n_samples,
        hidden_dim=hidden,
        num_conv_layers=layers,
        batch_size=max(max_batch, 2),
        unit_cells=(2, 4),
    )
    registry = ModelRegistry()
    served = registry.register("bench_serve_chaos", model, variables)
    requests = list(loader.all_samples)
    server = ModelServer(
        served,
        requests,
        ServeConfig(
            max_batch=max_batch,
            max_delay_ms=3.0,
            max_pending=max(8 * n_requests, 256),
            dispatch_stall_s=0.25,
            dispatch_backoff_base_s=0.2,
        ),
        flight=flight,
    )
    server.start()

    # readiness sampler: the recovery-time measurement
    ready_samples: list = []
    sampling = threading.Event()

    # graftsync: thread-root
    def sampler() -> None:
        while not sampling.wait(0.01):
            ready_samples.append((time.perf_counter(), server.health()["ready"]))

    sampler_t = threading.Thread(target=sampler, daemon=True)
    sampler_t.start()

    rng = np.random.default_rng(0)
    order = rng.integers(0, len(requests), size=n_requests)
    futures = []
    t0 = time.perf_counter()
    reload_info = None
    for i, idx in enumerate(order):
        futures.append(server.submit(requests[int(idx)]))
        time.sleep(0.002)  # paced open-loop: faults land mid-traffic
        if i == n_requests // 2:
            # hot reload mid-traffic (fresh copy of the same weights:
            # the canary + atomic-swap path, architecture unchanged)
            reload_info = server.reload(variables=dict(variables))
    results, typed_failures, lost = 0, 0, 0
    for f in futures:
        try:
            f.result(timeout=120)
            results += 1
        except RequestFailed:
            typed_failures += 1
        except BaseException:
            lost += 1  # an UNtyped failure is a lost contract
    wall = time.perf_counter() - t0

    # settle, then measure the not-ready gaps out of the sampler trace
    deadline = time.perf_counter() + 10.0
    while not server.health()["ready"] and time.perf_counter() < deadline:
        time.sleep(0.01)
    sampling.set()
    sampler_t.join(timeout=2.0)
    gaps, gap_start = [], None
    for t, ready in ready_samples:
        if not ready and gap_start is None:
            gap_start = t
        elif ready and gap_start is not None:
            gaps.append(t - gap_start)
            gap_start = None
    if gap_start is not None:
        gaps.append(ready_samples[-1][0] - gap_start)

    health = server.health()
    snap = server.metrics_snapshot()
    server.stop()
    for k in list(os.environ):
        if k.startswith("HYDRAGNN_INJECT_SERVE_"):
            del os.environ[k]

    from hydragnn_tpu.obs.flight import read_flight_record

    events = read_flight_record(flight.path)
    fcounts = {
        kind: sum(1 for e in events if e.get("kind") == kind)
        for kind in ("quarantine", "dispatch_restart", "watchdog", "reload", "reload_failed")
    }

    plan = {"quarantined": 1, "dispatch_restarts": 1, "reloads": 1}
    failures = []
    if lost:
        failures.append(f"{lost} futures failed UNtyped (lost contract)")
    if results + typed_failures != n_requests:
        failures.append(
            f"resolved {results}+{typed_failures} != submitted {n_requests}"
        )
    if not health["ready"]:
        failures.append(f"server not ready at end: {health['reasons']}")
    for key, want in plan.items():
        if snap[key] != want:
            failures.append(f"metrics {key}={snap[key]} != plan {want}")
    if fcounts["quarantine"] != plan["quarantined"]:
        failures.append(f"flight quarantine={fcounts['quarantine']} != 1")
    if fcounts["dispatch_restart"] != plan["dispatch_restarts"]:
        failures.append(f"flight dispatch_restart={fcounts['dispatch_restart']} != 1")
    if fcounts["reload"] != plan["reloads"] or fcounts["reload_failed"]:
        failures.append(
            f"flight reload={fcounts['reload']}/failed={fcounts['reload_failed']}"
        )
    if fcounts["watchdog"] < 1:
        failures.append("wedged dispatch never tripped the watchdog")
    if snap["compile_misses"] != 0:
        failures.append(
            f"{snap['compile_misses']} compile misses — recovery recompiled"
        )

    record = {
        "metric": metric,
        "value": round(max(gaps), 3) if gaps else 0.0,
        "unit": "s_worst_not_ready_gap",
        "requests": n_requests,
        "wall_s": round(wall, 2),
        "results": results,
        "typed_failures": typed_failures,
        "lost_futures": lost,
        "injection_plan": {
            "raise_at_seq": seq_raise,
            "wedge_at_seq": [seq_wedge, wedge_s],
            "kill_dispatch_at_batch": kill_batch,
            "reload_at_request": n_requests // 2,
        },
        "not_ready_gaps_s": [round(g, 3) for g in gaps],
        "reload": reload_info,
        "metrics": {k: snap[k] for k in (
            "quarantined", "poison_retries", "dispatch_restarts", "reloads",
            "reload_failed", "errors", "compile_misses",
        )},
        "flight_counts": fcounts,
        "observability": server.obs_arming,
        "failures": failures,
    }
    flight.record("bench_result", record=record, passed=not failures)
    flight.close()
    print(json.dumps(record))
    if failures:
        for f in failures:
            print(f"FAIL: {f}", file=sys.stderr)
        raise SystemExit(1)


def fleet_chaos() -> None:
    """Fleet acceptance run (``--fleet``, docs/FLEET.md): sustained QPS
    at fixed p99 through an N>=2 replica fleet on one host, then the
    three fleet chaos scenarios against live traffic — replica-kill
    mid-traffic (controller restores capacity), scale-up-under-load
    (trigger verdict spawns a replica), and a fleet-wide rolling reload
    — each asserting p99 under the SLO throughout and ZERO lost
    futures (result or typed error; the router's death-retry absorbs
    the kill). Every post-first replica must warm-start from the shared
    exec cache with 0 AOT compiles; scale-out efficiency (QPS at N=2 vs
    N=1) lands in the committed, schema-validated BENCH_FLEET.json.
    Per-replica SLO trigger rules stay armed, so any breach
    auto-captures an incident bundle (counted in the record)."""
    from bench import init_device_with_flight, open_bench_flight

    metric = "fleet_sustained_qps"
    flight = open_bench_flight("BENCH_FLEET_FLIGHT.jsonl")
    device = init_device_with_flight(metric, flight)

    import tempfile

    import numpy as np

    from hydragnn_tpu.fleet import ControllerConfig, Fleet, FleetController
    from hydragnn_tpu.flagship import build_flagship
    from hydragnn_tpu.serve import ModelRegistry, ServeConfig

    n_requests = int(os.environ.get("SERVE_REQUESTS", 96))
    n_threads = int(os.environ.get("SERVE_THREADS", 4))
    max_batch = int(os.environ.get("SERVE_MAX_BATCH", 8))
    n_samples = int(os.environ.get("SERVE_SAMPLES", 64))
    hidden = int(os.environ.get("SERVE_HIDDEN", 16))
    layers = int(os.environ.get("SERVE_LAYERS", 2))
    slo_p99_ms = float(os.environ.get("FLEET_SLO_P99_MS", 3000.0))
    out_path = os.environ.get("FLEET_BENCH_OUT", "BENCH_FLEET.json")

    cache_dir = os.environ.get("SERVE_EXEC_CACHE") or tempfile.mkdtemp(
        prefix="fleet_exec_cache_"
    )
    incident_dir = tempfile.mkdtemp(prefix="fleet_incidents_")

    _, model, variables, loader = build_flagship(
        n_samples=n_samples,
        hidden_dim=hidden,
        num_conv_layers=layers,
        batch_size=max(max_batch, 2),
        unit_cells=(2, 4),
    )
    registry = ModelRegistry()
    requests = list(loader.all_samples)
    serve_cfg = ServeConfig(
        max_batch=max_batch,
        max_delay_ms=3.0,
        max_pending=max(8 * n_requests, 256),
        dispatch_backoff_base_s=0.2,
        slo_p99_ms=slo_p99_ms,
        incident_dir=incident_dir,
    )
    rng = np.random.default_rng(0)
    failures: list = []
    lost_total = 0

    def run_traffic(fleet, n: int, tag: str) -> dict:
        """Closed-loop clients through the ROUTER; returns QPS + p99 +
        the resolve ledger (every submitted future accounted for)."""
        nonlocal lost_total
        order = rng.integers(0, len(requests), size=n)
        per_thread = np.array_split(order, n_threads)
        latencies: list = []
        ledger = {"results": 0, "typed": 0, "lost": 0}
        ledger_lock = threading.Lock()

        # graftsync: thread-root
        def client(idx_list) -> None:
            from hydragnn_tpu.serve import Overloaded, RequestFailed
            from hydragnn_tpu.serve.batcher import ServerClosed

            for i in idx_list:
                t0 = time.perf_counter()
                try:
                    fleet.predict(requests[int(i)], timeout=120)
                    with ledger_lock:
                        latencies.append(time.perf_counter() - t0)
                        ledger["results"] += 1
                except (RequestFailed, Overloaded, ServerClosed):
                    with ledger_lock:
                        ledger["typed"] += 1
                except BaseException:
                    with ledger_lock:
                        ledger["lost"] += 1

        # graftsync: disable=HS004 -- every element is joined in the loop below
        threads = [threading.Thread(target=client, args=(ix,)) for ix in per_thread]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
        lat_sorted = sorted(latencies)
        p99 = (
            lat_sorted[min(len(lat_sorted) - 1, int(round(0.99 * (len(lat_sorted) - 1))))]
            * 1e3
            if lat_sorted
            else 0.0
        )
        lost_total += ledger["lost"]
        if ledger["lost"]:
            failures.append(f"{tag}: {ledger['lost']} futures failed UNtyped")
        if p99 > slo_p99_ms:
            failures.append(f"{tag}: p99 {p99:.0f}ms over SLO {slo_p99_ms:g}ms")
        return {
            "qps": round(n / wall, 2),
            "p99_ms": round(p99, 1),
            "wall_s": round(wall, 2),
            **ledger,
        }

    scenarios = {}

    # -- phase A: N=1 baseline QPS (pays the one-time AOT compiles) --------
    fleet1 = Fleet(exec_cache_dir=cache_dir, flight=flight)
    fleet1.add_model("flagship", registry.register("fleet_n1", model, variables),
                     requests, serve_cfg, replicas=1)
    scenarios["baseline_n1"] = run_traffic(fleet1, n_requests, "baseline_n1")
    fleet1.stop()
    qps_n1 = scenarios["baseline_n1"]["qps"]

    # -- phase B: N=2 fleet from the same cache (both replicas warm) -------
    fleet = Fleet(exec_cache_dir=cache_dir, flight=flight)
    reps = fleet.add_model(
        "flagship", registry.register("fleet_n2", model, variables),
        requests, serve_cfg, replicas=2,
    )
    warm_aot = sum(r.server.metrics_snapshot()["compile_warmup"] for r in reps)
    if warm_aot:
        failures.append(
            f"{warm_aot} AOT compiles in the N=2 fleet — the shared exec "
            "cache did not cover the ladder"
        )
    ctl = FleetController(
        fleet,
        registry=fleet.registry,
        config=ControllerConfig(
            min_replicas=1, max_replicas=3, cooldown_s=0.0, quiet_for_s=3600.0,
            slo_queue_depth=4.0, breach_evals=2,
        ),
        flight=flight,
    )

    scenarios["sustained_n2"] = run_traffic(fleet, n_requests, "sustained_n2")
    qps_n2 = scenarios["sustained_n2"]["qps"]

    # -- scenario: replica-kill mid-traffic --------------------------------
    victim = fleet.replicas()[0]
    killer = threading.Timer(0.05, victim.kill)
    killer.start()
    kill_stats = run_traffic(fleet, n_requests, "replica_kill")
    killer.join()
    ctl.step()  # reap + replace, outside any cooldown
    replacement = [
        r for r in fleet.replicas() if r.name not in (victim.name,)
    ]
    kill_stats["replaced"] = fleet.replica_count() == 2
    kill_stats["replacement_aot_compiles"] = sum(
        r.server.metrics_snapshot()["compile_warmup"]
        for r in replacement
    )
    if not kill_stats["replaced"]:
        failures.append("replica_kill: controller did not restore capacity")
    if kill_stats["replacement_aot_compiles"]:
        failures.append("replica_kill: replacement replica paid AOT compiles")
    if not all(r.ready for r in fleet.replicas()):
        failures.append("replica_kill: fleet not READY after replacement")
    scenarios["replica_kill"] = kill_stats

    # -- scenario: scale-up under load -------------------------------------
    burst = [fleet.submit(requests[int(i)]) for i in
             rng.integers(0, len(requests), size=6 * max_batch)]
    decisions = []
    deadline = time.perf_counter() + 30.0
    while time.perf_counter() < deadline:
        if fleet.total_load() <= 4:
            # keep the queue over the trigger threshold until the
            # controller has seen a SUSTAINED breach (breach_evals=2)
            burst += [
                fleet.submit(requests[int(i)])
                for i in rng.integers(0, len(requests), size=2 * max_batch)
            ]
        decisions += ctl.step()
        if any(d["action"] == "up" for d in decisions):
            break
    burst_lost = 0
    for f in burst:
        try:
            f.result(timeout=120)
        except BaseException as exc:
            from hydragnn_tpu.serve import Overloaded, RequestFailed

            if not isinstance(exc, (RequestFailed, Overloaded)):
                burst_lost += 1
    lost_total += burst_lost
    scaled = any(d["action"] == "up" for d in decisions)
    new_replicas = [r for r in fleet.replicas()]
    scenarios["scale_up_under_load"] = {
        "scaled": scaled,
        "replicas_after": fleet.replica_count(),
        "burst": len(burst),
        "lost": burst_lost,
        "new_replica_aot_compiles": sum(
            r.server.metrics_snapshot()["compile_warmup"] for r in new_replicas
        ),
        "decisions": [d["action"] for d in decisions],
    }
    if not scaled:
        failures.append("scale_up: no up decision under sustained queue breach")
    if burst_lost:
        failures.append(f"scale_up: {burst_lost} burst futures failed UNtyped")
    if scenarios["scale_up_under_load"]["new_replica_aot_compiles"]:
        failures.append("scale_up: scaled-up replica paid AOT compiles")
    if not all(r.ready for r in fleet.replicas()):
        failures.append("scale_up: fleet not READY after scale-up")

    # -- scenario: fleet-wide rolling reload mid-traffic -------------------
    roller_result: list = []

    # graftsync: thread-root
    def roller() -> None:
        try:
            roller_result.append(
                fleet.rolling_reload("flagship", variables=dict(variables))
            )
        except BaseException as exc:  # pragma: no cover - surfaced below
            roller_result.append(exc)

    roll_t = threading.Thread(target=roller)
    roll_t.start()
    reload_stats = run_traffic(fleet, n_requests, "rolling_reload")
    roll_t.join(timeout=120)
    ok = (
        roller_result
        and isinstance(roller_result[0], list)
        and all(o["ok"] for o in roller_result[0])
        and len(roller_result[0]) == fleet.replica_count()
    )
    reload_stats["reloaded_replicas"] = (
        len(roller_result[0]) if ok else 0
    )
    if not ok:
        failures.append(f"rolling_reload failed: {roller_result[:1]!r}")
    if not all(r.ready for r in fleet.replicas()):
        failures.append("rolling_reload: fleet not READY at end")
    scenarios["rolling_reload"] = reload_stats

    # every replica shares one ServeConfig, so one replica's arming
    # blocks describe the whole fleet's drift-observability posture
    reps = fleet.replicas()
    obs_arming = reps[0].server.obs_arming if reps else None

    health = fleet.health()
    fleet.stop()

    incidents = sum(
        1 for root, dirs, files in os.walk(incident_dir)
        if "trigger.json" in files
    )
    record = {
        "metric": metric,
        "value": qps_n2,
        "unit": "graphs/sec",
        "replicas": 2,
        "requests_per_phase": n_requests,
        "threads": n_threads,
        "slo_p99_ms": slo_p99_ms,
        "qps_n1": qps_n1,
        "qps_n2": qps_n2,
        "scaleout_efficiency": round(qps_n2 / max(2 * qps_n1, 1e-9), 3),
        "warm_replica_aot_compiles": warm_aot,
        "lost_futures": lost_total,
        "incidents_captured": incidents,
        "final_health": {
            k: health[k] for k in ("replica_count", "ready_count", "live_count")
        },
        "scenarios": scenarios,
        "observability": obs_arming,
        "failures": failures,
    }
    flight.record("bench_result", record=record, passed=not failures)
    flight.close()
    with open(out_path, "w") as f:
        json.dump(record, f, indent=1, sort_keys=True)
        f.write("\n")
    print(json.dumps(record))
    if failures:
        for msg in failures:
            print(f"FAIL: {msg}", file=sys.stderr)
        raise SystemExit(1)


if __name__ == "__main__":
    if "--fleet" in sys.argv or os.environ.get("SERVE_FLEET") == "1":
        fleet_chaos()
    elif "--chaos" in sys.argv or os.environ.get("SERVE_CHAOS") == "1":
        chaos()
    elif "--cold-warm" in sys.argv or os.environ.get("SERVE_COLD_WARM") == "1":
        cold_warm()
    else:
        main()
