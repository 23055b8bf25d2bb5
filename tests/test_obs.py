"""Unified-telemetry tests (hydragnn_tpu/obs): registry semantics,
flight-record schema round-trip, compile-monitor windows (including the
acceptance contract — zero train-step recompiles after step 1), span
tracing, exporters, the disabled path's zero-overhead guarantees, the
bench retry-with-backoff, and the chip-hygiene report."""

import io
import json
import os
import threading
import time

import numpy as np
import pytest

from hydragnn_tpu.obs import (
    epoch_phases,
    BACKEND_COMPILE_EVENT,
    CompileMonitor,
    FlightRecorder,
    MetricsRegistry,
    StepSpans,
    get_registry,
    read_flight_record,
    registry_to_jsonl,
    registry_to_prometheus,
    registry_to_prometheus_text,
    reset_registry,
    validate_flight_record,
)
from hydragnn_tpu.obs.registry import NULL_COUNTER, NULL_GAUGE, NULL_HISTOGRAM


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------


def test_registry_counter_gauge_histogram_semantics():
    r = MetricsRegistry(rank=0)
    c = r.counter("train.steps")
    c.inc()
    c.inc(3)
    assert c.value == 4
    assert r.counter("train.steps") is c  # same name -> same metric

    g = r.gauge("serve.queue_depth")
    g.set(7)
    g.set(2)
    assert g.value == 2 and g.peak == 7

    h = r.histogram("latency_s", window=4)
    for v in (0.1, 0.2, 0.3, 0.4, 0.5):
        h.observe(v)
    snap = h.snapshot()
    # window=4: the 0.1 aged out of percentiles, but count/sum are all-time
    assert snap["count"] == 5 and abs(snap["sum"] - 1.5) < 1e-9
    assert snap["p50"] == pytest.approx(0.4) and snap["p99"] == pytest.approx(0.5)

    nested = r.snapshot()
    assert nested["train"]["steps"] == 4
    assert nested["serve"]["queue_depth"] == 2
    assert nested["latency_s"]["count"] == 5

    with pytest.raises(TypeError):
        r.gauge("train.steps")  # name already registered as a Counter


def test_registry_thread_safety_smoke():
    r = MetricsRegistry()
    c = r.counter("hits")

    def worker():
        for _ in range(1000):
            c.inc()

    threads = [threading.Thread(target=worker) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value == 4000


def test_disabled_registry_hands_out_null_singletons():
    r = MetricsRegistry(enabled=False)
    c, g, h = r.counter("a"), r.gauge("b"), r.histogram("c")
    # process-wide singletons: the disabled path allocates no metric
    # objects per call site, and recording is a no-op
    assert c is NULL_COUNTER and g is NULL_GAUGE and h is NULL_HISTOGRAM
    c.inc(100)
    g.set(5)
    h.observe(1.0)
    assert c.value == 0 and g.value == 0 and h.count == 0
    assert r.snapshot() == {}


def test_global_registry_honors_env_gate(monkeypatch):
    monkeypatch.setenv("HYDRAGNN_TELEMETRY", "0")
    reset_registry()
    try:
        assert get_registry().enabled is False
        assert get_registry().counter("x") is NULL_COUNTER
    finally:
        monkeypatch.delenv("HYDRAGNN_TELEMETRY")
        reset_registry()


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------


def test_flight_record_roundtrip_and_schema(tmp_path):
    path = str(tmp_path / "flight.jsonl")
    with FlightRecorder(path) as fr:
        fr.start_run({"run": "t", "config": {"a": 1}, "pad_plans": {}})
        fr.epoch(
            0,
            train_loss=1.0,
            val_loss=2.0,
            step_time={"data_wait_s": 0.1, "dispatch_s": 0.2},
            compiles={"count": 3, "available": True},
        )
        fr.retry(1, "UNAVAILABLE: chip busy", stage="backend_init")
        fr.error(ValueError("boom"), stage="epoch")
        fr.end_run(status="completed", epochs=1)
    events = read_flight_record(path)
    assert [e["kind"] for e in events] == [
        "run_start",
        "epoch",
        "retry",
        "error",
        "run_end",
    ]
    # envelope + autofilled manifest environment fields
    man = events[0]["manifest"]
    assert man["jax_version"] and man["backend"] and man["num_processes"] >= 1
    assert all({"v", "kind", "t", "rank"} <= set(e) for e in events)
    assert events[3]["error_type"] == "ValueError"
    assert validate_flight_record(path, require_complete=True) == []


def test_flight_record_tolerates_truncated_tail(tmp_path):
    path = str(tmp_path / "flight.jsonl")
    with FlightRecorder(path) as fr:
        fr.start_run({"run": "t"})
        fr.epoch(0, train_loss=1.0, val_loss=1.0)
    with open(path, "a") as f:
        f.write('{"v": 1, "kind": "run_end", "t": 1.0, "ra')  # crash mid-write
    events = read_flight_record(path)
    assert [e["kind"] for e in events] == ["run_start", "epoch"]
    # incomplete run still validates structurally...
    assert validate_flight_record(events) == []
    # ...but fails the completeness gate (no run_end)
    problems = validate_flight_record(events, require_complete=True)
    assert any("run_end" in p for p in problems)


def test_flight_record_validation_flags_missing_fields(tmp_path):
    path = str(tmp_path / "bad.jsonl")
    with open(path, "w") as f:
        f.write(json.dumps({"v": 1, "kind": "epoch", "t": 1.0, "rank": 0}) + "\n")
        f.write("not json at all\n")
        f.write(json.dumps({"v": 1, "kind": "run_end", "t": 2.0, "rank": 0, "status": "x"}) + "\n")
    problems = validate_flight_record(path)
    assert any("train_loss" in p for p in problems)
    assert any("unparseable" in p for p in problems)


def test_disabled_flight_recorder_writes_nothing(tmp_path):
    path = str(tmp_path / "off.jsonl")
    fr = FlightRecorder(path, enabled=False)
    fr.start_run({"run": "t"})
    fr.end_run(status="completed")
    fr.close()
    assert not os.path.exists(path)
    # a None path is equally inert (the server's default)
    FlightRecorder(None).record("anything")


# ---------------------------------------------------------------------------
# span tracing
# ---------------------------------------------------------------------------


def test_spans_decompose_data_wait_dispatch_device():
    import jax.numpy as jnp

    spans = StepSpans(sample_steps=2, skip_first=1)
    spans.epoch_start(0)

    def slow_loader():
        import time

        for _ in range(4):
            time.sleep(0.002)
            yield jnp.ones(())

    def step(x):
        return x + 1

    for batch in spans.timed_iter(slow_loader()):
        spans.step(step, batch)
    snap = spans.epoch_snapshot()
    assert snap["steps"] == 4
    assert snap["data_wait_s"] >= 0.004  # the loader sleeps were seen
    assert snap["dispatch_s"] > 0
    assert snap["sampled_steps"] == 2  # steps 1 and 2 were fenced
    assert snap["device_wait_ms_mean"] is not None
    assert snap["sync_step_ms_mean"] >= 0
    # epoch reset
    spans.epoch_start(1)
    assert spans.epoch_snapshot()["steps"] == 0


def test_disabled_spans_add_no_per_step_work(monkeypatch):
    """The telemetry-off contract: identity iteration, direct step
    calls, and NO device syncs — block_until_ready is poisoned to prove
    the disabled path never touches it."""
    import jax

    def _boom(*a, **kw):  # pragma: no cover - must never run
        raise AssertionError("disabled spans must not sync")

    monkeypatch.setattr(jax, "block_until_ready", _boom)
    spans = StepSpans.disabled()
    spans.epoch_start(0)
    batches = [1, 2, 3]
    assert spans.timed_iter(batches) is batches  # identity, not a wrapper
    calls = []
    out = spans.step(lambda x: calls.append(x) or x * 2, 21)
    assert out == 42 and calls == [21]
    assert spans.epoch_snapshot() is None
    # disabled() returns the shared singleton: no per-epoch allocation
    assert StepSpans.disabled() is StepSpans.disabled()


# ---------------------------------------------------------------------------
# the span primitive (obs/spans.py:span)
# ---------------------------------------------------------------------------


def test_span_takes_its_parent_from_nesting():
    from hydragnn_tpu.obs import spans

    spans.drain()
    with spans.span("outer", epoch=3):
        with spans.span("inner"):
            time.sleep(0.002)
        with spans.span("inner"):
            pass
    spans.count("graphs", 5)
    spans.count("graphs", 2)
    phases = spans.drain()
    assert phases["outer"]["parent"] is None and phases["outer"]["n"] == 1
    assert phases["inner"] == {"s": phases["inner"]["s"], "n": 2, "parent": "outer"}
    assert phases["outer"]["s"] >= phases["inner"]["s"] >= 0.002
    assert spans.drain_counts() == {"graphs": 7}
    assert spans.drain() == {} and spans.drain_counts() == {}  # flushed means gone

    # each thread nests on its own stack
    seen = {}

    def other():
        with spans.span("in_thread"):
            pass
        seen.update(spans.drain())

    with spans.span("outer"):
        t = threading.Thread(target=other)
        t.start()
        t.join(timeout=10)
    assert not t.is_alive() and seen["in_thread"]["parent"] is None
    spans.drain()


def test_span_is_one_shared_null_context_when_telemetry_is_off(monkeypatch):
    from hydragnn_tpu.obs import spans

    spans.drain()
    monkeypatch.setenv("HYDRAGNN_TELEMETRY", "0")
    assert spans.span("a") is spans.span("b", step=1)
    with spans.span("a"):
        spans.count("graphs", 1)
    assert spans.drain() == {} and spans.drain_counts() == {}


def test_span_reaches_no_host_sync(monkeypatch):
    """A span may sit around any phase of the hot path: nothing it runs
    waits for the device."""
    import inspect

    import jax

    from hydragnn_tpu.obs import spans
    from hydragnn_tpu.utils import gptl, profile

    def _boom(*a, **kw):  # pragma: no cover - must never run
        raise AssertionError("a span must not sync")

    monkeypatch.setattr(jax, "block_until_ready", _boom)
    monkeypatch.setattr(jax, "device_get", _boom)
    for opener in (spans.span, profile.trace_annotation, gptl.nvtx_range):
        with opener("phase"):
            pass
    assert spans.drain()["phase"]["n"] == 3  # the two shims ARE the primitive
    source = "".join(inspect.getsource(f) for f in (spans._Span, spans.span, spans.count, spans.drain))
    for sync in ("block_until_ready", "device_get", "asarray", ".item("):
        assert sync not in source


@pytest.mark.parametrize(
    "mode,batch_size",
    # 5 does not divide over the suite's 8 virtual devices: one device;
    # 8 does: the sharded steps api.py builds, dispatched per step
    [("scan_epoch", 5), ("per_step", 5), ("per_step", 8)],
    ids=["scan_epoch", "per_step", "per_step_mesh"],
)
def test_run_training_records_setup_and_epoch_phases(tmp_path, mode, batch_size):
    import glob

    from hydragnn_tpu.api import run_training
    from hydragnn_tpu.data.synthetic import deterministic_graph_data
    from hydragnn_tpu.flagship import flagship_config

    log_dir = str(tmp_path / "logs") + "/"
    # 250-atom graphs at width 64: epochs of a few hundred ms on the CPU, so
    # that the lines between the children of ``epoch`` (about 1 ms, most of
    # it the sentry's read-back) stay under 1% of it and the 5% below holds
    # when six workers share the machine
    cfg = flagship_config(hidden_dim=64, num_conv_layers=2, batch_size=batch_size, num_epoch=3)
    training = cfg["NeuralNetwork"]["Training"]
    training["checkpoint_every"] = 2
    if mode == "per_step" and batch_size == 5:
        training["scan_epoch"] = False
    samples = deterministic_graph_data(
        number_configurations=80,
        unit_cell_x_range=(5, 6),
        unit_cell_y_range=(5, 6),
        unit_cell_z_range=(5, 6),
        seed=0,
    )
    run_training(cfg, samples=samples, log_dir=log_dir)

    (path,) = glob.glob(log_dir + "*/flight.jsonl")
    assert validate_flight_record(path, require_complete=True) == []
    events = read_flight_record(path)
    man = [e for e in events if e["kind"] == "run_start"][0]["manifest"]
    assert man["dispatch_mode"]["mode"] == mode

    (setup,) = [e for e in events if e["kind"] == "setup"]
    top = {k for k, p in setup["phases"].items() if p["parent"] is None}
    assert {"setup.backend", "setup.data", "setup.model_init", "setup.restore", "setup.step_builders",
            "setup.tensorboard", "setup.drift_reference", "setup.graftcheck", "setup.exec_cache",
            "setup.manifest"} <= top
    assert all(p["s"] >= 0 and p["n"] >= 1 for p in setup["phases"].values())
    if mode == "scan_epoch":
        assert setup["phases"]["setup.stack_splits"]["parent"] == "setup.step_builders"

    epochs = [e for e in events if e["kind"] == "epoch"]
    assert [e["epoch"] for e in epochs] == [0, 1, 2]
    plan = man["pad_plans"]["train"]
    by_epoch = epoch_phases(events)
    inside_train = (
        {"train.stack", "train.dispatch", "train.sync"}
        if mode == "scan_epoch"
        else {"train.loader_wait", "train.step", "train.sync"}
    )
    if batch_size == 5:  # the loop's own step, guarded: the sentry reads its flags back last
        inside_train.add("train.sentry")
    for ev in epochs:
        assert ev["graphs"] == plan["num_samples"] and ev["steps"] == plan["num_batches"]
        assert ev["compiles"]["seconds"] >= 0
        phases = by_epoch[ev["epoch"]]
        assert inside_train <= {k for k, p in phases.items() if p["parent"] == "epoch.train"}
        if mode == "per_step":
            assert phases["train.step"]["n"] == plan["num_batches"]
        children = {k: p for k, p in phases.items() if p["parent"] == "epoch"}
        assert {"epoch.train", "epoch.validate", "epoch.test", "epoch.head_quality",
                "epoch.diag_snapshot", "epoch.record"} <= set(children)
        assert ("epoch.checkpoint" in children) == (ev["epoch"] == 1)
        if ev["epoch"] == 1:  # the save's parts, a fetch and a write a leaf under the stream
            saved = {k for k, p in phases.items() if p["parent"] == "epoch.checkpoint"}
            assert saved == {"checkpoint.stream", "checkpoint.versioned", "checkpoint.pod", "checkpoint.meta"}
            leaves = phases["checkpoint.fetch"]["n"]
            assert phases["checkpoint.fetch"]["parent"] == phases["checkpoint.write"]["parent"] == "checkpoint.stream"
            assert leaves > 1 and phases["checkpoint.write"]["n"] == leaves
            (late,) = [x for e in events for x in e.get("phases_late") or [] if x.get("counts")]
            assert late["epoch"] == 1 and late["counts"]["checkpoint.leaves"] == leaves
            assert late["counts"]["checkpoint.bytes"] == os.path.getsize(glob.glob(log_dir + "*/*.mp")[0])
        wall = phases["epoch"]["s"]
        assert sum(p["s"] for p in children.values()) == pytest.approx(wall, rel=0.05)
    assert epochs[1]["compiles"]["count"] == 0 and epochs[1]["compiles"]["seconds"] == 0

    # tools/obs_report.py prints them: the set-up tree, a row an epoch, the mean tree
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir, "tools"))
    try:
        import obs_report
    finally:
        sys.path.pop(0)

    report = obs_report.render_report(events)
    assert "== setup phases ==" in report and "setup.model_init" in report
    assert "== epoch phases (ms) ==" in report
    assert "mean of 2 whole epoch(s) after the first" in report and "    epoch.train" in report


def test_programs_are_lowered_under_their_own_names(tiny_flagship):
    """The compiled module's name is what a profiler trace's ``XLA
    Modules`` line shows: each program of the train, eval and diagnostics
    paths has one of its own."""
    import jax.numpy as jnp

    from hydragnn_tpu.obs.introspect import make_diagnostics_step
    from hydragnn_tpu.train import create_train_state, make_eval_step, make_train_step, select_optimizer
    from hydragnn_tpu.train.state import make_scan_epoch, make_scan_eval, make_stats_step

    config, model, variables, loader = tiny_flagship
    tx = select_optimizer(config["NeuralNetwork"]["Training"])
    state = create_train_state(variables, tx)
    batch = next(iter(loader))
    stacked = loader.stacked_device_batches(0)
    order = jnp.arange(len(loader), dtype=jnp.int32)
    consec = jnp.zeros((), jnp.int32)
    programs = {
        "jit_train_step": make_train_step(model, tx).lower(state, batch),
        "jit_train_step ": make_train_step(model, tx, guard_nonfinite=True).lower(state, batch, consec),
        "jit_train_scan_epoch": make_scan_epoch(model, tx).lower(state, stacked, order),
        "jit_train_scan_epoch_guarded": make_scan_epoch(model, tx, guard_nonfinite=True).lower(
            state, stacked, order, consec),
        "jit_eval_scan": make_scan_eval(model).lower(state, stacked),
        "jit_eval_step": make_eval_step(model).lower(state, batch),
        "jit_eval_step_outputs": make_eval_step(model, with_outputs=True).lower(state, batch),
        "jit_bn_stats_step": make_stats_step(model).lower(state, batch),
        "jit_diagnostics_step": make_diagnostics_step(model, tx).lower(state, batch),
    }
    for name, lowered in programs.items():
        assert f"module @{name.strip()} " in lowered.as_text()[:200], name


# ---------------------------------------------------------------------------
# compile monitor
# ---------------------------------------------------------------------------


def test_compile_monitor_counts_backend_compiles():
    import jax
    import jax.numpy as jnp

    # arrays built OUTSIDE the monitored windows: jnp.ones itself
    # dispatches a fill computation whose compile would otherwise be
    # (correctly!) counted against the window
    x3, x5 = jnp.ones((3,)), jnp.ones((5,))
    with CompileMonitor() as mon:
        assert mon.available, "jax.monitoring should exist on this jax"

        @jax.jit
        def f(x):
            return x * 2 + 1

        f(x3)  # compile
        assert mon.count >= 1
        mon.mark("warm")
        f(x3)  # cache hit
        f(x3)
        assert mon.count_since("warm") == 0
        f(x5)  # new shape -> recompile
        assert mon.count_since("warm") == 1
    snap = mon.snapshot()
    assert snap["count"] == mon.count and snap["total_duration_s"] >= 0


def test_monitor_stop_detaches_from_event_stream():
    import jax
    import jax.numpy as jnp

    mon = CompileMonitor().start()
    mon.stop()
    before = mon.count

    @jax.jit
    def g(x):
        return x - 1

    g(jnp.ones((7,)))
    assert mon.count == before  # events after stop() are not counted


@pytest.fixture(scope="module")
def tiny_flagship():
    from hydragnn_tpu.flagship import build_flagship

    config, model, variables, loader = build_flagship(
        n_samples=12,
        hidden_dim=8,
        num_conv_layers=2,
        batch_size=4,
        unit_cells=(2, 3),
    )
    return config, model, variables, loader


def test_zero_train_step_recompiles_after_step_one(tiny_flagship):
    """The acceptance contract: repeated same-shape train steps compile
    exactly once — every step after step 1 is a cache hit, measured by
    the jax.monitoring event stream, the same way serving proves its
    steady-state no-compile property."""
    from hydragnn_tpu.train import create_train_state, make_train_step, select_optimizer

    config, model, variables, loader = tiny_flagship
    tx = select_optimizer(config["NeuralNetwork"]["Training"])
    state = create_train_state(variables, tx)
    step = make_train_step(model, tx)
    batches = list(loader)
    assert len(batches) >= 2

    with CompileMonitor() as mon:
        state, loss, _ = step(state, batches[0])  # step 1: the one compile
        import jax

        jax.block_until_ready(loss)
        assert mon.count >= 1, "step 1 must have compiled"
        mon.mark("after_step_1")
        for i in range(4):
            state, loss, _ = step(state, batches[i % len(batches)])
        jax.block_until_ready(loss)
        assert mon.count_since("after_step_1") == 0, (
            "train step recompiled after step 1 — the fixed-shape loader "
            "contract is broken"
        )


# ---------------------------------------------------------------------------
# exporters
# ---------------------------------------------------------------------------


def _example_registry() -> MetricsRegistry:
    r = MetricsRegistry(rank=0)
    r.counter("serve.requests_total").inc(5)
    r.gauge("serve.queue_depth").set(3)
    h = r.histogram("serve.latency_s")
    h.observe(0.01)
    h.observe(0.03)
    return r


def test_prometheus_text_format():
    text = registry_to_prometheus_text(_example_registry())
    assert "# TYPE hydragnn_serve_requests_total counter" in text
    assert 'hydragnn_serve_requests_total{rank="0"} 5' in text
    assert "# TYPE hydragnn_serve_queue_depth gauge" in text
    assert 'hydragnn_serve_latency_s{rank="0",quantile="0.50"} 0.01' in text
    assert 'hydragnn_serve_latency_s_count{rank="0"} 2' in text


def test_prometheus_textfile_atomic_write(tmp_path):
    path = str(tmp_path / "metrics" / "hydragnn.prom")
    registry_to_prometheus(_example_registry(), path)
    with open(path) as f:
        assert "hydragnn_serve_requests_total" in f.read()
    assert not [p for p in os.listdir(os.path.dirname(path)) if ".tmp." in p]


def test_registry_jsonl_export(tmp_path):
    path = str(tmp_path / "metrics.jsonl")
    registry_to_jsonl(path, _example_registry(), extra={"phase": "test"})
    registry_to_jsonl(path, _example_registry())
    lines = [json.loads(line) for line in open(path)]
    assert len(lines) == 2
    assert lines[0]["phase"] == "test"
    assert lines[0]["metrics"]["serve"]["requests_total"] == 5


def test_tensorboard_export_handles_numpy_scalars():
    from hydragnn_tpu.utils.tensorboard import write_scalar_dict

    class _Rec:
        def __init__(self):
            self.rows = []

        def add_scalar(self, tag, value, step):
            self.rows.append((tag, value, step))

    w = _Rec()
    n = write_scalar_dict(
        w,
        {"a": np.float32(1.5), "b": {"c": np.int64(2), "skip": "str"}},
        step=3,
        prefix="obs",
    )
    assert n == 2
    assert ("obs/a", 1.5, 3) in w.rows and ("obs/b/c", 2.0, 3) in w.rows


def test_serve_metrics_is_registry_backed():
    from hydragnn_tpu.serve.metrics import ServeMetrics

    m = ServeMetrics(num_buckets=1)
    m.record_request(0)
    m.observe_latency(0.02)
    reg_snap = m.registry.snapshot()
    assert reg_snap["serve"]["requests_total"] == 1
    assert reg_snap["serve"]["bucket_0"]["requests"] == 1
    assert "hydragnn_serve_requests_total" in m.to_prometheus_text()
    # two servers' metrics never alias (private registries by default)
    m2 = ServeMetrics(num_buckets=1)
    assert m2.snapshot()["requests_total"] == 0


# ---------------------------------------------------------------------------
# backend init: typed failure, at once, no retry
# ---------------------------------------------------------------------------


def test_check_backend_returns_devices_when_backend_matches(monkeypatch):
    from hydragnn_tpu.utils import platform as plat

    monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")  # a priority list: any entry is fine
    devices = plat.check_backend()
    assert len(devices) >= 1 and devices[0].platform == "cpu"


def test_check_backend_mismatch_raises_typed_error(monkeypatch):
    from hydragnn_tpu.utils import platform as plat

    # the backend that is up (cpu) is not the one the variable names
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    with pytest.raises(plat.BackendInitError) as ei:
        plat.check_backend()
    rec = ei.value.record
    assert rec["failure"] == "backend_init" and rec["stage"] == "platform_check"
    assert rec["jax_platforms"] == "tpu" and "'cpu'" in rec["error"]


@pytest.mark.parametrize(
    "message",
    [
        "UNAVAILABLE: failed to connect to TPU worker",
        "The TPU is already in use by process with pid 123 (libtpu lockfile)",
        "Unknown backend: 'nope' requested",
    ],
)
def test_backend_init_failure_fails_at_once(monkeypatch, message):
    """A chip that is busy is held by another process: one attempt, the
    typed error, no sleep — whatever the backend's message says."""
    import jax

    from hydragnn_tpu.utils import platform as plat

    calls = {"n": 0}

    def down():
        calls["n"] += 1
        raise RuntimeError(message)

    monkeypatch.setattr(jax, "devices", down)
    monkeypatch.setattr(time, "sleep", lambda s: pytest.fail("slept on a failed init"))
    with pytest.raises(plat.BackendInitError) as ei:
        plat.check_backend()
    assert calls["n"] == 1
    assert ei.value.record["stage"] == "backend_init"
    assert ei.value.record["error_type"] == "RuntimeError"
    assert not hasattr(plat, "init_backend_with_retry")


# ---------------------------------------------------------------------------
# chip hygiene report
# ---------------------------------------------------------------------------


def test_chip_hygiene_report_structure():
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir, "tools"))
    try:
        import chip_hygiene
    finally:
        sys.path.pop(0)

    report = chip_hygiene.find_chip_holders()
    assert {"targets_present", "holders", "foreign_holder_count", "unreadable_proc_count"} <= set(report)
    for h in report["holders"]:
        assert {"pid", "cmdline", "targets", "is_self_tree"} <= set(h)


def test_chip_hygiene_detects_self_held_lockfile(tmp_path, monkeypatch):
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir, "tools"))
    try:
        import chip_hygiene
    finally:
        sys.path.pop(0)

    lock = tmp_path / "libtpu_lockfile"
    lock.write_text("")
    monkeypatch.setattr(
        chip_hygiene, "_TARGET_GLOBS", (str(tmp_path / "libtpu_lockfile*"),)
    )
    with open(lock):
        report = chip_hygiene.find_chip_holders()
    me = [h for h in report["holders"] if h["pid"] == os.getpid()]
    assert me and me[0]["is_self_tree"]
    assert report["foreign_holder_count"] == 0  # our own tree is not "lingering"


# ---------------------------------------------------------------------------
# obs_report tool
# ---------------------------------------------------------------------------


def _write_run(path, run_name, losses, status="completed"):
    with FlightRecorder(str(path)) as fr:
        fr.start_run(
            {"run": run_name, "config": {"lr": 1e-3}, "num_epoch": len(losses)}
        )
        for ep, loss in enumerate(losses):
            fr.epoch(
                ep,
                train_loss=loss,
                val_loss=loss * 1.1,
                lr=1e-3,
                step_time={
                    "mode": "per_step",
                    "steps": 4,
                    "data_wait_s": 0.01,
                    "dispatch_s": 0.1,
                    "device_wait_ms_mean": 1.5,
                },
                compiles={"count": 9 if ep == 0 else 0, "available": True},
            )
        fr.end_run(status=status, epochs=len(losses), best_val_loss=min(losses) * 1.1)


def test_obs_report_render_and_validate(tmp_path, capsys):
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir, "tools"))
    try:
        import obs_report
    finally:
        sys.path.pop(0)

    a = tmp_path / "a.jsonl"
    _write_run(a, "run_a", [1.0, 0.5])
    events = read_flight_record(str(a))
    text = obs_report.render_report(events)
    assert "== manifest ==" in text and "run_a" in text
    assert "== epochs ==" in text and "data_wait_s" in text
    assert "== run_end ==" in text

    assert obs_report.main(["--validate", "--require-complete", str(a)]) == 0
    out = capsys.readouterr().out
    assert "OK" in out


def test_obs_report_diff(tmp_path):
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir, "tools"))
    try:
        import obs_report
    finally:
        sys.path.pop(0)

    a, b = tmp_path / "a.jsonl", tmp_path / "b.jsonl"
    _write_run(a, "run_a", [1.0, 0.5])
    _write_run(b, "run_b", [0.9, 0.4, 0.3])
    text = obs_report.render_diff(
        read_flight_record(str(a)), read_flight_record(str(b))
    )
    assert "manifest drift" in text
    assert "run: run_a -> run_b" in text
    assert "ep 0:" in text and "train_loss -0.1" in text
    assert "epochs only in B: [2]" in text


# ---------------------------------------------------------------------------
# acceptance: a default run_training emits a schema-valid flight record
# ---------------------------------------------------------------------------


def test_run_training_emits_valid_flight_record(tmp_path, monkeypatch):
    from hydragnn_tpu.api import run_training
    from hydragnn_tpu.data.synthetic import deterministic_graph_data
    from hydragnn_tpu.flagship import flagship_config

    # introspection is conftest-disabled for the suite's many tiny
    # trainings; THIS test asserts the production default-on record
    monkeypatch.setenv("HYDRAGNN_DIAGNOSTICS", "1")
    log_dir = str(tmp_path / "logs") + "/"
    cfg = flagship_config(hidden_dim=8, num_conv_layers=2, batch_size=5, num_epoch=2)
    samples = deterministic_graph_data(
        number_configurations=20,
        unit_cell_x_range=(2, 3),
        unit_cell_y_range=(2, 3),
        unit_cell_z_range=(2, 3),
        seed=0,
    )
    run_training(cfg, samples=samples, log_dir=log_dir)

    import glob

    paths = glob.glob(log_dir + "*/flight.jsonl")
    assert len(paths) == 1, "default run_training must write one flight record"
    assert validate_flight_record(paths[0], require_complete=True) == []
    events = read_flight_record(paths[0])
    man = [e for e in events if e["kind"] == "run_start"][0]["manifest"]
    # resolved config + environment + pad plans in the manifest
    assert "NeuralNetwork" in man["config"]
    assert man["backend"] and man["jax_version"]
    assert man["pad_plans"]["train"]["pad_nodes"] > 0
    assert man["mesh"]["process_count"] >= 1

    # v2 manifest: the introspection identity card
    assert man["head_names"] == ["sum_x_x2_x3", "x", "x2", "x3"]
    assert man["diagnostics"]["enabled"] is True
    assert "available" in man["hw_cost"]
    if man["hw_cost"]["available"]:
        assert man["hw_cost"]["flops_per_step"] > 0

    # dispatch-mode resolution: the single-device default is the
    # whole-epoch scan dispatch, recorded with its reason; the per-step
    # span decomposition is pinned by tests/test_dispatch_modes.py
    # (explicit Training.scan_epoch=false)
    dm = man["dispatch_mode"]
    assert dm["mode"] == "scan_epoch" and dm["auto"] is True, dm
    assert man["scan_epoch"] is True

    epochs = [e for e in events if e["kind"] == "epoch"]
    assert len(epochs) == 2
    for ep in epochs:
        st = ep["step_time"]
        assert st["mode"] == "scan_epoch"
        assert "count" in ep["compiles"] and ep["compiles"]["available"]
        # per-task losses keyed by head name, not positional index
        assert set(ep["train_tasks"]) == set(man["head_names"])
        assert set(ep["val_tasks"]) == set(man["head_names"])
        # model-level introspection: per-head grad norms, the conflict
        # matrix, per-head MAE/RMSE, and the hardware ledger
        heads = ep["heads"]
        assert heads["available"]
        assert set(heads["grad_norm"]) == set(man["head_names"])
        cos = heads["cosine"]
        assert len(cos) == 4 and all(len(row) == 4 for row in cos)
        assert all(abs(cos[i][i] - 1.0) < 1e-5 for i in range(4))
        assert set(heads["mae"]) == set(man["head_names"])
        assert set(heads["rmse"]) == set(man["head_names"])
        hw = ep["hw"]
        assert "available" in hw and "available" in hw["memory"]
        if hw["available"]:
            assert hw["achieved_tflops"] > 0 and "mfu" in hw
    # steady state: epoch 1 must not have recompiled the train step —
    # including the separate diagnostics executable (compiled in epoch
    # 0, cache-hit thereafter)
    assert epochs[1]["compiles"]["unexpected"] is False
    assert epochs[1]["compiles"]["count"] == 0

    end = events[-1]
    assert end["kind"] == "run_end" and end["status"] == "completed"
    assert end["timers"] and "metrics" in end


def test_crashed_training_leaves_failed_flight_record(tmp_path):
    """A run that dies mid-epoch-loop must still leave a structurally
    valid flight record ending in a failed run_end with the error event
    — the r05 'only a traceback to explain it' failure mode, closed."""
    from hydragnn_tpu.api import prepare_loaders_and_config, train_with_loaders
    from hydragnn_tpu.data.synthetic import deterministic_graph_data
    from hydragnn_tpu.flagship import flagship_config

    cfg = flagship_config(hidden_dim=8, num_conv_layers=2, batch_size=5, num_epoch=3)
    # the crash simulation is iteration-based, so pin the per-step
    # dispatch (the auto default would scan stacked batches and never
    # touch __iter__ during the epoch loop)
    cfg["NeuralNetwork"]["Training"]["scan_epoch"] = False
    samples = deterministic_graph_data(
        number_configurations=20,
        unit_cell_x_range=(2, 3),
        unit_cell_y_range=(2, 3),
        unit_cell_z_range=(2, 3),
        seed=0,
    )
    tr, va, te, cfg = prepare_loaders_and_config(cfg, samples)

    class Boom:
        """Crashes on the THIRD iteration: 1 = model-init example,
        2 = epoch 0 training, 3 = epoch 1 -> a genuine mid-run crash.
        (With introspection enabled — HYDRAGNN_DIAGNOSTICS=1, off in
        this suite — the hardware ledger consumes one extra example
        iteration before epoch 0.)"""

        def __init__(self, inner):
            self.inner = inner
            self.n = 0

        def __getattr__(self, k):
            return getattr(self.inner, k)

        def __len__(self):
            return len(self.inner)

        def set_epoch(self, e):
            self.inner.set_epoch(e)

        def __iter__(self):
            self.n += 1
            if self.n >= 3:
                raise RuntimeError("synthetic mid-run crash")
            return iter(self.inner)

    log_dir = str(tmp_path / "logs") + "/"
    with pytest.raises(RuntimeError, match="synthetic mid-run crash"):
        train_with_loaders(cfg, Boom(tr), va, te, log_dir=log_dir)

    import glob

    paths = glob.glob(log_dir + "*/flight.jsonl")
    assert paths, "failed run must still leave a flight record"
    events = read_flight_record(paths[0])
    kinds = [e["kind"] for e in events]
    assert kinds[0] == "run_start" and kinds[-1] == "run_end"
    assert "error" in kinds and "epoch" in kinds  # epoch 0 completed
    assert events[-1]["status"] == "failed" and events[-1]["epochs"] == 1
    err = [e for e in events if e["kind"] == "error"][0]
    assert err["error_type"] == "RuntimeError"
    assert validate_flight_record(events) == []  # crashed, still parseable
    # the process-global epoch timer must not be left running — a leaked
    # interval poisons every later training run in this process
    from hydragnn_tpu.utils.time_utils import Timer

    assert Timer("train_validate_test")._start is None


def test_the_sentry_reads_its_flags_back_once_under_train_sentry():
    """The guard's flags are read back as the train phase's last work, under
    ``train.sentry`` inside ``epoch.train``; ``Run.close_nonfinite`` then takes
    what that read gave instead of reading again."""
    import jax.numpy as jnp

    from hydragnn_tpu.obs.spans import drain, span
    from hydragnn_tpu.resilience.sentry import NonFiniteSentry
    from hydragnn_tpu.train.loop import _sentry_read_back

    sentry = NonFiniteSentry(patience=2)
    sentry.observe_scan(jnp.asarray([0.0, 1.0, 1.0]), jnp.asarray(2, jnp.int32))
    drain()
    with span("epoch.train"):
        _sentry_read_back(sentry)
    phases = drain()
    assert phases["train.sentry"]["parent"] == "epoch.train" and phases["train.sentry"]["n"] == 1
    assert sentry.take_finalized() == (2, 2) and sentry.skipped_total == 2
    # nothing kept any more: a second take reads the (empty) flags now
    assert sentry.take_finalized() == (0, 2) and sentry.skipped_total == 2
    _sentry_read_back(None)  # no guard, nothing to read
