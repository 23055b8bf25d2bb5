"""Fault-tolerant training (hydragnn_tpu/resilience): deterministic
fault-injection coverage of every path docs/RESILIENCE.md claims —
preemption, non-finite sentry + rollback, hang watchdog, checkpoint
retention/integrity fallback, and the bounded restart supervisor.
All CPU; process-killing faults run in subprocesses."""

import contextlib
import glob
import json
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hydragnn_tpu.obs.flight import read_flight_record, validate_flight_record
from hydragnn_tpu.resilience import (
    EXIT_CONFIG_ERROR,
    EXIT_HUNG,
    EXIT_PREEMPTED,
    EXIT_ROLLBACK_EXHAUSTED,
    HangWatchdog,
    NonFiniteRollbackExhausted,
    Supervisor,
    SupervisorPolicy,
    TrainingPreempted,
    classify_exit,
    run_guard,
)

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# ---------------------------------------------------------------------------
# tiny shared run config

def _tiny_config(num_epoch=2, **training_overrides):
    from hydragnn_tpu.flagship import flagship_config

    cfg = flagship_config(
        hidden_dim=8, num_conv_layers=2, batch_size=5, num_epoch=num_epoch
    )
    cfg["NeuralNetwork"]["Training"].update(training_overrides)
    return cfg


def _tiny_samples():
    from hydragnn_tpu.data.synthetic import deterministic_graph_data

    return deterministic_graph_data(
        number_configurations=20,
        unit_cell_x_range=(2, 3),
        unit_cell_y_range=(2, 3),
        unit_cell_z_range=(2, 3),
        seed=0,
    )


_CHILD = r"""
import sys
sys.path.insert(0, {repo!r})
from __graft_entry__ import _load_platform_module
_load_platform_module().pin_virtual_cpu_mesh(1)

from hydragnn_tpu.resilience import run_guard
from hydragnn_tpu.api import run_training
from hydragnn_tpu.data.synthetic import deterministic_graph_data
from hydragnn_tpu.flagship import flagship_config

cfg = flagship_config(hidden_dim=8, num_conv_layers=2, batch_size=5, num_epoch=2)
cfg["NeuralNetwork"]["Training"].update({training!r})
samples = deterministic_graph_data(
    number_configurations=20, unit_cell_x_range=(2, 3), unit_cell_y_range=(2, 3),
    unit_cell_z_range=(2, 3), seed=0)
with run_guard():
    run_training(cfg, samples=samples, log_dir=sys.argv[1] + "/logs/")
print("CHILD-COMPLETED")
"""


def _run_child(tmp_path, training, env_extra, timeout=240):
    script = tmp_path / "child.py"
    script.write_text(_CHILD.format(repo=_REPO, training=dict(training)))
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, str(script), str(tmp_path)],
        env=env,
        stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT,
        text=True,
        timeout=timeout,
    )
    return proc


def _flight_events(tmp_path):
    (fl,) = glob.glob(str(tmp_path / "logs" / "*" / "flight.jsonl"))
    return read_flight_record(fl)


def _final_val_loss(tmp_path):
    (mp,) = glob.glob(str(tmp_path / "logs" / "*" / "metrics.jsonl"))
    with open(mp) as f:
        rows = [json.loads(line) for line in f]
    return rows[-1]["val_loss"]


@pytest.fixture(scope="module")
def reference_run(tmp_path_factory):
    """One clean uninterrupted run of the tiny config — the equivalence
    baseline the interrupted-then-resumed scenarios must match."""
    from hydragnn_tpu.api import run_training

    d = tmp_path_factory.mktemp("reference")
    cfg = _tiny_config(checkpoint_every=1)
    _, _, history, _ = run_training(
        cfg, samples=_tiny_samples(), log_dir=str(d / "logs/")
    )
    return d, history


# ---------------------------------------------------------------------------
# exit-code contract + supervisor policy (no jax, no processes)

def pytest_classify_exit_contract():
    assert classify_exit(0) == "completed"
    assert classify_exit(EXIT_PREEMPTED) == "preempted"
    assert classify_exit(EXIT_ROLLBACK_EXHAUSTED) == "rollback_exhausted"
    assert classify_exit(EXIT_CONFIG_ERROR) == "config_error"
    assert classify_exit(EXIT_HUNG) == "hung"
    assert classify_exit(1) == "crash"
    assert classify_exit(-9) == "crash"  # SIGKILL


def pytest_supervisor_retries_crashes_with_backoff():
    codes = iter([1, EXIT_HUNG, 0])
    calls = []
    delays = []
    sup = Supervisor(
        ["cmd"],
        policy=SupervisorPolicy(max_restarts=5, backoff_base_s=1.0, backoff_max_s=60),
        env={"HYDRAGNN_INJECT_SIGTERM_STEP": "3", "KEEP": "1"},
        runner=lambda argv, env: (calls.append(dict(env)), next(codes))[1],
        sleep=delays.append,
    )
    result = sup.run()
    assert result["status"] == "completed"
    assert result["restarts"] == 2
    assert delays == [1.0, 2.0]  # exponential backoff
    # first attempt keeps injection + no resume; restarts strip/resume
    assert "HYDRAGNN_INJECT_SIGTERM_STEP" in calls[0]
    assert "HYDRAGNN_AUTO_RESUME" not in calls[0]
    for env in calls[1:]:
        assert "HYDRAGNN_INJECT_SIGTERM_STEP" not in env
        assert env["HYDRAGNN_AUTO_RESUME"] == "1"
        assert env["KEEP"] == "1"


def pytest_supervisor_fail_fast_and_give_up():
    # config error: exactly one attempt, no sleeps
    delays = []
    sup = Supervisor(
        ["cmd"],
        runner=lambda argv, env: EXIT_CONFIG_ERROR,
        sleep=delays.append,
    )
    result = sup.run()
    assert result["status"] == "failed_fast"
    assert result["cause"] == "config_error"
    assert result["attempts"] == 1 and delays == []
    # rollback exhausted: also fail-fast
    assert (
        Supervisor(["c"], runner=lambda a, e: EXIT_ROLLBACK_EXHAUSTED).run()["status"]
        == "failed_fast"
    )
    # unbounded crashes: bounded give-up
    sup = Supervisor(
        ["cmd"],
        policy=SupervisorPolicy(max_restarts=2, backoff_base_s=0.0),
        runner=lambda argv, env: 1,
        sleep=lambda s: None,
    )
    result = sup.run()
    assert result["status"] == "gave_up"
    assert result["attempts"] == 3  # initial + 2 restarts


def pytest_supervisor_preemption_restarts_promptly():
    codes = iter([EXIT_PREEMPTED, EXIT_PREEMPTED, 0])
    delays = []
    sup = Supervisor(
        ["cmd"],
        policy=SupervisorPolicy(max_restarts=0),  # preemptions aren't crashes
        runner=lambda argv, env: next(codes),
        sleep=delays.append,
    )
    result = sup.run()
    assert result["status"] == "completed"
    assert result["preemptions"] == 2
    assert delays == []  # no backoff for eviction


def pytest_run_guard_exit_codes():
    with pytest.raises(SystemExit) as e:
        with run_guard():
            raise TrainingPreempted(15, 3)
    assert e.value.code == EXIT_PREEMPTED
    with pytest.raises(SystemExit) as e:
        with run_guard():
            raise NonFiniteRollbackExhausted("gave up")
    assert e.value.code == EXIT_ROLLBACK_EXHAUSTED
    with pytest.raises(SystemExit) as e:
        with run_guard():
            raise ValueError("bad config")
    assert e.value.code == EXIT_CONFIG_ERROR
    with pytest.raises(RuntimeError):
        with run_guard():  # crash class propagates untouched
            raise RuntimeError("boom")


def pytest_watchdog_arms_after_warmup_and_fires(tmp_path):
    from hydragnn_tpu.obs.flight import FlightRecorder

    fired = []
    flight = FlightRecorder(str(tmp_path / "flight.jsonl"))
    wd = HangWatchdog(
        stall_s=0.2,
        flight=flight,
        action=lambda: fired.append(True),
        poll_s=0.02,
        warmup_beats=2,
    )
    wd.start()
    try:
        time.sleep(0.5)  # unarmed: setup/compile time never fires
        assert not wd.fired
        for _ in range(3):
            wd.beat()
        assert wd.armed
        time.sleep(0.5)
        assert wd.fired and fired
    finally:
        wd.stop()
    events = read_flight_record(str(tmp_path / "flight.jsonl"))
    (wd_ev,) = [e for e in events if e["kind"] == "watchdog"]
    assert wd_ev["stall_s"] >= 0.2 and wd_ev["stacks"]
    assert events[-1]["kind"] == "run_end" and events[-1]["status"] == "hung"
    assert not validate_flight_record(events)


# ---------------------------------------------------------------------------
# checkpoint retention + integrity fallback (in-process)

def _fake_state(step, value):
    from hydragnn_tpu.train.state import TrainState

    return TrainState(
        step=jnp.asarray(step, jnp.int32),
        params={"w": jnp.full((4,), float(value))},
        batch_stats={},
        opt_state=(),
        rng=jax.random.PRNGKey(0),
    )


def pytest_checkpoint_retention_prunes_and_falls_back(tmp_path):
    from hydragnn_tpu.utils.checkpoint import (
        checkpoint_exists,
        list_versioned_checkpoints,
        load_existing_model,
        save_model,
        validate_checkpoint_file,
    )

    log_dir = str(tmp_path)
    for step in (1, 2, 3):
        save_model(_fake_state(step, step * 10.0), "run", log_dir, keep_last=2)
    versions = list_versioned_checkpoints("run", log_dir)
    assert [s for s, _ in versions] == [3, 2]  # keep-last-2, newest first
    assert all(validate_checkpoint_file(p) for _, p in versions)
    assert checkpoint_exists("run", log_dir)

    # torn latest-pointer write: truncated file fails validation, the
    # restore falls back to the newest intact version
    pointer = os.path.join(log_dir, "run", "run.mp")
    with open(pointer, "rb") as f:
        data = f.read()
    with open(pointer, "wb") as f:
        f.write(data[: len(data) // 2])
    assert not validate_checkpoint_file(pointer)
    with pytest.warns(RuntimeWarning, match="rejected"):
        restored = load_existing_model(_fake_state(0, 0.0), "run", log_dir)
    assert int(restored.step) == 3
    np.testing.assert_allclose(np.asarray(restored.params["w"]), 30.0)

    # every candidate corrupt -> loud failure, not a silent fresh start
    for _, p in list_versioned_checkpoints("run", log_dir):
        with open(p, "wb") as f:
            f.write(b"junk")
    with pytest.raises(ValueError, match="no valid checkpoint"):
        load_existing_model(_fake_state(0, 0.0), "run", log_dir)


# ---------------------------------------------------------------------------
# guarded train step (device half of the sentry)

def pytest_guarded_step_skips_nonfinite_batch():
    from hydragnn_tpu.graph import batch_graphs
    from hydragnn_tpu.models import ModelConfig, create_model
    from hydragnn_tpu.train import create_train_state, make_train_step, select_optimizer

    rng = np.random.default_rng(0)
    n, e = 24, 64
    g = {
        "x": rng.normal(size=(n, 4)).astype(np.float32),
        "senders": rng.integers(0, n, e).astype(np.int32),
        "receivers": np.sort(rng.integers(0, n, e)).astype(np.int32),
        "graph_targets": {"energy": np.asarray([1.0], np.float32)},
    }
    batch = batch_graphs([g], n_node_pad=n + 8, n_edge_pad=e + 8, n_graph_pad=2)
    cfg = ModelConfig(
        model_type="GIN",
        input_dim=4,
        hidden_dim=8,
        output_dim=(1,),
        output_type=("graph",),
        output_names=("energy",),
        task_weights=(1.0,),
        num_conv_layers=2,
        graph_num_sharedlayers=1,
        graph_dim_sharedlayers=8,
        graph_num_headlayers=1,
        graph_dim_headlayers=(8,),
    )
    model, variables = create_model(cfg, batch)
    tx = select_optimizer({"Optimizer": {"type": "SGD", "learning_rate": 0.05}})
    step = make_train_step(model, tx, guard_nonfinite=True)

    state = create_train_state(variables, tx, seed=0)
    before = jax.device_get(state.params)
    consec = jnp.zeros((), jnp.int32)

    nan_batch = batch.replace(nodes=np.full_like(np.asarray(batch.nodes), np.nan))
    state, loss, tasks, consec, bad = step(state, nan_batch, consec)
    assert float(bad) == 1.0 and int(consec) == 1
    assert float(loss) == 0.0 and int(state.step) == 0  # update skipped
    for a, b in zip(
        jax.tree_util.tree_leaves(before),
        jax.tree_util.tree_leaves(jax.device_get(state.params)),
    ):
        np.testing.assert_array_equal(a, b)

    state, loss, tasks, consec, bad = step(state, batch, consec)
    assert float(bad) == 0.0 and int(consec) == 0  # consec resets
    assert np.isfinite(float(loss)) and int(state.step) == 1
    changed = any(
        not np.array_equal(a, b)
        for a, b in zip(
            jax.tree_util.tree_leaves(before),
            jax.tree_util.tree_leaves(jax.device_get(state.params)),
        )
    )
    assert changed  # the good batch's update landed


# ---------------------------------------------------------------------------
# in-process fault injection through the full loop

def pytest_nan_injection_skipped_and_counted(tmp_path, monkeypatch):
    from hydragnn_tpu.api import run_training

    monkeypatch.setenv("HYDRAGNN_INJECT_NAN_STEP", "3:2")
    cfg = _tiny_config(num_epoch=3)
    _, _, history, _ = run_training(
        cfg, samples=_tiny_samples(), log_dir=str(tmp_path / "logs/")
    )
    assert np.isfinite(np.asarray(history["train_loss"])).all()
    assert history["train_loss"][-1] < history["train_loss"][0]
    skipped = {
        e["epoch"]: e["nonfinite"]["skipped"]
        for e in _flight_events(tmp_path)
        if e.get("kind") == "epoch" and e.get("nonfinite")
    }
    assert skipped == {0: 1, 1: 1}  # steps 3 and 4 (epochs of 4 steps)


def pytest_consecutive_nans_roll_back_to_last_good(tmp_path, monkeypatch):
    from hydragnn_tpu.api import run_training

    # steps 6-7: the tail of epoch 1 — its end-of-epoch consec (2)
    # meets the patience and rollback fires against epoch 0's checkpoint
    monkeypatch.setenv("HYDRAGNN_INJECT_NAN_STEP", "6:2")
    cfg = _tiny_config(num_epoch=4, checkpoint_every=1, nonfinite_patience=2)
    _, _, history, _ = run_training(
        cfg, samples=_tiny_samples(), log_dir=str(tmp_path / "logs/")
    )
    events = _flight_events(tmp_path)
    rollbacks = [e for e in events if e.get("kind") == "rollback"]
    assert len(rollbacks) == 1
    assert rollbacks[0]["epoch"] == 1 and rollbacks[0]["consec"] == 2
    assert events[-1]["kind"] == "run_end" and events[-1]["status"] == "completed"
    # the reduced-LR signal
    assert history["lr"][-1] == pytest.approx(history["lr"][0] * 0.5)
    assert not validate_flight_record(events)


def pytest_rollback_budget_exhausts_to_typed_failure(tmp_path, monkeypatch):
    from hydragnn_tpu.api import run_training

    # NaNs from step 6 onward: every epoch tail is bad; one rollback is
    # allowed, the second trips the budget -> typed fail-fast exception
    monkeypatch.setenv("HYDRAGNN_INJECT_NAN_STEP", "6:100")
    cfg = _tiny_config(
        num_epoch=6,
        checkpoint_every=1,
        nonfinite_patience=2,
        nonfinite_max_rollbacks=1,
    )
    with pytest.raises(NonFiniteRollbackExhausted):
        run_training(cfg, samples=_tiny_samples(), log_dir=str(tmp_path / "logs/"))
    events = _flight_events(tmp_path)
    assert sum(e.get("kind") == "rollback" for e in events) == 1
    assert events[-1]["kind"] == "run_end" and events[-1]["status"] == "failed"


# ---------------------------------------------------------------------------
# the three endings of a run: one routine, the same steps

_ENDINGS = {
    # status: (environment, Training keys, what run_training raises)
    "completed": ({}, {}, None),
    "failed": (
        {"HYDRAGNN_INJECT_NAN_STEP": "0:100"},
        {"nonfinite_patience": 2, "nonfinite_max_rollbacks": 0},
        NonFiniteRollbackExhausted,
    ),
    "preempted": ({"HYDRAGNN_INJECT_SIGTERM_EPOCH": "1"}, {}, TrainingPreempted),
}


@pytest.mark.parametrize("status", sorted(_ENDINGS))
def pytest_every_ending_closes_the_run(status, tmp_path, monkeypatch):
    """However a run ends — it completes, something raises inside an
    epoch, a SIGTERM preempts it — ``run_end`` with that status is the
    record's last event, and nothing of the run outlives it: compile
    monitor detached, tensorboard writer closed, preemption handler
    and watchdog torn down, the process-global timer stopped."""
    import signal
    import threading

    from hydragnn_tpu.api import run_training
    from hydragnn_tpu.obs import compile_monitor
    from hydragnn_tpu.train import run as run_module
    from hydragnn_tpu.utils.time_utils import Timer

    class Writer:
        opened = []

        def __init__(self):
            self.scalars, self.closed = 0, False
            Writer.opened.append(self)

        def add_scalar(self, *a, **kw):
            assert not self.closed
            self.scalars += 1

        def flush(self):
            pass

        def close(self):
            self.closed = True

    env, training, raises = _ENDINGS[status]
    monkeypatch.setenv("HYDRAGNN_TELEMETRY", "1")
    for k, v in env.items():
        monkeypatch.setenv(k, v)
    monkeypatch.setattr(run_module, "get_summary_writer", lambda *a, **kw: Writer())
    handlers = {s: signal.getsignal(s) for s in (signal.SIGTERM, signal.SIGINT)}
    monitors = list(compile_monitor._active)
    # the watchdog on (it forces per-step dispatch), its limit out of reach;
    # a grace window no failing assertion can outlast
    cfg = _tiny_config(
        num_epoch=3, checkpoint_every=1, watchdog_stall_s=600, preempt_grace_s=600, **training
    )
    with pytest.raises(raises) if raises else contextlib.nullcontext():
        run_training(cfg, samples=_tiny_samples(), log_dir=str(tmp_path / "logs/"))

    events = _flight_events(tmp_path)
    assert events[-1]["kind"] == "run_end" and events[-1]["status"] == status
    assert [e["kind"] for e in events].count("run_end") == 1
    if status != "completed":
        assert events[-2]["kind"] == {"failed": "error", "preempted": "preempt"}[status]
    assert not validate_flight_record(events)
    assert compile_monitor._active == monitors
    (writer,) = Writer.opened
    assert writer.closed and (writer.scalars > 0 or status == "failed")
    assert {s: signal.getsignal(s) for s in handlers} == handlers
    assert not [t for t in threading.enumerate() if t.name == "hydragnn-watchdog"]
    assert Timer("train_validate_test")._start is None


# ---------------------------------------------------------------------------
# process-killing faults (subprocess)

@pytest.mark.slow
def pytest_sigterm_preempts_then_resumes(tmp_path, reference_run):
    # SIGTERM mid-epoch: distinct exit code, checkpoint + meta written,
    # flight ends preempted
    proc = _run_child(
        tmp_path,
        {"checkpoint_every": 1},
        {"HYDRAGNN_INJECT_SIGTERM_STEP": "2"},
    )
    assert proc.returncode == EXIT_PREEMPTED, proc.stdout
    events = _flight_events(tmp_path)
    assert events[-1]["kind"] == "run_end" and events[-1]["status"] == "preempted"
    (preempt,) = [e for e in events if e.get("kind") == "preempt"]
    assert preempt["signal"] == 15
    assert glob.glob(str(tmp_path / "logs" / "*" / "*.mp"))
    assert glob.glob(str(tmp_path / "logs" / "*" / "*.meta.json"))

    # resume (what the supervisor does): completes, one resumed event,
    # and the merged record stays schema-valid
    proc = _run_child(tmp_path, {"checkpoint_every": 1}, {"HYDRAGNN_AUTO_RESUME": "1"})
    assert proc.returncode == 0, proc.stdout
    assert "CHILD-COMPLETED" in proc.stdout
    events = _flight_events(tmp_path)
    assert sum(e.get("kind") == "resumed" for e in events) == 1
    statuses = [e["status"] for e in events if e.get("kind") == "run_end"]
    assert statuses == ["preempted", "completed"]
    assert not validate_flight_record(events)
    # the resumed run converges to the uninterrupted reference
    _, ref_history = reference_run
    assert _final_val_loss(tmp_path) == pytest.approx(
        ref_history["val_loss"][-1], rel=0.2
    )


@pytest.mark.slow
def pytest_sigkill_mid_checkpoint_restores_previous_valid(tmp_path, reference_run):
    # the 2nd checkpoint save tears the latest-pointer write and
    # SIGKILLs; subprocess reports the signal death
    proc = _run_child(
        tmp_path,
        {"checkpoint_every": 1},
        {"HYDRAGNN_INJECT_KILL_CHECKPOINT": "2"},
    )
    assert proc.returncode == -9, proc.stdout
    from hydragnn_tpu.utils.checkpoint import validate_checkpoint_file

    (run_dir,) = glob.glob(str(tmp_path / "logs" / "*/"))
    pointer = [
        p
        for p in glob.glob(os.path.join(run_dir, "*.mp"))
        if ".step" not in os.path.basename(p)
    ]
    assert pointer and not validate_checkpoint_file(pointer[0])

    # restart: integrity check rejects the torn pointer, restores the
    # newest intact version, and the run completes
    proc = _run_child(tmp_path, {"checkpoint_every": 1}, {"HYDRAGNN_AUTO_RESUME": "1"})
    assert proc.returncode == 0, proc.stdout
    assert "rejected" in proc.stdout  # the integrity warning fired
    events = _flight_events(tmp_path)
    assert sum(e.get("kind") == "resumed" for e in events) == 1
    assert events[-1]["status"] == "completed"
    # final eval loss matches an uninterrupted run of the same config
    _, ref_history = reference_run
    assert _final_val_loss(tmp_path) == pytest.approx(
        ref_history["val_loss"][-1], rel=1e-3
    )


@pytest.mark.slow
def pytest_stalled_loader_trips_watchdog_with_stacks(tmp_path):
    proc = _run_child(
        tmp_path,
        {"watchdog_stall_s": 3.0},
        {"HYDRAGNN_INJECT_STALL_LOADER": "2:120"},
        timeout=180,
    )
    assert proc.returncode == EXIT_HUNG, proc.stdout
    events = _flight_events(tmp_path)
    (wd,) = [e for e in events if e.get("kind") == "watchdog"]
    assert wd["stall_s"] >= 3.0
    assert "MainThread" in wd["stacks"]  # the blocked consumer's stack
    assert events[-1]["kind"] == "run_end" and events[-1]["status"] == "hung"
    assert not validate_flight_record(events)


def pytest_strip_injection_env_derives_from_knob_registry():
    """strip_injection_env must drop EVERY registered HYDRAGNN_INJECT_*
    knob — derived from knobs.active_injections(), not a hand-kept list
    that silently rots when a new injection is added — plus any
    unregistered INJECT-prefixed stragglers, while preserving
    everything else (including HYDRAGNN_AUTO_RESUME / exec-cache env)."""
    from hydragnn_tpu.resilience.inject import strip_injection_env
    from hydragnn_tpu.utils import knobs

    registered = [
        k for k in knobs.KNOBS if k.startswith(knobs.INJECT_PREFIX)
    ]
    assert "HYDRAGNN_INJECT_POD_KILL_HOST" in registered  # pod faults too
    assert "HYDRAGNN_INJECT_STRAGGLER" in registered
    env = {k: "1" for k in registered}
    env["HYDRAGNN_INJECT_FUTURE_UNREGISTERED"] = "1"  # prefix backstop
    env["HYDRAGNN_AUTO_RESUME"] = "1"
    env["HYDRAGNN_EXEC_CACHE"] = "/tmp/cache"
    env["KEEP"] = "x"
    out = strip_injection_env(env)
    assert not any(k.startswith(knobs.INJECT_PREFIX) for k in out)
    assert out["HYDRAGNN_AUTO_RESUME"] == "1"
    assert out["HYDRAGNN_EXEC_CACHE"] == "/tmp/cache"
    assert out["KEEP"] == "x"


# ---------------------------------------------------------------------------
# obs_report --faults view

def pytest_obs_report_faults_view(tmp_path, monkeypatch, capsys):
    sys.path.insert(0, os.path.join(_REPO, "tools"))
    import obs_report

    from hydragnn_tpu.obs.flight import FlightRecorder

    path = str(tmp_path / "flight.jsonl")
    with FlightRecorder(path) as fl:
        fl.start_run({"run": "x"})
        fl.record("preempt", signal=15, epoch=1, step=9)
        fl.end_run(status="preempted")
        fl.start_run({"run": "x"})
        fl.record("resumed", epoch=1)
        fl.record("rollback", epoch=2, consec=4, rollbacks=1, lr=5e-4)
        fl.record("restart", attempt=1, cause="crash", exit_code=1, delay_s=1.0)
        fl.end_run(status="completed")
    assert obs_report.main(["--faults", path]) == 0
    out = capsys.readouterr().out
    assert "preempted=1" in out and "resumed=1" in out and "rollbacks=1" in out
    assert "[watchdog]" not in out and "[rollback]" in out

    # a fault event missing required fields is a schema failure
    with open(path, "a") as f:
        f.write(json.dumps({"v": 1, "kind": "rollback", "t": 0, "rank": 0}) + "\n")
        f.write("{}\n")  # keep a parseable final line so the tail isn't dropped
    assert obs_report.main(["--faults", path]) == 1
