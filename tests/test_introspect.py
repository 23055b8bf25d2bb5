"""Model-level introspection tests (hydragnn_tpu/obs/introspect.py):
per-head gradient norm / conflict-cosine / update-ratio math against a
pure-numpy reference on a tiny 2-head model, per-head MAE/RMSE against
numpy, sampling discipline (zero unexpected recompiles, no per-step
host syncs, telemetry-off bit-identical training), the hardware ledger
degradations, flight-record v1/v2 forward compat, and the anomaly
heuristics the --heads report renders."""

import json

import numpy as np
import jax
import jax.numpy as jnp
import optax
import pytest

from hydragnn_tpu.graph import batch_graphs
from hydragnn_tpu.models import ModelConfig, create_model, model_loss
from hydragnn_tpu.obs import (
    CompileMonitor,
    FlightRecorder,
    HardwareLedger,
    HeadDiagnostics,
    collect_head_series,
    flag_anomalies,
    flight_record_warnings,
    make_diagnostics_step,
    per_head_error_metrics,
    read_flight_record,
    validate_flight_record,
)
from hydragnn_tpu.train import create_train_state, make_train_step


def _tiny_two_head(seed: int = 0):
    """A 2-head (graph energy + node charge) GIN on a handful of ring
    graphs — small enough that a numpy reference over flattened
    gradients is exact and fast."""
    rng = np.random.RandomState(seed)
    graphs = []
    for gi in range(6):
        n = 4 + gi % 3
        s = np.concatenate([np.arange(n), np.roll(np.arange(n), 1)]).astype(np.int32)
        r = np.concatenate([np.roll(np.arange(n), 1), np.arange(n)]).astype(np.int32)
        graphs.append(
            {
                "x": rng.rand(n, 2).astype(np.float32),
                "senders": s,
                "receivers": r,
                "pos": rng.rand(n, 3).astype(np.float32),
                "graph_targets": {"energy": np.asarray([rng.rand()], np.float32)},
                "node_targets": {"charge": rng.rand(n, 1).astype(np.float32)},
            }
        )
    batch = batch_graphs(graphs)
    cfg = ModelConfig(
        model_type="GIN",
        input_dim=2,
        hidden_dim=8,
        output_dim=(1, 1),
        output_type=("graph", "node"),
        output_names=("energy", "charge"),
        task_weights=(2.0, 1.0),
        num_conv_layers=2,
        graph_num_sharedlayers=1,
        graph_dim_sharedlayers=8,
        graph_num_headlayers=1,
        graph_dim_headlayers=(8,),
        node_num_headlayers=1,
        node_dim_headlayers=(8,),
    )
    model, variables = create_model(cfg, batch)
    return cfg, model, variables, batch


def _flatten_tree(tree) -> np.ndarray:
    return np.concatenate(
        [np.asarray(leaf, np.float64).ravel() for leaf in jax.tree_util.tree_leaves(tree)]
    )


# ---------------------------------------------------------------------------
# the diagnostics math vs a pure-numpy reference
# ---------------------------------------------------------------------------


def test_diagnostics_step_matches_numpy_reference():
    cfg, model, variables, batch = _tiny_two_head()
    tx = optax.adam(1e-3)
    state = create_train_state(variables, tx)
    diag_fn = make_diagnostics_step(model, tx)
    out = jax.device_get(diag_fn(state, batch))

    # independent per-head gradients: jax.grad of each scalar head loss
    # (a different autodiff path than the shared-vjp one-hot pulls),
    # flattened to numpy where norms/cosine/ratio are recomputed
    _, dropout_rng = jax.random.split(state.rng)

    def head_loss(params, ihead):
        outputs, _ = model.apply(
            {"params": params, "batch_stats": state.batch_stats},
            batch,
            train=True,
            mutable=["batch_stats"],
            rngs={"dropout": dropout_rng},
        )
        outputs = [o.astype(jnp.float32) for o in outputs]
        _, tasks = model_loss(cfg, outputs, batch)
        return tasks[ihead]

    flats = []
    for ihead in range(2):
        g = jax.grad(lambda p, i=ihead: head_loss(p, i))(state.params)
        flats.append(_flatten_tree(g))
    ref_norms = [float(np.linalg.norm(f)) for f in flats]
    ref_cos = float(flats[0] @ flats[1] / (ref_norms[0] * ref_norms[1]))

    np.testing.assert_allclose(out["grad_norms"], ref_norms, rtol=1e-4)
    cos = np.asarray(out["cosine"])
    assert cos.shape == (2, 2)
    np.testing.assert_allclose(np.diagonal(cos), [1.0, 1.0], atol=1e-5)
    np.testing.assert_allclose(cos[0, 1], ref_cos, rtol=1e-3, atol=1e-5)
    np.testing.assert_allclose(cos[1, 0], ref_cos, rtol=1e-3, atol=1e-5)

    # total gradient = weight-vector cotangent pull; update ratio from
    # an independent optax update over the numpy-recombined total
    w = np.asarray(cfg.normalized_weights, np.float64)
    ref_total = float(np.linalg.norm(w[0] * flats[0] + w[1] * flats[1]))
    np.testing.assert_allclose(out["grad_norm_total"], ref_total, rtol=1e-4)

    total_tree = jax.grad(
        lambda p: w[0] * head_loss(p, 0) + w[1] * head_loss(p, 1)
    )(state.params)
    updates, _ = tx.update(total_tree, state.opt_state, state.params)
    ref_update = float(np.linalg.norm(_flatten_tree(updates)))
    ref_param = float(np.linalg.norm(_flatten_tree(state.params)))
    np.testing.assert_allclose(out["update_norm"], ref_update, rtol=1e-4)
    np.testing.assert_allclose(out["param_norm"], ref_param, rtol=1e-5)
    np.testing.assert_allclose(out["update_ratio"], ref_update / ref_param, rtol=1e-4)

    # per-head losses come along for free (the forward's task vector)
    np.testing.assert_allclose(
        out["tasks_loss"][0], float(head_loss(state.params, 0)), rtol=1e-5
    )


def test_per_head_error_metrics_matches_numpy():
    rng = np.random.RandomState(1)
    trues = [rng.rand(17, 1), rng.rand(40, 1)]
    preds = [rng.rand(17, 1), rng.rand(40, 1)]
    m = per_head_error_metrics(trues, preds, ["energy", "charge"])
    for name, t, p in zip(["energy", "charge"], trues, preds):
        d = (p - t).ravel()
        assert m[name]["count"] == t.size
        np.testing.assert_allclose(m[name]["mae"], np.abs(d).mean(), rtol=1e-12)
        np.testing.assert_allclose(
            m[name]["rmse"], np.sqrt((d * d).mean()), rtol=1e-12
        )
    empty = per_head_error_metrics([np.zeros((0, 1))], [np.zeros((0, 1))], ["x"])
    assert empty["x"] == {"mae": None, "rmse": None, "count": 0}


# ---------------------------------------------------------------------------
# sampling discipline: separate executable, compiled once, no per-step syncs
# ---------------------------------------------------------------------------


def test_diagnostics_zero_unexpected_recompiles_and_no_per_step_syncs(monkeypatch):
    """The hot-path contract: diagnostics at default sampling add ONE
    new executable compiled on the first sampled step and nothing after;
    non-sampled and sampled steps alike perform no host sync (the
    snapshot at the epoch boundary is the only D2H)."""
    cfg, model, variables, batch = _tiny_two_head()
    tx = optax.adam(1e-3)
    state = create_train_state(variables, tx)
    step, diag_fn = make_train_step(model, tx, diagnostics=True)
    diag = HeadDiagnostics(diag_fn, cfg.output_names, every=3)

    with CompileMonitor() as mon:
        diag.maybe_sample(state, batch)  # sampled step 0: diag compiles
        state, loss, _ = step(state, batch)  # train step compiles
        jax.block_until_ready(loss)
        assert mon.count >= 1
        mon.mark("warm")

        def _boom(*a, **kw):  # pragma: no cover - must never run
            raise AssertionError("introspection must not sync per step")

        monkeypatch.setattr(jax, "block_until_ready", _boom)
        monkeypatch.setattr(jax, "device_get", _boom)
        for _ in range(5):  # steps 1..5: step 3 re-samples (warm cache)
            diag.maybe_sample(state, batch)
            state, loss, _ = step(state, batch)
        monkeypatch.undo()

        jax.block_until_ready(loss)
        assert mon.count_since("warm") == 0, (
            "a diagnostics-enabled loop recompiled after the first step"
        )

    snap = diag.epoch_snapshot()
    assert snap is not None and snap["available"]
    assert set(snap["grad_norm"]) == {"energy", "charge"}
    assert snap["sampled_step"] == 3
    # snapshot drains the pending sample: nothing to report until the
    # next sampled step
    assert diag.epoch_snapshot() is None

    # A loop that scans its epochs: the diagnosed step IS the epoch's first
    # train step, its batch cut from the stack inside the program. Two
    # executables in epoch 0, and none when the shuffle moves order[0];
    # HeadDiagnostics dispatches nothing and syncs nothing.
    from hydragnn_tpu.train.state import make_diagnosed_first_step, make_scan_epoch

    stacked = jax.tree_util.tree_map(lambda x: jnp.stack([x] * 3), batch)
    first, scan = make_diagnosed_first_step(model, tx), make_scan_epoch(model, tx)
    diag = HeadDiagnostics(None, cfg.output_names, every=1)
    state = create_train_state(variables, tx)

    def epoch(state, order):
        assert diag.due
        state, head, diagnostics = first(state, stacked, jnp.asarray(order, jnp.int32))
        diag.count_step(diagnostics)
        return scan(state, stacked, jnp.asarray(order, jnp.int32), head)

    with CompileMonitor() as mon:
        state, losses, _, counts = epoch(state, [0, 1, 2])
        jax.block_until_ready(losses)
        mon.mark("warm")
        monkeypatch.setattr(jax, "block_until_ready", _boom)
        monkeypatch.setattr(jax, "device_get", _boom)
        for order in ([2, 0, 1], [1, 2, 0]):
            state, losses, _, counts = epoch(state, order)
        monkeypatch.undo()
        jax.block_until_ready(losses)
        assert mon.count_since("warm") == 0, "a shuffled order recompiled the diagnosed first step"
    assert int(state.step) == 9 and losses.shape == counts.shape == (3,)
    assert diag.epoch_snapshot()["sampled_step"] == 2


@pytest.mark.parametrize("dispatch", ["per_step", "scan_diagnostics_off", "scan"])
def test_telemetry_disabled_training_is_bit_identical(tmp_path, monkeypatch, dispatch):
    """HYDRAGNN_TELEMETRY=0 must leave the training computation
    untouched: same config + data + seeds with telemetry fully enabled vs
    fully disabled produce bit-identical final parameters, wherever
    telemetry adds no program to the ones that train: with its default-on
    diagnostics as an observer (per-step dispatch), and under the scan
    with the diagnostics off. Under the scan with the diagnostics on, the
    epoch's first train step is run by the program that diagnoses it
    (ISSUE 30: "training's numbers are today's to rounding"): the same
    operations from another program, held to rounding element by element
    by the rule of ``assert_adam_states_agree_to_rounding``, over the
    whole state."""
    import glob

    from hydragnn_tpu.api import run_training
    from hydragnn_tpu.data.synthetic import deterministic_graph_data
    from hydragnn_tpu.flagship import flagship_config
    from hydragnn_tpu.obs import read_flight_record, reset_registry
    from test_diagnosed_step import assert_adam_states_agree_to_rounding

    def _run(log_dir, telemetry: bool):
        if not telemetry:
            monkeypatch.setenv("HYDRAGNN_TELEMETRY", "0")
        else:
            monkeypatch.delenv("HYDRAGNN_TELEMETRY", raising=False)
            # the on-run must exercise the full introspection path the
            # suite's conftest otherwise disables
            monkeypatch.setenv("HYDRAGNN_DIAGNOSTICS", "0" if dispatch == "scan_diagnostics_off" else "1")
        reset_registry()
        try:
            cfg = flagship_config(
                hidden_dim=8, num_conv_layers=2, batch_size=5, num_epoch=1
            )
            if dispatch == "per_step":
                cfg["NeuralNetwork"]["Training"]["scan_epoch"] = False
            samples = deterministic_graph_data(
                number_configurations=20,
                unit_cell_x_range=(2, 3),
                unit_cell_y_range=(2, 3),
                unit_cell_z_range=(2, 3),
                seed=0,
            )
            _, state, _, _ = run_training(cfg, samples=samples, log_dir=str(log_dir))
            return jax.device_get(state)
        finally:
            monkeypatch.delenv("HYDRAGNN_TELEMETRY", raising=False)
            reset_registry()

    on = _run(tmp_path / "on", telemetry=True)
    off = _run(tmp_path / "off", telemetry=False)
    events = read_flight_record(glob.glob(str(tmp_path / "on") + "/*/flight.jsonl")[0])
    manifest = [e for e in events if e.get("kind") == "run_start"][0]["manifest"]
    want = {"per_step": "observer", "scan_diagnostics_off": "off", "scan": "first_step"}[dispatch]
    assert manifest["dispatch_mode"]["diagnostics"]["path"] == want
    flat_on, flat_off = _flatten_tree(on.params), _flatten_tree(off.params)
    assert flat_on.shape == flat_off.shape
    if dispatch == "scan":
        assert int(on.step) == int(off.step) == 4
        assert_adam_states_agree_to_rounding(on, off, lr=1e-3, steps=4)
    else:
        np.testing.assert_array_equal(flat_on, flat_off)


# ---------------------------------------------------------------------------
# hardware-efficiency ledger
# ---------------------------------------------------------------------------


def test_hardware_ledger_prices_a_jitted_step():
    f = jax.jit(lambda x: (x @ x).sum())
    ledger = HardwareLedger.from_step(f, (jnp.ones((16, 16)),))
    assert ledger.available
    man = ledger.manifest()
    assert man["available"] and man["flops_per_step"] > 0
    assert "peak_bf16_tflops" in man  # None on CPU, a number on TPU
    rec = ledger.epoch_record(steps=10, wall_s=0.25)
    assert rec["available"] and rec["achieved_tflops"] > 0
    assert rec["steps"] == 10 and rec["train_wall_s"] == 0.25
    # MFU needs a known chip peak; memory needs backend memory_stats —
    # both degrade to explicit unavailability, never a crash
    assert "mfu" in rec
    assert "available" in rec["memory"]
    summary = ledger.run_summary()
    assert summary["available"]


def test_hardware_ledger_degrades_on_unlowerable_step():
    ledger = HardwareLedger.from_step(lambda x: x, (1,))
    assert not ledger.available
    assert ledger.manifest()["available"] is False
    assert ledger.manifest()["reason"].startswith("lowering_failed")
    rec = ledger.epoch_record(steps=4, wall_s=1.0)
    assert rec["available"] is False and "achieved_tflops" not in rec
    assert "available" in rec["memory"]


# ---------------------------------------------------------------------------
# flight schema v2 + forward compat
# ---------------------------------------------------------------------------


def test_flight_v1_records_still_validate(tmp_path):
    path = str(tmp_path / "v1.jsonl")
    with open(path, "w") as f:
        f.write(
            json.dumps(
                {
                    "v": 1,
                    "kind": "run_start",
                    "t": 1.0,
                    "rank": 0,
                    "manifest": {
                        "jax_version": "0.4",
                        "backend": "cpu",
                        "num_processes": 1,
                    },
                }
            )
            + "\n"
        )
        f.write(
            json.dumps(
                {
                    "v": 1,
                    "kind": "epoch",
                    "t": 2.0,
                    "rank": 0,
                    "epoch": 0,
                    "train_loss": 1.0,
                    "val_loss": 1.1,
                    "train_tasks": [0.5, 0.5],  # v1 positional lists
                }
            )
            + "\n"
        )
        f.write(
            json.dumps(
                {"v": 1, "kind": "run_end", "t": 3.0, "rank": 0, "status": "completed"}
            )
            + "\n"
        )
    assert validate_flight_record(path, require_complete=True) == []
    assert flight_record_warnings(path) == []
    # the head-series reader accepts v1 positional task lists
    series = collect_head_series(read_flight_record(path))
    assert series["names"] == ["task0", "task1"]
    assert series["train_loss"]["task0"] == [0.5]


def test_flight_unknown_kinds_and_newer_versions_warn_not_fail(tmp_path):
    path = str(tmp_path / "future.jsonl")
    with FlightRecorder(path) as fr:
        fr.start_run({"run": "t"})
        fr.epoch(0, train_loss=1.0, val_loss=1.0)
        fr.end_run(status="completed")
    with open(path, "a") as f:
        f.write(
            json.dumps({"v": 2, "kind": "quantum_leap", "t": 4.0, "rank": 0}) + "\n"
        )
        f.write(
            json.dumps(
                {"v": 3, "kind": "run_end", "t": 5.0, "rank": 0, "status": "x"}
            )
            + "\n"
        )
    events = read_flight_record(path)
    assert validate_flight_record(events) == []  # accepted, not failed
    warnings = flight_record_warnings(events)
    assert any("unknown event kind 'quantum_leap'" in w for w in warnings)
    assert any("newer than this reader" in w for w in warnings)
    # a genuinely bogus version is still a validation problem
    bogus = [{"v": "two", "kind": "epoch", "t": 1.0, "rank": 0,
              "epoch": 0, "train_loss": 1.0, "val_loss": 1.0}]
    assert any("schema version" in p for p in validate_flight_record(bogus))


def test_current_writer_emits_v2(tmp_path):
    path = str(tmp_path / "now.jsonl")
    with FlightRecorder(path) as fr:
        fr.start_run({"run": "t"})
    assert read_flight_record(path)[0]["v"] == 2


# ---------------------------------------------------------------------------
# head-series extraction + anomaly heuristics (the --heads view's math)
# ---------------------------------------------------------------------------


def _series(**overrides):
    base = {
        "names": ["a", "b"],
        "epochs": [0, 1, 2, 3],
        "train_loss": {"a": [1.0, 1.0, 1.0, 1.0], "b": [1.0, 1.0, 1.0, 1.0]},
        "grad_norm": {"a": [1.0] * 4, "b": [1.0] * 4},
        "mae": {"a": [None] * 4, "b": [None] * 4},
        "rmse": {"a": [None] * 4, "b": [None] * 4},
        "cosine": [[[1.0, 0.5], [0.5, 1.0]]] * 4,
        "update_ratio": [0.01] * 4,
    }
    base.update(overrides)
    return base


def test_flag_anomalies_healthy_run_is_quiet():
    assert flag_anomalies(_series()) == []


def test_flag_anomalies_detects_all_three_classes():
    flags = flag_anomalies(
        _series(
            train_loss={"a": [1.0, 1.0, 1.0, 9.0], "b": [1.0] * 4},
            grad_norm={"a": [50.0] * 4, "b": [1.0] * 4},
            cosine=[[[1.0, -0.4], [-0.4, 1.0]]] * 4,
        )
    )
    assert any("loss spike" in f and "'a'" in f for f in flags)
    assert any("task conflict" in f for f in flags)
    assert any("gradient imbalance" in f and "50" in f for f in flags)


def test_flag_anomalies_ignores_transient_negatives():
    # one negative-cosine epoch out of four is a blip, not a conflict
    flags = flag_anomalies(
        _series(
            cosine=[[[1.0, -0.4], [-0.4, 1.0]]]
            + [[[1.0, 0.3], [0.3, 1.0]]] * 3
        )
    )
    assert not any("task conflict" in f for f in flags)


def test_collect_head_series_reads_v2_epoch_events():
    events = [
        {
            "kind": "epoch",
            "epoch": e,
            "train_tasks": {"energy": 1.0 / (e + 1), "charge": 0.5},
            "heads": {
                "names": ["energy", "charge"],
                "grad_norm": {"energy": 2.0, "charge": 1.0},
                "mae": {"energy": 0.1, "charge": 0.2},
                "rmse": {"energy": 0.2, "charge": 0.3},
                "cosine": [[1.0, 0.1], [0.1, 1.0]],
                "update_ratio": 0.005,
            },
        }
        for e in range(3)
    ]
    s = collect_head_series(events)
    assert s["names"] == ["energy", "charge"]
    assert s["train_loss"]["energy"] == [1.0, 0.5, pytest.approx(1 / 3)]
    assert s["grad_norm"]["charge"] == [1.0, 1.0, 1.0]
    assert s["mae"]["energy"] == [0.1, 0.1, 0.1]
    assert len(s["cosine"]) == 3 and s["update_ratio"] == [0.005] * 3


def test_obs_report_heads_view_renders(tmp_path, capsys):
    import os
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir, "tools"))
    try:
        import obs_report
    finally:
        sys.path.pop(0)

    path = str(tmp_path / "flight.jsonl")
    with FlightRecorder(path) as fr:
        fr.start_run({"run": "t", "head_names": ["energy", "charge"]})
        for ep in range(2):
            fr.epoch(
                ep,
                train_loss=1.0,
                val_loss=1.0,
                train_tasks={"energy": 0.6, "charge": 0.4},
                val_tasks={"energy": 0.7, "charge": 0.5},
                heads={
                    "names": ["energy", "charge"],
                    "available": True,
                    "grad_norm": {"energy": 2.0, "charge": 1.0},
                    "cosine": [[1.0, -0.3], [-0.3, 1.0]],
                    "update_ratio": 0.004,
                    "mae": {"energy": 0.1, "charge": 0.2},
                    "rmse": {"energy": 0.15, "charge": 0.25},
                },
                hw={
                    "available": True,
                    "achieved_tflops": 1.25,
                    "mfu": 0.41,
                    "memory": {"available": True, "peak_bytes_in_use": 123456},
                },
            )
        fr.end_run(status="completed")

    assert obs_report.main(["--heads", path]) == 0
    out = capsys.readouterr().out
    assert "task-conflict matrix" in out
    assert "energy" in out and "charge" in out
    assert "hardware-efficiency ledger" in out and "0.41" in out
    assert "task conflict" in out  # -0.3 in both epochs flags the pair


# ---------------------------------------------------------------------------
# the HeadDiagnostics sampler cadence
# ---------------------------------------------------------------------------


def test_head_diagnostics_sampling_cadence():
    calls = []

    def fake_fn(state, batch):
        calls.append(state)
        return {
            "tasks_loss": np.asarray([0.1, 0.2]),
            "grad_norms": np.asarray([1.0, 2.0]),
            "cosine": np.eye(2),
            "grad_norm_total": np.float32(2.0),
            "param_norm": np.float32(4.0),
            "update_norm": np.float32(0.1),
            "update_ratio": np.float32(0.025),
        }

    diag = HeadDiagnostics(fake_fn, ["a", "b"], every=4)
    for step in range(10):
        diag.maybe_sample(step, None)
    assert calls == [0, 4, 8]  # steps 0, 4, 8 sampled
    snap = diag.epoch_snapshot()
    assert snap["sampled_step"] == 8
    assert snap["grad_norm"] == {"a": 1.0, "b": 2.0}
    assert snap["update_ratio"] == pytest.approx(0.025)

    # a loop that scans counts epochs, asks ``due`` and hands the diagnosed
    # first step's dictionary over: no observer, the same cadence
    diag = HeadDiagnostics(None, ["a", "b"], every=2)
    due = []
    for epoch in range(5):
        due.append(diag.due)
        diag.count_step(fake_fn(epoch, None) if diag.due else None)
        snap = diag.epoch_snapshot()
        assert (snap is not None) == due[-1]
        if snap is not None:
            assert snap["sampled_step"] == epoch
    assert due == [True, False, True, False, True]
    assert calls[3:] == [0, 2, 4]
