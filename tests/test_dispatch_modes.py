"""Dispatch-mode satellites (ISSUE 6): scan_epoch as the automatic
default where eligible (with the flight-record field saying which mode
ran), the guarded scan body, and the per-step sync discipline — zero
``block_until_ready`` / ``device_get`` outside the sampled span window
and the epoch boundary."""

import glob
import json
import os

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from hydragnn_tpu.data.ingest import prepare_dataset
from hydragnn_tpu.data.loader import GraphLoader
from hydragnn_tpu.data.synthetic import deterministic_graph_data
from hydragnn_tpu.models.create import create_model_config
from hydragnn_tpu.train import (
    create_train_state,
    make_train_step,
    select_optimizer,
)
from hydragnn_tpu.train.loop import _scan_auto_eligible, train_epoch
from hydragnn_tpu.utils.config import update_config

from test_data_pipeline import base_config


@pytest.fixture(scope="module")
def tiny_problem():
    cfg = base_config(multihead=False)
    cfg["NeuralNetwork"]["Architecture"]["model_type"] = "GIN"
    samples = deterministic_graph_data(number_configurations=24, seed=7)
    train, val, test, _, _ = prepare_dataset(samples, cfg)
    cfg = update_config(cfg, train, val, test)
    loader = GraphLoader(train, 6, shuffle=False)
    example = next(iter(loader))
    model, variables = create_model_config(cfg["NeuralNetwork"], example)
    return cfg, model, variables, loader


# -- eligibility unit tests -------------------------------------------------


def pytest_scan_auto_eligibility(tiny_problem, monkeypatch):
    cfg, _, _, loader = tiny_problem
    nn = cfg["NeuralNetwork"]
    ok, reason = _scan_auto_eligible(loader, nn)
    assert ok, reason

    class NoStack:
        pass

    ok, reason = _scan_auto_eligible(NoStack(), nn)
    assert not ok and "stack" in reason

    monkeypatch.setenv("HYDRAGNN_INJECT_SIGTERM_STEP", "5")
    ok, reason = _scan_auto_eligible(loader, nn)
    assert not ok and "fault injection" in reason
    monkeypatch.delenv("HYDRAGNN_INJECT_SIGTERM_STEP")

    # serve-side injection does not force per-step training dispatch
    monkeypatch.setenv("HYDRAGNN_INJECT_SERVE_RAISE", "1")
    ok, _ = _scan_auto_eligible(loader, nn)
    assert ok
    monkeypatch.delenv("HYDRAGNN_INJECT_SERVE_RAISE")

    # the checks that read the configuration and the profiler argument
    stalled = dict(nn, Training=dict(nn["Training"], watchdog_stall_s=5))
    ok, reason = _scan_auto_eligible(loader, stalled)
    assert not ok and "watchdog" in reason
    ok, reason = _scan_auto_eligible(loader, dict(nn, Profile={"enable": 1}))
    assert not ok and "profiler" in reason
    ok, reason = _scan_auto_eligible(loader, nn, profiler=object())
    assert not ok and "profiler" in reason

    monkeypatch.setenv("HYDRAGNN_WATCHDOG_S", "30")
    ok, reason = _scan_auto_eligible(loader, nn)
    assert not ok and "watchdog" in reason


def pytest_multi_device_stack_not_eligible(tiny_problem):
    cfg, _, _, _ = tiny_problem
    samples = deterministic_graph_data(number_configurations=24, seed=7)
    train, _, _, _, _ = prepare_dataset(samples, base_config(multihead=False))
    if jax.local_device_count() < 2:
        pytest.skip("needs the virtual multi-device mesh")
    loader = GraphLoader(train, 8, shuffle=False, device_stack=2)
    ok, reason = _scan_auto_eligible(loader, cfg["NeuralNetwork"])
    assert not ok and "multi-device" in reason


# -- flight-record dispatch_mode field --------------------------------------


def _read_manifest(log_dir):
    from hydragnn_tpu.obs.flight import read_flight_record

    path = glob.glob(log_dir + "/*/flight.jsonl")[0]
    events = read_flight_record(path)
    man = [e for e in events if e.get("kind") == "run_start"][0]["manifest"]
    epochs = [e for e in events if e.get("kind") == "epoch"]
    return man, epochs


def _assert_pad_plans(man, train_plan):
    """The manifest says which pad plan each loader got and the largest
    batch it was cut to (docs/OBSERVABILITY.md): validation and test never
    shuffle, the train loader's plan follows the dispatch mode."""
    plans = man["pad_plans"]
    assert plans["train"]["plan"] == train_plan
    assert plans["val"]["plan"] == plans["test"]["plan"] == "fixed_membership"
    for split in ("train", "val", "test"):
        p = plans[split]
        assert 0 < p["real_nodes_max"] < p["pad_nodes"]
        assert 0 < p["real_edges_max"] <= p["pad_edges"]
        assert p["dense_slots"] is None or p["run_align"] == 0


def pytest_auto_scan_default_and_flight_field(tmp_path, monkeypatch):
    """A default run_training on the single-device path must pick the
    scan dispatch automatically and say so in the flight record."""
    monkeypatch.setenv("HYDRAGNN_TELEMETRY", "1")
    from hydragnn_tpu.api import run_training
    from test_train_e2e import make_config

    config = make_config("GIN", False, str(tmp_path), num_epoch=2)
    # batch NOT divisible by the virtual 8-device mesh, so run_training
    # takes the single-device (loop-owned) path the auto default targets
    config["NeuralNetwork"]["Training"]["batch_size"] = 5
    samples = deterministic_graph_data(number_configurations=30, seed=0)
    run_training(config, samples=samples, log_dir=str(tmp_path) + "/logs/")
    man, epochs = _read_manifest(str(tmp_path) + "/logs")
    assert man["scan_epoch"] is True
    dm = man["dispatch_mode"]
    assert dm["mode"] == "scan_epoch" and dm["auto"] is True, dm
    assert "stacked loader" in dm["reason"]
    assert all(e["step_time"]["mode"] == "scan_epoch" for e in epochs)
    # the scan permutes batch order only: every plan is cut to real batches
    _assert_pad_plans(man, "fixed_membership")


def pytest_explicit_false_keeps_per_step(tmp_path, monkeypatch):
    monkeypatch.setenv("HYDRAGNN_TELEMETRY", "1")
    from hydragnn_tpu.api import run_training
    from test_train_e2e import make_config

    config = make_config("GIN", False, str(tmp_path), num_epoch=1)
    config["NeuralNetwork"]["Training"]["batch_size"] = 5
    config["NeuralNetwork"]["Training"]["scan_epoch"] = False
    samples = deterministic_graph_data(number_configurations=30, seed=0)
    run_training(config, samples=samples, log_dir=str(tmp_path) + "/logs/")
    man, epochs = _read_manifest(str(tmp_path) + "/logs")
    dm = man["dispatch_mode"]
    assert dm["mode"] == "per_step" and dm["auto"] is False
    assert dm["reason"] == "Training.scan_epoch=false"
    # per-step shuffling re-draws membership: the train plan stays worst-case
    _assert_pad_plans(man, "worst_case")
    for e in epochs:
        st = e["step_time"]
        # the per-step span decomposition (data-wait / dispatch /
        # sampled device) — moved here from the obs e2e now that the
        # default dispatch is scan
        assert st["mode"] == "per_step"
        assert st["data_wait_s"] >= 0 and st["dispatch_s"] > 0
        assert st["sampled_steps"] >= 1 and st["device_wait_ms_mean"] is not None


def pytest_injection_forces_per_step(tmp_path, monkeypatch):
    """Step-indexed fault injection needs batch granularity: the auto
    default must fall back to per-step dispatch (NAN_STEP far beyond the
    epoch so nothing actually fires)."""
    monkeypatch.setenv("HYDRAGNN_TELEMETRY", "1")
    monkeypatch.setenv("HYDRAGNN_INJECT_NAN_STEP", "99999")
    from hydragnn_tpu.api import run_training
    from test_train_e2e import make_config

    config = make_config("GIN", False, str(tmp_path), num_epoch=1)
    config["NeuralNetwork"]["Training"]["batch_size"] = 5
    samples = deterministic_graph_data(number_configurations=30, seed=0)
    run_training(config, samples=samples, log_dir=str(tmp_path) + "/logs/")
    man, _ = _read_manifest(str(tmp_path) + "/logs")
    dm = man["dispatch_mode"]
    assert dm["mode"] == "per_step" and "fault injection" in dm["reason"]
    _assert_pad_plans(man, "worst_case")


def pytest_scan_built_loader_gone_per_step_says_so(tmp_path, monkeypatch):
    """The stack is refused after the train loader was built for the scan:
    the run goes per-step over the loader's fixed batches, and the
    manifest's dispatch reason says that only their order is shuffled."""
    monkeypatch.setenv("HYDRAGNN_TELEMETRY", "1")
    from hydragnn_tpu.api import run_training
    from hydragnn_tpu.train import loop
    from test_train_e2e import make_config

    monkeypatch.setattr(loop, "_stack_refusal", lambda loader: "no room (test)")
    config = make_config("GIN", False, str(tmp_path), num_epoch=2)
    config["NeuralNetwork"]["Training"]["batch_size"] = 5
    samples = deterministic_graph_data(number_configurations=30, seed=0)
    run_training(config, samples=samples, log_dir=str(tmp_path) + "/logs/")
    man, epochs = _read_manifest(str(tmp_path) + "/logs")
    dm = man["dispatch_mode"]
    assert dm["mode"] == "per_step" and dm["auto"] is True
    assert dm["reason"].startswith("stacking failed: no room (test)")
    assert "only their order is shuffled" in dm["reason"]
    _assert_pad_plans(man, "fixed_membership")
    assert all(e["step_time"]["mode"] == "per_step" for e in epochs)


# -- the train loader is built knowing whether its membership is fixed -------

_PLANNED = {
    # name: (Training keys, NeuralNetwork keys, environment, device_stack, train plan)
    "default": ({}, {}, {}, 1, "fixed_membership"),
    "scan_true": ({"scan_epoch": True}, {}, {}, 1, "fixed_membership"),
    "scan_true_beats_injection": (
        {"scan_epoch": True}, {}, {"HYDRAGNN_INJECT_NAN_STEP": "9"}, 1, "fixed_membership",
    ),
    "scan_false": ({"scan_epoch": False}, {}, {}, 1, "worst_case"),
    "scan_false_cached": (
        {"scan_epoch": False, "cache_device_batches": True}, {}, {}, 1, "fixed_membership",
    ),
    "reshuffle": ({"scan_reshuffle_every": 1}, {}, {}, 1, "worst_case"),
    "mesh": ({}, {}, {}, 2, "worst_case"),
    "mesh_scan_true": ({"scan_epoch": True}, {}, {}, 2, "worst_case"),
    "edge_sharded": ({}, {"Parallel": {"edge": 2}}, {}, 1, "worst_case"),
    "injection": ({}, {}, {"HYDRAGNN_INJECT_SIGTERM_STEP": "5"}, 1, "worst_case"),
    "watchdog_env": ({}, {}, {"HYDRAGNN_WATCHDOG_S": "30"}, 1, "worst_case"),
    "watchdog_cfg": ({"watchdog_stall_s": 5}, {}, {}, 1, "worst_case"),
    "profiler": ({}, {"Profile": {"enable": 1}}, {}, 1, "worst_case"),
}


@pytest.mark.parametrize("case", sorted(_PLANNED))
def pytest_create_dataloaders_plans_for_the_dispatch(case, monkeypatch):
    """api.create_dataloaders asks the loop's own question
    (scan_dispatch_planned) before the loaders exist: the train loader's
    membership is fixed exactly when the run will scan it, and the loop,
    asked later with the loader in hand, resolves the same mode."""
    from hydragnn_tpu.api import create_dataloaders
    from hydragnn_tpu.train.loop import scan_dispatch_planned

    training, nn_extra, env, device_stack, want = _PLANNED[case]
    for k, v in env.items():
        monkeypatch.setenv(k, v)
    config = base_config(multihead=False)
    config["NeuralNetwork"]["Training"].update(batch_size=6, **training)
    config["NeuralNetwork"].update(nn_extra)
    samples = deterministic_graph_data(number_configurations=30, seed=3)
    train, val, test, _, _ = prepare_dataset(samples, config)
    train_loader, val_loader, test_loader = create_dataloaders(
        train, val, test, config, device_stack=device_stack
    )
    assert train_loader.plan == want
    assert val_loader.plan == test_loader.plan == "fixed_membership"
    worst = GraphLoader(train, 6, shuffle=True, device_stack=device_stack)
    plan = (train_loader.pad_nodes, train_loader.pad_edges)
    if want == "worst_case":
        assert plan == (worst.pad_nodes, worst.pad_edges)  # bit for bit today's
    else:
        assert plan[0] <= worst.pad_nodes and plan[1] <= worst.pad_edges
    nn = config["NeuralNetwork"]
    edge = (nn.get("Parallel") or {}).get("edge", 1)
    single = device_stack == 1 and edge == 1
    if single:
        planned, reason = scan_dispatch_planned(nn, single_device=True)
        if nn["Training"].get("scan_epoch") is None:
            assert (planned, reason) == _scan_auto_eligible(train_loader, nn)
        fixed = planned and not nn["Training"].get("scan_reshuffle_every")
        fixed = fixed or bool(nn["Training"].get("cache_device_batches"))
        assert (want == "fixed_membership") == fixed
    for epoch in range(2):  # whatever the run does with it, every batch fits
        train_loader.set_epoch(epoch)
        assert sum(int(np.asarray(b.graph_mask).sum()) for b in train_loader) == len(train)


# -- guarded scan body ------------------------------------------------------


def pytest_guarded_scan_matches_unguarded_on_finite_data(tiny_problem):
    from hydragnn_tpu.train import make_scan_epoch

    cfg, model, variables, loader = tiny_problem
    tx = select_optimizer({"Optimizer": {"type": "AdamW", "learning_rate": 1e-3}})
    stacked = loader.stacked_device_batches()
    order = jnp.arange(len(loader), dtype=jnp.int32)

    s0 = create_train_state(variables, tx, seed=0)
    plain = make_scan_epoch(model, tx)
    s0, losses0, _, counts0 = plain(s0, stacked, order)

    s1 = create_train_state(variables, tx, seed=0)
    guarded = make_scan_epoch(model, tx, guard_nonfinite=True)
    s1, losses1, _, counts1, bads, consec = guarded(
        s1, loader.stacked_device_batches(), order, jnp.zeros((), jnp.int32)
    )
    assert float(jnp.asarray(bads).sum()) == 0.0
    assert int(consec) == 0
    np.testing.assert_allclose(np.asarray(losses1), np.asarray(losses0),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(counts1), np.asarray(counts0))
    for a, b in zip(
        jax.tree_util.tree_leaves(jax.device_get(s0.params)),
        jax.tree_util.tree_leaves(jax.device_get(s1.params)),
    ):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


def pytest_guarded_scan_skips_nan_batch(tiny_problem):
    """A poisoned batch inside the stack must be skipped (zero loss and
    count, bad flag set) without corrupting the carried params."""
    from hydragnn_tpu.train import make_scan_epoch

    cfg, model, variables, loader = tiny_problem
    tx = select_optimizer({"Optimizer": {"type": "AdamW", "learning_rate": 1e-3}})
    stacked = loader.stacked_device_batches()
    nb = len(loader)
    poisoned = stacked.replace(
        nodes=stacked.nodes.at[1].set(jnp.nan)
    )
    order = jnp.arange(nb, dtype=jnp.int32)
    state = create_train_state(variables, tx, seed=0)
    guarded = make_scan_epoch(model, tx, guard_nonfinite=True)
    state, losses, _, counts, bads, consec = guarded(
        state, poisoned, order, jnp.zeros((), jnp.int32)
    )
    bads = np.asarray(bads)
    assert bads[1] == 1.0 and bads.sum() == 1.0, bads
    assert float(np.asarray(losses)[1]) == 0.0
    assert float(np.asarray(counts)[1]) == 0.0
    for leaf in jax.tree_util.tree_leaves(jax.device_get(state.params)):
        assert np.isfinite(leaf).all()


# -- per-step sync discipline ----------------------------------------------


def pytest_zero_syncs_outside_sampled_window(tiny_problem):
    """The per-step loop must not block on the device outside the span
    tracer's sampled window, and must not call device_get at all until
    the epoch-boundary finalize — the dispatch-overhead contract the
    deferred _MetricAccum provides."""
    from hydragnn_tpu.obs import StepSpans

    cfg, model, variables, loader = tiny_problem
    tx = select_optimizer({"Optimizer": {"type": "AdamW", "learning_rate": 1e-3}})
    state = create_train_state(variables, tx)
    step = make_train_step(model, tx)

    real_block = jax.block_until_ready
    real_get = jax.device_get
    calls = {"block": 0, "get": 0}

    def counting_block(tree):
        calls["block"] += 1
        return real_block(tree)

    def counting_get(tree):
        calls["get"] += 1
        return real_get(tree)

    spans = StepSpans(sample_steps=2, skip_first=1)
    spans.epoch_start(0)
    jax.block_until_ready = counting_block
    jax.device_get = counting_get
    try:
        state, loss, tasks = train_epoch(loader, state, step, spans=spans)
        in_loop = dict(calls)
    finally:
        jax.block_until_ready = real_block
        jax.device_get = real_get
    assert len(loader) > spans.sample_steps + 1
    # exactly the sampled window blocks; nothing else syncs per step
    assert in_loop["block"] == spans.sample_steps, in_loop
    assert in_loop["get"] == 0, in_loop
    assert np.isfinite(loss)


def pytest_metric_accum_defers_and_weights():
    """_MetricAccum with raw masks + bad flags reproduces the weighted
    mean the old per-step-multiply accumulator computed."""
    from hydragnn_tpu.train.loop import _MetricAccum

    acc = _MetricAccum()
    masks = [
        jnp.asarray([True, True, False]),
        jnp.asarray([True, False, False]),
        jnp.asarray([True, True, True]),
    ]
    losses = [jnp.asarray(2.0), jnp.asarray(4.0), jnp.asarray(1.0)]
    tasks = [jnp.asarray([2.0, 0.0]), jnp.asarray([4.0, 1.0]), jnp.asarray([1.0, 2.0])]
    bads = [None, jnp.asarray(1.0), None]  # batch 1 skipped by the sentry
    for l, t, m, b in zip(losses, tasks, masks, bads):
        acc.add(l, t, m, bad=b)
    avg_loss, avg_tasks = acc.finalize()
    # weights: 2, 0 (bad), 3 -> loss = (2*2 + 1*3) / 5
    assert avg_loss == pytest.approx((2.0 * 2 + 1.0 * 3) / 5)
    np.testing.assert_allclose(
        avg_tasks, [(2.0 * 2 + 1.0 * 3) / 5, (0.0 * 2 + 2.0 * 3) / 5]
    )


def pytest_metric_accum_scalar_counts_still_work():
    from hydragnn_tpu.train.loop import _MetricAccum

    acc = _MetricAccum()
    acc.add(jnp.asarray(3.0), jnp.asarray([3.0]), jnp.asarray(2.0))
    acc.add(jnp.asarray(5.0), jnp.asarray([5.0]), jnp.asarray(6.0))
    avg_loss, avg_tasks = acc.finalize()
    assert avg_loss == pytest.approx((3.0 * 2 + 5.0 * 6) / 8)
