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


# -- the test split lives on the device (ISSUE 28) ---------------------------


@pytest.fixture(scope="module")
def multihead_split():
    """A split of four batches, the last one partial (24 graphs by 7),
    with a graph head and three node heads."""
    cfg = base_config(multihead=True)
    cfg["NeuralNetwork"]["Architecture"]["model_type"] = "GIN"
    samples = deterministic_graph_data(number_configurations=30, seed=11)
    train, val, test, _, _ = prepare_dataset(samples, cfg)
    cfg = update_config(cfg, train, val, test)
    loader = GraphLoader(train, 7, shuffle=False)
    assert len(loader) > 2 and len(train) % 7
    model, variables = create_model_config(cfg["NeuralNetwork"], next(iter(loader)))
    tx = select_optimizer(cfg["NeuralNetwork"]["Training"]["Optimizer"])
    return model, create_train_state(variables, tx), loader


@pytest.mark.parametrize("case", ["samples", "no_samples", "later_epoch"])
def pytest_kept_test_epoch_equals_rebuilt(multihead_split, case, monkeypatch):
    """``test_epoch`` over a loader that keeps its batches on the device
    returns what it returns over one that builds them every epoch: the
    losses, and per head the true and predicted values of the real
    entries, in the same order and of the same lengths. ``later_epoch``:
    a second pass, with other weights, builds no batch and yields the
    very arrays the first pass read."""
    from hydragnn_tpu.train import make_eval_step
    from hydragnn_tpu.train.loop import test_epoch as run_test_epoch

    model, state, rebuilt = multihead_split
    cfg = model.cfg
    step = make_eval_step(model, with_outputs=True)
    kept = GraphLoader(rebuilt.all_samples, rebuilt.batch_size, shuffle=False)
    kept.keep_on_device()
    assert kept.cache_device_batches and (kept.pad_nodes, kept.pad_edges) == (
        rebuilt.pad_nodes, rebuilt.pad_edges)
    want_samples = case != "no_samples"
    if case == "later_epoch":
        first = list(kept)
        run_test_epoch(kept, state, step, cfg)
        state = state.replace(
            params=jax.tree_util.tree_map(lambda p: p * 1.25 + 0.01, state.params)
        )
        monkeypatch.setattr(
            GraphLoader, "_make_batch", lambda self, idx: pytest.fail("a kept batch was rebuilt")
        )
    loss_k, tasks_k, true_k, pred_k = run_test_epoch(
        kept, state, step, cfg, return_samples=want_samples
    )
    if case == "later_epoch":
        assert all(a is b for a, b in zip(first, kept))
        monkeypatch.undo()
    loss_r, tasks_r, true_r, pred_r = run_test_epoch(
        rebuilt, state, step, cfg, return_samples=want_samples
    )
    np.testing.assert_allclose(loss_k, loss_r, rtol=1e-6)
    np.testing.assert_allclose(tasks_k, tasks_r, rtol=1e-6)
    assert len(true_k) == len(pred_k) == (cfg.num_heads if want_samples else 0)
    assert len(true_r) == len(true_k)
    rows = {"graph": rebuilt.num_graphs_total(), "node": sum(s.num_nodes for s in rebuilt.samples)}
    for ihead in range(len(true_k)):
        assert true_k[ihead].shape == true_r[ihead].shape == pred_k[ihead].shape == pred_r[ihead].shape
        assert true_k[ihead].shape[0] == rows[cfg.output_type[ihead]]
        np.testing.assert_array_equal(true_k[ihead], true_r[ihead])
        np.testing.assert_allclose(pred_k[ihead], pred_r[ihead], rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("case", ["shuffles", "too_large"])
def pytest_keep_on_device_refused_leaves_the_loader_as_it_was(multihead_split, case, monkeypatch):
    """A loader that shuffles fixes its membership when it is built, so it
    cannot be asked later; a split too large for the device raises what the
    placement raised. Either way the loader goes on building its batches."""
    _, _, rebuilt = multihead_split
    loader = GraphLoader(rebuilt.all_samples, rebuilt.batch_size, shuffle=case == "shuffles")
    if case == "too_large":
        def no_room(self, batch):
            raise jax.errors.JaxRuntimeError("RESOURCE_EXHAUSTED: out of memory (test)")

        monkeypatch.setattr(GraphLoader, "_place", no_room)
    with pytest.raises(ValueError if case == "shuffles" else jax.errors.JaxRuntimeError):
        loader.keep_on_device()
    monkeypatch.undo()
    assert not loader.cache_device_batches and loader._cached_batches is None
    assert [int(b.graph_mask.sum()) for b in loader] == [7, 7, 7, len(loader.samples) - 21]


def _refuse_keeping(monkeypatch):
    def keep_on_device(self):
        raise jax.errors.JaxRuntimeError("RESOURCE_EXHAUSTED: out of memory (test)")

    monkeypatch.setattr(GraphLoader, "keep_on_device", keep_on_device)


def _caller_supplies_eval_step_out(monkeypatch):
    from hydragnn_tpu import api
    from hydragnn_tpu.train import make_eval_step

    real = api.train_validate_test

    def with_own_step(model, *args, **kwargs):
        kwargs["eval_step_out"] = make_eval_step(model, with_outputs=True)
        return real(model, *args, **kwargs)

    monkeypatch.setattr(api, "train_validate_test", with_own_step)


_TEST_SPLIT_PATHS = {
    # name: (Training keys, what the case changes, train mode, path, reason starts with)
    "default": ({}, None, "scan_epoch", "on_device", "scan dispatch: test batches built once"),
    "scan_true": ({"scan_epoch": True}, None, "scan_epoch", "on_device", "scan dispatch"),
    "scan_false": ({"scan_epoch": False}, None, "per_step", "rebuilt", "the train split"),
    "keeping_refused": (
        {}, _refuse_keeping, "scan_epoch", "rebuilt",
        "keeping failed: JaxRuntimeError: RESOURCE_EXHAUSTED: out of memory (test)",
    ),
    "caller_step": (
        {}, _caller_supplies_eval_step_out, "scan_epoch", "rebuilt",
        "caller-supplied eval_step_out",
    ),
}


@pytest.mark.parametrize("case", sorted(_TEST_SPLIT_PATHS))
def pytest_test_split_path_in_manifest_and_loader(case, tmp_path, monkeypatch):
    """The choice is made once, at set-up, and shows twice: the flight
    manifest's ``dispatch_mode.test_split`` says whether the test pass
    iterates batches kept on the device or rebuilds them every epoch, and
    why; and the test loader builds its batches once (under
    ``setup.stack_splits``) or once an epoch. Either way ``epoch.test``
    has the same children, the head quality is there, and no program
    compiles after epoch 0."""
    monkeypatch.setenv("HYDRAGNN_TELEMETRY", "1")
    monkeypatch.setenv("HYDRAGNN_DIAGNOSTICS", "1")  # the test pass gathers samples
    from hydragnn_tpu.api import run_training
    from hydragnn_tpu.obs import epoch_phases
    from test_train_e2e import make_config

    training, change, mode, path, reason = _TEST_SPLIT_PATHS[case]
    if change is not None:
        change(monkeypatch)
    from hydragnn_tpu.train import loop

    built, built_by_pass = [], []  # batches built on the host: all, and by each test pass
    real_make, real_test_epoch = GraphLoader._make_batch, loop.test_epoch

    def counting_make(self, idx):
        built.append(len(idx))
        return real_make(self, idx)

    def counting_test_epoch(*args, **kwargs):
        before = len(built)
        out = real_test_epoch(*args, **kwargs)
        built_by_pass.append(len(built) - before)
        return out

    monkeypatch.setattr(GraphLoader, "_make_batch", counting_make)
    monkeypatch.setattr(loop, "test_epoch", counting_test_epoch)
    config = make_config("GIN", True, str(tmp_path), num_epoch=3)
    config["NeuralNetwork"]["Training"].update(training, batch_size=5)
    samples = deterministic_graph_data(number_configurations=60, seed=0)
    run_training(config, samples=samples, log_dir=str(tmp_path) + "/logs/")

    from hydragnn_tpu.obs.flight import read_flight_record

    events = read_flight_record(glob.glob(str(tmp_path) + "/logs/*/flight.jsonl")[0])
    (man,) = [e["manifest"] for e in events if e.get("kind") == "run_start"]
    epochs = [e for e in events if e.get("kind") == "epoch"]
    dm = man["dispatch_mode"]
    assert dm["mode"] == mode
    assert dm["test_split"]["path"] == path, dm
    assert dm["test_split"]["reason"].startswith(reason), dm
    nb = man["pad_plans"]["test"]["num_batches"]
    assert nb > 1
    by_epoch = epoch_phases(events)
    for ev in epochs:
        under_test = {
            k: p for k, p in by_epoch[ev["epoch"]].items() if p["parent"] == "epoch.test"
        }
        assert {"test.loader_wait", "test.dispatch", "test.gather", "test.sync"} <= set(under_test)
        assert under_test["test.dispatch"]["n"] == nb
        assert ev["heads"]["available"] and set(ev["heads"]["mae"]) == set(man["head_names"])
        if ev["epoch"] > 0:
            assert ev["compiles"]["count"] == 0 and not ev["compiles"]["unexpected"]
    assert built_by_pass == [0 if path == "on_device" else nb] * len(epochs)
