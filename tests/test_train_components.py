"""Unit tests for train-layer components: optimizer factory, dynamic LR,
plateau scheduler, early stopping, freeze mask, checkpoint round-trip.

Interface-parity model: the reference smoke-tests every optimizer flavor
(reference: tests/test_optimizer.py:23-113) and loss flavor
(tests/test_loss.py:22-100) by running 2 epochs; here the optimizer matrix
runs one jitted step each, plus direct asserts on scheduler/stopper
semantics the reference delegates to torch.
"""

import numpy as np
import jax
import jax.numpy as jnp
import optax
import pytest

from hydragnn_tpu.data.synthetic import deterministic_graph_data
from hydragnn_tpu.data.ingest import prepare_dataset
from hydragnn_tpu.data.loader import GraphLoader
from hydragnn_tpu.models.create import create_model_config
from hydragnn_tpu.train import (
    EarlyStopping,
    ReduceLROnPlateau,
    create_train_state,
    current_learning_rate,
    make_eval_step,
    make_train_step,
    select_optimizer,
    set_learning_rate,
)
from hydragnn_tpu.train.optimizer import OPTIMIZERS
from hydragnn_tpu.utils.checkpoint import load_existing_model, save_model
from hydragnn_tpu.utils.config import update_config

from test_data_pipeline import base_config


@pytest.fixture(scope="module")
def small_problem():
    cfg = base_config(multihead=False)
    cfg["NeuralNetwork"]["Architecture"]["model_type"] = "GIN"
    samples = deterministic_graph_data(number_configurations=40, seed=3)
    train, val, test, _, _ = prepare_dataset(samples, cfg)
    cfg = update_config(cfg, train, val, test)
    loader = GraphLoader(train, 8, shuffle=True)
    example = next(iter(loader))
    model, variables = create_model_config(cfg["NeuralNetwork"], example)
    return cfg, model, variables, example


@pytest.mark.parametrize("opt_type", OPTIMIZERS)
def pytest_optimizer_types_one_step(small_problem, opt_type):
    cfg, model, variables, batch = small_problem
    tx = select_optimizer({"Optimizer": {"type": opt_type, "learning_rate": 1e-3}})
    state = create_train_state(variables, tx)
    step = make_train_step(model, tx)
    new_state, loss, tasks = step(state, batch)
    assert np.isfinite(float(loss))
    assert int(new_state.step) == 1


@pytest.mark.parametrize("loss_type", ["mse", "mae", "rmse"])
def pytest_loss_types_one_step(small_problem, loss_type):
    cfg, model, variables, batch = small_problem
    import dataclasses

    model2 = type(model)(dataclasses.replace(model.cfg, loss_function_type=loss_type))
    tx = select_optimizer({"Optimizer": {"type": "AdamW", "learning_rate": 1e-3}})
    state = create_train_state(variables, tx)
    step = make_train_step(model2, tx)
    _, loss, _ = step(state, batch)
    assert np.isfinite(float(loss))


def pytest_unknown_optimizer_raises():
    with pytest.raises(NameError):
        select_optimizer({"Optimizer": {"type": "Nope", "learning_rate": 1e-3}}).init({})


def pytest_dynamic_learning_rate(small_problem):
    cfg, model, variables, batch = small_problem
    tx = select_optimizer({"Optimizer": {"type": "AdamW", "learning_rate": 0.01}})
    state = create_train_state(variables, tx)
    assert current_learning_rate(state.opt_state) == pytest.approx(0.01)
    state = state.replace(opt_state=set_learning_rate(state.opt_state, 0.005))
    assert current_learning_rate(state.opt_state) == pytest.approx(0.005)
    # changed lr must not retrigger compilation (same shapes/dtypes)
    step = make_train_step(model, tx)
    step(state, batch)


def pytest_freeze_conv_zeroes_conv_updates(small_problem):
    cfg, model, variables, batch = small_problem
    tx = select_optimizer(
        {"Optimizer": {"type": "SGD", "learning_rate": 0.1}}, freeze_conv=True
    )
    state = create_train_state(variables, tx)
    step = make_train_step(model, tx)
    params_before = jax.device_get(state.params)  # step() donates state
    new_state, _, _ = step(state, batch)
    for key, sub in params_before.items():
        before = jax.tree_util.tree_leaves(sub)
        after = jax.tree_util.tree_leaves(new_state.params[key])
        same = all(np.allclose(b, a) for b, a in zip(before, after))
        if key.startswith("conv_"):
            assert same, f"frozen conv subtree {key} changed"
        elif key.startswith("graph_head") or key == "graph_shared":
            assert not same, f"trainable subtree {key} did not change"


def pytest_reduce_lr_on_plateau(small_problem):
    cfg, model, variables, batch = small_problem
    tx = select_optimizer({"Optimizer": {"type": "AdamW", "learning_rate": 0.01}})
    state = create_train_state(variables, tx)
    sched = ReduceLROnPlateau(factor=0.5, patience=2, min_lr=1e-5)
    state = sched.step(state, 1.0)  # best
    for _ in range(2):  # bad epochs within patience
        state = sched.step(state, 2.0)
        assert current_learning_rate(state.opt_state) == pytest.approx(0.01)
    state = sched.step(state, 2.0)  # exceeds patience -> halve
    assert current_learning_rate(state.opt_state) == pytest.approx(0.005)
    # floor at min_lr
    for _ in range(40):
        state = sched.step(state, 2.0)
    assert current_learning_rate(state.opt_state) == pytest.approx(1e-5, rel=1e-5)


def pytest_early_stopping_semantics():
    stopper = EarlyStopping(patience=3)
    assert not stopper(1.0)
    assert not stopper(0.9)  # improvement resets
    assert not stopper(1.1)
    assert not stopper(1.1)
    assert stopper(1.1)  # third bad epoch


def pytest_checkpoint_roundtrip(small_problem, tmp_path):
    cfg, model, variables, batch = small_problem
    tx = select_optimizer({"Optimizer": {"type": "AdamW", "learning_rate": 0.01}})
    state = create_train_state(variables, tx)
    step = make_train_step(model, tx)
    state, _, _ = step(state, batch)
    save_model(state, "ckpt_test", str(tmp_path) + "/")

    fresh = create_train_state(variables, tx)
    restored = load_existing_model(fresh, "ckpt_test", str(tmp_path) + "/")
    assert int(restored.step) == 1
    for a, b in zip(
        jax.tree_util.tree_leaves(state.params),
        jax.tree_util.tree_leaves(restored.params),
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b))
    # restored state must produce identical eval outputs
    ev = make_eval_step(model)
    l1, _ = ev(state, batch)
    l2, _ = ev(restored, batch)
    assert float(l1) == pytest.approx(float(l2), rel=1e-6)


def pytest_mixed_precision_step_trains():
    """bf16 compute path: finite loss that decreases, f32 master state
    and BatchNorm statistics preserved."""
    import jax
    import jax.numpy as jnp
    from hydragnn_tpu.flagship import build_flagship
    from hydragnn_tpu.train import (
        create_train_state,
        make_train_step,
        select_optimizer,
    )

    config, model, variables, loader = build_flagship(
        n_samples=48, hidden_dim=16, num_conv_layers=2, batch_size=8
    )
    tx = select_optimizer(config["NeuralNetwork"]["Training"])
    state = create_train_state(variables, tx)
    step = make_train_step(model, tx, compute_dtype=jnp.bfloat16)
    batches = list(loader)
    first = None
    for epoch in range(6):
        for b in batches:
            state, loss, _ = step(state, b)
            if first is None:
                first = float(loss)
    last = float(loss)
    assert np.isfinite(last)
    assert last < first
    # master params and BN stats stay f32
    for leaf in jax.tree_util.tree_leaves(state.params):
        assert leaf.dtype == jnp.float32
    for leaf in jax.tree_util.tree_leaves(state.batch_stats):
        assert leaf.dtype == jnp.float32


def pytest_per_split_raw_paths(tmp_path):
    """Dataset.path.{train,validate,test} layout: pre-defined split
    membership, normalization spanning all splits (reference:
    load_data.py:352-393)."""
    from hydragnn_tpu.api import prepare_loaders_and_config
    from hydragnn_tpu.data.synthetic import write_lsms_files

    counts = {"train": 30, "validate": 10, "test": 10}
    paths = {}
    start = 0
    for split_idx, (key, n) in enumerate(counts.items()):
        d = tmp_path / key
        write_lsms_files(str(d), number_configurations=n,
                         configuration_start=start, seed=split_idx)
        paths[key] = str(d)
        start += n

    config = {
        "Verbosity": {"level": 0},
        "Dataset": {
            "name": "unit_test",
            "format": "unit_test",
            "path": paths,
            "compositional_stratified_splitting": False,
            "rotational_invariance": False,
            "node_features": {
                "name": ["x", "x2", "x3"],
                "dim": [1, 1, 1],
                "column_index": [0, 6, 7],
            },
            "graph_features": {
                "name": ["sum_x_x2_x3"], "dim": [1], "column_index": [0],
            },
        },
        "NeuralNetwork": {
            "Architecture": {
                "model_type": "GIN",
                "radius": 2.0,
                "max_neighbours": 100,
                "hidden_dim": 8,
                "num_conv_layers": 2,
                "output_heads": {
                    "graph": {
                        "num_sharedlayers": 1, "dim_sharedlayers": 5,
                        "num_headlayers": 1, "dim_headlayers": [10],
                    }
                },
                "task_weights": [1.0],
            },
            "Variables_of_interest": {
                "input_node_features": [0],
                "output_names": ["sum_x_x2_x3"],
                "output_index": [0],
                "type": ["graph"],
            },
            "Training": {
                "num_epoch": 1,
                "perc_train": 0.7,
                "loss_function_type": "mse",
                "batch_size": 8,
                "Optimizer": {"type": "AdamW", "learning_rate": 0.01},
            },
        },
        "Visualization": {"create_plots": False},
    }
    train_loader, val_loader, test_loader, config = prepare_loaders_and_config(config)
    assert train_loader.num_samples == counts["train"]
    assert val_loader.num_samples == counts["validate"]
    assert test_loader.num_samples == counts["test"]


def pytest_config_gated_profiler_writes_trace(tmp_path):
    """NeuralNetwork.Profile.enable drives an epoch-gated jax.profiler
    trace from the train loop (reference: train_validate_test.py:99-101)."""
    import glob

    from hydragnn_tpu.api import run_training
    from hydragnn_tpu.data.synthetic import deterministic_graph_data

    # 200 configs -> ~140 train samples -> 18 batches/epoch, comfortably
    # above the profiler schedule's wait+warmup+active = 11 steps
    samples = deterministic_graph_data(number_configurations=200)
    config = {
        "Verbosity": {"level": 0},
        "Dataset": {
            "name": "prof",
            "format": "unit_test",
            "node_features": {"name": ["x", "x2", "x3"], "dim": [1, 1, 1],
                              "column_index": [0, 6, 7]},
            "graph_features": {"name": ["sum"], "dim": [1], "column_index": [0]},
        },
        "NeuralNetwork": {
            "Profile": {"enable": 1, "target_epoch": 1},
            "Architecture": {
                "model_type": "GIN", "radius": 2.0, "max_neighbours": 100,
                "hidden_dim": 8, "num_conv_layers": 1,
                "output_heads": {"graph": {"num_sharedlayers": 1,
                    "dim_sharedlayers": 5, "num_headlayers": 1,
                    "dim_headlayers": [10]}},
                "task_weights": [1.0],
            },
            "Variables_of_interest": {
                "input_node_features": [0], "output_names": ["sum"],
                "output_index": [0], "type": ["graph"],
            },
            "Training": {
                "num_epoch": 2, "perc_train": 0.7, "loss_function_type": "mse",
                "batch_size": 8, "Optimizer": {"type": "AdamW", "learning_rate": 0.01},
            },
        },
        "Visualization": {"create_plots": False},
    }
    run_training(config, samples=samples, log_dir=str(tmp_path) + "/logs/")
    artifacts = glob.glob(
        str(tmp_path) + "/logs/**/profile/**/*", recursive=True
    )
    assert artifacts, "Profile.enable must produce profiler artifacts"


def pytest_print_peak_memory_smoke(capsys):
    """print_peak_memory (reference: hydragnn/utils/distributed.py:236-243)
    must return the peak byte count where the backend exposes memory_stats
    and None (silently) where it doesn't — never raise. It's wired into
    train_validate_test after epoch 0."""
    from hydragnn_tpu.utils.print_utils import print_peak_memory

    peak = print_peak_memory(verbosity_level=4, prefix="smoke")
    out = capsys.readouterr().out
    if peak is None:
        assert "peak device memory" not in out
    else:
        assert peak >= 0
        assert "peak device memory" in out


def pytest_remat_step_matches_plain(small_problem):
    """Training.remat trades FLOPs for memory; it must be numerically a
    no-op: one rematerialized step produces the same loss and parameter
    update as the plain step."""
    import jax

    cfg, model, variables, example = small_problem
    tx = select_optimizer({"Optimizer": {"type": "AdamW", "learning_rate": 0.01}})

    results = []
    for remat in (False, True):
        state = create_train_state(variables, tx, seed=0)
        step = make_train_step(model, tx, remat=remat)
        state, loss, tasks = step(state, example)
        results.append((float(loss), state.params))
    assert np.isfinite(results[0][0])
    np.testing.assert_allclose(results[0][0], results[1][0], rtol=1e-6)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-6, atol=1e-7
        ),
        results[0][1],
        results[1][1],
    )


def pytest_grad_accum_steps(small_problem):
    """Training.grad_accum_steps=k must hold parameters fixed for k-1
    micro-steps, apply the averaged update on the k-th, and keep the
    dynamic-LR plumbing (plateau scheduler) working through the wrapper."""
    import jax

    from hydragnn_tpu.train.optimizer import (
        current_learning_rate,
        set_learning_rate,
    )

    cfg, model, variables, example = small_problem
    tx = select_optimizer(
        {"Optimizer": {"type": "SGD", "learning_rate": 0.05}, "grad_accum_steps": 2}
    )
    state = create_train_state(variables, tx, seed=0)
    step = make_train_step(model, tx)
    p0 = jax.device_get(state.params)

    state, loss1, _ = step(state, example)
    p1 = jax.device_get(state.params)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a), np.asarray(b)),
        p0,
        p1,
    )  # micro-step 1: accumulate only

    state, loss2, _ = step(state, example)
    p2 = jax.device_get(state.params)
    changed = any(
        not np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(
            jax.tree_util.tree_leaves(p1), jax.tree_util.tree_leaves(p2)
        )
    )
    assert changed, "second micro-step must apply the accumulated update"
    assert np.isfinite(float(loss1)) and np.isfinite(float(loss2))

    # LR read/write through the MultiSteps wrapper
    assert current_learning_rate(state.opt_state) == pytest.approx(0.05)
    state = state.replace(opt_state=set_learning_rate(state.opt_state, 0.025))
    assert current_learning_rate(state.opt_state) == pytest.approx(0.025)


def pytest_scan_epoch_matches_sequential(small_problem):
    """One scan-epoch dispatch must produce the same final params and
    weighted loss as stepping the same batches sequentially."""
    import jax
    import jax.numpy as jnp

    from hydragnn_tpu.train import make_scan_epoch

    cfg, model, variables, _ = small_problem
    samples = deterministic_graph_data(number_configurations=40, seed=3)
    train, _, _, _, _ = prepare_dataset(samples, base_config(multihead=False))
    loader = GraphLoader(train, 8, shuffle=False)
    tx = select_optimizer({"Optimizer": {"type": "AdamW", "learning_rate": 0.01}})

    # sequential
    state_seq = create_train_state(variables, tx, seed=0)
    step = make_train_step(model, tx)
    losses_seq, counts = [], []
    for batch in loader:
        state_seq, loss, _ = step(state_seq, batch)
        losses_seq.append(float(loss))
        counts.append(float(np.asarray(batch.graph_mask).sum()))

    # one scan dispatch
    state_scan = create_train_state(variables, tx, seed=0)
    scan_fn = make_scan_epoch(model, tx)
    stacked = loader.stacked_device_batches()
    order = jnp.arange(len(loader), dtype=jnp.int32)
    state_scan, losses, tasks, cnts = scan_fn(state_scan, stacked, order)

    np.testing.assert_allclose(np.asarray(losses), losses_seq, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(cnts), counts)
    for a, b in zip(
        jax.tree_util.tree_leaves(jax.device_get(state_seq.params)),
        jax.tree_util.tree_leaves(jax.device_get(state_scan.params)),
    ):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


def pytest_scan_epoch_run_training(tmp_path):
    """Training.scan_epoch=True through the full run_training pipeline:
    converges like the streaming path and writes the same artifacts."""
    from hydragnn_tpu.api import run_training
    from test_train_e2e import make_config

    config = make_config("GIN", False, str(tmp_path), num_epoch=12)
    config["NeuralNetwork"]["Training"]["scan_epoch"] = True
    samples = deterministic_graph_data(number_configurations=120, seed=0)
    _, _, history, _ = run_training(
        config, samples=samples, log_dir=str(tmp_path) + "/logs/"
    )
    losses = history["train_loss"]
    assert all(np.isfinite(losses))
    assert min(losses) < 0.5 * losses[0], losses


def pytest_scan_eval_matches_sequential(small_problem):
    """One scan-eval dispatch must equal per-batch evaluation."""
    from hydragnn_tpu.train import make_eval_step
    from hydragnn_tpu.train.state import make_scan_eval
    from hydragnn_tpu.train.loop import evaluate_epoch, evaluate_epoch_scan

    cfg, model, variables, _ = small_problem
    samples = deterministic_graph_data(number_configurations=40, seed=3)
    train, _, _, _, _ = prepare_dataset(samples, base_config(multihead=False))
    loader = GraphLoader(train, 8, shuffle=False)
    tx = select_optimizer({"Optimizer": {"type": "AdamW", "learning_rate": 0.01}})
    state = create_train_state(variables, tx, seed=0)

    seq_loss, seq_tasks = evaluate_epoch(loader, state, make_eval_step(model))
    scan_loss, scan_tasks = evaluate_epoch_scan(loader, state, make_scan_eval(model))
    np.testing.assert_allclose(scan_loss, seq_loss, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(scan_tasks, seq_tasks, rtol=1e-5, atol=1e-6)


def pytest_checkpoint_resume_exact(tmp_path):
    """Per-epoch checkpointing (Training.checkpoint_every) + continue must
    resume EXACTLY: an interrupted-at-3-then-resumed-to-6 run reproduces
    the uninterrupted 6-epoch run's history and parameters (rng chain,
    epoch-seeded shuffles, scheduler and early-stop counters all survive
    the restart). The reference restores only model+optimizer and restarts
    epoch numbering (SURVEY §5)."""
    from hydragnn_tpu.api import run_training
    from hydragnn_tpu.utils.config import get_log_name_config
    from test_train_e2e import make_config

    def fresh_samples():
        # the ingest pipeline mutates the sample list in place; every run
        # gets an identical fresh copy (same seed)
        return deterministic_graph_data(number_configurations=80, seed=0)

    def cfg_for(num_epoch):
        c = make_config("GIN", False, str(tmp_path), num_epoch=num_epoch)
        t = c["NeuralNetwork"]["Training"]
        t["bn_recalibration"] = False  # final recal would diverge from the mid-run save
        t["checkpoint_every"] = 1
        return c

    # uninterrupted reference run
    _, state_a, hist_a, _ = run_training(
        cfg_for(6), samples=fresh_samples(), log_dir=str(tmp_path) + "/a/"
    )

    # interrupted at 3 ...
    _, _, hist_b, full_b = run_training(
        cfg_for(3), samples=fresh_samples(), log_dir=str(tmp_path) + "/b/"
    )
    name_b = get_log_name_config(full_b)

    # ... resumed to 6 in the same log dir
    cfg_c = cfg_for(6)
    cfg_c["NeuralNetwork"]["Training"]["continue"] = 1
    cfg_c["NeuralNetwork"]["Training"]["startfrom"] = name_b
    _, state_c, hist_c, _ = run_training(
        cfg_c, samples=fresh_samples(), log_dir=str(tmp_path) + "/b/"
    )

    assert len(hist_c["train_loss"]) == 6
    np.testing.assert_allclose(hist_c["train_loss"][:3], hist_b["train_loss"], rtol=1e-6)
    np.testing.assert_allclose(
        hist_c["train_loss"], hist_a["train_loss"], rtol=1e-5, atol=1e-7
    )
    for a, b in zip(
        jax.tree_util.tree_leaves(jax.device_get(state_a.params)),
        jax.tree_util.tree_leaves(jax.device_get(state_c.params)),
    ):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-7)


def pytest_device_stack_fallback_warns():
    """A batch size that doesn't divide the local device count must fall
    back to single-device LOUDLY (silent 8x throughput loss otherwise)."""
    from hydragnn_tpu.api import _choose_device_stack

    n_local = jax.local_device_count()
    assert n_local > 1  # conftest pins the 8-device CPU mesh

    cfg = {"NeuralNetwork": {"Training": {"batch_size": n_local + 1}}}
    with pytest.warns(RuntimeWarning, match="SINGLE-DEVICE"):
        assert _choose_device_stack(cfg) == 1

    cfg_ok = {"NeuralNetwork": {"Training": {"batch_size": 2 * n_local}}}
    assert _choose_device_stack(cfg_ok) == n_local


def pytest_scan_reshuffle_membership():
    """scan_reshuffle_every=k rebuilds sample-to-batch membership every k
    epochs (reference DataLoader(shuffle=True) parity for the scan path);
    the default keeps the one-time stack."""
    samples = deterministic_graph_data(number_configurations=40, seed=3)
    train, _, _, _, _ = prepare_dataset(samples, base_config(multihead=False))

    frozen = GraphLoader(train, 8, shuffle=True)
    s0 = frozen.stacked_device_batches(0)
    s1 = frozen.stacked_device_batches(1)
    assert s0 is s1  # built once, membership fixed

    reshuf = GraphLoader(train, 8, shuffle=True, scan_reshuffle_every=1)
    r0 = reshuf.stacked_device_batches(0)
    r1 = reshuf.stacked_device_batches(1)
    assert r0 is not r1
    assert not np.array_equal(np.asarray(r0.nodes), np.asarray(r1.nodes))
    # same epoch -> same membership (cached, no rebuild churn)
    assert reshuf.stacked_device_batches(1) is r1
    # every sample appears exactly once regardless of membership shuffle
    for st in (r0, r1):
        n_real = int(np.asarray(st.node_mask).sum())
        assert n_real == sum(s.num_nodes for s in train)


def pytest_resume_noop_is_pure(tmp_path):
    """Resuming a completed run (start_epoch >= num_epoch) must not touch
    the saved checkpoint: no BN recalibration, no rewrite."""
    import os

    from hydragnn_tpu.api import run_training
    from hydragnn_tpu.utils.config import get_log_name_config
    from test_train_e2e import make_config

    def fresh_samples():
        return deterministic_graph_data(number_configurations=80, seed=0)

    cfg = make_config("GIN", False, str(tmp_path), num_epoch=3)
    cfg["NeuralNetwork"]["Training"]["checkpoint_every"] = 1
    _, _, hist, full = run_training(
        cfg, samples=fresh_samples(), log_dir=str(tmp_path) + "/logs/"
    )
    name = get_log_name_config(full)
    model_files = [
        os.path.join(str(tmp_path), "logs", name, f)
        for f in os.listdir(os.path.join(str(tmp_path), "logs", name))
        if f.endswith((".msgpack", ".meta.json"))
    ]
    assert model_files
    before = {p: open(p, "rb").read() for p in model_files}

    cfg2 = make_config("GIN", False, str(tmp_path), num_epoch=3)
    cfg2["NeuralNetwork"]["Training"]["checkpoint_every"] = 1
    cfg2["NeuralNetwork"]["Training"]["continue"] = 1
    cfg2["NeuralNetwork"]["Training"]["startfrom"] = name
    _, _, hist2, _ = run_training(
        cfg2, samples=fresh_samples(), log_dir=str(tmp_path) + "/logs/"
    )
    assert len(hist2["train_loss"]) == len(hist["train_loss"])
    for p, content in before.items():
        assert open(p, "rb").read() == content, f"no-op resume rewrote {p}"


def pytest_resume_reads_the_parents_sidecar(tmp_path):
    """A checkpoint and loop-state sidecar written by the code before
    ``LoopState`` owned the format (commit 17bd3b9: ``make_config("GIN",
    False, ..., num_epoch=2)`` with ``checkpoint_every=1, EarlyStopping=True,
    patience=7`` over ``deterministic_graph_data(80, seed=0)``; kept under
    tests/data/loop_sidecar_parent/) resumes under today's: same keys read,
    same keys written."""
    import json
    import os
    import shutil

    from hydragnn_tpu.api import run_training
    from hydragnn_tpu.obs.flight import read_flight_record
    from hydragnn_tpu.utils.config import get_log_name_config
    from test_train_e2e import make_config

    src = os.path.join(os.path.dirname(__file__), "data", "loop_sidecar_parent")
    logs = os.path.join(str(tmp_path), "logs")
    shutil.copytree(src, os.path.join(logs, "parent_run"))
    old = json.load(open(os.path.join(logs, "parent_run", "parent_run.meta.json")))
    history_keys = {"train_loss", "val_loss", "test_loss", "train_tasks", "val_tasks",
                    "test_tasks", "lr"}
    assert set(old) == {"epoch", "step", "early_stopped", "scheduler", "stopper", "history",
                        "format_version"}
    assert set(old["scheduler"]) == {"best", "num_bad_epochs"}
    assert set(old["stopper"]) == {"count", "min_loss"}
    assert set(old["history"]) == history_keys and old["epoch"] == 2

    cfg = make_config("GIN", False, str(tmp_path), num_epoch=4)
    cfg["NeuralNetwork"]["Training"].update(
        checkpoint_every=1, EarlyStopping=True, patience=7, startfrom="parent_run",
        **{"continue": 1},
    )
    _, _, hist, full = run_training(
        cfg, samples=deterministic_graph_data(number_configurations=80, seed=0),
        log_dir=logs + "/",
    )
    for k in history_keys:
        assert len(hist[k]) == 4 and hist[k][:2] == old["history"][k]
    name = get_log_name_config(full)
    events = read_flight_record(os.path.join(logs, name, "flight.jsonl"))
    assert [e["epoch"] for e in events if e["kind"] == "resumed"] == [2]
    assert [e["epoch"] for e in events if e["kind"] == "epoch"] == [2, 3]
    new = json.load(open(os.path.join(logs, name, f"{name}.meta.json")))
    assert set(new) == set(old) and new["format_version"] == old["format_version"]
    for block in ("scheduler", "stopper", "history"):
        assert set(new[block]) == set(old[block])
    assert new["epoch"] == 4 and new["step"] == 2 * old["step"] and not new["early_stopped"]
    # the plateau scheduler went on from the restored best, not from infinity
    assert new["scheduler"]["best"] <= old["scheduler"]["best"]


def pytest_meta_step_mismatch_rederives_epoch(tmp_path):
    """A meta sidecar older than the weights (crash between the two
    writes) must not replay epochs on the newer weights: resume derives
    the epoch from the weights' optimizer step instead."""
    import json
    import os

    from hydragnn_tpu.api import run_training
    from hydragnn_tpu.utils.config import get_log_name_config
    from test_train_e2e import make_config

    def fresh_samples():
        return deterministic_graph_data(number_configurations=80, seed=0)

    cfg = make_config("GIN", False, str(tmp_path), num_epoch=4)
    cfg["NeuralNetwork"]["Training"]["checkpoint_every"] = 1
    cfg["NeuralNetwork"]["Training"]["bn_recalibration"] = False
    _, state, hist, full = run_training(
        cfg, samples=fresh_samples(), log_dir=str(tmp_path) + "/logs/"
    )
    name = get_log_name_config(full)
    meta_path = os.path.join(str(tmp_path), "logs", name, f"{name}.meta.json")
    meta = json.load(open(meta_path))

    # simulate the crash: meta describes epoch 2 / half the steps, while
    # the weight file stays at its final (epoch-4) state
    meta["epoch"] = 2
    meta["step"] = meta["step"] // 2
    meta["history"] = {k: v[:2] for k, v in meta["history"].items()}
    json.dump(meta, open(meta_path, "w"))

    cfg2 = make_config("GIN", False, str(tmp_path), num_epoch=4)
    cfg2["NeuralNetwork"]["Training"]["checkpoint_every"] = 1
    cfg2["NeuralNetwork"]["Training"]["bn_recalibration"] = False
    cfg2["NeuralNetwork"]["Training"]["continue"] = 1
    cfg2["NeuralNetwork"]["Training"]["startfrom"] = name
    _, state2, hist2, _ = run_training(
        cfg2, samples=fresh_samples(), log_dir=str(tmp_path) + "/logs/"
    )
    # epoch re-derived from the weights' step (4 full epochs) -> no replay
    for a, b in zip(
        jax.tree_util.tree_leaves(jax.device_get(state.params)),
        jax.tree_util.tree_leaves(jax.device_get(state2.params)),
    ):
        np.testing.assert_array_equal(a, b)
    # history re-aligned to the derived epoch and the sidecar repaired
    assert len(hist2["train_loss"]) == 4
    repaired = json.load(open(meta_path))
    assert repaired["epoch"] == 4
    assert repaired["step"] == meta["step"] * 2
