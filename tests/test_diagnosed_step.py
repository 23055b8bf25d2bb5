"""The diagnosed step is the epoch's first train step (ISSUE 30): where
the loop scans its epochs with the diagnostics on, one program linearises
the task losses once, for the diagnostics and for the update, and the scan
runs the other steps. CPU, float32, diagnostics opted back in."""

import glob

import numpy as np
import optax
import pytest
import jax
import jax.numpy as jnp

from hydragnn_tpu.data.ingest import prepare_dataset
from hydragnn_tpu.data.loader import GraphLoader
from hydragnn_tpu.data.synthetic import deterministic_graph_data
from hydragnn_tpu.models.create import create_model_config
from hydragnn_tpu.obs import HeadDiagnostics, make_diagnostics_step, read_flight_record
from hydragnn_tpu.resilience.sentry import NonFiniteSentry
from hydragnn_tpu.train import create_train_state, select_optimizer
from hydragnn_tpu.train.loop import DispatchPlan, train_epoch_scan
from hydragnn_tpu.train.state import make_diagnosed_first_step, make_scan_epoch
from hydragnn_tpu.utils.config import update_config

from test_data_pipeline import base_config

DIAG_KEYS = {"tasks_loss", "grad_norms", "cosine", "grad_norm_total", "param_norm", "update_norm",
             "update_ratio"}


@pytest.fixture(scope="module")
def problems():
    """Two four-head models over one stack of five batches: GIN (no
    BatchNorm) and PNA (BatchNorm after every conv, so ``batch_stats`` is
    in the state)."""
    out = {}
    for model_type in ("GIN", "PNA"):
        cfg = base_config(multihead=True)
        cfg["NeuralNetwork"]["Architecture"]["model_type"] = model_type
        samples = deterministic_graph_data(number_configurations=40, seed=7)
        train, val, test, _, _ = prepare_dataset(samples, cfg)
        cfg = update_config(cfg, train, val, test)
        loader = GraphLoader(train, 6, shuffle=False)
        model, variables = create_model_config(cfg["NeuralNetwork"], next(iter(loader)))
        out[model_type] = cfg, model, variables, loader
    return out


def _order(nb: int) -> jnp.ndarray:
    return jnp.asarray(np.random.default_rng(3).permutation(nb), jnp.int32)


def _through_first_step(model, tx, variables, stacked, order, guarded):
    """The epoch as ``DispatchPlan.first_step_epoch`` composes it."""
    first = make_diagnosed_first_step(model, tx, guard_nonfinite=guarded)
    scan = make_scan_epoch(model, tx, guard_nonfinite=guarded)
    consec = (jnp.zeros((), jnp.int32),) if guarded else ()
    state, head, *consec, diagnostics = first(create_train_state(variables, tx), stacked, order, *consec)
    return (*scan(state, stacked, order, *consec, head), diagnostics)


def _assert_trees_close(new, ref, rtol=2e-5, atol=1e-7):
    flat_new = jax.tree_util.tree_leaves_with_path(jax.device_get(new))
    flat_ref = jax.tree_util.tree_leaves(jax.device_get(ref))
    assert len(flat_new) == len(flat_ref)
    for (path, a), b in zip(flat_new, flat_ref):
        a, b = np.asarray(a), np.asarray(b)
        if a.dtype.kind == "f":
            scale = max(float(np.abs(b).max()), 1.0) if b.size else 1.0
            np.testing.assert_allclose(a, b, rtol=rtol, atol=atol * scale, err_msg=jax.tree_util.keystr(path))
        else:  # step, rng, Adam's count
            np.testing.assert_array_equal(a, b, err_msg=jax.tree_util.keystr(path))


EPS32 = float(np.finfo(np.float32).eps)


def assert_adam_states_agree_to_rounding(new, ref, lr, steps, b2=0.999, noise=64.0):
    """``new`` and ``ref``: the ``TrainState`` of two Adam(W) runs of ``steps``
    steps whose gradients are the same operations from different programs.
    Rounding moves every gradient element by about ``noise`` ulps of the
    largest gradient element, whatever its own size. Adam divides a gradient
    by its own running magnitude, so an element moves by that noise's SHARE
    of its reference gradient, times the learning rate, a step: nothing where
    the gradient is resolved, the whole step where the reference gradient is
    rounding itself (a bias in front of a BatchNorm: exactly zero in exact
    arithmetic). The reference gradient is read from the reference run's own
    second moment, element by element; no leaf is named. Counters, ``step``
    and ``rng`` are equal; a BatchNorm running mean follows the bias in front
    of it one for one, so a statistic may move as far as a parameter may."""
    new, ref = jax.device_get((new, ref))

    def is_adam(x):
        return isinstance(x, optax.ScaleByAdamState)

    (adam,) = [s for s in jax.tree_util.tree_leaves(ref.opt_state, is_leaf=is_adam) if is_adam(s)]
    tmap = jax.tree_util.tree_map
    ghat = tmap(lambda nu: np.sqrt(np.asarray(nu, np.float64) / (1.0 - b2 ** steps)), adam.nu)
    dg = noise * EPS32 * max(float(g.max()) for g in jax.tree_util.tree_leaves(ghat))
    with np.errstate(divide="ignore"):
        share = tmap(lambda g: np.minimum(1.0, dg / g), ghat)
    tol_p = tmap(lambda p, sh: 4 * EPS32 * np.abs(p) + steps * lr * sh, ref.params, share)
    furthest = max(float(t.max()) for t in jax.tree_util.tree_leaves(tol_p))
    tol_adam = adam._replace(
        count=0,
        mu=tmap(lambda m: 4 * EPS32 * np.abs(m) + dg, adam.mu),
        nu=tmap(lambda v, g: 4 * EPS32 * np.abs(v) + 2 * g * dg + dg * dg, adam.nu, ghat),
    )
    tol = ref.replace(
        step=0,
        rng=np.zeros_like(ref.rng),
        params=tol_p,
        batch_stats=tmap(lambda s: 4 * EPS32 * np.abs(s) + furthest, ref.batch_stats),
        opt_state=tmap(lambda x: tol_adam if is_adam(x) else np.zeros_like(x), ref.opt_state, is_leaf=is_adam),
    )
    for (path, a), b, t in zip(
        jax.tree_util.tree_leaves_with_path(new), jax.tree_util.tree_leaves(ref), jax.tree_util.tree_leaves(tol)
    ):
        over = np.abs(np.asarray(a, np.float64) - np.asarray(b, np.float64)) - t
        assert (over <= 0).all(), f"{jax.tree_util.keystr(path)}: {over.max():.3e} over its tolerance"
    # the rule binds: of the parameters that have a gradient at all, the median
    # is held to under a hundredth of what Adam could have moved it
    sh, g = (np.concatenate([x.ravel() for x in jax.tree_util.tree_leaves(t)]) for t in (share, ghat))
    assert np.median(sh[g > 0]) < 1e-2


# -- (a) the epoch through the new callable is the plain scan's epoch ----------

# AdamW, which both cells run, and momentum SGD. Under SGD every leaf is held
# to float32 rounding; under Adam a gradient that is exactly zero in exact
# arithmetic (a bias in front of a BatchNorm) turns the two programs' different
# roundings of it into full-size moves, and the rule above says where.
_OPTIMIZERS = {"adamw": lambda: optax.adamw(1e-3), "sgd": lambda: optax.sgd(1e-2, momentum=0.9)}


@pytest.mark.parametrize("guarded", [False, True], ids=["unguarded", "guarded"])
@pytest.mark.parametrize("optimizer", ["adamw", "sgd"])
@pytest.mark.parametrize("model_type", ["GIN", "PNA"])
def test_first_step_epoch_equals_plain_scan(problems, model_type, optimizer, guarded):
    _, model, variables, loader = problems[model_type]
    tx = _OPTIMIZERS[optimizer]()
    stacked, order = loader.stacked_device_batches(), _order(len(loader))
    consec = (jnp.zeros((), jnp.int32),) if guarded else ()
    ref = make_scan_epoch(model, tx, guard_nonfinite=guarded)(
        create_train_state(variables, tx), stacked, order, *consec
    )
    *new, diagnostics = _through_first_step(model, tx, variables, stacked, order, guarded)
    assert len(new) == len(ref) == (6 if guarded else 4)
    assert int(new[0].step) == len(loader)
    if model_type == "PNA":
        assert jax.tree_util.tree_leaves(new[0].batch_stats)
    # state (parameters, optimizer state, batch_stats, rng, step), losses,
    # tasks, counts and under the guard bads and consec
    if optimizer == "adamw":
        assert_adam_states_agree_to_rounding(new[0], ref[0], lr=1e-3, steps=len(loader))
        _assert_trees_close(tuple(new[1:]), ref[1:])
    else:
        _assert_trees_close(tuple(new), ref)
    assert set(diagnostics) == DIAG_KEYS


def test_rounding_rule_catches_one_step_of_one_leaf(problems):
    """The rule is no blanket: one learning rate's move of a leaf whose
    gradient is resolved, or a first moment off by a thousandth, fails it."""
    _, model, variables, loader = problems["PNA"]
    tx = optax.adamw(1e-3)
    stacked, order = loader.stacked_device_batches(), _order(len(loader))
    ref = jax.device_get(make_scan_epoch(model, tx)(create_train_state(variables, tx), stacked, order)[0])
    assert_adam_states_agree_to_rounding(ref, ref, lr=1e-3, steps=len(loader))
    kernel = ref.params["graph_shared"]["Dense_0"]["kernel"]
    moved = jax.tree_util.tree_map(lambda x: x + 1e-3 if x is kernel else x, ref.params)
    with pytest.raises(AssertionError, match="graph_shared"):
        assert_adam_states_agree_to_rounding(ref.replace(params=moved), ref, lr=1e-3, steps=len(loader))
    scaled = jax.tree_util.tree_map(lambda x: x * 1.001 if x.dtype.kind == "f" else x, ref.opt_state)
    with pytest.raises(AssertionError, match="mu"):
        assert_adam_states_agree_to_rounding(ref.replace(opt_state=scaled), ref, lr=1e-3, steps=len(loader))


# -- (b) its diagnostics are the observer's, of the step that landed -----------


@pytest.mark.parametrize("model_type", ["GIN", "PNA"])
def test_first_step_diagnostics_equal_observer(problems, model_type):
    cfg, model, variables, loader = problems[model_type]
    tx = optax.adam(1e-3)
    stacked, order = loader.stacked_device_batches(), _order(len(loader))
    state = create_train_state(variables, tx)
    batch = jax.tree_util.tree_map(lambda x: x[int(order[0])], stacked)
    want = jax.device_get(make_diagnostics_step(model, tx)(state, batch))
    new_state, _, got = make_diagnosed_first_step(model, tx)(state, stacked, order)
    got = jax.device_get(got)
    assert set(got) == set(want) == DIAG_KEYS
    for key in sorted(DIAG_KEYS):
        np.testing.assert_allclose(got[key], want[key], rtol=2e-5, atol=1e-7, err_msg=key)
    # Adam's first moment after its first step is 0.1 x the gradient the
    # update was built from
    mu = new_state.opt_state[0].mu
    np.testing.assert_allclose(float(optax.global_norm(mu)) / 0.1, got["grad_norm_total"], rtol=1e-5)


# -- (c) a non-finite first batch under the guard --------------------------------


def test_nonfinite_first_batch_is_skipped_and_counted(problems):
    _, model, variables, loader = problems["GIN"]
    tx = optax.adam(1e-3)
    stacked, nb = loader.stacked_device_batches(), len(loader)
    order = _order(nb)
    poisoned = stacked.replace(nodes=stacked.nodes.at[order[0]].set(jnp.nan).at[order[1]].set(jnp.nan))
    first = make_diagnosed_first_step(model, tx, guard_nonfinite=True)
    state0 = create_train_state(variables, tx)
    before = jax.device_get((state0.params, state0.opt_state, state0.batch_stats, state0.step))
    state, (loss, tasks, count, bad), consec, _ = first(state0, poisoned, order, jnp.zeros((), jnp.int32))
    after = jax.device_get((state.params, state.opt_state, state.batch_stats, state.step))
    for a, b in zip(jax.tree_util.tree_leaves(after), jax.tree_util.tree_leaves(before)):
        np.testing.assert_array_equal(a, b)
    assert (float(bad), float(loss), float(count), int(consec)) == (1.0, 0.0, 0.0, 1)
    assert not np.asarray(tasks).any()

    # the counter goes on into the scan: two bad steps in a row, then good ones
    scan = make_scan_epoch(model, tx, guard_nonfinite=True)
    state, losses, _, counts, bads, consec_end = scan(state, poisoned, order, consec, (loss, tasks, count, bad))
    assert np.asarray(bads).tolist() == [1.0, 1.0] + [0.0] * (nb - 2)
    assert np.asarray(counts)[:2].tolist() == [0.0, 0.0] and (np.asarray(counts)[2:] > 0).all()
    assert int(consec_end) == 0 and int(state.step) == nb - 2
    # and out of it, where the epoch ends on them
    state, (loss, tasks, count, bad), consec, _ = first(
        create_train_state(variables, tx), poisoned, order[:2], jnp.zeros((), jnp.int32)
    )
    *_, consec_end = scan(state, poisoned, order[:2], consec, (loss, tasks, count, bad))
    assert int(consec_end) == 2


# -- (d) through run_training: nothing compiles after epoch 0 --------------------


def _run(tmp_path, monkeypatch, name, diagnostics: bool, **training):
    from hydragnn_tpu.api import run_training
    from test_train_e2e import make_config

    monkeypatch.setenv("HYDRAGNN_TELEMETRY", "1")
    monkeypatch.setenv("HYDRAGNN_DIAGNOSTICS", "1" if diagnostics else "0")
    config = make_config("GIN", True, str(tmp_path), num_epoch=training.pop("num_epoch"))
    # not divisible by the virtual 8-device mesh: the single-device, loop-owned path
    config["NeuralNetwork"]["Training"].update(batch_size=5, **training)
    samples = deterministic_graph_data(number_configurations=30, seed=0)
    log_dir = str(tmp_path / name)
    _, state, _, _ = run_training(config, samples=samples, log_dir=log_dir)
    events = read_flight_record(glob.glob(log_dir + "/*/flight.jsonl")[0])
    manifest = [e for e in events if e.get("kind") == "run_start"][0]["manifest"]
    return state, manifest, [e for e in events if e.get("kind") == "epoch"]


def test_three_shuffled_epochs_compile_nothing_after_epoch_0(tmp_path, monkeypatch):
    _, manifest, epochs = _run(tmp_path, monkeypatch, "on", True, num_epoch=3)
    assert manifest["pad_plans"]["train"]["num_batches"] > 2
    assert manifest["dispatch_mode"]["mode"] == "scan_epoch"
    assert manifest["dispatch_mode"]["diagnostics"]["path"] == "first_step"
    assert manifest["compile_monitor_available"]
    assert epochs[0]["compiles"]["count"] > 0
    for i, e in enumerate(epochs):
        if i:  # the shuffle moves order[0]; the index is a device value
            assert e["compiles"]["count"] == 0 and not e["compiles"]["unexpected"], e["compiles"]
        assert e["diagnosed_steps"] == 1 and e["steps"] == manifest["pad_plans"]["train"]["num_batches"]
        assert e["heads"]["sampled_step"] == i and e["heads"]["grad_norm_total"] > 0
        assert len(e["heads"]["cosine"]) == 4
        assert "train.diag_sample" not in e["phases"] and "train.dispatch" in e["phases"]


def test_diag_every_two_epochs_alternates_with_the_plain_scan(tmp_path, monkeypatch):
    # SGD for the comparison of final states: at make_config's learning rate of
    # 0.01 AdamW walks this model's unresolved gradients (GIN's eps is 100 in
    # front of a BatchNorm) a tenth away in 4 epochs, whichever program rounds
    # them; (a) and test_introspect.py hold AdamW to its rule
    sgd = {"type": "SGD", "learning_rate": 0.01}
    off_state, off_manifest, _ = _run(tmp_path, monkeypatch, "off", False, num_epoch=4, Optimizer=sgd)
    assert off_manifest["dispatch_mode"]["diagnostics"]["path"] == "off"
    nb = off_manifest["pad_plans"]["train"]["num_batches"]
    state, manifest, epochs = _run(
        tmp_path, monkeypatch, "on", True, num_epoch=4, diag_every=2 * nb, Optimizer=sgd
    )
    assert manifest["dispatch_mode"]["diagnostics"]["path"] == "first_step"
    assert manifest["diagnostics"]["diag_every"] == 2
    assert [e["diagnosed_steps"] for e in epochs] == [1, 0, 1, 0]
    assert ["grad_norm" in e["heads"] for e in epochs] == [True, False, True, False]
    # the plain scan is traced in the first epoch that runs it, and never again
    assert epochs[1]["compiles"]["count"] > 0
    assert [e["compiles"]["unexpected"] for e in epochs] == [False] * 4
    assert epochs[2]["compiles"]["count"] == epochs[3]["compiles"]["count"] == 0
    # the parent's programs (diagnostics off: the plain scan every epoch) end
    # where this run ends, to rounding
    _assert_trees_close(state.params, off_state.params, rtol=1e-4, atol=1e-6)
    _assert_trees_close(state.batch_stats, off_state.batch_stats, rtol=1e-4, atol=1e-6)
    assert int(state.step) == int(off_state.step) == 4 * nb


# -- (e) what the loop hands train_epoch_scan keeps benchmark/taps.py's contract --


def test_first_step_epoch_keeps_the_scan_fn_contract(problems):
    cfg, model, variables, loader = problems["GIN"]
    nn = cfg["NeuralNetwork"]
    tx = select_optimizer(nn["Training"])
    plan = DispatchPlan(
        model, tx, nn, (loader, loader, loader), train_step=None, eval_step=None, eval_step_out=None,
        stats_step=None, partitioner=None, profiler=None, verbosity=0,
    )
    assert plan.mode == "scan_epoch" and plan.guard_nonfinite
    assert plan.manifest()["dispatch_mode"]["diagnostics"]["path"] == "off"
    diag = plan.open_diagnostics(model, tx, True, model.cfg.output_names, 0)
    assert plan.manifest()["dispatch_mode"]["diagnostics"]["path"] == "first_step"
    assert isinstance(diag, HeadDiagnostics) and diag.fn is None and diag.every == 1

    nb = len(loader)
    stacked, order = loader.stacked_device_batches(), _order(nb)
    seen = {}

    def capturing(state, stacked, order, *rest):
        # as taps.py calls it: once on a copy, once for real, all extras passed on
        copy = jax.tree_util.tree_map(jnp.copy, state)
        seen["probe"] = plan.first_step_epoch(copy, stacked, order, *rest)
        seen["real"] = out = plan.first_step_epoch(state, stacked, order, *rest)
        return out

    out = plan.first_step_epoch(create_train_state(variables, tx), stacked, order, jnp.zeros((), jnp.int32))
    assert len(out) == 7 and set(out[-1]) == DIAG_KEYS
    assert int(out[0].step) == nb and out[1].shape == (nb,) and out[3].shape == (nb,)
    assert out[2].shape == (nb, 4) and out[4].shape == (nb,) and out[5].shape == ()
    assert float(out[3].sum()) == len(loader.samples)

    sentry = NonFiniteSentry(patience=16, max_rollbacks=2, lr_factor=0.5)
    state, loss, tasks = train_epoch_scan(
        loader, create_train_state(variables, tx), capturing, 0, diag=diag, sentry=sentry
    )
    assert int(state.step) == nb and np.isfinite(loss) and tasks.shape == (4,)
    np.testing.assert_allclose(np.asarray(seen["probe"][1]), np.asarray(seen["real"][1]), rtol=1e-6)
    snap = diag.epoch_snapshot()
    assert snap["sampled_step"] == 0 and set(snap["grad_norm"]) == set(model.cfg.output_names)
    assert sentry.epoch_finalize() == (0, 0)


def test_per_step_and_caller_supplied_plans_keep_the_observer(problems):
    cfg, model, variables, loader = problems["GIN"]
    nn = dict(cfg["NeuralNetwork"], Training=dict(cfg["NeuralNetwork"]["Training"], scan_epoch=False))
    tx = select_optimizer(nn["Training"])
    kw = dict(eval_step=None, eval_step_out=None, stats_step=None, partitioner=None, profiler=None, verbosity=0)
    plan = DispatchPlan(model, tx, nn, (loader, loader, loader), train_step=None, **kw)
    diag = plan.open_diagnostics(model, tx, True, model.cfg.output_names, 0)
    assert plan.manifest()["dispatch_mode"]["diagnostics"]["path"] == "observer"
    assert plan.first_step is None and callable(diag.fn) and diag.every == len(loader)
    assert plan.open_diagnostics(model, tx, False, model.cfg.output_names, 0) is None

    handed = DispatchPlan(model, tx, nn, (loader, loader, loader), train_step=lambda s, b: None, **kw)
    assert handed.open_diagnostics(model, tx, True, model.cfg.output_names, 0) is None
    block = handed.manifest()["dispatch_mode"]["diagnostics"]
    assert block == {"path": "off", "reason": "caller-supplied train step"}
