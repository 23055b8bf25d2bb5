"""Unified Partitioner tests (hydragnn_tpu/parallel/partitioner.py) on
the forced 8-device CPU host mesh (conftest pins
``--xla_force_host_platform_device_count=8``): mesh composition with
size-1 auto-collapse, FSDP parameter+optimizer sharding that bit-matches
the replicated data-parallel reference, per-device memory accounting,
the replicated-leaf loudness contract, serve warmup under a partitioner
mesh with zero post-warmup compile misses, and the scan-eligibility
"partitioner says single-device" path. docs/PARALLELISM.md is the prose
companion of these contracts.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from hydragnn_tpu.data.ingest import prepare_dataset
from hydragnn_tpu.data.loader import GraphLoader
from hydragnn_tpu.data.synthetic import deterministic_graph_data
from hydragnn_tpu.models.create import create_model_config
from hydragnn_tpu.parallel import FSDP_AXIS, ParallelConfig, Partitioner
from hydragnn_tpu.train import create_train_state, select_optimizer
from hydragnn_tpu.utils.config import update_config

from test_data_pipeline import base_config

D = 8  # virtual devices from conftest


def _is_fsdp_sharded(leaf) -> bool:
    spec = leaf.sharding.spec
    return any(
        e == FSDP_AXIS or (isinstance(e, tuple) and FSDP_AXIS in e)
        for e in spec
        if e is not None
    )


def _shardable(leaf, fsdp: int) -> bool:
    return any(d > 0 and d % fsdp == 0 for d in leaf.shape)


@pytest.fixture(scope="module")
def problem():
    cfg = base_config(multihead=True)
    arch = cfg["NeuralNetwork"]["Architecture"]
    arch["model_type"] = "GIN"
    # fsdp-friendly widths: hidden/head dims divisible by the test's
    # fsdp factors so the sharding coverage (and the >=3x per-device
    # byte drop) is dominated by shardable leaves, like a real config
    arch["hidden_dim"] = 16
    arch["output_heads"]["graph"]["dim_sharedlayers"] = 8
    arch["output_heads"]["graph"]["dim_headlayers"] = [16, 16]
    arch["output_heads"]["node"]["dim_headlayers"] = [8, 8]
    cfg["NeuralNetwork"]["Training"]["batch_size"] = 16
    samples = deterministic_graph_data(number_configurations=64, seed=7)
    train, val, test, _, _ = prepare_dataset(samples, cfg)
    cfg = update_config(cfg, train, val, test)
    loader = GraphLoader(train, 16, shuffle=False, device_stack=D, drop_last=True)
    example = jax.tree_util.tree_map(lambda x: x[0], next(iter(loader)))
    model, variables = create_model_config(cfg["NeuralNetwork"], example)
    return cfg, model, variables, loader


# ---------------------------------------------------------------------------
# mesh composition
# ---------------------------------------------------------------------------


def pytest_mesh_composition_and_auto_collapse():
    p = Partitioner(data=8)
    assert p.axis_names == ("data",)
    assert dict(p.mesh.shape) == {"data": 8}
    assert p.batch_sharding().spec == P("data")
    assert not p.single_device and p.device_stack == 8

    p = Partitioner(data=2, fsdp=4)
    assert p.axis_names == ("data", "fsdp")
    assert p.lead_spec == ("data", "fsdp")
    assert p.fsdp_factor == 4 and p.device_stack == 8

    # size-1 axes collapse out of the mesh entirely
    p = Partitioner(fsdp=8)
    assert p.axis_names == ("fsdp",) and p.lead_spec == "fsdp"
    p = Partitioner(data=2, fsdp=2, edge=2)
    assert p.axis_names == ("data", "fsdp", "edge")

    # the degenerate config is the single-device story
    p = Partitioner()
    assert p.single_device and p.mesh is None and p.device_stack == 1
    assert p.batch_sharding() is None

    with pytest.raises(ValueError):
        ParallelConfig(data=0)
    with pytest.raises(ValueError):
        Partitioner(data=16)  # more devices than the host mesh has


def pytest_from_config_knobs():
    nn = {"Parallel": {"fsdp": 2}, "Training": {"Optimizer": {}}}
    p = Partitioner.from_config(nn, device_stack=8)
    assert p.config.data == 4 and p.config.fsdp == 2

    # fsdp must divide the batch device axis
    with pytest.raises(ValueError):
        Partitioner.from_config(
            {"Parallel": {"fsdp": 3}, "Training": {}}, device_stack=8
        )

    # ZeRO-1 is subsumed by (and ignored under) fsdp > 1
    nn = {
        "Parallel": {"fsdp": 2},
        "Training": {"Optimizer": {"use_zero_redundancy": True}},
    }
    assert Partitioner.from_config(nn, device_stack=8).config.zero1 is False
    nn = {"Training": {"Optimizer": {"use_zero_redundancy": True}}}
    assert Partitioner.from_config(nn, device_stack=8).config.zero1 is True


# ---------------------------------------------------------------------------
# FSDP training: parity with replicated DP + committed shardings
# ---------------------------------------------------------------------------


def pytest_fsdp_train_matches_replicated_dp(problem):
    """fsdp=2 and fsdp=4 train steps match the replicated data=8
    reference (same devices, same pmean — only the state layout and
    collective reduction order differ, hence the tolerance), and every
    shardable parameter AND optimizer leaf is committed-sharded over the
    fsdp axis (asserted from the NamedShardings, not inferred)."""
    cfg, model, variables, loader = problem
    tx = select_optimizer({"Optimizer": {"type": "AdamW", "learning_rate": 0.01}})
    batches = list(loader)[:3]

    ref = Partitioner(data=D)
    state_ref = ref.shard_init(create_train_state(variables, tx, seed=0))
    step_ref = ref.shard_train_step(model, tx)
    ref_losses = []
    for b in batches:
        state_ref, loss, _ = step_ref(state_ref, b)
        ref_losses.append(float(loss))
    ref_params = jax.device_get(state_ref.params)

    for fsdp in (2, 4):
        part = Partitioner(data=D // fsdp, fsdp=fsdp)
        state = part.shard_init(create_train_state(variables, tx, seed=0))
        man = part.manifest(state=state)
        reported = set(man["replicated_leaves"])
        # committed shardings: every shardable leaf carries the fsdp
        # axis; the rest are accounted for in replicated_leaves
        for section, tree in (
            ("params", state.params),
            ("opt_state", state.opt_state),
        ):
            flat = jax.tree_util.tree_leaves_with_path(tree)
            for path, leaf in flat:
                if not hasattr(leaf, "sharding") or leaf.ndim == 0:
                    continue
                if _shardable(leaf, fsdp):
                    assert _is_fsdp_sharded(leaf), (
                        fsdp,
                        section,
                        jax.tree_util.keystr(path),
                        leaf.shape,
                    )
                elif int(np.prod(leaf.shape)) > 1:
                    assert section + jax.tree_util.keystr(path) in reported

        step = part.shard_train_step(model, tx)
        losses = []
        for b in batches:
            state, loss, _ = step(state, b)
            losses.append(float(loss))
        # documented reduction-order tolerance (hierarchical psum over
        # (data, fsdp) vs flat psum over data)
        np.testing.assert_allclose(losses, ref_losses, rtol=2e-5)
        for a, b in zip(
            jax.tree_util.tree_leaves(ref_params),
            jax.tree_util.tree_leaves(jax.device_get(state.params)),
        ):
            np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-5)
        # the updated state keeps the committed fsdp layout (no silent
        # re-replication across donated steps)
        n_sharded = sum(
            _is_fsdp_sharded(l)
            for l in jax.tree_util.tree_leaves(state.params)
            if hasattr(l, "sharding")
        )
        assert n_sharded == man["params"]["sharded"] > 0


def pytest_fsdp_memory_drop_at_least_3x(problem):
    """The acceptance criterion: fsdp=4 drops per-device param+optimizer
    bytes >=3x vs the replicated layout, as reported by the same
    manifest block the flight record carries."""
    cfg, model, variables, loader = problem
    tx = select_optimizer({"Optimizer": {"type": "AdamW", "learning_rate": 0.01}})
    state = create_train_state(variables, tx)

    rep = Partitioner(data=D).manifest(state=state)
    rep_dev = rep["params"]["bytes_per_device"] + rep["opt"]["bytes_per_device"]
    assert rep_dev == rep["params"]["bytes_global"] + rep["opt"]["bytes_global"]

    part = Partitioner(data=2, fsdp=4)
    man = part.manifest(state=state)
    f_dev = man["params"]["bytes_per_device"] + man["opt"]["bytes_per_device"]
    assert f_dev * 3 <= rep_dev, (f_dev, rep_dev)
    assert man["params"]["sharded"] > 0 and man["opt"]["sharded"] > 0


def pytest_fsdp_eval_and_stats_parity(problem):
    cfg, model, variables, loader = problem
    tx = select_optimizer({"Optimizer": {"type": "AdamW", "learning_rate": 0.01}})
    batch = next(iter(loader))

    ref = Partitioner(data=D)
    state_ref = ref.shard_init(create_train_state(variables, tx, seed=0))
    loss_ref, tasks_ref = ref.shard_eval_step(model)(state_ref, batch)

    part = Partitioner(data=2, fsdp=4)
    state = part.shard_init(create_train_state(variables, tx, seed=0))
    loss, tasks = part.shard_eval_step(model)(state, batch)
    np.testing.assert_allclose(float(loss), float(loss_ref), rtol=1e-5)
    np.testing.assert_allclose(
        np.asarray(tasks), np.asarray(tasks_ref), rtol=1e-5
    )

    # with_outputs keeps the device-concatenated contract test_epoch needs
    loss2, _, outputs = part.shard_eval_step(model, with_outputs=True)(
        state, batch
    )
    assert np.asarray(outputs[0]).shape[0] == batch.graph_mask.shape[0] * (
        batch.graph_mask.shape[1]
    )

    # BN recalibration runs and stays finite under the fsdp layout
    state = part.shard_stats_step(model)(state, batch)
    for leaf in jax.tree_util.tree_leaves(state.batch_stats):
        assert np.isfinite(np.asarray(leaf)).all()


# ---------------------------------------------------------------------------
# replicated-leaf loudness (the ZeRO-1 silent-replication fix)
# ---------------------------------------------------------------------------


def pytest_replicated_leaves_warn_with_paths():
    from hydragnn_tpu.train.state import TrainState

    state = TrainState(
        step=jnp.zeros((), jnp.int32),
        params={"w": jnp.zeros((8, 8)), "odd": jnp.zeros((3, 5))},
        batch_stats={},
        opt_state={"mu": {"w": jnp.zeros((8, 8)), "odd": jnp.zeros((3, 5))}},
        rng=jax.random.PRNGKey(0),
    )
    part = Partitioner(data=2, fsdp=4)
    with pytest.warns(RuntimeWarning, match="REPLICATED"):
        placed = part.shard_init(state)
    man = part.manifest(state=state)
    assert "params['odd']" in man["replicated_leaves"]
    assert "opt_state['mu']['odd']" in man["replicated_leaves"]
    assert _is_fsdp_sharded(placed.params["w"])
    assert not _is_fsdp_sharded(placed.params["odd"])
    # the warning is once-per-partitioner, not once-per-placement
    import warnings as _w

    with _w.catch_warnings():
        _w.simplefilter("error")
        part.shard_init(state)


def pytest_zero1_replication_warns_with_paths(problem):
    """The legacy ZeRO-1 path inherits the loudness contract: a
    non-divisible first axis logs one rank-0 warning naming the leaf."""
    import hydragnn_tpu.parallel.sharded as sharded_mod

    cfg, model, variables, loader = problem
    tx = select_optimizer({"Optimizer": {"type": "AdamW", "learning_rate": 0.01}})
    state = create_train_state(variables, tx)
    part = Partitioner(data=D, zero1=True)
    with pytest.warns(RuntimeWarning, match="REPLICATED"):
        part.shard_init(state)
    man = part.manifest(state=state)
    # every reported path names an optimizer leaf
    assert man["replicated_leaves"]
    assert all(p.startswith("opt_state") for p in man["replicated_leaves"])

    # the legacy entry point (place_state(zero1=True)) warns too
    from hydragnn_tpu.parallel import place_state

    sharded_mod._warned_zero1_replicated = False
    with pytest.warns(RuntimeWarning, match="ZeRO-1.*REPLICATED"):
        place_state(part.mesh, state, zero1=True)
    sharded_mod._warned_zero1_replicated = False


# ---------------------------------------------------------------------------
# composed edge axis
# ---------------------------------------------------------------------------


def pytest_edge_composed_mesh_smoke():
    cfg = base_config(multihead=False)
    cfg["NeuralNetwork"]["Architecture"]["model_type"] = "GIN"
    cfg["NeuralNetwork"]["Training"]["batch_size"] = 8
    samples = deterministic_graph_data(number_configurations=16, seed=3)
    train, _, _, _, _ = prepare_dataset(samples, cfg)
    cfg = update_config(cfg, train, train, train)
    d_data, d_edge = 2, 2
    loader = GraphLoader(
        train, 8, shuffle=False, device_stack=d_data, edge_multiple=d_edge * 8
    )
    example = jax.tree_util.tree_map(lambda x: x[0], next(iter(loader)))
    model, variables = create_model_config(cfg["NeuralNetwork"], example)
    tx = select_optimizer({"Optimizer": {"type": "SGD", "learning_rate": 0.05}})

    part = Partitioner(data=d_data, edge=d_edge)
    part.attach_loader(loader)  # per-field placer: edge leaves split too
    state = part.shard_init(create_train_state(variables, tx, seed=0))
    step = part.shard_train_step(model, tx)
    batch = next(iter(loader))
    assert batch.senders.sharding.spec == P("data", "edge")
    state, loss, _ = step(state, batch)
    assert np.isfinite(float(loss))
    loss_e, tasks_e = part.shard_eval_step(model)(state, batch)
    assert np.isfinite(float(loss_e))
    state = part.shard_stats_step(model)(state, batch)
    for leaf in jax.tree_util.tree_leaves(state.batch_stats):
        assert np.isfinite(np.asarray(leaf)).all()


# ---------------------------------------------------------------------------
# serve warmup under a partitioner mesh
# ---------------------------------------------------------------------------


def pytest_serve_warmup_under_partitioner_mesh():
    """The bucket ladder AOT-compiles under the partitioner's mesh with
    fsdp-sharded served variables; traffic then runs with 0 post-warmup
    compile misses and answers matching the single-device server."""
    from hydragnn_tpu.flagship import build_flagship
    from hydragnn_tpu.serve import ModelRegistry, ModelServer, ServeConfig

    _, model, variables, loader = build_flagship(
        n_samples=24, hidden_dim=8, num_conv_layers=2, batch_size=4,
        unit_cells=(2, 3),
    )
    samples = list(loader.all_samples)
    registry = ModelRegistry()

    served_1dev = registry.register("plain", model, variables)
    part = Partitioner(fsdp=2)
    served_fsdp = registry.register(
        "fsdp", model, variables, partitioner=part
    )
    assert any(
        _is_fsdp_sharded(l)
        for l in jax.tree_util.tree_leaves(served_fsdp.variables["params"])
    )

    sc = ServeConfig(max_batch=4, num_buckets=2, max_delay_ms=2.0)
    with ModelServer(served_1dev, samples, sc) as ref_server:
        ref = ref_server.predict_many(samples[:6], timeout=120)
    with ModelServer(served_fsdp, samples, sc) as server:
        assert server.partitioner is part
        got = server.predict_many(samples[:6], timeout=120)
        snap = server.metrics_snapshot()
        assert snap["compile_misses"] == 0, snap
        # zero-downtime reload reuses the warm fsdp ladder
        server.reload(variables=dict(variables))
        got2 = server.predict(samples[0], timeout=120)
        snap = server.metrics_snapshot()
        assert snap["compile_misses"] == 0 and snap["reloads"] == 1, snap
    for a, b in zip(ref, got):
        for k in a:
            np.testing.assert_allclose(b[k], a[k], rtol=2e-5, atol=1e-6)
    for k in ref[0]:
        np.testing.assert_allclose(got2[k], ref[0][k], rtol=2e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# scan-epoch eligibility: the partitioner is the topology oracle
# ---------------------------------------------------------------------------


def pytest_scan_eligibility_uses_partitioner():
    from hydragnn_tpu.train.loop import _scan_auto_eligible

    cfg = base_config(multihead=False)
    cfg["NeuralNetwork"]["Training"]["batch_size"] = 4
    samples = deterministic_graph_data(number_configurations=8, seed=1)
    train, _, _, _, _ = prepare_dataset(samples, cfg)
    loader = GraphLoader(train, 4, shuffle=False)

    nn = cfg["NeuralNetwork"]
    ok, reason = _scan_auto_eligible(loader, nn, partitioner=Partitioner())
    assert ok, reason
    ok, reason = _scan_auto_eligible(
        loader, nn, partitioner=Partitioner(data=2, fsdp=4)
    )
    assert not ok and "partitioner" in reason
