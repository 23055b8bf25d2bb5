"""Data pipeline tests: generator round-trip, radius graph, normalization,
splitting, loader shapes. Mirrors the reference's unit-test strategy of a
deterministic dataset with known closed-form structure (reference:
tests/deterministic_graph_data.py, tests/test_periodic_boundary_conditions.py)."""

from collections import Counter

import numpy as np
import pytest

from hydragnn_tpu.data.synthetic import deterministic_graph_data, write_lsms_files
from hydragnn_tpu.data.lsms import read_lsms_dir
from hydragnn_tpu.data.radius_graph import radius_graph, radius_graph_pbc, edge_lengths
from hydragnn_tpu.data.ingest import prepare_dataset, build_edges
from hydragnn_tpu.data.loader import GraphLoader
from hydragnn_tpu.data.splitting import split_dataset
from hydragnn_tpu.utils.config import update_config


def base_config(multihead=True):
    voi = (
        {
            "input_node_features": [0],
            "output_names": ["sum_x_x2_x3", "x", "x2", "x3"],
            "output_index": [0, 0, 1, 2],
            "type": ["graph", "node", "node", "node"],
        }
        if multihead
        else {
            "input_node_features": [0],
            "output_names": ["sum_x_x2_x3"],
            "output_index": [0],
            "type": ["graph"],
        }
    )
    return {
        "Dataset": {
            "name": "unit_test",
            "format": "unit_test",
            "compositional_stratified_splitting": True,
            "rotational_invariance": False,
            "node_features": {
                "name": ["x", "x2", "x3"],
                "dim": [1, 1, 1],
                "column_index": [0, 6, 7],
            },
            "graph_features": {
                "name": ["sum_x_x2_x3"],
                "dim": [1],
                "column_index": [0],
            },
        },
        "NeuralNetwork": {
            "Architecture": {
                "model_type": "PNA",
                "radius": 2.0,
                "max_neighbours": 100,
                "periodic_boundary_conditions": False,
                "hidden_dim": 8,
                "num_conv_layers": 2,
                "output_heads": {
                    "graph": {
                        "num_sharedlayers": 2,
                        "dim_sharedlayers": 4,
                        "num_headlayers": 2,
                        "dim_headlayers": [10, 10],
                    },
                    "node": {"num_headlayers": 2, "dim_headlayers": [4, 4], "type": "mlp"},
                },
                "task_weights": [20.0, 1.0, 1.0, 1.0] if multihead else [1.0],
            },
            "Variables_of_interest": voi,
            "Training": {
                "num_epoch": 2,
                "perc_train": 0.7,
                "loss_function_type": "mse",
                "batch_size": 16,
                "Optimizer": {"type": "AdamW", "learning_rate": 0.01},
            },
        },
    }


def pytest_generator_lsms_roundtrip(tmp_path):
    mem = deterministic_graph_data(number_configurations=20, seed=11)
    write_lsms_files(str(tmp_path), number_configurations=20, seed=11)
    cfg = base_config()["Dataset"]
    disk = read_lsms_dir(str(tmp_path), cfg)
    # files sort lexically; match by configuration id
    order = sorted(range(20), key=lambda k: f"output{k}.txt")
    for file_pos, conf_id in enumerate(order):
        np.testing.assert_allclose(disk[file_pos].x, mem[conf_id].x, rtol=1e-6)
        np.testing.assert_allclose(disk[file_pos].pos, mem[conf_id].pos, rtol=1e-6)
        np.testing.assert_allclose(
            disk[file_pos].graph_y, mem[conf_id].graph_y, rtol=1e-6
        )


def pytest_radius_graph_simple():
    # 3 points on a line, spacing 1; r=1.5 connects neighbors only
    pos = np.array([[0.0, 0, 0], [1.0, 0, 0], [2.0, 0, 0]])
    ei = radius_graph(pos, 1.5)
    pairs = set(map(tuple, ei.T))
    assert pairs == {(0, 1), (1, 0), (1, 2), (2, 1)}
    lengths = edge_lengths(pos, ei)
    np.testing.assert_allclose(lengths, np.ones((4, 1)))


def pytest_radius_graph_max_neighbors():
    # hub with 4 spokes at increasing distance; cap keeps the 2 nearest
    pos = np.array(
        [[0.0, 0, 0], [1.0, 0, 0], [0, 1.1, 0], [0, 0, 1.2], [1.3, 0, 0]]
    )
    ei = radius_graph(pos, 2.0, max_num_neighbors=2)
    incoming0 = ei[0][ei[1] == 0]
    assert set(incoming0.tolist()) == {1, 2}


def pytest_radius_graph_brute_vs_celllist():
    rng = np.random.default_rng(0)
    pos = rng.uniform(0, 10, size=(300, 3))  # large enough for cell-list path
    r = 1.2
    ei = radius_graph(pos, r)
    # brute force reference
    diff = pos[:, None] - pos[None, :]
    dist = np.sqrt((diff**2).sum(-1))
    expect = {(j, i) for j in range(300) for i in range(300) if j != i and dist[j, i] <= r}
    assert set(map(tuple, ei.T)) == expect


def pytest_radius_graph_pbc_counts():
    # single atom in a unit cube with r=1: 6 face-neighbor images
    pos = np.zeros((1, 3))
    cell = np.eye(3)
    ei = radius_graph_pbc(pos, 1.0, cell)
    assert ei.shape[1] == 6
    # two atoms: H2-like pair, each sees the other plus its own images
    pos2 = np.array([[0.0, 0, 0], [0.5, 0, 0]])
    ei2 = radius_graph_pbc(pos2, 0.6, np.eye(3) * 1.0)
    # each atom: other atom at 0.5 in both x directions = 2 edges each way
    pairs = [tuple(e) for e in ei2.T]
    assert pairs.count((0, 1)) == 2 and pairs.count((1, 0)) == 2


def pytest_prepare_dataset_normalized_and_packed():
    config = base_config()
    samples = deterministic_graph_data(number_configurations=40, seed=5)
    train, val, test, mm_g, mm_n = prepare_dataset(samples, config)
    for split in (train, val, test):
        for s in split:
            assert 0.0 <= s.x.min() and s.x.max() <= 1.0
            assert s.edge_attr.max() <= 1.0 + 1e-6
            assert set(s.node_targets) == {"x", "x2", "x3"}
            assert set(s.graph_targets) == {"sum_x_x2_x3"}
            assert s.x.shape[1] == 1  # input selection applied


def pytest_update_config_inference():
    config = base_config()
    samples = deterministic_graph_data(number_configurations=40, seed=5)
    train, val, test, _, _ = prepare_dataset(samples, config)
    config = update_config(config, train, val, test)
    arch = config["NeuralNetwork"]["Architecture"]
    assert arch["output_dim"] == [1, 1, 1, 1]
    assert arch["output_type"] == ["graph", "node", "node", "node"]
    assert arch["input_dim"] == 1
    assert arch["max_neighbours"] > 0
    assert arch["pna_deg"] is not None and sum(arch["pna_deg"]) > 0
    assert arch["edge_dim"] is None  # no edge_features declared


def pytest_split_plain_proportions():
    samples = deterministic_graph_data(number_configurations=50, seed=1)
    tr, va, te = split_dataset(samples, 0.7, stratify_splitting=False)
    assert len(tr) == 35 and len(va) == 7 and len(te) == 8


def pytest_stratified_split_covers_categories():
    from hydragnn_tpu.data.splitting import composition_categories

    samples = deterministic_graph_data(number_configurations=200, seed=2)
    tr, va, te = split_dataset(samples, 0.7, stratify_splitting=True)
    cats_all = set(composition_categories(list(samples)))
    cats_train = set(composition_categories(tr))
    # every category with >=2 members must appear in train
    from collections import Counter

    counts = Counter(composition_categories(list(samples)))
    for c, n in counts.items():
        if n >= 2:
            assert c in cats_train


def pytest_loader_fixed_shapes_and_masks():
    config = base_config()
    samples = deterministic_graph_data(number_configurations=40, seed=5)
    train, _, _, _, _ = prepare_dataset(samples, config)
    loader = GraphLoader(train, batch_size=8, shuffle=True, seed=0)
    shapes = set()
    total_real = 0
    for epoch in range(2):
        loader.set_epoch(epoch)
        epoch_real = 0
        for b in loader:
            shapes.add((b.num_nodes, b.num_edges, b.num_graphs))
            epoch_real += int(np.asarray(b.graph_mask).sum())
        assert epoch_real == len(train)
    assert len(shapes) == 1  # one compiled shape for the whole run


def pytest_loader_device_stack():
    config = base_config()
    samples = deterministic_graph_data(number_configurations=40, seed=5)
    train, _, _, _, _ = prepare_dataset(samples, config)
    loader = GraphLoader(train, batch_size=8, device_stack=4)
    seen = 0
    for b in loader:
        assert b.nodes.ndim == 3 and b.nodes.shape[0] == 4
        seen += int(np.asarray(b.graph_mask).sum())
    assert seen == len(train)


def pytest_loader_sharding():
    samples = deterministic_graph_data(number_configurations=41, seed=5)
    build_edges(samples, radius=2.0, max_neighbours=100)
    l0 = GraphLoader(samples, batch_size=8, num_shards=2, shard_rank=0)
    l1 = GraphLoader(samples, batch_size=8, num_shards=2, shard_rank=1)
    # DistributedSampler-style equalization: both shards get ceil(41/2)=21
    # samples (one wraps around) so every host runs the same step count.
    assert l0.num_samples == 21 and l1.num_samples == 21
    assert len(l0) == len(l1) == 3
    assert (l0.pad_nodes, l0.pad_edges) == (l1.pad_nodes, l1.pad_edges)


def pytest_rotational_invariance():
    """Edge sets and edge lengths must be invariant under an arbitrary
    rigid rotation + translation when rotation normalization is applied
    (reference: tests/test_rotational_invariance.py:52-112 — float32 tol
    1e-4, float64 tol 1e-14)."""
    from hydragnn_tpu.data.dataset import GraphSample
    from hydragnn_tpu.data.ingest import normalize_rotation
    from hydragnn_tpu.data.radius_graph import edge_lengths, radius_graph

    rng = np.random.RandomState(13)
    n, radius = 24, 0.9

    def random_rotation():
        q, _ = np.linalg.qr(rng.normal(size=(3, 3)))
        if np.linalg.det(q) < 0:
            q[:, 0] = -q[:, 0]
        return q

    for dtype, tol in ((np.float32, 1e-4), (np.float64, 1e-14)):
        pos = rng.rand(n, 3).astype(dtype)
        rot = (random_rotation() @ pos.astype(np.float64).T).T + rng.normal(size=3)
        s_a = GraphSample(x=np.zeros((n, 1), np.float32), pos=pos.astype(dtype))
        s_b = GraphSample(x=np.zeros((n, 1), np.float32), pos=rot.astype(dtype))
        normalize_rotation([s_a, s_b])
        assert s_a.pos.dtype == dtype  # dtype preserved through normalization

        # normalization must actually ALIGN the copies: same canonical
        # coordinates per node, up to SVD's per-axis sign ambiguity (a
        # broken/no-op normalize_rotation would fail this even though
        # distances below are invariant under any rigid transform)
        pa = s_a.pos.astype(np.float64)
        pb = s_b.pos.astype(np.float64)
        for axis in range(3):
            col_a, col_b = pa[:, axis], pb[:, axis]
            err = min(np.abs(col_a - col_b).max(), np.abs(col_a + col_b).max())
            # coordinates accumulate a few ulps more SVD round-off than
            # the derived edge lengths the reference bounds at `tol`;
            # broken normalization errs at O(1), far above 100x tol
            assert err < 100 * tol, f"axis {axis} not aligned ({dtype}): {err}"

        ei_a = radius_graph(pa, radius)
        ei_b = radius_graph(pb, radius)
        set_a = {(int(u), int(v)) for u, v in ei_a.T}
        set_b = {(int(u), int(v)) for u, v in ei_b.T}
        assert set_a == set_b, f"edge sets differ under rotation ({dtype})"

        # edge lengths in full float64 (the helper casts to f32, which
        # would make the 1e-14 band vacuous)
        len_a = np.sort(np.linalg.norm(pa[ei_a[0]] - pa[ei_a[1]], axis=1))
        len_b = np.sort(np.linalg.norm(pb[ei_b[0]] - pb[ei_b[1]], axis=1))
        np.testing.assert_allclose(len_a, len_b, rtol=tol, atol=tol)


def pytest_rotation_keeps_dimensions_for_tiny_graphs():
    """Graphs with fewer than 3 nodes must keep 3-D positions through
    rotation normalization (regression: reduced SVD projected a 2-node
    graph down to 2-D and broke the in-place write)."""
    from hydragnn_tpu.data.dataset import GraphSample
    from hydragnn_tpu.data.ingest import normalize_rotation

    for n in (1, 2):
        s = GraphSample(
            x=np.zeros((n, 1), np.float32),
            pos=np.arange(3 * n, dtype=np.float32).reshape(n, 3),
        )
        normalize_rotation([s])
        assert s.pos.shape == (n, 3)
        assert np.isfinite(s.pos).all()


def pytest_periodic_bcc_supercell():
    """5x5x5 BCC Cr supercell (a=3.6, radius=5.0): every atom must see
    exactly its 8 first-shell + 6 second-shell periodic neighbors — 14
    without self-loops, 15 with (reference:
    tests/test_periodic_boundary_conditions.py pytest_periodic_bcc_large,
    built there with ase.build; constructed directly here)."""
    a, reps, radius = 3.6, 5, 5.0
    basis = np.array([[0.0, 0.0, 0.0], [a / 2, a / 2, a / 2]])
    shifts = np.array(
        [[i, j, k] for i in range(reps) for j in range(reps) for k in range(reps)]
    ) * a
    pos = (basis[None, :, :] + shifts[:, None, :]).reshape(-1, 3)
    cell = np.eye(3) * (reps * a)
    n = pos.shape[0]
    assert n == 250

    ei = radius_graph_pbc(pos, radius, cell, loop=False)
    assert ei.shape[1] == 14 * n, ei.shape
    ei_loops = radius_graph_pbc(pos, radius, cell, loop=True)
    assert ei_loops.shape[1] == 15 * n, ei_loops.shape


def pytest_stratified_subsample():
    """Variables_of_interest.subsample_percentage downselects with
    composition stratification (reference: stratified_sampling,
    abstractrawdataset.py:412-452): ~the requested fraction overall,
    every multi-member category still represented."""
    from hydragnn_tpu.data.splitting import (
        stratified_subsample,
        subsample_categories,
    )

    samples = deterministic_graph_data(number_configurations=200, seed=2)
    sub = stratified_subsample(list(samples), 0.3)
    assert 0.2 * len(samples) <= len(sub) <= 0.45 * len(samples)
    cats_all = Counter(subsample_categories(list(samples)))
    cats_sub = set(subsample_categories(sub))
    # floor allocation guarantees representation once frac * n >= 1
    for c, n in cats_all.items():
        if 0.3 * n >= 1:
            assert c in cats_sub

    with pytest.raises(ValueError):
        stratified_subsample(list(samples), 0.0)
    assert len(stratified_subsample(list(samples), 1.0)) == len(samples)


def pytest_subsample_through_prepare_dataset():
    config = base_config()
    config["NeuralNetwork"]["Variables_of_interest"]["subsample_percentage"] = 0.5
    # plain split: the stratified splitter would re-inflate the count by
    # duplicating singleton categories (its own reference-parity behavior)
    config["Dataset"]["compositional_stratified_splitting"] = False
    samples = deterministic_graph_data(number_configurations=100, seed=5)
    train, val, test, _, _ = prepare_dataset(samples, config)
    assert len(train) + len(val) + len(test) == 50


def pytest_point_pair_features():
    """PointPairFeatures descriptor (reference usage:
    abstractrawdataset.py:380-383; PyG transform semantics): 4 extra
    edge-attr columns [rho_norm, angle(n_i,d), angle(n_j,d),
    angle(n_i,n_j)], rotation-invariant, requiring meta['norm']."""
    from hydragnn_tpu.data.ingest import build_edges

    samples = deterministic_graph_data(number_configurations=6, seed=3)
    for s in samples:
        rng = np.random.default_rng(s.num_nodes)
        n = rng.normal(size=(s.num_nodes, 3))
        s.meta["norm"] = n / np.linalg.norm(n, axis=1, keepdims=True)
    build_edges(samples, radius=2.0, max_neighbours=100, point_pair_features=True)
    for s in samples:
        assert s.edge_attr.shape[1] == 5  # length + 4 PPF columns
        ppf = s.edge_attr[:, 1:]
        assert (ppf[:, 0] >= 0).all() and (ppf[:, 0] <= 1.0 + 1e-6).all()
        # angles in [0, pi]
        assert (ppf[:, 1:] >= 0).all() and (ppf[:, 1:] <= np.pi + 1e-6).all()
        # angle(n_i, n_j) symmetric in edge direction: the reversed edge
        # (present in an undirected radius graph) has the same value
        fwd = {(int(a), int(b)): v for a, b, v in zip(*s.edge_index, ppf[:, 3])}
        for (a, b), v in fwd.items():
            assert abs(fwd[(b, a)] - v) < 1e-5

    # missing normals is a clear error, not a crash downstream
    bad = deterministic_graph_data(number_configurations=2, seed=3)
    with pytest.raises(ValueError, match="norm"):
        build_edges(bad, radius=2.0, max_neighbours=100, point_pair_features=True)


def pytest_descriptors_grow_edge_dim():
    config = base_config()
    config["NeuralNetwork"]["Architecture"]["model_type"] = "PNA"
    config["NeuralNetwork"]["Architecture"]["edge_features"] = ["lengths"]
    config["Dataset"]["Descriptors"] = {
        "SphericalCoordinates": True,
        "PointPairFeatures": True,
    }
    samples = deterministic_graph_data(number_configurations=30, seed=5)
    for s in samples:
        s.meta["norm"] = np.ones((s.num_nodes, 3), dtype=np.float32) / np.sqrt(3.0)
    train, val, test, _, _ = prepare_dataset(samples, config)
    config = update_config(config, train, val, test)
    assert config["NeuralNetwork"]["Architecture"]["edge_dim"] == 1 + 2 + 4
    for s in train:
        assert s.edge_attr.shape[1] == 1 + 2 + 4

    # the model consumes the widened edge attributes end-to-end
    from hydragnn_tpu.models.create import create_model_config

    loader = GraphLoader(train, 8)
    example = next(iter(loader))
    model, variables = create_model_config(config["NeuralNetwork"], example)
    outputs = model.apply(variables, example, train=False)
    assert all(np.isfinite(np.asarray(o)).all() for o in outputs)

    # descriptors without edge_features: loud config error
    config2 = base_config()
    config2["Dataset"]["Descriptors"] = {"SphericalCoordinates": True}
    samples2 = deterministic_graph_data(number_configurations=30, seed=5)
    train2, val2, test2, _, _ = prepare_dataset(samples2, config2)
    with pytest.raises(ValueError, match="edge_features"):
        update_config(config2, train2, val2, test2)


# ---------------------------------------------------------------------------
# pad plans: cut to the batches that exist where membership is fixed, the
# worst case (pad_plan_for) where every epoch re-draws it
# ---------------------------------------------------------------------------


def _ring_samples(n_samples, lo, hi, degree, seed=11):
    """Heterogeneous graphs: ``lo..hi`` nodes, every node ``degree`` in-edges."""
    from hydragnn_tpu.data.dataset import GraphSample

    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n_samples):
        n = int(rng.integers(lo, hi + 1))
        recv = np.repeat(np.arange(n), degree)
        send = (recv + np.tile(np.arange(1, degree + 1), n)) % n
        out.append(
            GraphSample(
                x=rng.standard_normal((n, 2)).astype(np.float32),
                edge_index=np.stack([send, recv]).astype(np.int32),
                graph_targets={"e": rng.standard_normal(1).astype(np.float32)},
            )
        )
    return out


# how the run consumes the loader -> (constructor arguments, drawing the
# epoch's batches); "stacked" and "reshuffle" are loaders built for scan
_PLAN_PATHS = {
    "iter": dict(),
    "cache": dict(cache_device_batches=True),
    "stacked": dict(fixed_membership=True),
    "reshuffle": dict(fixed_membership=True, scan_reshuffle_every=1),
}


def _membership_is_fixed(shuffle, path):
    return not shuffle or path in ("cache", "stacked")


def _epoch_batches(loader, path, epoch):
    """Every (sub-)batch of one epoch as (real nodes, real edges, graphs)."""
    loader.set_epoch(epoch)
    if path in ("stacked", "reshuffle"):
        st = loader.stacked_device_batches(epoch)
        masks = [np.asarray(m) for m in (st.node_mask, st.edge_mask, st.graph_mask)]
    else:
        batches = list(loader)
        masks = [np.stack([np.asarray(getattr(b, f)) for b in batches])
                 for f in ("node_mask", "edge_mask", "graph_mask")]
    # [batches, (devices,) slots] -> one row a (sub-)batch
    nm, em, gm = (m.reshape(-1, m.shape[-1]) for m in masks)
    return nm.sum(1), em.sum(1), gm.sum(1), nm.shape[1], em.shape[1]


def _expected_plan(samples, bs, stack, num_shards, run_align, fixed, drop_last=False):
    """The plan from first principles: every chunk of every shard, or the
    ``sub`` largest graphs in one batch."""
    import math

    from hydragnn_tpu.data.loader import _aligned_edge_counts, pad_plan_for

    sub = bs // stack
    nodes = [s.num_nodes for s in samples]
    edges = [s.num_edges for s in samples]
    aligned = _aligned_edge_counts(samples, run_align) if run_align > 1 else edges
    if not fixed:
        pn, pe, pg = pad_plan_for(samples, sub)
        top = lambda v: sum(sorted(v, reverse=True)[:sub])  # noqa: E731
        return pn, pe, pg, top(nodes), top(edges), top(aligned)
    n = len(samples)
    per = math.ceil(n / num_shards) if num_shards > 1 else n
    best = [0, 0, 0]
    for r in range(num_shards):
        idx = [(r + k * num_shards) % n for k in range(per)] if num_shards > 1 else list(range(n))
        nb = per // bs if drop_last else math.ceil(per / bs)
        for b in range(nb):
            chunk = idx[b * bs : (b + 1) * bs]
            for d in range(stack):
                part = chunk[d * sub : (d + 1) * sub]
                for j, v in enumerate((nodes, edges, aligned)):
                    best[j] = max(best[j], sum(v[i] for i in part))
    r16 = lambda x, m: -(-x // m) * m  # noqa: E731
    return r16(best[0] + 1, 16), r16(best[1] + 1, 8), sub + 1, best[0], best[1], best[2]


@pytest.mark.parametrize("run_align", [0, 8])
@pytest.mark.parametrize("num_shards", [1, 2])
@pytest.mark.parametrize("device_stack", [1, 2])
@pytest.mark.parametrize("path", sorted(_PLAN_PATHS))
@pytest.mark.parametrize("shuffle", [False, True])
def pytest_pad_plan_follows_membership(shuffle, path, device_stack, num_shards, run_align):
    """Fixed membership -> the plan is the largest batch actually built (over
    every shard and sub-batch); re-drawn membership -> pad_plan_for's worst
    case. Either way every batch of three epochs fits and all shards agree."""
    samples = _ring_samples(45, 4, 40, degree=3)
    bs = 8
    loaders = [
        GraphLoader(
            samples, bs, shuffle=shuffle, seed=2, num_shards=num_shards, shard_rank=r,
            device_stack=device_stack, dense_slots=False, run_align=run_align,
            prefetch=0, **_PLAN_PATHS[path],
        )
        for r in range(num_shards)
    ]
    fixed = _membership_is_fixed(shuffle, path)
    pn, pe, pg, rn, re_, ra = _expected_plan(
        samples, bs, device_stack, num_shards, run_align, fixed
    )
    if run_align > 1:
        pe = -(-max(ra + 1, pe) // 8) * 8  # below the kernels' scale: lcm(8, K)
    seen_nodes = seen_edges = 0
    for ld in loaders:
        assert ld.plan == ("fixed_membership" if fixed else "worst_case")
        assert (ld.pad_nodes, ld.pad_edges, ld.pad_graphs) == (pn, pe, pg)
        assert (ld.real_nodes_max, ld.real_edges_max) == (rn, re_)
        assert ld.aligned_edges_max == (ra if run_align > 1 else None)
        membership = set()
        for epoch in range(3):
            # batch_graphs raises on a batch over its plan: drawing is the check
            nodes, edges, graphs, n_pad, e_pad = _epoch_batches(ld, path, epoch)
            assert (n_pad, e_pad) == (pn, pe)
            assert int(graphs.sum()) == ld.num_samples
            assert nodes.max() < pn and edges.max() <= pe
            seen_nodes = max(seen_nodes, int(nodes.max()))
            seen_edges = max(seen_edges, int(edges.max()))
            membership.add(tuple(sorted(zip(nodes.tolist(), edges.tolist()))))
        if fixed:
            assert len(membership) == 1  # the same batches in every epoch
    if fixed:
        # tight: some batch that was built reaches the plan's real maxima
        assert (seen_nodes, seen_edges) == (rn, re_)


@pytest.mark.parametrize("device_stack", [1, 2])
@pytest.mark.parametrize("fixed", [False, True])
def pytest_pad_plan_grid_rounding_at_scale(fixed, device_stack):
    """At the kernels' scale the edge pad stays a multiple of
    lcm(run_align*CE, _BCAST_CE) under either plan, so no pallas_call pads
    its input and gather_presum stays eligible."""
    import math

    from hydragnn_tpu.ops.segment_pallas import _BCAST_CE, CE

    samples = _ring_samples(200, 30, 90, degree=20)
    ld = GraphLoader(
        samples, 64, shuffle=True, device_stack=device_stack, dense_slots=False,
        run_align=8, fixed_membership=fixed,
    )
    assert ld.plan == ("fixed_membership" if fixed else "worst_case")
    assert ld.aligned_edges_max + 1 >= 8 * 8 * CE  # the case under test
    assert ld.pad_edges % math.lcm(8 * CE, _BCAST_CE) == 0
    assert ld.pad_edges - ld.aligned_edges_max <= math.lcm(8 * CE, _BCAST_CE)
    worst = GraphLoader(samples, 64, shuffle=True, device_stack=device_stack,
                        dense_slots=False, run_align=8)
    assert ld.pad_edges <= worst.pad_edges and ld.pad_nodes <= worst.pad_nodes
    assert fixed or (ld.pad_nodes, ld.pad_edges) == (worst.pad_nodes, worst.pad_edges)


@pytest.mark.parametrize("drop_last", [False, True])
@pytest.mark.parametrize("device_stack", [1, 2])
def pytest_scan_built_loader_iterated_per_step(device_stack, drop_last):
    """The _stack_refusal fallback: a train loader built for the scan and then
    iterated per step draws the SAME chunks the stack holds, in an
    epoch-seeded order, never overflows its plan, and _order() is what
    __iter__ drew (benchmark/taps.py rebuilds membership from it)."""
    samples = _ring_samples(45, 4, 40, degree=3)
    bs = 8
    ld = GraphLoader(samples, bs, shuffle=True, seed=5, device_stack=device_stack,
                     dense_slots=False, prefetch=0, drop_last=drop_last,
                     fixed_membership=True)
    loose = GraphLoader(samples, bs, shuffle=True, seed=5, device_stack=device_stack,
                        dense_slots=False, drop_last=drop_last)
    assert ld.plan == "fixed_membership" and loose.plan == "worst_case"
    assert ld.pad_edges < loose.pad_edges and ld.pad_nodes < loose.pad_nodes
    nb = len(ld)
    chunks = [frozenset(range(b * bs, min((b + 1) * bs, len(samples)))) for b in range(nb)]
    orders = set()
    for epoch in range(3):
        ld.set_epoch(epoch)
        order = ld._order()
        assert sorted(order.tolist()) == list(range(len(samples)))
        drawn = [frozenset(order[b * bs : (b + 1) * bs].tolist()) for b in range(nb)]
        assert sorted(map(sorted, drawn)) == sorted(map(sorted, chunks))
        orders.add(tuple(order.tolist()))
        # what __iter__ builds IS _order()'s chunks (node counts identify them)
        want = [sum(samples[i].num_nodes for i in order[b * bs : (b + 1) * bs]) for b in range(nb)]
        got = [int(np.asarray(b.node_mask).sum()) for b in ld]
        assert got == want
        peek = ld.peek_batch()
        assert int(np.asarray(peek.node_mask).sum()) == want[0]
    assert len(orders) == 3  # the batch order is re-drawn every epoch
    # and the stack it was built for holds the same chunks
    st = ld.stacked_device_batches(0)
    per_batch = np.asarray(st.node_mask).reshape(nb, -1).sum(1).tolist()
    assert per_batch == [sum(samples[i].num_nodes for i in sorted(c)) for c in chunks]


@pytest.mark.parametrize("drop_last", [False, True])
def pytest_cached_loader_keeps_its_draw(drop_last):
    """cache_device_batches draws as it always did: an epoch-seeded
    permutation of ALL its batches, a partial one landing anywhere. Its plan
    is cut to those batches, and peek_batch is the first of them."""
    samples = _ring_samples(45, 4, 40, degree=3)
    bs = 8
    ld = GraphLoader(samples, bs, shuffle=True, seed=5, dense_slots=False,
                     drop_last=drop_last, cache_device_batches=True)
    assert ld.plan == "fixed_membership"
    nb = len(ld)
    per_chunk = [sum(s.num_nodes for s in samples[b * bs : (b + 1) * bs]) for b in range(nb)]
    partial_moved = False
    for epoch in range(4):
        ld.set_epoch(epoch)
        drawn = np.random.default_rng(5 + epoch).permutation(nb)
        got = [int(np.asarray(b.node_mask).sum()) for b in ld]
        assert got == [per_chunk[b] for b in drawn]
        assert int(np.asarray(ld.peek_batch().node_mask).sum()) == got[0]
        order = ld._order()
        assert sorted(order.tolist()) == list(range(len(samples)))
        in_batches = [i for b in drawn for i in range(b * bs, min((b + 1) * bs, len(samples)))]
        assert order[: len(in_batches)].tolist() == in_batches
        partial_moved |= not drop_last and drawn[-1] != nb - 1
    assert partial_moved or drop_last  # the case the fixed order would change


def pytest_worst_case_plan_is_pad_plan_for():
    """pad_plan_for and bucket_pad_plans return what they returned, and a
    per-step shuffled loader's plan is pad_plan_for's, bit for bit."""
    from hydragnn_tpu.data.loader import bucket_pad_plans, pad_plan_for

    samples = _ring_samples(45, 4, 40, degree=3)
    nodes = sorted((s.num_nodes for s in samples), reverse=True)
    edges = sorted((s.num_edges for s in samples), reverse=True)
    want = (-(-(sum(nodes[:8]) + 1) // 16) * 16, -(-(sum(edges[:8]) + 1) // 8) * 8, 9)
    assert pad_plan_for(samples, 8) == want
    ld = GraphLoader(samples, 8, shuffle=True, dense_slots=False, run_align=False)
    assert (ld.pad_nodes, ld.pad_edges, ld.pad_graphs) == want
    cap_n, cap_e = nodes[0], edges[0]
    (caps, plan), = bucket_pad_plans(samples, 4, num_buckets=1)
    assert caps == (cap_n, cap_e)
    assert plan == (-(-(4 * cap_n + 1) // 16) * 16, -(-(4 * cap_e + 1) // 8) * 8, 5)
