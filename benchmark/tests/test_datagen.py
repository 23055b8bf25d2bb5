"""The traffic generator: the same seed gives the same samples, every seed
the same multiset of sizes in balanced groups, and the targets follow the
closed form."""

import numpy as np

import datagen

TRAFFIC = {"samples": 64, "unit_cells": [2, 4], "number_types": 3, "number_neighbors": 2}


def test_same_seed_same_samples_and_large_seeds():
    a = datagen.generate(TRAFFIC, 2**31 + 12345)
    b = datagen.generate(TRAFFIC, 2**31 + 12345)
    c = datagen.generate(TRAFFIC, 7)
    assert all(np.array_equal(x["x"], y["x"]) and np.array_equal(x["pos"], y["pos"]) for x, y in zip(a, b))
    assert any(len(x["x"]) != len(y["x"]) or not np.array_equal(x["x"], y["x"]) for x, y in zip(a, c))


def test_every_group_holds_every_shape_once():
    for seed in (1, 2, 3):
        sizes = np.array([len(s["x"]) for s in datagen.generate(TRAFFIC, seed)]).reshape(-1, 8)
        for group in sizes:
            assert sorted(group) == [16, 24, 24, 24, 36, 36, 36, 54]


def test_targets_follow_the_closed_form():
    s = datagen.generate(TRAFFIC, 5)[3]
    pos, typ = s["pos"].astype(np.float64), s["x"][:, 0]
    d = np.sqrt(((pos[:, None] - pos[None]) ** 2).sum(-1))
    nearest = np.argsort(d, axis=1, kind="stable")[:, :2]
    knn = typ[nearest].mean(1)
    assert np.allclose(s["x"][:, 1], knn**2) and np.allclose(s["x"][:, 2], knn**3)
    assert np.isclose(s["graph_y"][0], (knn + knn**2 + typ + knn**3).sum())
    assert set(np.unique(typ)) <= {0.0, 1.0, 2.0}
