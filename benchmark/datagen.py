"""Traffic generator: BCC lattices with closed-form targets, from a seed.

A copy of the semantics of the program's test fixture
(``hydragnn_tpu/data/synthetic.py:deterministic_graph_data``, itself after
the reference's ``tests/deterministic_graph_data.py``), kept here so that
no PR to the program can change what the benchmark feeds it. It reads one
traffic file (``workloads/<cell>.json``, key ``traffic``) and imports
nothing of the program.

What differs from the program's generator, on purpose: every seed gets the
SAME multiset of lattice sizes, in another order, and the order is
balanced in groups (each consecutive group of ``len(shapes)`` samples holds
every shape once). The program sizes its padded batches from the largest
graphs of each split, so sizes drawn freely from the seed would change the
compiled shapes, and with them the work, from run to run. The node types,
and so every target, are drawn from the seed.

Per sample (raw, before the program's or the reference's preparation):
  x        [n, 3] float64   columns: type, knn(type)^2, knn(type)^3
  pos      [n, 3] float32   BCC positions, lattice constant 1
  graph_y  [1]    float64   sum over nodes of knn + (knn^2 + type) + knn^3
where knn is the mean of ``type`` over the ``number_neighbors`` nearest
sites, the site itself included (sklearn KNeighborsRegressor semantics).
"""

from __future__ import annotations

import itertools
from typing import Dict, List

import numpy as np


def bcc_positions(ux: int, uy: int, uz: int) -> np.ndarray:
    """[2*ux*uy*uz, 3] positions: corner and body centre of each cell."""
    cells = np.array(list(itertools.product(range(ux), range(uy), range(uz))), np.float64)
    pos = np.empty((2 * len(cells), 3), np.float64)
    pos[0::2] = cells
    pos[1::2] = cells + 0.5
    return pos


def knn_index(pos: np.ndarray, k: int) -> np.ndarray:
    """[n, k] indices of each site's k nearest sites, itself first (ties
    by index: a stable sort, as the program's and sklearn's)."""
    diff = pos[:, None, :] - pos[None, :, :]
    dist = np.sqrt((diff * diff).sum(-1))
    return np.argsort(dist, axis=1, kind="stable")[:, :k]


def lattice_shapes(lo: int, hi: int) -> List[tuple]:
    """Every (ux, uy, uz) with lo <= u < hi: the high end is exclusive,
    as in the program's generator."""
    return list(itertools.product(range(lo, hi), repeat=3))


def generate(traffic: Dict, seed: int) -> List[Dict[str, np.ndarray]]:
    """``traffic``: {"samples": int, "unit_cells": [lo, hi],
    "number_types": int, "number_neighbors": int}. Returns the raw
    samples in dataset order."""
    n = int(traffic["samples"])
    lo, hi = traffic["unit_cells"]
    shapes = lattice_shapes(int(lo), int(hi))
    if n % len(shapes):
        raise ValueError(f"samples={n} is not a multiple of the {len(shapes)} lattice shapes")
    types = int(traffic.get("number_types", 3))
    k = int(traffic.get("number_neighbors", 2))
    rng = np.random.default_rng(int(seed))
    groups = n // len(shapes)
    # shape id of every sample: a fresh permutation of all shapes per group
    shape_of = rng.permuted(np.tile(np.arange(len(shapes)), (groups, 1)), axis=1).reshape(-1)

    out: List[Dict[str, np.ndarray]] = [None] * n  # type: ignore[list-item]
    for sid, shape in enumerate(shapes):
        pos = bcc_positions(*shape)
        nbr = knn_index(pos, k)
        where = np.nonzero(shape_of == sid)[0]
        feature = rng.integers(0, types, size=(len(where), pos.shape[0])).astype(np.float64)
        knn = feature[:, nbr].mean(axis=2)
        x2, x3 = knn**2, knn**3
        total = knn.sum(1) + (x2 + feature).sum(1) + x3.sum(1)
        pos32 = pos.astype(np.float32)
        for j, i in enumerate(where):
            out[i] = {
                "x": np.stack([feature[j], x2[j], x3[j]], axis=1),
                "pos": pos32.copy(),
                "graph_y": np.array([total[j]], np.float64),
            }
    return out
