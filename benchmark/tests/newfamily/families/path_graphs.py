"""A second family, for the tests alone: path graphs of seeded, unequal
lengths. ``tests/test_families.py`` adds this directory's three files to a
copy of ``benchmark/`` (new files only, none that exists is touched) and
runs a rehearsal of the cell they define: the proof that a family needs
no edit to the harness.

Atoms sit on a line at distance 1 and the configuration's radius is 1.5,
so the radius graph IS the path: 2 (n - 1) directed edges for n atoms.
The family makes its own samples, wraps the message-passing reference
(the chassis is the same; a family with another chassis would bring its
own) and adds exact checks of its own: the nodes and the edges the
program prepared for the first step's samples, against the lengths drawn
here.
"""

from __future__ import annotations

from typing import Any, Dict, List

import numpy as np

from families import message_passing as mp

program_samples = mp.program_samples
rehearsal_overrides = mp.rehearsal_overrides
faults = mp.faults


def generate(traffic: Dict[str, Any], seed: int) -> List[Dict[str, np.ndarray]]:
    """``traffic``: {"samples": int, "lengths": [lo, hi], "number_types": int}."""
    rng = np.random.default_rng(int(seed))
    lo, hi = traffic["lengths"]
    out = []
    for n in rng.integers(int(lo), int(hi) + 1, size=int(traffic["samples"])):
        kind = rng.integers(0, int(traffic.get("number_types", 3)), size=n).astype(np.float64)
        near = (kind + np.roll(kind, 1)) / 2.0  # the mean over an atom and the one before it (cyclic)
        pos = np.zeros((n, 3), np.float32)
        pos[:, 0] = np.arange(n)
        out.append({
            "x": np.stack([kind, near**2, near**3], axis=1),
            "pos": pos,
            "graph_y": np.array([(near + near**2 + kind + near**3).sum()], np.float64),
        })
    return out


def count_samples(ids: List[int], samples) -> Dict[str, Any]:
    counts = mp.count_samples(ids, samples)
    counts["program_nodes"] = {i: int(s.x.shape[0]) for i, s in zip(ids, samples)}
    return counts


def reference_run(cell, taps, raw, quant=None, fault=None) -> Dict[str, Any]:
    ref = mp.reference_run(cell, taps, raw, quant=quant, fault=fault)
    first = [i for g in taps.step_groups[0] for i in g]
    ref["path_nodes_step1"] = int(sum(len(raw[i]["x"]) for i in first))
    ref["path_edges_step1"] = int(sum(2 * (len(raw[i]["x"]) - 1) for i in first))
    ref["real"]["paths_per_epoch"] = len(taps.train_ids)
    return ref


def exact_checks(taps, ref) -> Dict[str, Dict[str, Any]]:
    checks = mp.exact_checks(taps, ref)
    first = [i for g in taps.step_groups[0] for i in g]
    nodes = sum(taps.sample_counts["program_nodes"][i] for i in first)
    edges = sum(taps.sample_counts["program_edges"][i] for i in first)
    checks["path_nodes_step1_diff"] = {"value": abs(nodes - ref["path_nodes_step1"]), "limit": 0}
    checks["path_edges_step1_diff"] = {"value": abs(edges - ref["path_edges_step1"]), "limit": 0}
    return checks
