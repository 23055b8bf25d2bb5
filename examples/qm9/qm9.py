"""QM9 example: molecular free-energy regression (graph head).

Mirrors the reference driver (examples/qm9/qm9.py:14-95): each molecule's
node feature is the element type, the target is the free energy divided
by the atom count (the ``y[:, 10] / len(x)`` pre-transform), proportional
split, then training. Instead of torch_geometric's downloaded copy, this
driver reads the raw GDB9 ``.xyz`` files natively when present at
``dataset/qm9/raw`` (including the Fortran ``*^`` float notation), and
otherwise generates a deterministic synthetic molecular dataset so the
pipeline runs offline. Bond connectivity is replaced by the framework's
radius graph (Architecture.radius / max_neighbours), the md17-example
pattern.

    python qm9.py [--data dataset/qm9/raw] [--nsamples 1000]
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

_here = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(_here)))  # repo root

from hydragnn_tpu.utils.platform import place_compile_cache

place_compile_cache()

from hydragnn_tpu.api import create_dataloaders, train_with_loaders
from hydragnn_tpu.data.dataset import GraphSample
from hydragnn_tpu.data.formats import SYMBOL_TO_Z
from hydragnn_tpu.data.ingest import prepare_dataset
from hydragnn_tpu.parallel import setup_distributed
from hydragnn_tpu.utils.config import update_config
from hydragnn_tpu.utils.print_utils import setup_log
from hydragnn_tpu.utils.time_utils import print_timers

# scalar properties on the GDB9 comment line after "gdb <idx>":
# [A, B, C, mu, alpha, homo, lumo, gap, r2, zpve, U0, U, H, G, Cv];
# free energy G is index 13 (the reference's y[:, 10] counts from mu).
G_INDEX = 13


def _gdb9_float(tok: str) -> float:
    return float(tok.replace("*^", "e"))


def read_gdb9_xyz(path: str) -> GraphSample:
    with open(path, encoding="utf-8") as f:
        lines = f.read().splitlines()
    n = int(lines[0].split()[0])
    props = [_gdb9_float(t) for t in lines[1].split()[2:]]
    zs = np.zeros(n, dtype=np.int64)
    pos = np.zeros((n, 3), dtype=np.float64)
    for i in range(n):
        parts = lines[2 + i].split()
        zs[i] = SYMBOL_TO_Z[parts[0]]
        pos[i] = [_gdb9_float(parts[1]), _gdb9_float(parts[2]), _gdb9_float(parts[3])]
    return GraphSample(
        x=zs[:, None].astype(np.float64),
        pos=pos.astype(np.float32),
        graph_y=np.asarray([props[G_INDEX]], dtype=np.float64),
    )


def load_qm9_raw(root: str, limit: int) -> list:
    files = sorted(f for f in os.listdir(root) if f.endswith(".xyz"))[:limit]
    return [read_gdb9_xyz(os.path.join(root, f)) for f in files]


def generate_synthetic_qm9(n_samples: int, seed: int = 0) -> list:
    """Random CHNOF clusters with a smooth per-atom free-energy-like
    target (element contribution + pair interaction), so training is
    well-posed offline."""
    rng = np.random.default_rng(seed)
    contrib = {1: -0.5, 6: -38.0, 7: -54.5, 8: -75.0, 9: -99.7}
    samples = []
    for _ in range(n_samples):
        n = int(rng.integers(4, 18))
        zs = rng.choice([1, 6, 7, 8, 9], size=n, p=[0.5, 0.3, 0.08, 0.08, 0.04])
        pos = rng.normal(0, 1.8, (n, 3))
        diff = pos[:, None] - pos[None, :]
        r = np.sqrt((diff**2).sum(-1)) + np.eye(n) * 1e9
        pair = (np.exp(-r / 1.5)).sum() / 2
        g = sum(contrib[int(z)] for z in zs) - 2.0 * pair
        samples.append(
            GraphSample(
                x=zs[:, None].astype(np.float64),
                pos=pos.astype(np.float32),
                graph_y=np.asarray([g], dtype=np.float64),
            )
        )
    return samples


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--data", type=str, default=os.path.join(_here, "dataset/qm9/raw"))
    parser.add_argument("--nsamples", type=int, default=1000,
                        help="sample cap (the reference's qm9_pre_filter)")
    parser.add_argument("--inputfile", type=str, default="qm9.json")
    args = parser.parse_args()

    with open(os.path.join(_here, args.inputfile)) as f:
        config = json.load(f)

    setup_distributed()
    setup_log("qm9_test")

    if os.path.isdir(args.data) and any(
        f.endswith(".xyz") for f in os.listdir(args.data)
    ):
        samples = load_qm9_raw(args.data, args.nsamples)
        print(f"read {len(samples)} GDB9 molecules from {args.data}")
    else:
        print(f"no raw QM9 at {args.data}; generating synthetic molecules")
        samples = generate_synthetic_qm9(args.nsamples)

    train, val, test, mm_g, mm_n = prepare_dataset(samples, config)
    voi = config["NeuralNetwork"]["Variables_of_interest"]
    voi["minmax_graph_feature"] = mm_g.tolist()
    voi["minmax_node_feature"] = mm_n.tolist()
    config = update_config(config, train, val, test)

    loaders = create_dataloaders(train, val, test, config)
    train_with_loaders(config, *loaders)
    print_timers(config["Verbosity"]["level"])


if __name__ == "__main__":
    main()
