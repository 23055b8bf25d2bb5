"""MD17 example: molecular-dynamics energy regression on uracil
trajectories (graph head) with in-config radius-graph construction.

Mirrors the reference driver (examples/md17/md17.py:14-104): node
feature = element type, target = energy / atom count, ~25% random
subsample of the trajectory, radius-graph edges from the Architecture
config, proportional split, then training. Instead of torch_geometric's
downloaded npz, this driver reads an MD17-format ``.npz`` natively when
present (keys ``R`` [m,n,3], ``z`` [n], ``E`` [m], ``F`` [m,n,3]) and
otherwise generates a synthetic harmonic uracil-like trajectory so the
pipeline runs offline.

    python md17.py [--data dataset/md17/md17_uracil.npz]
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
from hydragnn_tpu.data.ingest import prepare_dataset
from hydragnn_tpu.parallel import setup_distributed
from hydragnn_tpu.utils.config import update_config
from hydragnn_tpu.utils.print_utils import setup_log
from hydragnn_tpu.utils.time_utils import print_timers

# idealized planar uracil (C4H4N2O2), close enough for a synthetic
# harmonic trajectory around it
_URACIL_Z = np.array([7, 6, 7, 6, 6, 6, 8, 8, 1, 1, 1, 1])
_URACIL_POS = np.array([
    [0.00, 1.39, 0.0], [1.20, 0.69, 0.0], [1.20, -0.69, 0.0],
    [0.00, -1.39, 0.0], [-1.20, -0.69, 0.0], [-1.20, 0.69, 0.0],
    [2.30, 1.30, 0.0], [0.00, -2.60, 0.0],
    [-0.05, 2.40, 0.0], [2.10, -1.20, 0.0], [-2.10, -1.20, 0.0],
    [-2.15, 1.25, 0.0],
])


def load_md17_npz(path: str) -> tuple:
    data = np.load(path)
    return data["R"], data["z"], data["E"].reshape(-1)


def generate_synthetic_md17(n_frames: int = 4000, seed: int = 0) -> tuple:
    """Harmonic fluctuations around the uracil geometry: E = 0.5 k |dx|^2
    (per-frame), a well-posed stand-in for the real trajectory."""
    rng = np.random.default_rng(seed)
    n = len(_URACIL_Z)
    disp = rng.normal(0, 0.08, (n_frames, n, 3))
    R = _URACIL_POS[None] + disp
    k = 55.0
    E = -259640.0 + 0.5 * k * (disp**2).sum(axis=(1, 2))
    return R, _URACIL_Z, E


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument(
        "--data", type=str,
        default=os.path.join(_here, "dataset/md17/md17_uracil.npz"),
    )
    parser.add_argument("--subsample", type=float, default=0.25,
                        help="trajectory keep fraction (reference md17_pre_filter)")
    parser.add_argument("--maxframes", type=int, default=1000)
    parser.add_argument("--inputfile", type=str, default="md17.json")
    args = parser.parse_args()

    with open(os.path.join(_here, args.inputfile)) as f:
        config = json.load(f)

    setup_distributed()
    setup_log("md17_test")

    if os.path.isfile(args.data):
        R, z, E = load_md17_npz(args.data)
        print(f"read {len(E)} MD17 frames from {args.data}")
    else:
        print(f"no MD17 npz at {args.data}; generating synthetic uracil trajectory")
        R, z, E = generate_synthetic_md17()

    rng = np.random.default_rng(25)
    keep = np.where(rng.random(len(E)) < args.subsample)[0][: args.maxframes]
    samples = [
        GraphSample(
            x=np.asarray(z, dtype=np.float64)[:, None],
            pos=R[i].astype(np.float32),
            graph_y=np.asarray([E[i]], dtype=np.float64),
        )
        for i in keep
    ]

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
