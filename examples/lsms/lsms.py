"""LSMS example: FePt free-energy + nodal charge-density/magnetic-moment
multi-task training from LSMS text files.

Mirrors the reference driver (examples/lsms/lsms.py:29-218): rank-0
preprocessing of the raw LSMS directory, compositional stratified split,
container write (HGC replaces ADIOS/pickle), then training from the
container. The reference expects a real FePt_32atoms dataset on disk;
when it is absent this driver generates a synthetic FePt-like dataset in
the same text layout (``Z index x y z charge_density magnetic_moment``,
graph line = free energy) so the full pipeline runs offline.

    python lsms.py --preonly     # (generate if needed) + preprocess + write containers
    python lsms.py               # train from containers
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
from hydragnn_tpu.data.container import ContainerDataset, ContainerWriter
from hydragnn_tpu.data.ingest import load_raw_samples, prepare_dataset
from hydragnn_tpu.parallel import (
    barrier,
    get_comm_size_and_rank,
    nsplit,
    setup_distributed,
)
from hydragnn_tpu.utils.config import get_log_name_config, update_config
from hydragnn_tpu.utils.print_utils import setup_log
from hydragnn_tpu.utils.time_utils import Timer, print_timers

FE, PT = 26, 78


def generate_fept_like(out_dir: str, n_config: int = 200, seed: int = 17) -> None:
    """Synthetic FePt-like LSMS files: 2x2x2 BCC supercells (32 atoms)
    with random Fe/Pt occupation; free energy and nodal charge/moment are
    smooth functions of local composition, so the learning task is
    well-posed (the same idea as tests/deterministic_graph_data.py)."""
    os.makedirs(out_dir, exist_ok=True)
    rng = np.random.default_rng(seed)
    # 2x2x2 conventional BCC cells -> 2 atoms/cell * 16 cells = 32 atoms
    base = np.array([[0.0, 0.0, 0.0], [0.5, 0.5, 0.5]])
    cells = np.array(
        [[i, j, k] for i in range(2) for j in range(2) for k in range(4)], dtype=float
    )
    pos = (cells[:, None, :] + base[None, :, :]).reshape(-1, 3) * 2.87  # Fe a0 (A)
    n = pos.shape[0]
    for c in range(n_config):
        z = np.where(rng.random(n) < rng.uniform(0.2, 0.8), FE, PT).astype(float)
        frac_fe = (z == FE).mean()
        # distance to nearest unlike atom drives the fake local moments
        diff = pos[:, None, :] - pos[None, :, :]
        dist = np.sqrt((diff**2).sum(-1)) + np.eye(n) * 1e9
        unlike = z[:, None] != z[None, :]
        d_unlike = np.where(unlike, dist, np.inf).min(axis=1)
        d_unlike = np.where(np.isfinite(d_unlike), d_unlike, dist.min(axis=1))
        moment = np.where(z == FE, 2.2, 0.35) * np.exp(-d_unlike / 5.0)
        charge = z + 0.05 * np.tanh(moment) + rng.normal(0, 0.01, n)
        free_energy = (
            -4.0 * n * (frac_fe * (1 - frac_fe)) - 0.1 * moment.sum()
            + rng.normal(0, 0.05)
        )
        lines = [f"{free_energy:.10g}"]
        for i in range(n):
            lines.append(
                f"{z[i]:.10g}\t{i}\t{pos[i,0]:.10g}\t{pos[i,1]:.10g}\t{pos[i,2]:.10g}"
                f"\t{charge[i]:.10g}\t{moment[i]:.10g}"
            )
        with open(os.path.join(out_dir, f"out_{c:05d}.txt"), "w") as f:
            f.write("\n".join(lines))


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--preonly", action="store_true", help="preprocess only")
    parser.add_argument("--inputfile", type=str, default="lsms.json")
    parser.add_argument("--nconfig", type=int, default=200,
                        help="synthetic configurations when raw data is absent")
    parser.add_argument("--mode", type=str, default="preload",
                        choices=["mmap", "preload", "shm"])
    args = parser.parse_args()

    with open(os.path.join(_here, args.inputfile)) as f:
        config = json.load(f)

    setup_distributed()
    comm_size, rank = get_comm_size_and_rank()
    setup_log(get_log_name_config(config))

    datasetname = config["Dataset"]["name"]
    raw_dir = os.path.join(_here, config["Dataset"]["path"]["total"])
    container_dir = os.path.join(_here, "dataset", f"{datasetname}.hgc")

    if args.preonly:
        # rank-0 generates (the reference preprocesses rank-0-only,
        # lsms.py:83-85); every rank then runs the deterministic
        # preparation and contributes a disjoint shard, because
        # ContainerWriter.save is a collective op
        if rank == 0 and (not os.path.isdir(raw_dir) or not os.listdir(raw_dir)):
            print(f"raw LSMS data not found at {raw_dir}; generating synthetic")
            generate_fept_like(raw_dir, n_config=args.nconfig)
        barrier("lsms_generate")
        samples = load_raw_samples(config, raw_dir)
        train, val, test, mm_g, mm_n = prepare_dataset(samples, config)
        if rank == 0:
            print(len(samples), len(train), len(val), len(test))
        for name, split in (("trainset", train), ("valset", val), ("testset", test)):
            shard = list(nsplit(split, comm_size))[rank]
            w = ContainerWriter(os.path.join(container_dir, name))
            w.add(shard)
            w.add_global("minmax_graph_feature", mm_g)
            w.add_global("minmax_node_feature", mm_n)
            w.save()
        return

    timer = Timer("load_data")
    timer.start()
    splits = {
        name: ContainerDataset(os.path.join(container_dir, name), mode=args.mode)
        for name in ("trainset", "valset", "testset")
    }
    train, val, test = (splits[k].samples() for k in ("trainset", "valset", "testset"))
    train, val, test = list(train), list(val), list(test)
    mm_g, mm_n = splits["trainset"].minmax()
    timer.stop()

    voi = config["NeuralNetwork"]["Variables_of_interest"]
    voi["minmax_graph_feature"] = mm_g.tolist()
    voi["minmax_node_feature"] = mm_n.tolist()
    config = update_config(config, train, val, test)

    loaders = create_dataloaders(train, val, test, config)
    train_with_loaders(config, *loaders)
    print_timers(config["Verbosity"]["level"])


if __name__ == "__main__":
    main()
