"""Ising-model example: sharded data generation -> HGC container ->
multi-task (graph energy + node spin) training.

Mirrors the reference pipeline (examples/ising_model/train_ising.py:
63-265): generate configurations sharded across processes, read the raw
text dataset, split train/val/test, save to the parallel container
(ADIOS-equivalent: HGC), then train from the container. Run:

    python train_ising.py --preonly      # generate + write containers
    python train_ising.py                # train from containers
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys

import numpy as np

_here = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, _here)
sys.path.insert(0, os.path.dirname(os.path.dirname(_here)))  # repo root (no-install runs)

from hydragnn_tpu.utils.platform import place_compile_cache

place_compile_cache()
from create_configurations import create_dataset

import hydragnn_tpu
from hydragnn_tpu.api import create_dataloaders, train_with_loaders
from hydragnn_tpu.data.container import ContainerDataset, ContainerWriter
from hydragnn_tpu.data.ingest import load_raw_samples, prepare_dataset
from hydragnn_tpu.parallel import (
    barrier,
    get_comm_size_and_rank,
    nsplit,
    setup_distributed,
)
from hydragnn_tpu.utils.config import update_config
from hydragnn_tpu.utils.print_utils import setup_log
from hydragnn_tpu.utils.time_utils import Timer, print_timers


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--preonly", action="store_true", help="preprocess only")
    parser.add_argument("--natom", type=int, default=3, help="atoms per dimension")
    parser.add_argument(
        "--cutoff", type=int, default=1000, help="configurational histogram cutoff"
    )
    parser.add_argument("--inputfile", type=str, default="ising_model.json")
    parser.add_argument("--mode", type=str, default="preload",
                        choices=["mmap", "preload", "shm"],
                        help="container read mode")
    args = parser.parse_args()

    dirpwd = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(dirpwd, args.inputfile)) as f:
        config = json.load(f)

    setup_distributed()
    comm_size, rank = get_comm_size_and_rank()

    modelname = f"ising_model_{args.natom}_{args.cutoff}"
    raw_dir = os.path.join(dirpwd, "dataset", modelname)
    container_dir = os.path.join(dirpwd, "dataset", f"{modelname}.hgc")

    if args.preonly:
        if rank == 0 and os.path.exists(raw_dir):
            shutil.rmtree(raw_dir)
        barrier("ising_rmtree")
        # sine spin function + randomized magnitudes (the reference's
        # nonlinear extension, train_ising.py:205-216); composition loop
        # sharded across processes
        n = create_dataset(
            L=args.natom,
            histogram_cutoff=args.cutoff,
            out_dir=raw_dir,
            spin_function=lambda x: np.sin(np.pi * x / 2),
            scale_spin=True,
            num_shards=comm_size,
            shard=rank,
        )
        print(f"rank {rank}: generated {n} configurations")
        barrier("ising_generate")

        # every rank runs the (deterministic) full preparation, then
        # contributes a disjoint shard of each split to the collective
        # container save (ContainerWriter.save is a collective op)
        config["Dataset"]["path"]["total"] = raw_dir
        samples = load_raw_samples(config, raw_dir)
        train, val, test, mm_g, mm_n = prepare_dataset(samples, config)
        print(len(samples), len(train), len(val), len(test))

        for name, split in (("trainset", train), ("valset", val), ("testset", test)):
            shard = list(nsplit(split, comm_size))[rank]
            writer = ContainerWriter(os.path.join(container_dir, name))
            writer.add(shard)
            writer.add_global("minmax_graph_feature", mm_g)
            writer.add_global("minmax_node_feature", mm_n)
            writer.save()
        return

    timer = Timer("load_data")
    timer.start()
    splits = {
        name: ContainerDataset(os.path.join(container_dir, name), mode=args.mode)
        for name in ("trainset", "valset", "testset")
    }
    train = splits["trainset"].samples()
    val = splits["valset"].samples()
    test = splits["testset"].samples()
    mm_g, mm_n = splits["trainset"].minmax()
    timer.stop()

    voi = config["NeuralNetwork"]["Variables_of_interest"]
    voi["minmax_graph_feature"] = mm_g.tolist()
    voi["minmax_node_feature"] = mm_n.tolist()
    config = update_config(config, train, val, test)

    setup_log("ising_model_test")
    loaders = create_dataloaders(train, val, test, config)
    train_with_loaders(config, *loaders)
    print_timers(config["Verbosity"]["level"])


if __name__ == "__main__":
    main()
