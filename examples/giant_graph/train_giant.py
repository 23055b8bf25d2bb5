"""Giant-graph training demo: ONE graph too large for sensible
single-batch data parallelism, trained with its edge set sharded over
the device mesh.

The reference cannot partition a single graph across ranks — its
large-graph story is data-side only (SURVEY §5: out-of-core ADIOS
reads, DDStore fetches of whole graphs). This example exercises the
TPU-native headroom beyond that parity point (docs/DESIGN.md §3,
hydragnn_tpu/parallel/edge_sharded.py): a ~120k-node periodic cubic
lattice (6-neighbor adjacency, ~720k directed edges) is placed with
``place_giant_batch`` — edge arrays sharded ``P(data)``, node arrays
replicated — and a PLAIN jitted train step is partitioned by XLA's
SPMD pass: each device computes messages for its own edge shard, the
partial-aggregate all-reduce rides ICI, and the backward pass gets the
matching collectives automatically.

Memory accounting: per-device edge-buffer residency is O(E/D) — the
script asserts each edge leaf's addressable shard holds exactly
rows/D of the global array and prints the bytes.

Run on the virtual CPU mesh:
  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      python examples/giant_graph/train_giant.py --nx 50 --ny 50 --nz 48

The node-level target is closed-form (y_i = tanh of the neighbor-count-
normalized feature sum), so the loss must drop within a few steps.
"""

from __future__ import annotations

import argparse
import os
import sys

import numpy as np

_here = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(_here)))  # repo root


def build_lattice_graph(nx: int, ny: int, nz: int, seed: int = 0):
    """Periodic cubic lattice: N = nx*ny*nz nodes, 6 directed edges per
    node (+x,-x,+y,-y,+z,-z neighbors) built by pure index arithmetic —
    no neighbor search needed at this scale."""
    n = nx * ny * nz
    ids = np.arange(n, dtype=np.int32)
    ix = ids % nx
    iy = (ids // nx) % ny
    iz = ids // (nx * ny)

    def nid(x, y, z):
        return (x % nx) + (y % ny) * nx + (z % nz) * nx * ny

    neighbors = [
        nid(ix + 1, iy, iz), nid(ix - 1, iy, iz),
        nid(ix, iy + 1, iz), nid(ix, iy - 1, iz),
        nid(ix, iy, iz + 1), nid(ix, iy, iz - 1),
    ]
    senders = np.concatenate([nb.astype(np.int32) for nb in neighbors])
    receivers = np.concatenate([ids] * 6).astype(np.int32)

    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, 4)).astype(np.float32)
    # closed-form local target: learnable by 2 rounds of message passing
    neigh_sum = np.zeros((n, 4), np.float32)
    np.add.at(neigh_sum, receivers, x[senders])
    y = np.tanh(neigh_sum.mean(axis=1, keepdims=True) / 6.0).astype(np.float32)
    return x, senders, receivers, y


def build_giant_problem(nx: int, ny: int, nz: int, hidden: int, n_devices: int):
    """(model, variables, placed_batch, mesh) for the sharded step."""
    from hydragnn_tpu.graph import batch_graphs
    from hydragnn_tpu.models import ModelConfig, create_model
    from hydragnn_tpu.parallel import make_mesh
    from hydragnn_tpu.parallel.edge_sharded import place_giant_batch

    x, senders, receivers, y = build_lattice_graph(nx, ny, nz)
    n, e = x.shape[0], senders.shape[0]
    g = {
        "x": x,
        "senders": senders,
        "receivers": receivers,
        "node_targets": {"y": y},
    }
    batch = batch_graphs(
        [g],
        n_node_pad=n + 8,
        n_edge_pad=((e + n_devices - 1) // n_devices) * n_devices,
        n_graph_pad=2,
    )
    cfg = ModelConfig(
        model_type="GIN",
        input_dim=4,
        hidden_dim=hidden,
        output_dim=(1,),
        output_type=("node",),
        output_names=("y",),
        task_weights=(1.0,),
        num_conv_layers=2,
        node_num_headlayers=2,
        node_dim_headlayers=(hidden, hidden),
        node_head_type="mlp",
    )
    model, variables = create_model(cfg, batch)
    mesh = make_mesh(n_devices)
    placed = place_giant_batch(mesh, batch)
    return model, variables, placed, mesh


def check_edge_residency(placed, n_devices: int) -> dict:
    """Assert O(E/D) per-device edge residency; return the accounting."""
    acct = {}
    for name in ("senders", "receivers", "edge_mask"):
        arr = getattr(placed, name)
        shard_rows = arr.addressable_shards[0].data.shape[0]
        assert shard_rows * n_devices == arr.shape[0], (
            name, shard_rows, arr.shape)
        acct[name] = {
            "global_rows": int(arr.shape[0]),
            "rows_per_device": int(shard_rows),
            "bytes_per_device": int(arr.addressable_shards[0].data.nbytes),
        }
    # node features stay replicated: full rows on every device
    assert placed.nodes.addressable_shards[0].data.shape[0] == placed.nodes.shape[0]
    return acct


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--nx", type=int, default=50)
    parser.add_argument("--ny", type=int, default=50)
    parser.add_argument("--nz", type=int, default=48)
    parser.add_argument("--hidden", type=int, default=32)
    parser.add_argument("--steps", type=int, default=8)
    parser.add_argument("--lr", type=float, default=0.02)
    args = parser.parse_args(argv)

    from hydragnn_tpu.utils.platform import place_compile_cache

    place_compile_cache()
    import jax

    from hydragnn_tpu.train import create_train_state, make_train_step, select_optimizer

    n_devices = len(jax.devices())
    model, variables, placed, mesh = build_giant_problem(
        args.nx, args.ny, args.nz, args.hidden, n_devices
    )
    n = placed.nodes.shape[0]
    e = placed.senders.shape[0]
    print(f"giant graph: {n} nodes, {e} edges, mesh of {n_devices} devices")

    acct = check_edge_residency(placed, n_devices)
    for k, v in acct.items():
        print(
            f"  {k}: {v['global_rows']} rows -> {v['rows_per_device']}/device "
            f"({v['bytes_per_device']} bytes/device)  [O(E/D)]"
        )

    tx = select_optimizer({"Optimizer": {"type": "AdamW", "learning_rate": args.lr}})
    state = create_train_state(variables, tx, seed=0)
    step = make_train_step(model, tx)
    losses = []
    for i in range(args.steps):
        state, loss, _ = step(state, placed)
        losses.append(float(np.asarray(loss)))  # D2H: real sync
        print(f"step {i}: loss {losses[-1]:.6f}")
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0], "loss did not decrease"
    print("giant-graph sharded training OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
