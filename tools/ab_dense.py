"""A/B the flagship PNA step: CSR (+local-window sender kernels) vs the
dense ELL slot map, interleaved in one process (one process holds the
chip; absolute times of separate runs are not compared).

Usage: python tools/ab_dense.py [steps_per_arm]
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

t0 = time.time()


def log(msg):
    print(f"[{time.time()-t0:7.1f}s] {msg}", flush=True)


import jax
import jax.numpy as jnp
import numpy as np

from hydragnn_tpu.data.ingest import prepare_dataset
from hydragnn_tpu.data.loader import GraphLoader, max_in_degree
from hydragnn_tpu.data.synthetic import deterministic_graph_data
from hydragnn_tpu.flagship import flagship_config
from hydragnn_tpu.models.create import create_model_config
from hydragnn_tpu.utils.config import update_config
from hydragnn_tpu.train import create_train_state, make_train_step, select_optimizer

STEPS = int(sys.argv[1]) if len(sys.argv) > 1 else 8
BATCH = 1024

config = flagship_config(128, 6, BATCH)
samples = deterministic_graph_data(
    number_configurations=1280,
    unit_cell_x_range=(2, 4),
    unit_cell_y_range=(2, 4),
    unit_cell_z_range=(2, 4),
    seed=0,
)
train, val, test, _, _ = prepare_dataset(samples, config)
config = update_config(config, train, val, test)
log(f"dataset ready: {len(train)} train samples, dmax={max_in_degree(train)}")

arms = {}
for name, dense in (("csr", False), ("dense", max_in_degree(train))):
    # run_align=False: keep this a pure dense-vs-CSR comparison (the
    # loader default would silently run-align the CSR arm)
    loader = GraphLoader(
        train, BATCH, shuffle=True, drop_last=True, dense_slots=dense,
        run_align=False,
    )
    batches = list(loader)
    arms[name] = batches
    b = batches[0]
    log(
        f"{name}: node_pad={b.nodes.shape[0]} edge_pad={b.senders.shape[0]} "
        f"dense={None if b.dense_senders is None else b.dense_senders.shape} "
        f"sender_win={'y' if b.sender_win is not None else 'n'} "
        f"dense_win={'y' if b.dense_sender_win is not None else 'n'}"
    )

tx = select_optimizer(config["NeuralNetwork"]["Training"])
model, variables = create_model_config(config["NeuralNetwork"], arms["csr"][0])
state0 = create_train_state(variables, tx)
step = make_train_step(model, tx, compute_dtype=jnp.bfloat16)

compiled = {}
for name, batches in arms.items():
    compiled[name] = step.lower(state0, batches[0]).compile()
    log(f"{name}: compiled")

# the jitted step DONATES the state: give each arm its own copy
states = {
    name: jax.tree_util.tree_map(jnp.copy, state0) for name in arms
}

# warmup + loss parity check
losses = {}
for name, batches in arms.items():
    states[name], loss, _ = compiled[name](states[name], batches[0])
    losses[name] = float(np.asarray(loss))
log(f"warmup losses: {losses}")

# interleaved timing, D2H fence per arm segment
K = 4  # steps per segment
results = {name: [] for name in arms}
seg = 0
while seg * K < STEPS:
    for name, batches in arms.items():
        t1 = time.perf_counter()
        for i in range(K):
            states[name], loss, _ = compiled[name](
                states[name], batches[(seg * K + i) % len(batches)]
            )
        np.asarray(loss)
        results[name].append((time.perf_counter() - t1) / K * 1e3)
    seg += 1

for name, ts in results.items():
    med = sorted(ts)[len(ts) // 2]
    print(
        f"{name}: step_ms segments={['%.1f' % t for t in ts]} median={med:.1f} "
        f"graphs/sec={BATCH / med * 1e3:.0f}"
    )
