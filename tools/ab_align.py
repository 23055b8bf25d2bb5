"""A/B the flagship PNA step: current CSR layout vs the run-aligned
layout (graph/batch.py run_align), interleaved in one process.

Usage: python tools/ab_align.py [steps_per_arm] [K]
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
from hydragnn_tpu.data.loader import GraphLoader
from hydragnn_tpu.data.synthetic import deterministic_graph_data
from hydragnn_tpu.flagship import flagship_config
from hydragnn_tpu.models.create import create_model_config
from hydragnn_tpu.utils.config import update_config
from hydragnn_tpu.train import create_train_state, make_train_step, select_optimizer

STEPS = int(sys.argv[1]) if len(sys.argv) > 1 else 8
K = int(sys.argv[2]) if len(sys.argv) > 2 else 8
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
log(f"dataset ready: {len(train)} train samples")

arms = {}
for name, ra in (("plain", False), (f"align{K}", K)):
    loader = GraphLoader(
        train, BATCH, shuffle=True, drop_last=True, dense_slots=None, run_align=ra
    )
    batches = list(loader)
    arms[name] = batches
    b = batches[0]
    log(f"{name}: edge_pad={b.senders.shape[0]} run_align={b.run_align}")

tx = select_optimizer(config["NeuralNetwork"]["Training"])
model, variables = create_model_config(config["NeuralNetwork"], arms["plain"][0])
state0 = create_train_state(variables, tx)
step = make_train_step(model, tx, compute_dtype=jnp.bfloat16)

compiled = {}
for name, batches in arms.items():
    compiled[name] = step.lower(state0, batches[0]).compile()
    log(f"{name}: compiled")

states = {name: jax.tree_util.tree_map(jnp.copy, state0) for name in arms}
losses = {}
for name, batches in arms.items():
    states[name], loss, _ = compiled[name](states[name], batches[0])
    losses[name] = float(np.asarray(loss))
log(f"warmup losses: {losses}")

KSEG = 4
results = {name: [] for name in arms}
seg = 0
while seg * KSEG < STEPS:
    for name, batches in arms.items():
        t1 = time.perf_counter()
        for i in range(KSEG):
            states[name], loss, _ = compiled[name](
                states[name], batches[(seg * KSEG + i) % len(batches)]
            )
        np.asarray(loss)
        results[name].append((time.perf_counter() - t1) / KSEG * 1e3)
    seg += 1

for name, ts in results.items():
    med = sorted(ts)[len(ts) // 2]
    print(
        f"{name}: step_ms segments={['%.1f' % t for t in ts]} median={med:.1f} "
        f"graphs/sec={BATCH / med * 1e3:.0f}"
    )
