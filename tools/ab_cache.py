"""Measure the flagship aligned step with DEVICE-RESIDENT batches vs
host batches (H2D per step): quantifies the transfer share of the wall
step. Usage: python tools/ab_cache.py [steps]"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from hydragnn_tpu.flagship import build_flagship
from hydragnn_tpu.train import create_train_state, make_train_step, select_optimizer

STEPS = int(sys.argv[1]) if len(sys.argv) > 1 else 8
t0 = time.time()
config, model, variables, loader = build_flagship(
    n_samples=1280, hidden_dim=128, num_conv_layers=6, batch_size=1024,
    unit_cells=(2, 4),
)
tx = select_optimizer(config["NeuralNetwork"]["Training"])
state0 = create_train_state(variables, tx)
step = make_train_step(model, tx, compute_dtype=jnp.bfloat16)
host_batches = list(loader)
b0 = host_batches[0]
print(f"[{time.time()-t0:.0f}s] edge_pad={b0.senders.shape[0]} run_align={b0.run_align}", flush=True)
dev_batches = [jax.device_put(b) for b in host_batches]
compiled = step.lower(state0, host_batches[0]).compile()
print(f"[{time.time()-t0:.0f}s] compiled", flush=True)

states = {k: jax.tree_util.tree_map(jnp.copy, state0) for k in ("host", "device")}
for k, batches in (("host", host_batches), ("device", dev_batches)):
    states[k], loss, _ = compiled(states[k], batches[0])
    np.asarray(loss)

K = 4
res = {"host": [], "device": []}
for seg in range(STEPS // K):
    for k, batches in (("host", host_batches), ("device", dev_batches)):
        t1 = time.perf_counter()
        for i in range(K):
            states[k], loss, _ = compiled(states[k], batches[(seg * K + i) % len(batches)])
        np.asarray(loss)
        res[k].append((time.perf_counter() - t1) / K * 1e3)

for k, ts in res.items():
    med = sorted(ts)[len(ts) // 2]
    print(f"{k}: segments={['%.1f' % t for t in ts]} median={med:.1f} g/s={1024/med*1e3:.0f}")
