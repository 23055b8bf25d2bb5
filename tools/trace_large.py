"""Trace the large_graph bench config's train step (per-op device
table). Usage: python tools/trace_large.py"""

import glob
import os
import shutil
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from hydragnn_tpu.flagship import build_flagship
from hydragnn_tpu.train import create_train_state, make_train_step, select_optimizer

t0 = time.time()
config, model, variables, loader = build_flagship(
    n_samples=48, batch_size=32, hidden_dim=128, num_conv_layers=6,
    unit_cells=(6, 8),
)
tx = select_optimizer(config["NeuralNetwork"]["Training"])
state = create_train_state(variables, tx)
step = make_train_step(model, tx, compute_dtype=jnp.bfloat16)
batch = next(iter(loader))
print(f"[{time.time()-t0:.0f}s] node_pad={batch.nodes.shape[0]} "
      f"edge_pad={batch.senders.shape[0]} run_align={batch.run_align}", flush=True)
compiled = step.lower(state, batch).compile()
state, loss, _ = compiled(state, batch)
np.asarray(loss)
print(f"[{time.time()-t0:.0f}s] warmup loss={float(loss):.4f}", flush=True)
tdir = os.environ.get("TRACE_DIR", "/tmp/tb_large")
shutil.rmtree(tdir, ignore_errors=True)
with jax.profiler.trace(tdir):
    for _ in range(3):
        state, loss, _ = compiled(state, batch)
    np.asarray(loss)
print("traced; parse with: python tools/parse_trace.py", tdir, flush=True)
