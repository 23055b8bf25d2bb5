"""Capture a device trace of the flagship train step and write an xplane
profile under TRACE_DIR (default /tmp/tb_flagship). Dev tooling: pair
with tools/parse_trace.py to get the per-HLO-op time table that drove
the r03 backward-gather finding (docs/PERF.md)."""

import glob
import json
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

from hydragnn_tpu.flagship import build_flagship
from hydragnn_tpu.train import create_train_state, make_train_step, select_optimizer

config, model, variables, loader = build_flagship(
    n_samples=1280, hidden_dim=128, num_conv_layers=6, batch_size=1024,
    unit_cells=(2, 4),
)
log("flagship built")
tx = select_optimizer(config["NeuralNetwork"]["Training"])
state = create_train_state(variables, tx)
step = make_train_step(model, tx, compute_dtype=jnp.bfloat16)
batches = list(loader)
compiled = step.lower(state, batches[0]).compile()
log("compiled")

state, loss, _ = compiled(state, batches[0])
np.asarray(loss)
log(f"warmup done loss={float(loss):.4f}")

trace_dir = os.environ.get("TRACE_DIR", "/tmp/tb_flagship")
import shutil
shutil.rmtree(trace_dir, ignore_errors=True)
with jax.profiler.trace(trace_dir):
    for i in range(3):
        state, loss, _ = compiled(state, batches[(i + 1) % len(batches)])
    np.asarray(loss)
log("traced 3 steps")

planes = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
log(f"xplane files: {planes}")
