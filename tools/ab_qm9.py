"""A/B the qm9-scale config: dense gathers via local-window kernel vs
the permuted path (strip dense_sender_win), scan-slope timing.
Usage: python tools/ab_qm9.py"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ["HYDRAGNN_LOCAL_MIN_ROWS"] = "0"  # the A/B decides by batch, not gate

import jax
import jax.numpy as jnp
import numpy as np

from hydragnn_tpu.flagship import build_flagship
from hydragnn_tpu.train import create_train_state, select_optimizer
from hydragnn_tpu.train.state import _train_step_body
from hydragnn_tpu.utils.profile import scan_slope_ms

t0 = time.time()
config, model, variables, loader = build_flagship(
    n_samples=384, batch_size=256, hidden_dim=64, num_conv_layers=6,
    unit_cells=(2, 3), edge_lengths=True,
)
tx = select_optimizer(config["NeuralNetwork"]["Training"])
state = create_train_state(variables, tx)
body = _train_step_body(model, tx, compute_dtype=jnp.bfloat16)
batch = next(iter(loader))
print(f"[{time.time()-t0:.0f}s] dense={batch.dense_senders is not None} "
      f"win={batch.dense_sender_win is not None}", flush=True)

arms = {
    "win-kernel": batch,
    "permuted": batch.replace(dense_sender_win=None, sender_win=None),
}

def make_chain(b):
    def mk(k):
        def f(st, _):
            st, loss, _ = body(st, b)
            return st, loss
        fn = jax.jit(lambda st: jax.lax.scan(f, st, None, length=k))
        def run():
            _, losses = fn(state)
            np.asarray(losses[-1])
        return run
    return mk

for name, b in arms.items():
    ms = scan_slope_ms(make_chain(b), 4, 12)
    print(f"{name}: scan-slope step {ms:.3f} ms", flush=True)
