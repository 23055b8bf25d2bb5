"""Trace-based qm9-scale device time for both dense-gather paths —
scan-slope is noisy at this config's scale, the summed HLO self time is
not. Usage: python tools/trace_qm9.py [min_rows_values...]"""

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
    n_samples=384, batch_size=256, hidden_dim=64, num_conv_layers=6,
    unit_cells=(2, 3), edge_lengths=True,
)
tx = select_optimizer(config["NeuralNetwork"]["Training"])
state0 = create_train_state(variables, tx)
batch = next(iter(loader))
step = make_train_step(model, tx, compute_dtype=jnp.bfloat16)

arms = {
    "win-kernel": batch,
    "permuted": batch.replace(dense_sender_win=None, sender_win=None),
}
os.environ["HYDRAGNN_LOCAL_MIN_ROWS"] = "0"  # let the batch decide

for name, b in arms.items():
    compiled = step.lower(state0, b).compile()
    st = jax.tree_util.tree_map(jnp.copy, state0)
    st, loss, _ = compiled(st, b)
    np.asarray(loss)
    tdir = f"/tmp/tq_{name}"
    shutil.rmtree(tdir, ignore_errors=True)
    with jax.profiler.trace(tdir):
        for _ in range(3):
            st, loss, _ = compiled(st, b)
        np.asarray(loss)
    planes = glob.glob(f"{tdir}/**/*.xplane.pb", recursive=True)
    from xprof.convert import raw_to_tool_data as rd
    import json as _json

    data, _ = rd.xspace_to_tool_data(planes, "hlo_stats", {"tqx": "out:csv;"})
    tab = _json.loads(data.decode() if isinstance(data, bytes) else data)
    cols = [c["id"] for c in tab["cols"]]
    i_t = cols.index("total_self_time")
    tot = sum(float((r["c"][i_t] or {}).get("v") or 0) for r in tab["rows"])
    print(f"{name}: device {tot/3e3:.3f} ms/step", flush=True)
