"""Time the windowed one-hot gathers at several table-window widths
(``ops/segment_pallas.py:BW``), one process, on the chip (ISSUE 32).

    python tools/ab_window.py [--widths 128,256,528] [--reps 10] [--out FILE]

``--edges N`` keeps the first N ids of each case: a rehearsal on the CPU
(Pallas in interpret mode there), never a measurement.

Ids and shapes are the cells' own: the first train batch of
``pna-multihead-h128.train-bcc`` and of ``schnet-h128.train-bcc`` as the
program's loader builds them from the benchmark's samples (seed 1), plus
the low-degree sorted case of ``tests/test_ops_pallas.py`` (about one id
a row, so a 1,024-id chunk spans about 1,000 rows) scaled to 1.5M ids.
Per width, dtype and kernel: the median wall time of ``--reps`` calls
after one warm-up call (each ends in ``block_until_ready``), and whether
the output equals the first width's bit for bit. The width is a module
constant; this tool alone sets it, between compiles.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "benchmark"))


def cell_batch(workload: str, seed: int):
    import cell as cellmod
    from hydragnn_tpu.api import prepare_loaders_and_config

    c = cellmod.load_cell(workload)
    raw = c.fam.generate(c.traffic, seed)
    train, _, _, _ = prepare_loaders_and_config(c.run_config, c.fam.program_samples(raw))
    return train.peek_batch(), train.pad_nodes, int(train.run_align or 8)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--widths", default="128,256,528")
    ap.add_argument("--reps", type=int, default=10)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--out", default=os.path.join(ROOT, "chiprun_out", "ab_window.json"))
    ap.add_argument("--edges", type=int, default=0)
    args = ap.parse_args()
    widths = [int(w) for w in args.widths.split(",")]

    import jax
    import jax.numpy as jnp
    import numpy as np

    sp = importlib.import_module("hydragnn_tpu.ops.segment_pallas")
    fc = importlib.import_module("hydragnn_tpu.ops.fused_conv")
    interpret = jax.default_backend() != "tpu"

    rng = np.random.default_rng(args.seed)
    pna, pna_n, k_group = cell_batch("pna-multihead-h128.train-bcc", args.seed)
    sch, sch_n, _ = cell_batch("schnet-h128.train-bcc", args.seed)
    e_low = args.edges or 1_536_000
    cut = slice(0, args.edges or None)
    n_low = e_low * 2000 // 2048  # the test's 2,048 ids over 2,000 rows, scaled
    low_ids = jnp.asarray(np.sort(rng.integers(0, n_low, e_low)).astype(np.int32))

    def table(n, dt):
        return jnp.asarray(rng.normal(size=(n, 128)).astype(np.float32)).astype(dt)

    def kernels(dt):
        t_pna, t_sch, t_low = table(pna_n, dt), table(sch_n, dt), table(n_low, dt)
        e = sch.senders[cut].shape[0]
        scale = jnp.asarray(rng.normal(size=(e, 128)).astype(np.float32)).astype(dt)
        cases = {
            "bcast_gather.local (cell 1 senders)": (
                lambda t, i: sp._bcast_kernel_call(t, i, interpret, sorted_ids=False), (t_pna, pna.senders[cut])),
            "bcast_gather.sorted (cell 2 receivers)": (
                lambda t, i: sp._bcast_kernel_call(t, i, interpret, sorted_ids=True), (t_sch, sch.receivers[cut])),
            "gather_stats (cell 1 senders)": (
                lambda t, i, m: sp._gather_stats_call(t, i, m, k_group, interpret),
                (t_pna, pna.senders[cut], pna.edge_mask[cut])),
            "fused_conv.scale fwd (cell 2)": (
                lambda x, s, r, m, sc, occ: fc._fused_kernel_call(
                    x, s, r, m, None, None, None, None, sc, occ, sch_n, (0, ()), interpret),
                (t_sch, sch.senders[cut], sch.receivers[cut], sch.edge_mask[cut], scale, sch.edge_occupancy)),
            "bcast_gather.sorted low degree (1.5M ids)": (
                lambda t, i: sp._bcast_kernel_call(t, i, interpret, sorted_ids=True), (t_low, low_ids)),
        }
        # jax.clear_caches() below makes each wrapper trace again at the next width
        return {name: (jax.jit(fn), ops) for name, (fn, ops) in cases.items()}

    rows, first = [], {}
    for dt in (jnp.bfloat16, jnp.float32):
        ks = kernels(dt)
        for w in widths:
            sp.BW = fc.BW = w
            jax.clear_caches()
            for name, (f, ops) in ks.items():
                out = jax.block_until_ready(f(*ops))
                times = []
                for _ in range(args.reps):
                    t0 = time.perf_counter()
                    jax.block_until_ready(f(*ops))
                    times.append(time.perf_counter() - t0)
                host = [np.asarray(o) for o in jax.tree_util.tree_leaves(out)]
                key = (name, jnp.dtype(dt).name)
                same = None
                if key in first:
                    same = all(np.array_equal(a, b) for a, b in zip(host, first[key]))
                else:
                    first[key] = host
                row = {"kernel": name, "dtype": jnp.dtype(dt).name, "width": w,
                       "median_ms": 1e3 * statistics.median(times), "min_ms": 1e3 * min(times),
                       "max_ms": 1e3 * max(times), "equal_to_first_width": same}
                rows.append(row)
                print(json.dumps(row), flush=True)
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump({"device": jax.devices()[0].device_kind, "reps": args.reps, "rows": rows}, f, indent=1)
    return 0 if all(r["equal_to_first_width"] is not False for r in rows) else 1


if __name__ == "__main__":
    sys.exit(main())
