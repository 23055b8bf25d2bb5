"""Time PNA's gather-statistics backward, the one ``gather_stats_bwd``
kernel against the E-level composition it replaces, one process, on the
chip.

    python tools/ab_presum_bwd.py [--reps 10] [--calls 5] [--out FILE]

``--edges N`` keeps the first N ids (a multiple of 1,024): a rehearsal on
the CPU (Pallas in interpret mode there), never a measurement.

Ids and shapes are the cell's own: the first train batch of
``pna-multihead-h128.train-bcc`` as the program's loader builds it from the
benchmark's samples (``--seed``, default 1), a table of its node slots at
width 128 and the forward kernel's outputs (``_gather_stats_call``), with
cotangents drawn from the seed. Each side is called directly, with no
switch: ``ops/segment_pallas.py:_gather_stats_bwd_call`` (cast to the
table dtype, as the dispatch does) and ``_gather_presum_bwd_composed``.
One program runs ``--calls`` backwards (one a conv layer: the cotangents
scaled per call), so a call's launch and read-back are shared; per dtype
and side: the median wall time of ``--reps`` programs after one warm-up,
divided by ``--calls``. Compare what a side saves per call, not the level
of one lone call. The outputs must agree: the largest difference, in units
of the larger output's largest magnitude, is reported, and the exit code
is 1 where it passes one bfloat16 ulp (2**-8) of it.
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
    ap.add_argument("--reps", type=int, default=10)
    ap.add_argument("--calls", type=int, default=5)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--out", default=os.path.join(ROOT, "chiprun_out", "ab_presum_bwd.json"))
    ap.add_argument("--edges", type=int, default=0)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    sp = importlib.import_module("hydragnn_tpu.ops.segment_pallas")
    interpret = jax.default_backend() != "tpu"
    if interpret:
        os.environ["HYDRAGNN_PALLAS"] = "interpret"
        os.environ["HYDRAGNN_LOCAL_MIN_ROWS"] = "0"

    batch, n, k_group = cell_batch("pna-multihead-h128.train-bcc", args.seed)
    cut = slice(0, args.edges or None)
    ids, mask, win = batch.senders[cut], batch.edge_mask[cut], batch.sender_win
    if args.edges:
        from hydragnn_tpu.graph.batch import _block_windows

        host = np.asarray(ids)
        win = jnp.asarray(_block_windows(host, np.argsort(host, kind="stable").astype(np.int32), n))
    rng = np.random.default_rng(args.seed)
    scales = jnp.asarray(1.0 + np.arange(args.calls, dtype=np.float32) / args.calls)

    def kernel(t, b, gs, gb):
        return sp._gather_stats_bwd_call(t, ids, mask, b, gs, gb, k_group, interpret).astype(t.dtype)

    def composed(t, b, gs, gb):
        return sp._gather_presum_bwd_composed(t, ids, mask, win, b, gs, gb, n, k_group)

    def per_layer(fn):
        # one program, ``calls`` backwards: what a step's conv layers pay
        def run(t, b, gs, gb):
            return [fn(t, b, gs * s, (gb.astype(jnp.float32) * s).astype(gb.dtype)) for s in scales]
        return jax.jit(run)

    forward = jax.jit(lambda t: sp._gather_stats_call(t, ids, mask, k_group, interpret))
    sides = (("composed", per_layer(composed)), ("gather_stats_bwd", per_layer(kernel)))
    rows, worst = [], 0.0
    for dt in (jnp.bfloat16, jnp.float32):
        table = jnp.asarray(rng.normal(size=(n, 128)).astype(np.float32)).astype(dt)
        stats, both = forward(table)
        gs = jnp.asarray(rng.normal(size=stats.shape).astype(np.float32))
        gb = jnp.asarray(rng.normal(size=both.shape).astype(np.float32)).astype(dt)
        outs = {}
        for side, f in sides:
            out = jax.block_until_ready(f(table, both, gs, gb))
            times = []
            for _ in range(args.reps):
                t0 = time.perf_counter()
                jax.block_until_ready(f(table, both, gs, gb))
                times.append(time.perf_counter() - t0)
            outs[side] = [np.asarray(o.astype(jnp.float32)) for o in out]
            row = {"side": side, "dtype": jnp.dtype(dt).name, "edges": int(ids.shape[0]), "rows": n,
                   "calls": args.calls, "median_ms_per_call": 1e3 * statistics.median(times) / args.calls,
                   "min_ms_per_call": 1e3 * min(times) / args.calls,
                   "max_ms_per_call": 1e3 * max(times) / args.calls}
            rows.append(row)
            print(json.dumps(row), flush=True)
        scale = max(float(np.abs(o).max()) for o in outs["composed"])
        diff = max(float(np.abs(a - b).max()) for a, b in zip(outs["composed"], outs["gather_stats_bwd"]))
        equal = sum(int((a == b).sum()) for a, b in zip(outs["composed"], outs["gather_stats_bwd"]))
        size = sum(a.size for a in outs["composed"])
        saved = rows[-2]["median_ms_per_call"] - rows[-1]["median_ms_per_call"]
        agree = {"dtype": jnp.dtype(dt).name, "saved_ms_per_call": saved, "max_abs_diff": diff,
                 "max_abs": scale, "diff_over_max": diff / max(scale, 1e-30), "equal_share": equal / size}
        worst = max(worst, agree["diff_over_max"])
        rows.append(agree)
        print(json.dumps(agree), flush=True)
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump({"device": jax.devices()[0].device_kind, "reps": args.reps, "rows": rows}, f, indent=1)
    return 0 if worst <= 2.0**-8 else 1


if __name__ == "__main__":
    sys.exit(main())
