"""Readings for the limits of ``correct``, on the chip, at a cell's own size.

    python3 benchmark/readings.py --workload <cell> --seeds 11,12,13,... --controls 3

For each seed, in ONE process: the cell's own ``run_training`` through its
first epoch (``firststeps.capture``: the same entry, taps and compiled
program as a measured run, no window), the plain reference over the same
steps, and the numbers ``compare.numbers`` gives: the program's LOWER
readings. For the first ``--controls`` seeds also the UPPER readings: the
reference in the nearest precision below the configuration's (fp8 for
bfloat16) put in the program's place, and each planted fault the cell's
family names (for message passing: half of the batch left out; on a mesh,
the exchange between chips left out), each against the float32
reference. One JSON line per reading; the benchmark's
own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
for p in (os.path.dirname(HERE), HERE):
    if p not in sys.path:
        sys.path.insert(0, p)

KEYS = ("loss_gap", "grad_gap", "grad_gap_median", "grad_diff_gap", "grad_diff_median", "update_gap",
        "update_gap_median")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()

    import jax

    import cell as cellmod
    import compare
    import firststeps

    cellmod.place_compile_cache(args.rehearse)
    cell = cellmod.load_cell(args.workload, rehearse=args.rehearse)
    dev = jax.devices()
    if not args.rehearse and (dev[0].platform != "tpu" or len(dev) != cell.chips):
        raise SystemExit(f"readings: {cell.name} needs {cell.chips} TPU chip(s), found {dev}")
    fam = cell.fam
    for n, seed in enumerate(int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        taps, raw = firststeps.capture(cell, seed, os.path.join(HERE, "_work", "readings"))
        t1 = time.perf_counter()
        ref = fam.reference_run(cell, taps, raw)
        t2 = time.perf_counter()
        p0 = taps.initial_params

        def line(who, side, **extra):
            nums = compare.numbers(side, ref, p0)
            print(json.dumps({"seed": seed, "who": who, **{k: nums[k] for k in KEYS},
                              "grad_leaf": nums["grad_gap_leaf"], "grad_diff_leaf": nums["grad_diff_leaf"],
                              "grad_diff_leaf_gaps": nums["grad_diff_leaf_gaps"], "update_leaf": nums["update_gap_leaf"],
                              "loss_gaps": nums["loss_gaps"], "later_loss_gaps": nums["later_loss_gaps"],
                              "grad_leaf_gaps": nums["grad_leaf_gaps"], "update_leaf_gaps": nums["update_leaf_gaps"],
                              **extra}), flush=True)

        line("program", compare.program_side(taps), mode=taps.mode, steps=max(taps.states),
             graphs_seen=taps.graphs_seen, graphs_ref=ref["graphs"], program_s=t1 - t0, reference_s=t2 - t1)
        if n >= args.controls:
            continue
        for who, kw in [("control_fp8", {"quant": "fp8"})] + [(f"fault_{f}", {"fault": f}) for f in fam.faults(cell)]:
            side = fam.reference_run(cell, taps, raw, **kw)
            line(who, side, graphs_seen=side["graphs"], graphs_ref=ref["graphs"])


if __name__ == "__main__":
    main()
