"""Aggregate /tmp/hlo_stats.csv (xprof's hlo_stats table of a device
trace, exported by its converter) into a per-component time/bytes table.

Prints (a) top-K ops by total self time, (b) category rollup, and
(c) trace-measured HBM bytes per step — self_time x measured BW summed
over ops — the measurement that replaces cost-model bytes in bench.py
(VERDICT r03 Weak #2).

Usage: python tools/analyze_hlo_stats.py [/tmp/hlo_stats.csv] [n_steps] [n_top]
"""

import csv
import json
import re
import sys
from collections import defaultdict

_ITEMSIZE = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8, "u64": 8,
    "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1,
}

_SHAPE_RE = re.compile(
    r"\b(f64|f32|bf16|f16|s64|u64|s32|u32|s16|u16|s8|u8|pred)\[([\d,]*)\]"
)


def _customcall_bytes(expr: str) -> float:
    """Operand+result sizes of a custom-call (Pallas kernel). xprof
    reports no memory BW for custom-calls, so their DMA traffic is
    invisible to the measured total; the CSR kernels stream each
    operand exactly once by construction, so the static shape sum is
    a sound per-op estimate (window-looping chunks can re-read table
    rows, making this a slight UNDER-estimate on jumpy ids)."""
    head = expr.split("custom_call_target", 1)[0]
    total = 0.0
    for dt, dims in _SHAPE_RE.findall(head):
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _ITEMSIZE[dt]
    return total


def main():
    path = sys.argv[1] if len(sys.argv) > 1 else "/tmp/hlo_stats.csv"
    n_steps = int(sys.argv[2]) if len(sys.argv) > 2 else 3
    n_top = int(sys.argv[3]) if len(sys.argv) > 3 else 30

    raw = open(path).read()
    rows = []
    if raw.lstrip().startswith("{"):  # gviz JSON despite the .csv name
        tab = json.loads(raw)
        cols = [c["id"] for c in tab["cols"]]
        dicts = [
            {cols[i]: (cell or {}).get("v") for i, cell in enumerate(row["c"])}
            for row in tab["rows"]
        ]
    else:
        dicts = list(csv.DictReader(raw.splitlines()))
    for r in dicts:
        try:
            t_us = float(r.get("total_self_time", 0) or 0)
        except ValueError:
            continue
        if t_us <= 0:
            continue
        bw = float(r.get("measured_memory_bw", 0) or 0)  # GiB/s
        full_expr = str(r.get("hlo_op_expression", "") or "")
        cat = str(r.get("category", ""))
        rows.append(
            {
                "op": str(r.get("hlo_op_name", "")),
                "cat": cat,
                "tf": str(r.get("tf_op_name", "")),
                "n": int(float(r.get("occurrences", 1) or 1)),
                "us": t_us,
                "bytes": bw * (2**30) * (t_us / 1e6),
                "kbytes": _customcall_bytes(full_expr)
                * int(float(r.get("occurrences", 1) or 1))
                if cat == "custom-call"
                else 0.0,
                "bound": str(r.get("bound_by", "")),
                "expr": full_expr[:160],
            }
        )

    if not rows:
        raise SystemExit(f"no rows with positive self time parsed from {path}")
    tot_ms = sum(r["us"] for r in rows) / 1e3
    tot_bytes = sum(r["bytes"] for r in rows)
    kernel_bytes = sum(r["kbytes"] for r in rows)
    print(f"total device self time: {tot_ms:.1f} ms over {n_steps} steps "
          f"-> {tot_ms / n_steps:.1f} ms/step")
    print(f"trace-measured HBM traffic: {tot_bytes / 1e9:.2f} GB "
          f"-> {tot_bytes / n_steps / 1e9:.2f} GB/step "
          f"-> {tot_bytes / (tot_ms / 1e3) / 1e9:.1f} GB/s average")
    if kernel_bytes:
        comb = tot_bytes + kernel_bytes
        print(
            f"custom-call (Pallas) traffic, est. from operand+result "
            f"shapes (invisible to xprof BW counters): "
            f"{kernel_bytes / n_steps / 1e9:.2f} GB/step -> combined "
            f"{comb / n_steps / 1e9:.2f} GB/step = "
            f"{comb / (tot_ms / 1e3) / 1e9:.1f} GB/s average"
        )
    print()

    print(f"== top {n_top} ops by self time (ms/step) ==")
    for r in sorted(rows, key=lambda r: -r["us"])[:n_top]:
        print(
            f"{r['us']/1e3/n_steps:8.2f} ms {r['bytes']/n_steps/1e9:7.2f} GB "
            f"{r['cat'][:18]:18s} {r['bound'][:10]:10s} {r['op'][:28]:28s} "
            f"{r['tf'][:70]}"
        )

    print()
    print("== category rollup (ms/step) ==")
    cats = defaultdict(lambda: [0.0, 0.0, 0])
    for r in rows:
        c = cats[r["cat"]]
        c[0] += r["us"]
        c[1] += r["bytes"]
        c[2] += r["n"]
    for name, (us, b, n) in sorted(cats.items(), key=lambda kv: -kv[1][0]):
        print(f"{us/1e3/n_steps:8.2f} ms {b/n_steps/1e9:7.2f} GB  n={n:5d}  {name}")

    out = {
        "ms_per_step": tot_ms / n_steps,
        "measured_bytes_per_step": tot_bytes / n_steps,
        "measured_hbm_gbps": tot_bytes / (tot_ms / 1e3) / 1e9,
        "kernel_bytes_est_per_step": kernel_bytes / n_steps,
        "combined_hbm_gbps_est": (tot_bytes + kernel_bytes)
        / (tot_ms / 1e3)
        / 1e9,
        "n_steps": n_steps,
    }
    with open("/tmp/hlo_summary.json", "w") as f:
        json.dump(out, f)
    print("\nwrote /tmp/hlo_summary.json:", json.dumps(out))


if __name__ == "__main__":
    main()
