"""HLO-derived distributed scaling estimate (VERDICT r03 item 4 /
r04 item 6).

Real multi-chip hardware is unavailable here, so instead of ASSUMING a
DP efficiency factor (BASELINE.md previously used 0.9 with no support,
then a single 8-way-derived 0.997), this derives the scaling model from
the compiled programs themselves:

  1. jit the FULL flagship train step over 8-, 16-, and 32-way data
     meshes (virtual CPU devices — the SPMD partitioner emits the same
     collective structure it would on a TPU pod slice);
  2. read the per-step collective bytes straight from each compiled
     HLO (the gradient all-reduce over the data axis; ring all-reduce
     moves 2(n-1)/n x bytes over ICI per chip);
  3. add the OFF-STEP collectives a training run actually pays — the
     eval path's padded variable-length all-gather
     (train/loop.py:_allgather_varlen) and the checkpoint write
     (utils/checkpoint.py; ZeRO-1 shards write 1/n each) — amortized
     per step at a stated cadence;
  4. convert to expected wire time on the v5e/v4 public link budgets,
     with an optional DCN hop term for data axes spanning multiple ICI
     slices, and derive per-width DP efficiency;
  5. project the v4-32 (16-chip) north-star aggregate from the
     MEASURED single-chip traced step time, bandwidth-scaled to v4's
     HBM, times the DERIVED 16-way efficiency — replacing BASELINE.md's
     hand arithmetic.

Writes SCALING_est_r06.json (override with SCALING_OUT) and prints it.
FSDP variants (``FSDP_WIDTHS``, default "2,4") additionally compile the
largest width with parameters+optimizer sharded over an fsdp axis and
model the all-gather/reduce-scatter wire traffic the HLO then carries.

Link budgets: v5e exposes 4 ICI links/chip in a 2D torus (1,600 Gbps
aggregate = 200 GB/s); a ring all-reduce uses one axis, and achievable
efficiency on real pods is ~80-90% of nominal. ICI_GBPS (default 45 =
one link direction x 90%) keeps the estimate conservative. v4's ICI is
faster per link; reusing the v5e number is again conservative. DCN
(multi-slice) planning number: DCN_GBPS per host, default 12.5
(100 Gbps NICs x ~=1 direction), 4 chips/host on v4.
"""

import json
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

MESH_SIZES = [int(s) for s in os.environ.get("MESH_SIZES", "8,16,32").split(",")]

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "")
    + f" --xla_force_host_platform_device_count={max(MESH_SIZES)}"
)

import jax

jax.config.update("jax_platforms", "cpu")

import jax.numpy as jnp
import numpy as np
ICI_GBPS = float(os.environ.get("ICI_GBPS", 45.0))
DCN_GBPS = float(os.environ.get("DCN_GBPS", 12.5))
# single-chip flagship step, device self time from a trace of the
# earlier rig (its records are deleted; override with STEP_MS_DEVICE
# once the benchmark has a number)
STEP_MS_DEVICE = float(os.environ.get("STEP_MS_DEVICE", 77.8))
# v4 vs v5e HBM bandwidth ratio: the workload is bandwidth-bound
# (docs/PERF.md "Honest throughput"), so per-chip step time scales with
# HBM bandwidth to first order
V4_BW_SCALE = 1228.0 / 820.0
V4_32_CHIPS = 16  # a v4-32 slice = 16 chips (32 TensorCores)
BATCH_PER_CHIP = 1024
# off-step cadences for the amortized terms
STEPS_PER_EPOCH = int(os.environ.get("STEPS_PER_EPOCH", 50))
EPOCHS_PER_CHECKPOINT = int(os.environ.get("EPOCHS_PER_CHECKPOINT", 1))


def _dtype_bytes(tag: str) -> int:
    return {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4,
            "pred": 1, "s8": 1, "u8": 1}.get(tag, 4)


def collective_bytes(hlo: str) -> dict:
    """Sum result bytes of every collective in the HLO text, by kind.
    Handles tuple-typed results (one all-reduce over many gradient
    leaves) and async start/done pairs (counting the start only)."""
    shape_pat = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
    line_pat = re.compile(
        r"=\s*(.*?)\s*"
        r"(all-reduce|reduce-scatter|all-gather|all-to-all|collective-permute)"
        r"(?:-start)?\("
    )
    out = {}
    for line in hlo.splitlines():
        m = line_pat.search(line)
        if not m or f"{m.group(2)}-done" in line:
            continue
        total = 0
        for dtype, shape in shape_pat.findall(m.group(1)):
            elems = 1
            for d in shape.split(","):
                if d.strip():
                    elems *= int(d)
            total += elems * _dtype_bytes(dtype)
        out[m.group(2)] = out.get(m.group(2), 0) + total
    return out


def compile_width(n_dev: int, fsdp: int = 1) -> dict:
    """Compile the partitioned flagship step over an n_dev mesh
    (``data = n_dev/fsdp × fsdp``) and return its collective-bytes table
    + parameter size + the partitioner's per-device state bytes. The
    SAME Partitioner train/serve/bench use builds the step, so the HLO
    read here is the HLO a real run compiles."""
    from hydragnn_tpu.flagship import build_flagship
    from hydragnn_tpu.parallel import Partitioner
    from hydragnn_tpu.train import create_train_state, select_optimizer

    config, model, variables, loader = build_flagship(
        n_samples=4 * n_dev * 2, batch_size=4 * n_dev, device_stack=n_dev,
        hidden_dim=128, num_conv_layers=6,
    )
    part = Partitioner(data=n_dev // fsdp, fsdp=fsdp)
    tx = select_optimizer(config["NeuralNetwork"]["Training"])
    state = part.shard_init(create_train_state(variables, tx))
    step = part.shard_train_step(model, tx, compute_dtype=jnp.bfloat16)
    batch = next(iter(loader))
    hlo = step.lower(state, batch).compile().as_text()
    param_bytes = sum(
        int(np.prod(p.shape)) * 4
        for p in jax.tree_util.tree_leaves(variables["params"])
    )
    man = part.manifest(state=state)
    return {
        "collectives": collective_bytes(hlo),
        "param_bytes": param_bytes,
        "fsdp": fsdp,
        "state_bytes_per_device": (
            man["params"]["bytes_per_device"] + man["opt"]["bytes_per_device"]
        ),
        "state_bytes_global": (
            man["params"]["bytes_global"] + man["opt"]["bytes_global"]
        ),
    }


def width_record(n_dev: int, comp: dict, dcn_slices: int = 1) -> dict:
    """Efficiency model for one mesh width.

    In-step: ring all-reduce wire bytes over ICI; with an fsdp axis the
    compiled program additionally carries the FSDP parameter all-gather
    and gradient/state reduce-scatter — read from the SAME HLO and
    modeled as rings over the fsdp axis width (each chip wires
    (f-1)/f of the payload per collective). When the data axis spans
    ``dcn_slices`` ICI slices, the inter-slice fraction of the ring
    rides DCN instead (2(s-1)/s of the payload crosses a slice boundary
    once per direction, shared by the slice's hosts)."""
    ar = comp["collectives"].get("all-reduce", 0)
    n = n_dev
    wire = 2 * (n - 1) / n * ar
    t_ici_ms = wire / (ICI_GBPS * 1e9) * 1e3
    # FSDP wire traffic (zero on pure-DP meshes, whose HLO carries no
    # all-gather/reduce-scatter): parameters all-gather into the step,
    # gradients/optimizer state reduce-scatter out of it, both ringing
    # over the fsdp axis
    f = int(comp.get("fsdp", 1) or 1)
    ag = comp["collectives"].get("all-gather", 0)
    rs = comp["collectives"].get("reduce-scatter", 0)
    fsdp_wire = ((f - 1) / f) * (ag + rs) if f > 1 else 0.0
    t_fsdp_ms = fsdp_wire / (ICI_GBPS * 1e9) * 1e3
    t_dcn_ms = 0.0
    if dcn_slices > 1:
        # ring over slices: each slice boundary carries the full reduced
        # payload once per direction; per-host DCN bandwidth shared by
        # the 4 chips of a v4 host
        dcn_wire = 2 * (dcn_slices - 1) / dcn_slices * ar
        t_dcn_ms = dcn_wire / (DCN_GBPS * 1e9) * 1e3
    # off-step terms, amortized per step:
    #  - eval all-gather: every process contributes its padded
    #    predictions once per epoch (head dims ~4 f32 per graph at
    #    flagship scale; n_max rows ~ batch_per_chip * steps_per_epoch)
    eval_rows = BATCH_PER_CHIP * STEPS_PER_EPOCH
    eval_bytes = eval_rows * 4 * 4 * n  # rows x heads x f32 x processes
    t_eval_ms = eval_bytes / (DCN_GBPS * 1e9) * 1e3 / STEPS_PER_EPOCH
    #  - checkpoint: ZeRO-1 shards write param+opt (3x params f32) / n
    #    per chip to storage once per EPOCHS_PER_CHECKPOINT epochs
    ckpt_bytes = 3 * comp["param_bytes"] / n
    t_ckpt_ms = (
        ckpt_bytes / (DCN_GBPS * 1e9) * 1e3
        / (STEPS_PER_EPOCH * EPOCHS_PER_CHECKPOINT)
    )
    exposed = t_ici_ms + t_fsdp_ms + t_dcn_ms + t_eval_ms + t_ckpt_ms
    eff_no_overlap = STEP_MS_DEVICE / (STEP_MS_DEVICE + exposed)
    eff_half_overlap = STEP_MS_DEVICE / (STEP_MS_DEVICE + 0.5 * exposed)
    rec = {
        "n_devices": n,
        "fsdp": f,
        "dcn_slices": dcn_slices,
        "collective_bytes_per_step": comp["collectives"],
        "allreduce_bytes_per_step": int(ar),
        "wire_bytes_per_chip_ring": int(wire),
        "t_ici_ms": round(t_ici_ms, 3),
        "t_dcn_ms": round(t_dcn_ms, 3),
        "t_eval_allgather_ms_amortized": round(t_eval_ms, 4),
        "t_checkpoint_ms_amortized": round(t_ckpt_ms, 4),
        "dp_efficiency_no_overlap": round(eff_no_overlap, 4),
        "dp_efficiency_half_overlap": round(eff_half_overlap, 4),
    }
    if f > 1:
        rec.update(
            {
                "allgather_bytes_per_step": int(ag),
                "reduce_scatter_bytes_per_step": int(rs),
                "fsdp_wire_bytes_per_chip_ring": int(fsdp_wire),
                "t_fsdp_ms": round(t_fsdp_ms, 3),
                "state_bytes_per_device": comp.get("state_bytes_per_device"),
                "state_bytes_global": comp.get("state_bytes_global"),
            }
        )
    return rec


def skew_tolerance_block(widths: dict) -> dict:
    """Model-derived ``step_skew`` trigger defaults (consumed by
    ``obs/podview.py`` as the default threshold on the cross-host
    epoch-duration skew gauge). A layout's no-overlap efficiency already
    concedes ``1 - eff`` of step time to exposed wire; observed skew
    beyond ~4x that concession cannot be the modeled collectives and
    indicates a genuine straggler. The threshold is floored at 0.2
    (host-level noise on shared machines) and capped at 0.5."""
    per_width = {}
    worst = 0.0
    for name, w in sorted(widths.items()):
        eff = w.get("dp_efficiency_no_overlap")
        if eff is None:
            continue
        thr = round(min(0.5, max(0.2, 4.0 * (1.0 - float(eff)))), 4)
        per_width[name] = {
            "dp_efficiency_no_overlap": eff,
            "skew_frac_threshold": thr,
        }
        worst = max(worst, thr)
    return {
        "derivation": (
            "threshold = clamp(4 x (1 - dp_efficiency_no_overlap), 0.2, 0.5)"
        ),
        "per_width": per_width,
        "default_step_skew_threshold": round(worst, 4) if per_width else 0.25,
    }


def main():
    widths = {}
    comp_by_n = {}
    for n in MESH_SIZES:
        print(f"compiling {n}-way sharded step ...", file=sys.stderr)
        comp_by_n[n] = compile_width(n)
        widths[str(n)] = width_record(n, comp_by_n[n])
    # FSDP variants at the largest width: the (data = n/f, fsdp = f)
    # layouts of the SAME computation — all-gather/reduce-scatter wire
    # traffic read from their compiled HLO, state bytes per device from
    # the partitioner's committed shardings
    n_max = max(MESH_SIZES)
    fsdp_widths = [
        int(s)
        for s in os.environ.get("FSDP_WIDTHS", "2,4").split(",")
        if s.strip()
    ]
    for f in fsdp_widths:
        if f <= 1 or n_max % f:
            continue
        print(f"compiling {n_max}-way fsdp={f} step ...", file=sys.stderr)
        widths[f"{n_max}_fsdp{f}"] = width_record(
            n_max, compile_width(n_max, fsdp=f)
        )
    # multi-slice variants at 32-way: the data axis spanning 2 and 4
    # ICI slices (DCN between slices)
    if 32 in comp_by_n:
        for s in (2, 4):
            widths[f"32_dcn{s}slices"] = width_record(32, comp_by_n[32], dcn_slices=s)

    # v4-32 north-star projection from measured device time + derived
    # 16-way efficiency (replaces BASELINE.md's hand arithmetic)
    eff16 = widths.get("16", {}).get("dp_efficiency_no_overlap", None)
    step_ms_v4 = STEP_MS_DEVICE / V4_BW_SCALE
    gps_chip_v4 = BATCH_PER_CHIP / step_ms_v4 * 1e3
    projection = {
        "platform": "v4-32 (16 chips, one ICI slice)",
        "assumption": (
            "bandwidth-bound workload: per-chip step time scales with "
            "HBM bandwidth (v4 1228 / v5e 820); efficiency from the "
            "16-way compiled-HLO model (no-overlap floor)"
        ),
        "step_ms_device_v4_chip": round(step_ms_v4, 2),
        "graphs_per_sec_per_chip_v4": round(gps_chip_v4, 1),
        "dp_efficiency_16way": eff16,
        "aggregate_graphs_per_sec": (
            round(V4_32_CHIPS * gps_chip_v4 * eff16, 1) if eff16 else None
        ),
    }

    rec = {
        "mesh": (
            "Partitioner (data[, fsdp]) over ICI (+DCN variants); "
            "fsdp variants shard params+optimizer over the fsdp axis"
        ),
        "step_ms_device_single_chip": STEP_MS_DEVICE,
        "batch_per_chip": BATCH_PER_CHIP,
        "ici_gbps_assumed": ICI_GBPS,
        "dcn_gbps_assumed": DCN_GBPS,
        "steps_per_epoch_assumed": STEPS_PER_EPOCH,
        "param_bytes_f32": comp_by_n[MESH_SIZES[0]]["param_bytes"],
        "widths": widths,
        "skew_tolerance": skew_tolerance_block(widths),
        "v4_32_projection": projection,
        "note": (
            "Collective bytes read from compiled SPMD HLO at each width "
            "(virtual CPU mesh; same partitioner as TPU). Efficiency = "
            "compute / (compute + exposed wire time); no-overlap is the "
            "floor, half-overlap the planning number. Off-step terms "
            "(eval padded all-gather, ZeRO-1 sharded checkpoint write) "
            "amortized at the stated cadence. SCALING_cpu8.json remains "
            "correctness-only evidence (shared-core timings are not a "
            "scaling measurement)."
        ),
    }
    out = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        os.environ.get("SCALING_OUT", "SCALING_est_r06.json"),
    )
    with open(out, "w") as f:
        json.dump(rec, f, indent=1)
    print(json.dumps(rec, indent=1))


if __name__ == "__main__":
    main()
