"""Decides ``correct``: the timed program's first dispatches against the
plain reference's, each number beside a limit of its own.

What is compared is what the window's own compiled program produced at the
start of the run, captured by ``taps.py`` on its way through
``run_training``:

  loss_gap    the loss of the first optimizer step(s) at the initial
              weights, against the reference's: |program - reference| over
              the reference's, the worst of the steps seen.
  grad_gap    the norm of the gradients as the optimizer got them, leaf by
              leaf, from Adam's second moment (``grad_norms``).
  update_gap  the norm of each parameter leaf's change after the first
              real steps (8 in a scanned epoch, 3 on a mesh).
  graphs_step_diff, edges_step1_diff
              exact: the real graphs the program counted into each step,
              and the edges it built for the first step's samples, against
              the benchmark's own count.

On a mesh the program dispatches step by step and its state is visible
after every step: the loss is step 1's, the second moment after step 1 IS
the first gradient squared (times 0.001). On one chip a whole epoch is ONE
scanned program whose state shows only after its last step, and by then a
bfloat16 and a float32 run have drifted apart (at step 1 Adam moves every
weight by the learning rate times the SIGN of its gradient, so rounding in
a small gradient becomes a full-size move: the later steps' losses read
1-18% apart on sound runs, my chip runs, PR 22). So ``taps.py`` first
sends the same compiled program once over a copy of the state whose
learning rate is 0: all of the epoch's losses and gradients at the initial
weights, nothing moved. ``loss_gap`` and ``grad_gap`` are taken from that
pass, ``update_gap`` from the real first epoch that follows.

Norms are compared leaf by leaf, as the gap between the program's norm and
the reference's over the larger of the reference's norm of that leaf and of
the median leaf; the worst leaf counts. Leaves whose reference gradient is
under a thousandth of the median leaf's (a bias in front of a BatchNorm
has no gradient but rounding) are left out of the change.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Tuple

import jax
import numpy as np

NUMBERS = ("loss_gap", "grad_gap", "update_gap")


def leaf_norms(tree) -> Dict[str, float]:
    leaves, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {jax.tree_util.keystr(p): float(np.linalg.norm(np.asarray(x, np.float64))) for p, x in leaves}


def grad_norms(state) -> Dict[str, float]:
    """Per leaf, the root of the summed Adam second moment over 1 - b2:
    after one step exactly the norm of the first gradient as the optimizer
    got it, after n steps the root-sum-square of the n gradients' norms
    (0.999**k weighted). Unlike the first moment it cannot cancel."""
    leaves, _ = jax.tree_util.tree_flatten_with_path(state["nu"])
    return {jax.tree_util.keystr(p): float(np.sqrt(np.asarray(x, np.float64).sum() / 1e-3)) for p, x in leaves}


def leaf_gaps(prog: Dict[str, float], ref: Dict[str, float], skip=()) -> Dict[str, float]:
    """Per leaf |prog - ref| / max(ref_leaf, median ref leaf)."""
    med = float(np.median(list(ref.values())))
    return {k: abs(prog[k] - r) / max(r, med, 1e-30) for k, r in ref.items() if k not in skip}


def worst(gaps: Dict[str, float]) -> Tuple[float, str]:
    best, where = 0.0, ""
    for k, g in gaps.items():
        if not g <= best:  # NaN counts as the worst
            best, where = g, k
    return best, where


def numbers(prog: Dict[str, Any], ref: Dict[str, Any], p0) -> Dict[str, Any]:
    """``prog`` / ``ref``: {"losses": per real step, "states": {steps: state},
    "probe": None or {"losses", "state"}}. Used by the run, the readings
    and the tests alike."""
    out: Dict[str, Any] = {}
    if ref.get("probe"):
        pl, rl = prog["probe"]["losses"], ref["probe"]["losses"]
        g_prog, g_ref = grad_norms(prog["probe"]["state"]), grad_norms(ref["probe"]["state"])
    else:
        first = min(ref["states"])
        pl, rl = prog["losses"][:1], ref["losses"][:1]
        g_prog, g_ref = grad_norms(prog["states"][first]), grad_norms(ref["states"][first])
    out["loss_gaps"] = [abs(a - b) / max(abs(b), 1e-30) for a, b in zip(pl, rl)]
    out["loss_gap"] = float(max(out["loss_gaps"]))  # each step's loss has to agree: the worst counts
    gaps = leaf_gaps(g_prog, g_ref)
    out["grad_gap"], out["grad_gap_leaf"] = worst(gaps)
    out["grad_gap_median"] = float(np.median(list(gaps.values())))
    out["grad_leaf_gaps"] = gaps
    med = float(np.median(list(g_ref.values())))
    flat = [k for k, v in g_ref.items() if v < 1e-3 * med]
    last = max(ref["states"])

    def change(state):
        return jax.tree_util.tree_map(
            lambda a, b: np.asarray(a, np.float64) - np.asarray(b, np.float64), state["params"], p0
        )

    ugaps = leaf_gaps(leaf_norms(change(prog["states"][last])), leaf_norms(change(ref["states"][last])), skip=flat)
    out["update_gap"], out["update_gap_leaf"] = worst(ugaps)
    out["update_gap_median"] = float(np.median(list(ugaps.values())))
    out["update_leaf_gaps"] = ugaps
    out["leaves_left_out"] = flat
    out["later_loss_gaps"] = [
        abs(a - b) / max(abs(b), 1e-30) for a, b in zip(prog["losses"][1:3], ref["losses"][1:3])
    ]
    return out


def pads(step_groups, prepared) -> Tuple[int, int, int]:
    """One shape for every followed step, steady from seed to seed: the
    largest step, rounded up generously."""
    n = max(sum(len(prepared[i]["x"]) for g in groups for i in g) for groups in step_groups)
    e = max(sum(prepared[i]["edges"].shape[1] for g in groups for i in g) for groups in step_groups)
    g = max(sum(len(g) for g in groups) for groups in step_groups)

    def up(v, m):
        return -(-(v + 1) // m) * m

    return up(n, 2048), up(e, 32768), up(g, 8)


def program_side(taps) -> Dict[str, Any]:
    return {"losses": taps.losses, "states": taps.states, "probe": taps.probe}


def reference_run(cell, taps, raw, quant=None, fault=None) -> Dict[str, Any]:
    """The reference (or a control / a planted fault) over the same
    samples, dispatch for dispatch: the learning-rate-0 pass where the
    program made one, then the real steps up to its last captured state."""
    import reference
    from reference import common

    prepared = common.prepare(raw, cell.run_config)
    deg = common.degree_stats(prepared, taps.train_ids)
    mcfg = common.model_cfg(cell.run_config, deg)
    head_types = dict(zip(mcfg["head_names"], mcfg["head_types"]))
    capture_at = sorted(taps.states)
    groups = taps.step_groups[: capture_at[-1]]
    n_pad, e_pad, g_pad = pads(groups, prepared)
    batches = [common.assemble(prepared, g, head_types, n_pad, e_pad, g_pad) for g in groups]
    lr = float(cell.training["Optimizer"]["learning_rate"])
    step = common.make_step(reference.conv_for(cell.reference), mcfg, quant, fault)
    batches = jax.device_put(batches)  # once: both passes read the same arrays
    probe = None
    if taps.probe is not None:
        pl, ps = common.follow(step, taps.initial_params, batches, 0.0, [len(batches)])
        probe = {"losses": pl, "state": ps[len(batches)]}
    losses, states = common.follow(step, taps.initial_params, batches, lr, capture_at)
    share = 0.5 if fault == "half_batch" else 1.0
    return {
        "losses": losses, "states": states, "probe": probe,
        "graphs": [int(sum(len(g) for g in grp) * share) for grp in groups],
        "edges": [sum(prepared[i]["edges"].shape[1] for g in grp for i in g) for grp in groups],
        "real": {
            "nodes_per_epoch": sum(len(prepared[i]["x"]) for i in taps.train_ids),
            "edges_per_epoch": sum(prepared[i]["edges"].shape[1] for i in taps.train_ids),
            "graphs_per_epoch": len(taps.train_ids),
        },
    }


def decide(cell, taps, raw):
    ref = reference_run(cell, taps, raw)
    nums = numbers(program_side(taps), ref, taps.initial_params)
    checks: Dict[str, Dict[str, Any]] = {}
    for name in NUMBERS:
        if name in cell.limits:
            checks[name] = {"value": nums[name], "limit": cell.limits[name]}
    seen = taps.graphs_seen[: len(ref["graphs"])]
    checks["graphs_step_diff"] = {
        "value": sum(abs(a - b) for a, b in zip(seen, ref["graphs"])) + abs(len(seen) - len(ref["graphs"])),
        "limit": 0,
    }
    prog_edges = sum(taps.program_edges[i] for g in taps.step_groups[0] for i in g)
    checks["edges_step1_diff"] = {"value": abs(prog_edges - ref["edges"][0]), "limit": 0}
    correct = all(name in checks for name in NUMBERS) and all(
        isinstance(c["value"], (int, float)) and math.isfinite(c["value"]) and c["value"] <= c["limit"]
        for c in checks.values()
    )
    correct = correct and all(math.isfinite(x) for x in taps.losses)
    notes = {
        "grad_gap_leaf": nums["grad_gap_leaf"], "update_gap_leaf": nums["update_gap_leaf"],
        "grad_gap_median": nums["grad_gap_median"], "update_gap_median": nums["update_gap_median"],
        "leaves_left_out": len(nums["leaves_left_out"]), "steps_followed": len(ref["losses"]),
        "loss_gaps": nums["loss_gaps"], "later_loss_gaps": nums["later_loss_gaps"],
        "program_losses": taps.losses[:3], "reference_losses": ref["losses"][:3],
        "real": ref["real"],
    }
    return checks, correct, notes
