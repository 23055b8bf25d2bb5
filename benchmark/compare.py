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
  grad_diff_median
              (where the cell's limits name it) the norm of the DIFFERENCE
              between the two sides' Adam first moment at the initial
              weights, per leaf, the median leaf: a gap between two norms
              sees elementwise rounding only in second order, the norm of
              the difference in first order, which is what separates a
              lower precision from the configuration's own (PR 25).
  exact checks
              the family's own (``cell.fam.exact_checks``), each with the
              limit 0: for message passing ``graphs_step_diff``, the real
              graphs the program counted into each step, and the edges it
              built for the first step's samples, against the reference's
              own count.

The reference itself (chassis, loss, planted faults) is the family's:
``cell.fam.reference_run``. What is common, and here, is how two sides'
losses and Adam moments become these numbers.

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

NUMBERS = ("loss_gap", "grad_gap", "update_gap")  # every cell's limits name these
OPTIONAL = ("grad_diff_median",)  # compared where a cell's limits name them


def _leaves(tree) -> Dict[str, np.ndarray]:
    leaves, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {jax.tree_util.keystr(p): np.asarray(x, np.float64) for p, x in leaves}


def leaf_norms(tree) -> Dict[str, float]:
    return {k: float(np.linalg.norm(x)) for k, x in _leaves(tree).items()}


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


def diff_gaps(prog, ref, skip=()) -> Dict[str, float]:
    """Per leaf ||prog - ref|| / max(||ref|| of that leaf, of the median
    leaf): the norm of the difference, where both trees are at hand."""
    p, r = _leaves(prog), _leaves(ref)
    norms = {k: float(np.linalg.norm(x)) for k, x in r.items()}
    med = float(np.median(list(norms.values())))
    return {k: float(np.linalg.norm(p[k] - x)) / max(norms[k], med, 1e-30) for k, x in r.items() if k not in skip}


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
        m_prog, m_ref = prog["probe"]["state"]["mu"], ref["probe"]["state"]["mu"]
    else:
        first = min(ref["states"])
        pl, rl = prog["losses"][:1], ref["losses"][:1]
        g_prog, g_ref = grad_norms(prog["states"][first]), grad_norms(ref["states"][first])
        m_prog, m_ref = prog["states"][first]["mu"], ref["states"][first]["mu"]
    out["loss_gaps"] = [abs(a - b) / max(abs(b), 1e-30) for a, b in zip(pl, rl)]
    out["loss_gap"] = float(max(out["loss_gaps"]))  # each step's loss has to agree: the worst counts
    gaps = leaf_gaps(g_prog, g_ref)
    out["grad_gap"], out["grad_gap_leaf"] = worst(gaps)
    out["grad_gap_median"] = float(np.median(list(gaps.values())))
    out["grad_leaf_gaps"] = gaps
    med = float(np.median(list(g_ref.values())))
    flat = [k for k, v in g_ref.items() if v < 1e-3 * med]
    dgaps = diff_gaps(m_prog, m_ref, skip=flat)
    out["grad_diff_gap"], out["grad_diff_leaf"] = worst(dgaps)
    out["grad_diff_median"] = float(np.median(list(dgaps.values())))
    out["grad_diff_leaf_gaps"] = dgaps
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


def program_side(taps) -> Dict[str, Any]:
    return {"losses": taps.losses, "states": taps.states, "probe": taps.probe}


def decide(cell, taps, raw):
    ref = cell.fam.reference_run(cell, taps, raw)
    nums = numbers(program_side(taps), ref, taps.initial_params)
    checks: Dict[str, Dict[str, Any]] = {}
    for name in NUMBERS + OPTIONAL:
        if name in cell.limits:
            checks[name] = {"value": nums[name], "limit": cell.limits[name]}
    checks.update(cell.fam.exact_checks(taps, ref))
    correct = all(name in checks for name in NUMBERS) and all(
        isinstance(c["value"], (int, float)) and math.isfinite(c["value"]) and c["value"] <= c["limit"]
        for c in checks.values()
    )
    correct = correct and all(math.isfinite(x) for x in taps.losses)
    notes = {
        "grad_gap_leaf": nums["grad_gap_leaf"], "update_gap_leaf": nums["update_gap_leaf"],
        "grad_gap_median": nums["grad_gap_median"], "update_gap_median": nums["update_gap_median"],
        "grad_diff_gap": nums["grad_diff_gap"], "grad_diff_leaf": nums["grad_diff_leaf"],
        "grad_diff_median": nums["grad_diff_median"],
        "leaves_left_out": len(nums["leaves_left_out"]), "steps_followed": len(ref["losses"]),
        "loss_gaps": nums["loss_gaps"], "later_loss_gaps": nums["later_loss_gaps"],
        "program_losses": taps.losses[:3], "reference_losses": ref["losses"][:3],
        "real": ref["real"],
    }
    return checks, correct, notes
