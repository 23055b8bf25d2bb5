"""The plain references against ``run_training``'s own first steps, at a
tiny size on the CPU: the comparison the chip makes, rehearsed.

With mixed precision off the program computes in float32 like the
reference, and the two must agree to rounding: that pins the reference's
mathematics (batching, masks, every conv layer with its kernels and their
backward passes, BatchNorm, heads, loss, AdamW). With it on, as the cells
run, the gap is bfloat16's; the fp8 control and each planted fault must
then read well above it.
"""

import os

import pytest

import cell as cellmod
import compare
import firststeps

CELLS = [
    "pna-multihead-h128.train-bcc",
    "schnet-h128.train-bcc",
    "pna-multihead-h128.train-bcc-data4",
]
WORK = os.path.join(os.path.dirname(os.path.abspath(__file__)), "_work")


def _numbers(cell, taps, raw, **kw):
    return cell.fam.reference_run(cell, taps, raw, **kw)


@pytest.fixture(scope="module", params=CELLS)
def captured(request):
    import jax

    if cellmod.load_cell(request.param, rehearse=True).chips != jax.device_count():
        pytest.skip(f"needs a process with {cellmod.load_cell(request.param, rehearse=True).chips} device(s)")
    out = {}
    for mixed in (False, True):
        cell = cellmod.load_cell(request.param, rehearse=True)
        cell.run_config["NeuralNetwork"]["Training"]["mixed_precision"] = mixed
        taps, raw = firststeps.capture(cell, seed=2_500_000_123, work_dir=os.path.join(WORK, request.param))
        ref = _numbers(cell, taps, raw)
        out[mixed] = (cell, taps, raw, ref)
    return out


def test_float32_program_agrees_with_reference(captured):
    cell, taps, raw, ref = captured[False]
    nums = compare.numbers(compare.program_side(taps), ref, taps.initial_params)
    nums = {k: v for k, v in nums.items() if not k.endswith("leaf_gaps")}
    # rounding, and now and then one ReLU that flips on a value within
    # rounding of zero (1 node in ~1000 at this size)
    assert nums["loss_gap"] < 2e-5, nums
    assert all(g < 2e-3 for g in nums["later_loss_gaps"]), nums
    assert nums["grad_gap"] < 2e-2, nums
    assert nums["update_gap"] < 2e-2, nums
    assert taps.graphs_seen[: len(ref["graphs"])] == ref["graphs"]


def test_control_and_faults_read_above_the_program(captured):
    cell, taps, raw, ref = captured[True]
    p0 = taps.initial_params
    prog = compare.numbers(compare.program_side(taps), ref, p0)
    keys = list(compare.NUMBERS)
    faults = ["half_batch", "state_unchanged"] + (["no_exchange"] if cell.chips > 1 else [])
    for kind, kw in [("fp8", {"quant": "fp8"})] + [(f, {"fault": f}) for f in faults]:
        side = _numbers(cell, taps, raw, **kw)
        nums = compare.numbers(side, ref, p0)
        reads_above = any(nums[k] > 3 * max(prog[k], 1e-6) for k in keys)
        assert reads_above or side["graphs"] != ref["graphs"], (kind, {k: nums[k] for k in keys}, {k: prog[k] for k in keys})


def test_the_control_reads_above_the_program_in_the_gradients_difference(captured):
    """``grad_diff_median`` is the number that separates a lower precision
    from bfloat16 on the chip (PERF.md section 6, PR 25): the norm of the
    difference sees elementwise rounding in first order."""
    cell, taps, raw, ref = captured[True]
    p0 = taps.initial_params
    prog = compare.numbers(compare.program_side(taps), ref, p0)["grad_diff_median"]
    control = compare.numbers(_numbers(cell, taps, raw, quant="fp8"), ref, p0)["grad_diff_median"]
    assert control > 3 * prog > 0, (control, prog)
