"""The reduction from ``.xplane.pb`` to metrics, against a small recorded
trace: one epoch boundary of ``pna-multihead-h128.train-bcc`` on a v5e
(PR 22, cut from a whole trace to the device's ``XLA Ops`` line and the
benchmark's host spans: the last fifth of one scanned train dispatch,
validation, test, the epoch's tail, and the start of the next dispatch)."""

import os
import re

import pytest

import trace_reduce as tr

TRACE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data", "pna_epoch_boundary.xplane.pb")


@pytest.fixture(scope="module")
def reduced():
    return tr.reduce(TRACE)


def _raw():
    """The trace's device events, tallied the plain way."""
    pd = tr.load(TRACE)
    (ev,) = tr.device_events(pd).values()
    spans = tr.host_spans(pd)
    lo, hi = spans[tr.MARK_BEGIN][0][1], spans[tr.MARK_END][-1][0]
    return ev, lo, hi


def test_recorded_numbers(reduced):
    assert reduced["chips"] == 1
    assert reduced["window_s"] == pytest.approx(0.869152575, abs=1e-8)
    assert reduced["busy_s"] == pytest.approx(0.520821515, abs=1e-8)
    assert reduced["pallas_s"] == pytest.approx(0.250336296, abs=1e-8)
    assert reduced["collective_s"] == 0.0 and reduced["collective_exposed_s"] == 0.0
    assert 0 < reduced["train_busy_s"] < reduced["busy_s"] < reduced["window_s"]


def test_control_flow_is_not_counted():
    ev, lo, hi = _raw()
    whiles = [e for e in ev if re.search(r"\swhile\(", e[2])]
    assert whiles, "the scanned epoch should show as a while instruction"
    kept = tr.leaves(ev)
    assert not [e for e in kept if re.search(r"\swhile\(", e[2])]
    # counted as a leaf, the while alone would fill the dispatch
    assert sum(min(e[1], hi) - max(e[0], lo) for e in whiles if e[1] > lo) > 0.3e9


def test_pallas_time_is_the_custom_calls_and_nothing_else(reduced):
    ev, lo, hi = _raw()
    plain = sum(min(e, hi) - max(s, lo) for s, e, n, _ in tr.leaves(ev)
                if re.search(r"\scustom-call\(", n) and e > lo and s < hi)
    assert reduced["pallas_s"] == pytest.approx(plain * 1e-9, rel=1e-9)
    # a fusion that merely READS a custom call's result is not a kernel
    text = "%fusion.7 = bf16[8,128]{1,0} fusion(bf16[8,128]{1,0} %custom-call.126), kind=kLoop, calls=%fused_computation.7"
    assert tr.category(text) == "fused_elementwise"


def test_busy_plus_named_gaps_is_the_window(reduced):
    gaps = dict(reduced["breakdown"]["idle_gaps"])
    assert sum(gaps.values()) == pytest.approx(reduced["window_s"] - reduced["busy_max_s"], abs=1e-9)
    assert max(gaps, key=gaps.get) == "test"  # the host gathers the test split's outputs
    cats = reduced["by_category_s"]
    assert sum(cats.values()) == pytest.approx(reduced["busy_s"], rel=1e-6)  # one core: no overlap
    assert len(reduced["breakdown"]["device_ops"]) <= 10 and len(reduced["breakdown"]["idle_gaps"]) <= 10


def test_categories_and_names():
    cc = '%conv_1.67 = bf16[1724416,128]{1,0:T(8,128)(2,1)} custom-call(s32[3,1684]{1,0} %copy-done.313), custom_call_target="tpu_custom_call"'
    assert tr.category(cc) == "pallas" and tr.short_name(cc) == "conv_1.67:custom-call" and tr.kernel_stem(cc) == "conv"
    assert tr.category("%fusion.12 = bf16[55312,256]{1,0} fusion(s32[215552]{0} %copy-done.79), kind=kCustom, calls=%f") == "scatter"
    assert tr.category("%fusion.1474 = bf16[55312,2176]{1,0} fusion(bf16[55312,128]{1,0} %x), kind=kOutput, calls=%f") == "matmul"
    assert tr.category("%all-reduce.3 = f32[128]{0} all-reduce(f32[128]{0} %x), replica_groups={}") == "collective"
    assert tr.category("%copy-done.5 = f32[8]{0} copy-done((f32[8]{0}, f32[8]{0}, u32[]) %copy-start.5)") == "copy"


def test_exposed_collective_time():
    ar = "%all-reduce.1 = f32[8]{0} all-reduce(f32[8]{0} %g)"
    fu = "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %g), kind=kLoop, calls=%f"
    ev = [(0, 10, ar, None), (5, 8, fu, None), (20, 30, fu, None), (28, 34, ar, None)]
    assert tr.collective_times(ev) == (16, 7 + 4)
    assert tr.union([(0, 3), (2, 5), (7, 8)]) == [(0, 5), (7, 8)]
    assert tr.overlap([(0, 5), (7, 8)], [(4, 7)]) == 1
