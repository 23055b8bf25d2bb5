"""The reduction from ``.xplane.pb`` to metrics, against two small recorded
traces of ``pna-multihead-h128.train-bcc`` on a v5e, each one epoch
boundary cut from a whole trace: PR 22's (the device's ``XLA Ops`` line and
the window marks; the host spans it has are the ``bench_*`` ones the
benchmark opened until PR 25, which nothing reads any more) and PR 23's
(``XLA Ops``, ``XLA Modules``, the program's own spans and the marks)."""

import os
import re

import pytest

import program_spans as ps
import trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
TRACE = os.path.join(DATA, "pna_epoch_boundary.xplane.pb")
SPANS_TRACE = os.path.join(DATA, "pna_program_spans.xplane.pb")


@pytest.fixture(scope="module")
def reduced():
    return tr.reduce(TRACE)


@pytest.fixture(scope="module")
def reduced_spans():
    return tr.reduce(SPANS_TRACE)


def _raw(path=TRACE):
    """The trace's device events, tallied the plain way."""
    pd = tr.load(path)
    (ev,) = tr.device_events(pd).values()
    spans = tr.host_spans(pd)
    lo, hi = spans[tr.MARK_BEGIN][0][1], spans[tr.MARK_END][-1][0]
    return ev, lo, hi


def test_recorded_numbers(reduced, reduced_spans):
    # busy and Pallas time as read since PR 25; PR 22 to 24 read 0.520821515 and 0.250336296 from the
    # first trace, the operations that a zero-length custom call shares its start with left out
    assert reduced["chips"] == 1
    assert reduced["window_s"] == pytest.approx(0.869152575, abs=1e-8)
    assert reduced["busy_s"] == pytest.approx(0.551688527, abs=1e-8)
    assert reduced["pallas_s"] == pytest.approx(0.268279027, abs=1e-8)
    assert reduced["collective_s"] == 0.0 and reduced["collective_exposed_s"] == 0.0
    assert reduced["train_busy_s"] == 0.0  # no program span in PR 22's trace
    assert reduced_spans["window_s"] == pytest.approx(0.919710694, abs=1e-8)
    assert reduced_spans["busy_s"] == pytest.approx(0.629705416, abs=1e-8)
    assert reduced_spans["pallas_s"] == pytest.approx(0.294406842, abs=1e-8)
    assert reduced_spans["train_busy_s"] == pytest.approx(0.53976285, abs=1e-8)
    assert 0 < reduced_spans["train_busy_s"] < reduced_spans["busy_s"] < reduced_spans["window_s"]


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


@pytest.mark.parametrize("path", [TRACE, SPANS_TRACE])
def test_an_operation_that_shares_its_start_with_a_zero_length_call_is_a_leaf(path):
    ev, lo, hi = _raw(path)
    zero = [e for e in ev if e[1] == e[0]]
    assert zero and all('custom_call_target="tpu_custom_call"' not in e[2] for e in zero)  # XLA's own: ConcatBitcast, AllocateBuffer
    starts = {e[0] for e in zero}
    shared = [e for e in ev if e[1] > e[0] and e[0] in starts and not tr.CONTAINERS.match(e[2])]
    assert len(shared) >= 40
    kept = set(tr.leaves(ev))
    assert all(e in kept for e in shared)
    # what is dropped is control flow by name, and nothing else
    dropped = [e for e in ev if e not in kept]
    assert dropped and all(re.search(r"\s(while|conditional|call)\(", e[2]) for e in dropped)


def test_a_container_needs_a_child_with_a_length():
    op = "%fusion.{} = f32[8]{{0}} fusion(f32[8]{{0}} %x), kind=kLoop, calls=%f"
    zero = '%custom-call.9 = f32[8]{0} custom-call(), custom_call_target="AllocateBuffer"'
    a, b, c = (100, 150, op.format(1), None), (100, 100, zero, None), (110, 120, op.format(2), None)
    assert tr.leaves([a, b]) == [a, b]  # the zero-length call makes no container of a
    assert tr.leaves([a, b, c]) == [b, c]  # a real child does, the zero-length call before it or not
    assert tr.leaves([b, a, (150, 150, zero, None)]) == [a, b, (150, 150, zero, None)]


def test_leaves_and_program_spans_agree_on_the_idle_time(reduced_spans):
    table = ps.table(SPANS_TRACE)
    idle_ns = round((reduced_spans["window_s"] - reduced_spans["busy_max_s"]) * 1e9)
    assert round(table["idle_s"] * 1e9) == idle_ns == 290005278  # to the nanosecond
    assert round(table["busy_s"] * 1e9) == round(reduced_spans["busy_max_s"] * 1e9)
    # inside a running program the chip is now idle for what the whiles' own
    # bookkeeping and the gaps between two operations take: 0.6 ms of this
    # epoch boundary (34.3 ms before PR 25, all of it dropped operations)
    assert 0 < table["idle_in_program_s"] < 1e-3
    assert table["spans"]["train.sync"]["idle_in_program_s"] < 1e-3


def test_busy_plus_named_gaps_is_the_window(reduced, reduced_spans):
    for r in (reduced, reduced_spans):
        gaps = dict(r["breakdown"]["idle_gaps"])
        assert sum(gaps.values()) == pytest.approx(r["window_s"] - r["busy_max_s"], abs=1e-9)
        assert sum(r["by_category_s"].values()) == pytest.approx(r["busy_s"], rel=1e-6)  # one core: no overlap
        assert len(r["breakdown"]["device_ops"]) <= 10 and len(r["breakdown"]["idle_gaps"]) <= 10
    assert set(dict(reduced["breakdown"]["idle_gaps"])) == {"host_other"}  # no program span to name a gap by
    # the gaps carry the program's span names, and each is what program_spans.py reads under that span
    gaps = dict(reduced_spans["breakdown"]["idle_gaps"])
    assert set(gaps) == set(tr.HOST_SPANS) | {"host_other"} and not [k for k in gaps if k.startswith("bench_")]
    assert max(gaps, key=gaps.get) == "epoch.checkpoint"  # the save is synchronous
    table = ps.table(SPANS_TRACE)
    for name in tr.HOST_SPANS:
        assert gaps[name] == pytest.approx(table["spans"][name]["idle_s"], abs=1e-9)
    assert gaps["host_other"] == pytest.approx(table["unattributed_idle_s"], abs=1e-9)


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
