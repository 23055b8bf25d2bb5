"""``program_spans.py`` against a small trace recorded on a v5e in PR 23:
``pna-multihead-h128.train-bcc``, cut to the device's ``XLA Ops`` and
``XLA Modules`` lines and the host's program spans and benchmark marks,
from the last twelfth of one scanned train dispatch to the first twelfth
of the next (validation, test, the epoch's record, a checkpoint, the next
epoch's batch stack and diagnostics sample in between). And against
PR 22's trace, recorded before the program had a span."""

import os
import random

import pytest

import program_spans as ps
import trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
TRACE = os.path.join(DATA, "pna_program_spans.xplane.pb")
OLD_TRACE = os.path.join(DATA, "pna_epoch_boundary.xplane.pb")


@pytest.fixture(scope="module")
def table():
    return ps.table(TRACE)


def test_cover_agrees_with_a_plain_sum():
    rng = random.Random(7)
    points = sorted(rng.sample(range(1000), 40))
    iv = list(zip(points[0::2], points[1::2]))
    cover = ps._Cover(iv)
    assert cover.total == sum(e - s for s, e in iv)
    for _ in range(300):
        a, b = sorted(rng.sample(range(-5, 1010), 2))
        assert cover.inside(a, b) == sum(max(0, min(e, b) - max(s, a)) for s, e in iv)
    assert ps._Cover([]).inside(0, 10) == 0
    assert tr.complement([(2, 4), (6, 9)], 0, 10) == [(0, 2), (4, 6), (9, 10)]


def test_parents_come_from_the_nesting_of_one_thread():
    ev = [
        (10, 90, "epoch", "main"), (12, 50, "epoch.train", "main"), (13, 20, "train.stack", "main"),
        (55, 80, "epoch.test", "main"), (56, 60, "test.dispatch", "main"),
        # the capture began inside this epoch: no ``epoch`` around its children
        (0, 5, "epoch.test", "main"), (1, 2, "test.dispatch", "main"),
        (14, 30, "epoch.checkpoint", "other"),
    ]
    assert ps.parents_from_trace(ev) == {
        "epoch": None, "epoch.train": "epoch", "train.stack": "epoch.train", "epoch.test": "epoch",
        "test.dispatch": "epoch.test", "epoch.checkpoint": None,
    }


def test_every_idle_second_lies_under_one_child_of_epoch_or_under_none(table):
    assert table["chip"] == 0
    assert table["busy_s"] + table["idle_s"] == pytest.approx(table["window_s"], rel=1e-9)
    top = {k: r for k, r in table["spans"].items() if r["parent"] == ps.ROOT}
    assert {"epoch.train", "epoch.validate", "epoch.test", "epoch.head_quality", "epoch.diag_snapshot",
            "epoch.record", "epoch.checkpoint"} <= set(top)
    under = sum(r["idle_s"] for r in top.values())
    assert under + table["unattributed_idle_s"] == pytest.approx(table["idle_s"], rel=1e-9)
    assert 0 <= table["unattributed_idle_s"] < 0.1 * table["idle_s"]
    # the same number as the accepted reduction's, from the same trace
    old = tr.reduce(TRACE)
    assert table["idle_s"] == pytest.approx(old["window_s"] - old["busy_max_s"], rel=1e-9)


def test_a_gap_goes_to_the_deepest_span_that_covers_it(table):
    rows = table["spans"]
    for name, row in rows.items():
        below = sum(r["idle_s"] for r in rows.values() if r["parent"] == name)
        assert row["idle_self_s"] == pytest.approx(row["idle_s"] - below, abs=1e-12)
        assert row["idle_self_s"] > -1e-9 and row["idle_s"] <= row["s"] + 1e-9
    # the host builds the test split's batch with the chip idle: the gap is
    # under epoch.test, and inside it under test.loader_wait (not under
    # test.gather, where PR 22 looked for it), none of it epoch.test's own
    test = rows["epoch.test"]
    wait = rows["test.loader_wait"]
    assert wait["parent"] == "epoch.test" and wait["n"] == 2  # the batch, and the end of the loader
    assert wait["idle_s"] > 0.5 * test["idle_s"] > 0
    assert wait["idle_self_s"] == wait["idle_s"]  # a leaf
    assert test["idle_self_s"] < 0.01 * test["idle_s"]
    # the chip works while the host waits in train.sync: what is idle there
    # is the end of the wait, after the program (8.6 ms of 107 ms); inside
    # the running program next to nothing is (34.3 ms read so until PR 25,
    # operations that trace_reduce.leaves dropped)
    sync = rows["train.sync"]
    assert sync["idle_s"] < 0.1 * sync["s"] and sync["idle_in_program_s"] < 0.1 * sync["idle_s"]
    assert table["idle_in_program_s"] == pytest.approx(0.000619, abs=5e-6)


def test_programs_and_kernels_by_their_own_names(table):
    assert {"jit_train_scan_epoch_guarded", "jit_diagnostics_step", "jit_eval_scan",
            "jit_eval_step_outputs"} <= set(table["programs"])
    named = sum(p["device_s"] for p in table["programs"].values())
    assert named + table["device_s_outside_any_program"] == pytest.approx(table["busy_s"], rel=1e-9)
    assert table["device_s_outside_any_program"] < 0.01 * table["busy_s"]
    assert set(table["kernels"]) == {"bcast_gather", "csr_sum", "gather_stats", "segment_sum_local"}
    pallas = tr.reduce(TRACE)["pallas_s"]
    # but for XLA's own zero-length custom calls, the accepted reduction's Pallas time
    assert sum(k["device_s"] for k in table["kernels"].values()) == pytest.approx(pallas, abs=1e-6)


def test_metric_readers_on_the_recorded_trace(monkeypatch):
    """The nine readers of the program's spans, handed a run's context whose
    trace is the fixture and whose flight record has the spans' ``phases``."""
    import run

    class Taps:
        trace_dir = DATA

    monkeypatch.setattr(ps.glob, "glob", lambda *a, **k: [TRACE])
    phases = {name: {"s": r["s"], "n": r["n"], "parent": r["parent"]} for name, r in ps.table(TRACE)["spans"].items()}
    flight = [
        {"kind": "setup", "phases": {"setup.data": {"s": 4.0, "n": 1, "parent": None},
                                     "setup.model_init": {"s": 7.0, "n": 1, "parent": None},
                                     "setup.step_builders": {"s": 2.0, "n": 1, "parent": None},
                                     "setup.stack_splits": {"s": 1.5, "n": 2, "parent": "setup.step_builders"}}},
        {"kind": "epoch", "epoch": 0, "phases": phases},
        {"kind": "epoch", "epoch": 1, "phases": phases,
         "phases_late": [{"epoch": 0, "phases": {"epoch": {"s": 33.0, "n": 1, "parent": None}}}]},
    ]
    ctx = {"taps": Taps(), "flight": flight, "traced_epochs": 1, "quiet_epochs": [0], "setup": {"generate_s": 0.25}}
    got = {name: run.load_metric_reader(name).read(ctx) for name in (
        "train_host_gap_ms", "test_host_gap_ms", "checkpoint_stall_ms", "idle_unattributed_share",
        "diag_device_share", "setup_pre_epoch_s", "setup_epoch0_s", "setup_data_s", "epoch_train_share")}
    t = ctx["program_spans"]
    assert got["train_host_gap_ms"] == pytest.approx(1e3 * t["spans"]["epoch.train"]["idle_s"])
    assert got["test_host_gap_ms"] == pytest.approx(
        1e3 * (t["spans"]["epoch.test"]["idle_s"] + t["spans"]["epoch.head_quality"]["idle_s"]))
    assert got["checkpoint_stall_ms"] == pytest.approx(
        1e3 * t["spans"]["epoch.checkpoint"]["idle_s"] / t["spans"]["epoch.checkpoint"]["n"])
    assert 0 <= got["idle_unattributed_share"] < 10
    assert got["diag_device_share"] == pytest.approx(
        100 * t["programs"]["jit_diagnostics_step"]["device_s"] / t["busy_s"])
    assert got["setup_pre_epoch_s"] == 9.0 and got["setup_epoch0_s"] == 33.0
    assert got["setup_data_s"] == 4.25  # the benchmark's generation and the program's setup.data, each once
    assert got["epoch_train_share"] == pytest.approx(100 * phases["epoch.train"]["s"] / 33.0)
    # an epoch whose ``epoch`` span the record does not hold yet reads as nothing
    assert run.load_metric_reader("epoch_train_share").read(dict(ctx, quiet_epochs=[0, 2])) is None


def test_an_older_program_reads_as_nothing_and_raises_nothing(monkeypatch):
    """PR 22's trace has the ``bench_*`` spans the benchmark opened then and
    none of the program's; its flight record has no ``setup`` event and no
    ``phases``."""
    import run

    assert ps.table(OLD_TRACE) is None

    class Taps:
        trace_dir = DATA

    monkeypatch.setattr(ps.glob, "glob", lambda *a, **k: [OLD_TRACE])
    flight = [{"kind": "run_start", "manifest": {}}, {"kind": "epoch", "epoch": 0, "train_loss": 1.0},
              {"kind": "run_end", "status": "preempted"}]
    base = {"quiet_epochs": [0], "setup": {"generate_s": 0.25}}
    for ctx in (dict(base, taps=Taps(), flight=flight, traced_epochs=3),
                dict(base, taps=Taps(), flight=[], traced_epochs=0),
                dict(base, taps=type("NoTrace", (), {"trace_dir": None})(), flight=flight, traced_epochs=0)):
        for name in ("train_host_gap_ms", "test_host_gap_ms", "checkpoint_stall_ms", "idle_unattributed_share",
                     "diag_device_share", "setup_pre_epoch_s", "setup_epoch0_s", "setup_data_s", "epoch_train_share"):
            assert run.load_metric_reader(name).read(dict(ctx)) is None
    # spans in the record but none in the trace (a capture that missed them)
    spanned = flight + [{"kind": "epoch", "epoch": 1, "phases": {"epoch.train": {"s": 1.0, "n": 1, "parent": "epoch"}}}]
    ctx = {"taps": Taps(), "flight": spanned, "traced_epochs": 3}
    assert run.load_metric_reader("train_host_gap_ms").read(ctx) is None


def test_cli_prints_the_whole_table(capsys):
    ps.main(TRACE)
    out = capsys.readouterr().out
    for word in ("epoch.train", "  train.dispatch", "test.loader_wait", "jit_diagnostics_step", "gather_stats",
                 "idle under", "in program"):
        assert word in out
    ps.main(OLD_TRACE)
    assert "no program span" in capsys.readouterr().out
