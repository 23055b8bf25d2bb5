"""The split of a train step's device time by pass (``device_scopes.py``)
against a small recorded trace of ``schnet-h128.train-bcc`` on a v5e:
one epoch's train phase cut from a traced run (the device's ``XLA Ops`` and
``XLA Modules`` lines, the program's spans, and the ``/host:metadata``
entries of the programs that ran, their HLO cut to the names, opcodes,
``op_name`` and called computations the reduction reads). The two older
fixtures have no metadata plane: every reader returns ``None`` there.
The recorded trace is kept gzipped (6.4 MB as it is read)."""

import gzip
import os
import shutil
import types

import pytest

import device_scopes as ds
import trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
SCOPED_GZ = os.path.join(DATA, "schnet_device_scopes.xplane.pb.gz")
OLD = [os.path.join(DATA, n) for n in ("pna_epoch_boundary.xplane.pb", "pna_program_spans.xplane.pb")]

pytest.importorskip("tensorflow.tsl.profiler.protobuf.xplane_pb2")

# device seconds of the recorded epoch's train phase (8 steps, the first diagnosed)
RECORDED = {"diagnostics": 0.174658894, "optimizer": 0.000717849, "recompute": 0.0, "backward": 0.382064887,
            "forward": 0.274967155, "unscoped": 0.003117365}
RECORDED_MIXED = 0.311572682
METRICS = ("step_forward_ms", "step_backward_ms", "step_recompute_ms", "step_optimizer_ms", "diag_pull_ms",
           "step_unscoped_share")


def _ctx(path, tmp_path, steps=8, diagnosed=1):
    """What ``run.py`` hands a metric reader, around one recorded trace."""
    trace_dir = tmp_path / "trace"
    trace_dir.mkdir(parents=True)
    os.symlink(path, trace_dir / "t.xplane.pb")
    taps = types.SimpleNamespace(trace_dir=str(trace_dir), traced=(4, 5))
    return {"taps": taps, "traced_steps": steps, "epochs": {4: {"diagnosed_steps": diagnosed}}}


def _read(name, ctx):
    import run

    return run.load_metric_reader(name).read(ctx)


@pytest.fixture(scope="module")
def scoped(tmp_path_factory):
    path = str(tmp_path_factory.mktemp("scoped") / "schnet_device_scopes.xplane.pb")
    with gzip.open(SCOPED_GZ, "rb") as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return path


@pytest.fixture(scope="module")
def split(scoped):
    return ds.split(scoped)


@pytest.mark.parametrize("path", OLD)
def test_a_trace_without_the_programs_hlo_reads_nothing(path, tmp_path):
    assert ds.programs(path) is None and ds.split(path) is None
    ctx = _ctx(path, tmp_path)
    assert all(_read(name, ctx) is None for name in METRICS)


def test_recorded_split(split):
    assert split["scoped"] and split["chip"] == 0
    assert [p.split("(")[0] for p in split["programs"] if "train" in p or "diag" in p] == [
        "jit_diagnostics_step", "jit_train_scan_epoch_guarded"]
    got = {r: round(split["regions"][r]["s"], 9) for r in ds.REGIONS}
    assert got == pytest.approx(RECORDED, abs=1e-9)
    assert split["mixed_s"] == pytest.approx(RECORDED_MIXED, abs=1e-9)
    assert split["unjoined_s"] == 0.0


def test_the_regions_add_up_to_the_train_phases_busy_time(split, scoped):
    """The parts are a partition of what ``step_device_ms`` reads: the
    chip's busy time inside ``epoch.train``."""
    pd = tr.load(scoped)
    (ev,) = [tr.leaves(v) for v in tr.device_events(pd).values()]
    train = tr.union(tr.host_spans(pd)[tr.TRAIN_SPAN])
    busy = tr.overlap(tr.union([(s, e) for s, e, _, _ in ev]), train) * 1e-9
    assert sum(r["s"] for r in split["regions"].values()) == pytest.approx(split["total_s"], rel=1e-12)
    assert split["total_s"] == pytest.approx(busy, rel=1e-9)
    for r in split["regions"].values():
        assert sum(r["by_category"].values()) == pytest.approx(r["s"], rel=1e-12)
        assert sum(r["by_component"].values()) == pytest.approx(r["s"], rel=1e-12)


def test_the_metric_readers(split, scoped, tmp_path):
    ctx = _ctx(scoped, tmp_path, steps=8, diagnosed=1)
    reg = split["regions"]
    assert _read("step_forward_ms", ctx) == pytest.approx(1e3 * reg["forward"]["s"] / 8)
    assert _read("step_backward_ms", ctx) == pytest.approx(1e3 * reg["backward"]["s"] / 8)
    assert _read("step_optimizer_ms", ctx) == pytest.approx(1e3 * reg["optimizer"]["s"] / 8)
    assert _read("step_recompute_ms", ctx) == 0.0  # SchNet is not remat'd
    assert _read("diag_pull_ms", ctx) == pytest.approx(1e3 * reg["diagnostics"]["s"])
    assert _read("step_unscoped_share", ctx) == pytest.approx(100 * reg["unscoped"]["s"] / split["total_s"])
    # no diagnosed step in the traced epochs: no per-step figure for the pulls
    assert _read("diag_pull_ms", _ctx(scoped, tmp_path / "none", diagnosed=0)) is None


def test_the_look_by_hand(scoped, capsys):
    ds.main(scoped)
    text = capsys.readouterr().out
    for word in ("mixed fusions", "program scopes present", "forward", "backward", "optimizer", "diagnostics",
                 "by category", "by component"):
        assert word in text
