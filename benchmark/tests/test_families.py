"""The family seam: every configuration names a family that exists and has
the entry points the harness calls; a second family (``newfamily/``: path
graphs, with samples, a reference wrapper and exact checks of its own) is
added to a copy of ``benchmark/`` by NEW files alone and runs a rehearsal
to ``correct``; and a rehearsal of each workload file prints the
``checks`` the parent of PR 25 printed, before the seam was cut."""

import glob
import hashlib
import json
import os
import shutil
import subprocess
import sys

import pytest

import families

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
NEW = os.path.join(HERE, "newfamily")
ENTRY_POINTS = ("generate", "program_samples", "count_samples", "reference_run", "exact_checks", "faults",
                "rehearsal_overrides")
# the files the harness reaches a family THROUGH: none of them may know one
SEAM = ("run.py", "firststeps.py", "readings.py", "sizing.py", "compare.py", "cell.py", "taps.py")
FORBIDDEN = ("datagen", "reference.common", "GraphSample", "conv_for", "edge_index", "edges_step1_diff",
             "unit_cells", "hidden_dim")


def _files(top):
    out = {}
    for path in sorted(glob.glob(os.path.join(top, "**", "*"), recursive=True)):
        if os.path.isfile(path) and "__pycache__" not in path:
            with open(path, "rb") as f:
                out[os.path.relpath(path, top)] = hashlib.sha256(f.read()).hexdigest()
    return out


@pytest.mark.parametrize("path", sorted(glob.glob(os.path.join(BENCH, "configs", "*.json"))), ids=os.path.basename)
def test_every_configuration_names_a_family_that_exists(path):
    with open(path) as f:
        cfg = json.load(f)
    assert os.path.isfile(os.path.join(BENCH, "families", f"{cfg['family']}.py"))
    fam = families.load(cfg["family"])
    assert [name for name in ENTRY_POINTS if not callable(getattr(fam, name, None))] == []


@pytest.mark.parametrize("name", SEAM)
def test_the_harness_knows_no_family(name):
    with open(os.path.join(BENCH, name)) as f:
        text = f.read()
    assert [word for word in FORBIDDEN if word in text] == []


def test_a_new_family_is_new_files_only():
    tree = os.path.join(HERE, "_work", "newfamily_tree")
    shutil.rmtree(tree, ignore_errors=True)
    shutil.copytree(BENCH, os.path.join(tree, "benchmark"),
                    ignore=shutil.ignore_patterns("_work", "_cache", "_parent", "_archive", "tests", "__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tree)
    before = _files(tree)
    added = _files(NEW)
    assert len(added) == 3 and not set(os.path.join("benchmark", k) for k in added) & set(before)
    shutil.copytree(NEW, os.path.join(tree, "benchmark"), dirs_exist_ok=True,
                    ignore=shutil.ignore_patterns("__pycache__"))
    after = _files(tree)
    assert {k: v for k, v in after.items() if k in before} == before  # no file that was there has changed
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)  # the program itself is not copied
    env.pop("XLA_FLAGS", None)
    done = subprocess.run(
        [sys.executable, os.path.join(tree, "benchmark", "run.py"), "--workload", "path-schnet-h16.train-paths",
         "--seed", "2200000011", "--seconds", "1", "--rehearse"],
        env=env, cwd=tree, capture_output=True, text=True, timeout=900)
    assert done.returncode == 3, done.stderr[-2000:]
    result = json.loads(done.stdout.strip().splitlines()[-1])
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 0, result["checks"]
    checks = result["checks"]
    assert list(checks) == ["loss_gap", "grad_gap", "update_gap", "graphs_step_diff", "edges_step1_diff",
                            "path_nodes_step1_diff", "path_edges_step1_diff"]
    assert checks["path_edges_step1_diff"] == {"value": 0, "limit": 0}
    assert result["run"]["compare"]["real"]["paths_per_epoch"] == 128
    assert "check path_edges_step1_diff: value 0 limit 0" in done.stderr
    shutil.rmtree(tree, ignore_errors=True)


with open(os.path.join(HERE, "data", "parent_rehearsal_checks.json")) as _f:
    PARENT = json.load(_f)


@pytest.mark.parametrize("workload,seed", [(w, int(s)) for w, by_seed in PARENT["checks"].items() for s in by_seed])
def test_a_rehearsal_prints_what_the_parent_printed(workload, seed):
    """float32 on the CPU: ``checks`` equal to the digits printed, by the
    command they were recorded with and in a process of its own (the size of
    XLA's CPU thread pool, which ``conftest.py`` sets through ``NPROC``,
    moves the worst leaf's ``update_gap`` in its fourth digit; the file's
    ``recorded`` says where and how; another CPU or jaxlib may round
    otherwise, and then the file is recorded anew from the parent). The
    four-chip workload makes its own four virtual devices."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    for name in ("NPROC", "XLA_FLAGS"):
        env.pop(name, None)
    done = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", workload, "--seed", str(seed),
         "--seconds", "1", "--rehearse"], env=env, cwd=ROOT, capture_output=True, text=True, timeout=900)
    assert done.returncode == 3, done.stderr[-2000:]
    result = json.loads(done.stdout.strip().splitlines()[-1])
    assert result["checks"] == PARENT["checks"][workload][str(seed)]
    assert result["correct"] is True and result["failed"] == 0
    # how many epochs fit into a second follows the host; the window's mix does not
    assert result["attempted"] > 0 and result["attempted"] % PARENT["steps_per_window_unit"][workload] == 0
    assert sorted(result["rehearsal_metrics"]) == PARENT["rehearsal_metrics"] and result["metrics"] == {}
