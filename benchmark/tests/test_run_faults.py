"""A whole run on the CPU (the harness's look for a chip skipped), sound
and then with the timed path broken underneath: ``correct`` must come out
false for each fault a training cell can have.

  state_unchanged  the compiled step hands back the state it was given
  half_batch       half of every batch's graphs are masked out of the step
  no_exchange      (mesh cell) the gradients are not averaged over chips
"""

import argparse

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import cell as cellmod
import run as runmod

ONE_CHIP = "pna-multihead-h128.train-bcc"
MESH = "pna-multihead-h128.train-bcc-data4"


def _args(workload, seed=2_200_000_007):
    return argparse.Namespace(workload=workload, seed=seed, seconds=1.0, trace=0, rehearse=True, keep_trace=None)


def _need(workload):
    chips = cellmod.load_cell(workload, rehearse=True).chips
    if chips != jax.device_count():
        pytest.skip(f"needs a process with {chips} device(s)")


def _unchanged(real_factory):
    def factory(*a, **kw):
        real = real_factory(*a, **kw)

        def step(state, *rest):
            kept = jax.tree_util.tree_map(jnp.copy, state)
            out = real(state, *rest)
            return (kept,) + tuple(out[1:])

        return step

    return factory


def _half_batches(real):
    """The loader's batch builder, leaving the second half of every
    batch's graphs masked out: the step takes its means over the rest."""

    def batch_graphs(*args, **kwargs):
        b = real(*args, **kwargs)
        g = np.asarray(b.graph_mask)
        keep_g = g & (np.cumsum(g) <= g.sum() // 2)
        keep_n = np.asarray(b.node_mask) & keep_g[np.asarray(b.node_graph)]
        keep_e = np.asarray(b.edge_mask) & keep_n[np.asarray(b.receivers)]
        return b.replace(graph_mask=keep_g, node_mask=keep_n, edge_mask=keep_e)

    return batch_graphs


@pytest.mark.parametrize("workload", [ONE_CHIP, "schnet-h128.train-bcc", MESH])
def test_sound_run_is_correct(workload):
    _need(workload)
    result = runmod.run_cell(_args(workload), check_device=False)
    assert result["correct"] is True, result["checks"]
    assert result["failed"] == 0 and result["attempted"] > 0
    assert result["metrics"] == {} and "train_graphs_per_s" in result["rehearsal_metrics"]
    assert list(result)[-1] == "checks"


@pytest.mark.parametrize("workload,fault", [(ONE_CHIP, "state_unchanged"), (ONE_CHIP, "half_batch"),
                                            (MESH, "state_unchanged"), (MESH, "half_batch"), (MESH, "no_exchange")])
def test_broken_timed_path_is_not_correct(workload, fault, monkeypatch):
    _need(workload)
    import hydragnn_tpu.parallel.sharded as sharded
    import hydragnn_tpu.train.loop as loop
    import hydragnn_tpu.train.state as state

    if fault == "state_unchanged":
        monkeypatch.setattr(loop, "make_scan_epoch", _unchanged(loop.make_scan_epoch))
        monkeypatch.setattr(sharded, "make_sharded_train_step", _unchanged(sharded.make_sharded_train_step))
    elif fault == "half_batch":
        import hydragnn_tpu.data.loader as loader

        monkeypatch.setattr(loader, "batch_graphs", _half_batches(loader.batch_graphs))
    else:
        monkeypatch.setattr(jax.lax, "pmean", lambda x, axis_name, **kw: x)
    result = runmod.run_cell(_args(workload), check_device=False)
    assert result["correct"] is False, (fault, result["checks"])
    over = [k for k, c in result["checks"].items() if not c["value"] <= c["limit"]]
    assert over, result["checks"]
