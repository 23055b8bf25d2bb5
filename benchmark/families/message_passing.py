"""The family of both configurations that exist: message passing over a
radius graph of atoms (``datagen.py``'s BCC lattices), through HydraGNN's
chassis (``reference/common.py``) around ONE conv function that the
configuration file names under ``reference`` (``reference/pna.py``,
``reference/schnet.py``).

What the harness asks of a family, and of this one:

  generate(traffic, seed)        raw samples from the seed (``datagen``)
  program_samples(raw)           the same as ``run_training(samples=...)``
                                 takes them
  count_samples(ids, samples)    what ``taps.py`` keeps of the loader's
                                 prepared samples for the exact checks:
                                 here the edges the program built
  reference_run(cell, taps, raw, quant=None, fault=None)
                                 the plain reference (or a control, or a
                                 planted fault) over the steps the program
                                 made: losses, states, probe, the counts a
                                 step and ``real``
  exact_checks(taps, ref)        the exact counts, each beside its limit 0
  faults(cell)                   the planted faults ``readings.py`` reads
  rehearsal_overrides(nn, over)  a rehearsal's ``architecture`` block put
                                 into the configuration
  weights(template, seed)        optional; without it ``weights.make``

``real`` is ONE dictionary (``nodes_per_epoch``, ``edges_per_epoch``,
``graphs_per_epoch`` here) that ``cost.py`` and the cost models take
whole: a family counts what it has.
"""

from __future__ import annotations

from typing import Any, Dict, List, Tuple

import jax

import datagen

generate = datagen.generate


def program_samples(raw: List[Dict[str, Any]]):
    """The raw samples as the program's input type. The program prepares
    (normalizes, builds edges) IN PLACE, so it gets copies."""
    from hydragnn_tpu.data.dataset import GraphSample

    return [
        GraphSample(x=r["x"].copy(), pos=r["pos"].copy(), graph_y=r["graph_y"].copy())
        for r in raw
    ]


def count_samples(ids: List[int], samples) -> Dict[str, Any]:
    """Raw sample id -> edges the program built for it."""
    return {"program_edges": {i: int(s.edge_index.shape[1]) for i, s in zip(ids, samples)}}


def rehearsal_overrides(nn: Dict[str, Any], over: Dict[str, Any]) -> None:
    """A rehearsal narrows the model; the heads follow the conv width."""
    if "hidden_dim" in over:
        h = int(over["hidden_dim"])
        heads = nn["Architecture"]["output_heads"]
        heads["graph"].update(dim_sharedlayers=h, dim_headlayers=[h, max(h // 2, 1)])
        heads["node"].update(dim_headlayers=[h, max(h // 2, 1)])
    nn["Architecture"].update(over)


def faults(cell) -> List[str]:
    return ["half_batch"] + (["no_exchange"] if cell.chips > 1 else [])


def pads(step_groups, prepared) -> Tuple[int, int, int]:
    """One shape for every followed step, steady from seed to seed: the
    largest step, rounded up generously."""
    n = max(sum(len(prepared[i]["x"]) for g in groups for i in g) for groups in step_groups)
    e = max(sum(prepared[i]["edges"].shape[1] for g in groups for i in g) for groups in step_groups)
    g = max(sum(len(g) for g in groups) for groups in step_groups)

    def up(v, m):
        return -(-(v + 1) // m) * m

    return up(n, 2048), up(e, 32768), up(g, 8)


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


def exact_checks(taps, ref) -> Dict[str, Dict[str, Any]]:
    """The real graphs the program counted into each step, and the edges it
    built for the first step's samples, against the reference's own count."""
    seen = taps.graphs_seen[: len(ref["graphs"])]
    built = taps.sample_counts["program_edges"]
    prog_edges = sum(built[i] for g in taps.step_groups[0] for i in g)
    return {
        "graphs_step_diff": {
            "value": sum(abs(a - b) for a, b in zip(seen, ref["graphs"])) + abs(len(seen) - len(ref["graphs"])),
            "limit": 0,
        },
        "edges_step1_diff": {"value": abs(prog_edges - ref["edges"][0]), "limit": 0},
    }
