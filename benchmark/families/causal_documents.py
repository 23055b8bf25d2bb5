"""The family of configurations that train a decoder on packed token
documents by next-token prediction, with more prediction depths behind the
main head (``reference/joyai_llm_flash.py`` has the equations and their
sources). ``families/message_passing.py`` lists what the harness asks of a
family; it takes from ``token_documents.py`` the way host memory is given
back between the reference's passes, and keeps its packing: one step's
documents are one group of fixed lengths.

Data, all from ``--seed``: ``documents`` token documents whose lengths are
the cell's ``lengths`` (one group = one step's documents) in another order
in every group, so that every batch of every split is one whole group and
the program's pad plan has the same slots from seed to seed; ids uniform
over the vocabulary held. ONE copy of a document: row ``i`` holds the token
``t[i]`` and its index, and has the target ``t[i+1]`` (the main head) and
``t[i+2]`` (the first prediction depth), each with the weight 1 where the
document has that token and 0 past its end.

Exact checks, limit 0: documents a step, real rows a step, rows with a
target at the prediction depth a step. The assignments to held experts are
not one (a bfloat16 hidden state turns a ninth-against-eighth expert now
and then); ``real`` carries the reference's count.

The upper readings for the limits at the cell's own size, without a
training run (``readings.py --controls`` holds more host copies of a
5.9 GB state than the one-chip machine's 40 GiB allow):

    python3 benchmark/families/causal_documents.py --workload <cell> --seeds 11,12
"""

from __future__ import annotations

import json
import os
import sys
import time
import types
from typing import Any, Dict, Iterator, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

HEADS = ("token", "token_mtp")  # the configuration's output_names: t[i+1], t[i+2]


def generate(traffic: Dict[str, Any], seed: int) -> List[Dict[str, np.ndarray]]:
    lengths = np.asarray(traffic["lengths"], np.int64)
    vocab = int(traffic["vocab"])
    groups, rest = divmod(int(traffic["documents"]), len(lengths))
    if rest or np.any(lengths < 3):
        raise ValueError("documents must be whole groups of `lengths`, each of at least three tokens")
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32, 33])
    return [{"tokens": rng.integers(0, vocab, size=int(n)).astype(np.int32)}
            for _ in range(groups) for n in rng.permutation(lengths)]


def _rows(r: Dict[str, np.ndarray]) -> Dict[str, np.ndarray]:
    """One document's ``n`` rows."""
    t = r["tokens"]
    n = len(t)
    out = {"ids": t.astype(np.int32), "index": np.arange(n, dtype=np.int32), "cpy": np.zeros(n, np.int32)}
    for ahead, (target, weight) in ((1, ("target", "weight")), (2, ("target_mtp", "weight_mtp"))):
        out[target] = np.concatenate([t[ahead:], np.zeros(ahead, np.int32)]).astype(np.int32)
        out[weight] = np.concatenate([np.ones(n - ahead), np.zeros(ahead)]).astype(np.float32)
    return out


def program_samples(raw: List[Dict[str, np.ndarray]]):
    from hydragnn_tpu.data.dataset import GraphSample

    out = []
    for r in raw:
        rows = _rows(r)
        out.append(GraphSample(
            x=np.stack([rows["ids"], rows["index"], rows["cpy"]], axis=1),
            edge_index=np.zeros((2, 0), np.int32),
            node_targets={HEADS[0]: rows["target"][:, None], HEADS[0] + "_weight": rows["weight"][:, None],
                          HEADS[1]: rows["target_mtp"][:, None], HEADS[1] + "_weight": rows["weight_mtp"][:, None]},
        ))
    return out


def count_samples(ids: List[int], samples) -> Dict[str, Any]:
    """Raw sample id -> the rows the program holds for it, and those of them with a target at the depth."""
    return {
        "program_rows": {i: int(s.num_nodes) for i, s in zip(ids, samples)},
        "program_mtp": {i: int((np.asarray(s.node_targets[HEADS[1] + "_weight"]) > 0).sum()) for i, s in zip(ids, samples)},
    }


def rehearsal_overrides(nn: Dict[str, Any], over: Dict[str, Any]) -> None:
    nn["Architecture"].update(over)


def faults(cell) -> List[str]:
    return ["half_batch", "mtp_next", "softmax_router", "full_rope"]


class Step:
    """One step's documents as the reference takes them: rows padded to one
    length, the documents' first rows in the order of their (static) sizes."""

    def __init__(self, raw, ids: List[int], pad_rows: int):
        parts = [_rows(raw[i]) for i in ids]
        lens = [len(raw[i]["tokens"]) for i in ids]
        starts = np.concatenate([[0], np.cumsum(lens)[:-1]])
        order = np.argsort(lens, kind="stable")
        self.sizes = tuple(int(lens[i]) for i in order)
        rows = {k: np.concatenate([p[k] for p in parts]) for k in parts[0]}
        real = len(rows["ids"])
        doc = np.repeat(np.arange(len(ids)), lens)
        rows["valid"] = np.ones(real, bool)
        rows["first_half"] = doc < len(ids) // 2
        self.arrays = {
            "rows": {k: np.concatenate([v, np.zeros(pad_rows - real, v.dtype)]) for k, v in rows.items()},
            "starts": starts[order].astype(np.int32),
        }
        self.docs, self.rows = len(ids), real
        self.mtp_rows = int(sum(max(n - 2, 0) for n in lens))


def _follow(model, cfg, step_for, params0, steps: List[Step], lr: float, capture_at, keep=("params", "mu", "nu")):
    """``reference/common.py:follow`` for steps that also carry the
    balancing bias and count the assignments to held experts. The state is
    kept in the reference's stacked layout between steps and handed back
    under the program's names; ``keep`` says which trees of a captured
    state come to the host."""
    with jax.default_matmul_precision("highest"):
        params = jax.tree_util.tree_map(lambda x: jnp.asarray(x, jnp.float32),
                                        model.stack_layers(params0, cfg, np.stack))
        mu = jax.tree_util.tree_map(jnp.zeros_like, params)
        nu = jax.tree_util.tree_map(jnp.zeros_like, params)
        bias = model.zero_bias(cfg)
        losses, held, states = [], [], {}
        for k, s in enumerate(steps, start=1):
            params, mu, nu, bias, loss, h = step_for(s.sizes)(params, mu, nu, bias, jnp.float32(k), jnp.float32(lr), s.arrays)
            losses.append(float(loss))
            held.append(int(h))
            if k in capture_at:
                got = jax.device_get({n: t for n, t in (("params", params), ("mu", mu), ("nu", nu)) if n in keep})
                states[k] = {n: model.unstack_layers(t, cfg) for n, t in got.items()}
        del params, mu, nu
    return losses, held, states


def _drop_unread(taps) -> None:
    """Where the program made the learning-rate-0 pass, ``compare.numbers``
    reads of its captures that pass's moments and the real pass's
    parameters, nothing else: the other trees (2 and 3.9 GB at the cell's
    size) go back to the system before the reference takes its own host
    copies, or the one-chip machine's 40 GiB do not hold them all."""
    if not (taps.probe and "state" in taps.probe):
        return
    taps.probe["state"].pop("params", None)
    for state in taps.states.values():
        for name in ("mu", "nu"):
            state.pop(name, None)
    _release_host_memory()


def _release_host_memory() -> None:
    from families.token_documents import _release_host_memory as release

    release()


def reference_run(cell, taps, raw, quant=None, fault=None) -> Dict[str, Any]:
    from reference import joyai_llm_flash as model

    cfg = model.cfg_from_architecture(cell.run_config["NeuralNetwork"]["Architecture"])
    _drop_unread(taps)
    capture_at = sorted(taps.states)
    groups = taps.step_groups[: capture_at[-1]]
    pad_rows = max(sum(len(raw[i]["tokens"]) for i in g[0]) for g in groups) + 16
    steps = [Step(raw, g[0], pad_rows) for g in groups]
    lr = float(cell.training["Optimizer"]["learning_rate"])
    compiled: Dict[tuple, Any] = {}

    def step_for(sizes):
        if sizes not in compiled:
            compiled[sizes] = model.make_step(cfg, sizes, quant, fault)
        return compiled[sizes]

    probe = probe_held = None
    if taps.probe is not None:
        # compare.numbers reads the moments of this pass and the parameters of the real one
        pl, probe_held, ps = _follow(model, cfg, step_for, taps.initial_params, steps, 0.0, [len(steps)], keep=("mu", "nu"))
        probe = {"losses": pl, "state": ps[len(steps)]}
    losses, held, states = _follow(model, cfg, step_for, taps.initial_params, steps, lr, capture_at,
                                   keep=("params",) if probe else ("params", "mu", "nu"))
    share = 0.5 if fault == "half_batch" else 1.0
    lens = [len(raw[i]["tokens"]) for i in taps.train_ids]
    real = {
        "graphs_per_epoch": len(lens),
        "steps_per_epoch": taps.steps_per_epoch,
        "rows_per_epoch": sum(lens),
        "tokens_per_epoch": sum(lens),
        "main_rows_per_epoch": sum(n - 1 for n in lens),  # rows with a target t[i+1]
        "mtp_rows_per_epoch": sum(n - 2 for n in lens),  # rows with a target t[i+2]
        # query-key pairs the document-causal mask allows in a document of n tokens
        "allowed_pairs_per_epoch": sum(n * (n + 1) // 2 for n in lens),
        # at the initial weights where the program made that pass, else over the real steps
        "held_assignments_per_epoch": int(sum(probe_held or held) * taps.steps_per_epoch / len(steps)),
    }
    compiled.clear()
    _release_host_memory()
    return {
        "losses": losses, "states": states, "probe": probe,
        # "graphs" is the harness's word (readings.py prints it): a graph is a document here
        "graphs": [int(s.docs * share) for s in steps], "rows": [s.rows for s in steps],
        "mtp_rows": [s.mtp_rows for s in steps], "real": real,
    }


def exact_checks(taps, ref) -> Dict[str, Dict[str, Any]]:
    """Documents the program counted into each step, and the rows and the
    rows with a target at the prediction depth it holds for each step's
    samples, against the reference's own counts from the raw documents."""
    seen = taps.graphs_seen[: len(ref["graphs"])]
    rows, mtp = taps.sample_counts["program_rows"], taps.sample_counts["program_mtp"]
    groups = taps.step_groups[: len(ref["rows"])]
    prog_rows = [sum(rows[i] for i in g[0]) for g in groups]
    prog_mtp = [sum(mtp[i] for i in g[0]) for g in groups]

    def diff(a, b):
        return sum(abs(x - y) for x, y in zip(a, b)) + abs(len(a) - len(b))

    return {
        "documents_step_diff": {"value": diff(seen, ref["graphs"]), "limit": 0},
        "rows_step_diff": {"value": diff(prog_rows, ref["rows"]), "limit": 0},
        "mtp_rows_step_diff": {"value": diff(prog_mtp, ref["mtp_rows"]), "limit": 0},
    }


def control_readings(cell, seed: int, which: Sequence[str]) -> Iterator[Dict[str, Any]]:
    """The fp8 control (``"control_fp8"``) and planted faults
    (``"fault_<name>"``) of ``which`` on one seed, each against the float32
    reference over the first epoch's steps: what ``readings.py --controls``
    prints for them, without a training run (the program's initial weights
    and the documents of each step come from its own loader and model)."""
    import compare
    import weights

    from hydragnn_tpu.api import prepare_loaders_and_config
    from hydragnn_tpu.models.create import create_model_config

    raw = generate(cell.traffic, seed)
    samples = program_samples(raw)
    train_loader, _, _, config = prepare_loaders_and_config(cell.run_config, samples)
    _, variables = create_model_config(config["NeuralNetwork"], next(iter(train_loader)))
    p0 = jax.device_get(weights.make(variables["params"], seed))
    del variables
    bs, nb = cell.batch_size, len(train_loader)
    order = np.random.default_rng(train_loader.seed).permutation(nb)  # epoch 0's shuffle, as the scanned epoch draws it
    index_of = {id(s): i for i, s in enumerate(samples)}
    train_ids = [index_of[id(s)] for s in train_loader.samples]
    taps = types.SimpleNamespace(
        initial_params=p0, states={nb: None}, probe={"made": True}, steps_per_epoch=nb, train_ids=train_ids,
        step_groups=[[train_ids[int(b) * bs:(int(b) + 1) * bs]] for b in order])
    t0 = time.perf_counter()
    ref = reference_run(cell, taps, raw)
    yield {"seed": seed, "who": "reference", "seconds": time.perf_counter() - t0, "losses": ref["losses"], "real": ref["real"]}
    for who in which:
        kw = {"quant": "fp8"} if who == "control_fp8" else {"fault": who[len("fault_"):]}
        t0 = time.perf_counter()
        side = reference_run(cell, taps, raw, **kw)
        nums = compare.numbers(side, ref, p0)
        yield {"seed": seed, "who": who, **{k: nums[k] for k in ("loss_gap", "grad_gap", "grad_diff_median", "update_gap")},
               "grad_leaf": nums["grad_gap_leaf"], "update_leaf": nums["update_gap_leaf"], "graphs": side["graphs"],
               "seconds": time.perf_counter() - t0}
        del side, nums


def main() -> None:
    import argparse

    bench = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    for p in (os.path.dirname(bench), bench):
        if p not in sys.path:
            sys.path.insert(0, p)
    import cell as cellmod

    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--which", default=",".join(["control_fp8"] + [f"fault_{f}" for f in faults(None)]))
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    cellmod.place_compile_cache(args.rehearse)
    cell = cellmod.load_cell(args.workload, rehearse=args.rehearse)
    for seed in (int(s) for s in args.seeds.split(",")):
        for line in control_readings(cell, seed, args.which.split(",")):
            print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
