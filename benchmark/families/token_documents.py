"""The family of configurations that train a decoder on packed token
documents under the block-diffusion objective (``reference/sdar_moe.py``
has the equations and their sources). ``families/message_passing.py`` lists
what the harness asks of a family.

Data, all from ``--seed``: ``documents`` token documents whose lengths are
the cell's ``lengths`` (one group = one step's documents) in another order
in every group, so that every batch of every split is one whole group and
the program's pad plan has the same slots from seed to seed (as
``datagen.py`` does for the lattices); ids uniform over the vocabulary held
but its last row, which stands for the mask id; a masking rate a block by a
stratified draw over ``[t_min, t_max]``, each token of the block masked
with that probability. The noising is this file's own: the program's
transform (``hydragnn_tpu/data/tokens.py``) is for its users, and the
benchmark hands the program samples it made itself.

Exact checks, limit 0: documents a step, real rows a step, masked rows a
step. The assignments to held experts are NOT one: a bfloat16 hidden state
turns a ninth-against-eighth expert now and then, so ``real`` carries the
reference's count, and ``routing_flipped_rows_share`` says what share of
the first step's rows gets another set of experts in some layer when the
reference's matrix products take bfloat16 operands.

The upper readings for the limits at the cell's own size:

    python3 benchmark/families/token_documents.py --workload <cell> --seeds 11,12,13

``readings.py --controls`` holds the program's three host states, the
reference's and ``compare.numbers``' float64 copies beside each control's,
which at this cell's 5.5 GB state passes the one-chip machine's 40 GiB. A
control or a planted fault is compared with the float32 reference, and of
the program that needs only its initial weights and which documents went
into each step: :func:`control_readings` takes those from the program's
own loader and model and runs no training; the lines are ``readings.py``'s.
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

HEAD = "token"


def generate(traffic: Dict[str, Any], seed: int) -> List[Dict[str, np.ndarray]]:
    lengths = np.asarray(traffic["lengths"], np.int64)
    block, vocab = int(traffic["block_length"]), int(traffic["vocab"])
    t_min, t_max = float(traffic["t_min"]), float(traffic["t_max"])
    groups, rest = divmod(int(traffic["documents"]), len(lengths))
    if rest or np.any(lengths % block):
        raise ValueError("documents must be whole groups of `lengths`, each a multiple of the block length")
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32, 31])
    raw = []
    for _ in range(groups):
        for n in rng.permutation(lengths):
            blocks = int(n) // block
            rate = t_min + (t_max - t_min) * (rng.permutation(blocks) + rng.random(blocks)) / blocks
            rate = np.repeat(rate, block).astype(np.float32)
            raw.append({
                "tokens": rng.integers(0, vocab - 1, size=int(n)).astype(np.int32),
                "masked": rng.random(int(n)) < rate, "rate": rate, "mask_id": np.int32(vocab - 1),
            })
    return raw


def _rows(r: Dict[str, np.ndarray]) -> Dict[str, np.ndarray]:
    """One document's ``2n`` rows: the noised copy, then the clean copy."""
    n = len(r["tokens"])
    index = np.arange(n, dtype=np.int32)
    return {
        "ids": np.concatenate([np.where(r["masked"], r["mask_id"], r["tokens"]), r["tokens"]]).astype(np.int32),
        "index": np.concatenate([index, index]),
        "cpy": np.concatenate([np.ones(n, np.int32), np.zeros(n, np.int32)]),
        "target": np.concatenate([r["tokens"], r["tokens"]]).astype(np.int32),
        "weight": np.concatenate([np.where(r["masked"], 1.0 / r["rate"], 0.0), np.zeros(n)]).astype(np.float32),
    }


def program_samples(raw: List[Dict[str, np.ndarray]]):
    from hydragnn_tpu.data.dataset import GraphSample

    out = []
    for r in raw:
        rows = _rows(r)
        out.append(GraphSample(
            x=np.stack([rows["ids"], rows["index"], rows["cpy"]], axis=1),
            edge_index=np.zeros((2, 0), np.int32),
            node_targets={HEAD: rows["target"][:, None], HEAD + "_weight": rows["weight"][:, None]},
        ))
    return out


def count_samples(ids: List[int], samples) -> Dict[str, Any]:
    """Raw sample id -> the rows the program holds for it, and those of them that carry a weight."""
    return {
        "program_rows": {i: int(s.num_nodes) for i, s in zip(ids, samples)},
        "program_masked": {i: int((np.asarray(s.node_targets[HEAD + "_weight"]) > 0).sum()) for i, s in zip(ids, samples)},
    }


def rehearsal_overrides(nn: Dict[str, Any], over: Dict[str, Any]) -> None:
    nn["Architecture"].update(over)


def faults(cell) -> List[str]:
    return ["half_batch", "causal_mask", "lost_expert"]


class Step:
    """One step's documents as the reference takes them: rows padded to one
    length, and the documents' first rows in the order of their (static)
    sizes, so that steps holding the same lengths are one compiled program."""

    def __init__(self, raw, ids: List[int], pad_rows: int):
        parts = [_rows(raw[i]) for i in ids]
        lens = [len(raw[i]["tokens"]) for i in ids]
        starts = np.concatenate([[0], np.cumsum([2 * n for n in lens])[:-1]])
        order = np.argsort(lens, kind="stable")
        self.sizes = tuple(int(lens[i]) for i in order)
        rows = {k: np.concatenate([p[k] for p in parts]) for k in parts[0]}
        real = len(rows["ids"])
        doc = np.repeat(np.arange(len(ids)), [2 * n for n in lens])
        rows["valid"] = np.ones(real, bool)
        rows["first_half"] = doc < len(ids) // 2
        self.arrays = {
            "rows": {k: np.concatenate([v, np.zeros(pad_rows - real, v.dtype)]) for k, v in rows.items()},
            "starts": starts[order].astype(np.int32),
        }
        self.docs, self.rows = len(ids), real
        self.masked = int(sum(raw[i]["masked"].sum() for i in ids))


def _release_host_memory() -> None:
    """Hand what the compiler and the dropped states left in the C
    allocator's arenas back to the system: the one-chip machine has 40 GiB,
    and this family's cell holds float32 and float64 copies of a 5.5 GB
    state on the host while ``compare.numbers`` runs (39.7 GB at the peak of
    one run, my chip run, PR 31)."""
    import ctypes
    import gc

    gc.collect()
    try:
        ctypes.CDLL("libc.so.6").malloc_trim(0)
    except (OSError, AttributeError):
        pass


def _follow(step_for, params0, steps: List[Step], lr: float, capture_at, layers: int, keep=("params", "mu", "nu")):
    """``reference/common.py:follow`` for steps that also count the
    assignments to held experts. ``keep``: which of a captured state's
    trees come to the host (each is 1.8 GB at the cell's size, and the host
    holds the program's three states beside them). The state is kept in the
    reference's stacked layout between steps and handed back under the
    program's names."""
    from reference.sdar_moe import stack_layers, unstack_layers

    with jax.default_matmul_precision("highest"):
        params = jax.tree_util.tree_map(lambda x: jnp.asarray(x, jnp.float32), stack_layers(params0, layers, np.stack))
        mu = jax.tree_util.tree_map(jnp.zeros_like, params)
        nu = jax.tree_util.tree_map(jnp.zeros_like, params)
        losses, held, states = [], [], {}
        for k, s in enumerate(steps, start=1):
            params, mu, nu, loss, h = step_for(s.sizes)(params, mu, nu, jnp.float32(k), jnp.float32(lr), s.arrays)
            losses.append(float(loss))
            held.append(int(h))
            if k in capture_at:
                got = jax.device_get({n: t for n, t in (("params", params), ("mu", mu), ("nu", nu)) if n in keep})
                states[k] = {n: unstack_layers(t, layers) for n, t in got.items()}
        del params, mu, nu
    return losses, held, states


def reference_run(cell, taps, raw, quant=None, fault=None) -> Dict[str, Any]:
    from reference import sdar_moe as model

    cfg = model.cfg_from_architecture(cell.run_config["NeuralNetwork"]["Architecture"])
    capture_at = sorted(taps.states)
    groups = taps.step_groups[: capture_at[-1]]
    pad_rows = max(sum(2 * len(raw[i]["tokens"]) for i in g[0]) for g in groups) + 16
    block = int(cell.traffic["block_length"])
    steps = [Step(raw, g[0], pad_rows) for g in groups]
    lr = float(cell.training["Optimizer"]["learning_rate"])
    compiled: Dict[tuple, Any] = {}

    def step_for(sizes):
        if sizes not in compiled:
            compiled[sizes] = model.make_step(cfg, sizes, quant, fault, count_held=True)
        return compiled[sizes]

    probe = probe_held = None
    if taps.probe is not None:
        # compare.numbers reads the moments of this pass and the parameters of the real one
        pl, probe_held, ps = _follow(step_for, taps.initial_params, steps, 0.0, [len(steps)], cfg.layers, keep=("mu", "nu"))
        probe = {"losses": pl, "state": ps[len(steps)]}
    losses, held, states = _follow(step_for, taps.initial_params, steps, lr, capture_at, cfg.layers,
                                   keep=("params",) if probe else ("params", "mu", "nu"))
    share = 0.5 if fault == "half_batch" else 1.0
    lens = [len(raw[i]["tokens"]) for i in taps.train_ids]
    real = {
        "graphs_per_epoch": len(lens),
        "steps_per_epoch": taps.steps_per_epoch,
        "rows_per_epoch": 2 * sum(lens),
        "tokens_per_epoch": sum(lens),
        # query-key pairs the mask allows in a document of n tokens: n * block (noised to noised),
        # (n * n - n * block) / 2 (noised to clean), (n * n + n * block) / 2 (clean to clean)
        "allowed_pairs_per_epoch": sum(n * n + n * block for n in lens),
        # at the initial weights where the program made that pass, else over the real steps
        "held_assignments_per_epoch": int(sum(probe_held or held) * taps.steps_per_epoch / len(steps)),
    }
    compiled.clear()
    _release_host_memory()
    if quant is None and fault is None:
        with jax.default_matmul_precision("highest"):
            p0 = jax.tree_util.tree_map(lambda x: jnp.asarray(x, jnp.float32), taps.initial_params)
            real["routing_flipped_rows_share"] = float(model.routing_flips(p0, steps[0].arrays, steps[0].sizes, cfg))
    _release_host_memory()
    return {
        "losses": losses, "states": states, "probe": probe,
        # "graphs" is the harness's word (readings.py prints it): a graph is a document here
        "graphs": [int(s.docs * share) for s in steps], "rows": [s.rows for s in steps],
        "masked": [s.masked for s in steps], "real": real,
    }


def exact_checks(taps, ref) -> Dict[str, Dict[str, Any]]:
    """Documents the program counted into each step, and the rows and
    masked rows it holds for each step's samples, against the reference's
    own counts from the raw documents."""
    seen = taps.graphs_seen[: len(ref["graphs"])]
    rows, masked = taps.sample_counts["program_rows"], taps.sample_counts["program_masked"]
    groups = taps.step_groups[: len(ref["rows"])]
    prog_rows = [sum(rows[i] for i in g[0]) for g in groups]
    prog_masked = [sum(masked[i] for i in g[0]) for g in groups]

    def diff(a, b):
        return sum(abs(x - y) for x, y in zip(a, b)) + abs(len(a) - len(b))

    return {
        "documents_step_diff": {"value": diff(seen, ref["graphs"]), "limit": 0},
        "rows_step_diff": {"value": diff(prog_rows, ref["rows"]), "limit": 0},
        "masked_rows_step_diff": {"value": diff(prog_masked, ref["masked"]), "limit": 0},
    }


def control_readings(cell, seed: int, which: Sequence[str]) -> Iterator[Dict[str, Any]]:
    """The fp8 control (``"control_fp8"``) and planted faults
    (``"fault_<name>"``) of ``which`` on one seed, each against the float32
    reference over the first epoch's steps: what ``readings.py --controls``
    prints for them, without a training run."""
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
    train_ids = [index_of[id(s)] for s in train_loader.samples]  # raw documents of the train split, in the loader's order
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
    ap.add_argument("--which", default="control_fp8,fault_half_batch,fault_causal_mask,fault_lost_expert")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    cellmod.place_compile_cache(args.rehearse)
    cell = cellmod.load_cell(args.workload, rehearse=args.rehearse)
    for seed in (int(s) for s in args.seeds.split(",")):
        for line in control_readings(cell, seed, args.which.split(",")):
            print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
