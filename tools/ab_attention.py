"""Time the block-attention kernels (``block_attention_fwd``, ``_dq``,
``_dkv``) with one key-value head a grid step against the head block that
``ops/block_attention.py:kv_heads_per_step`` chooses, one process, on the
chip.

    python tools/ab_attention.py [--query-heads 4,16] [--reps 10] [--out FILE]

``--rows N`` keeps the first N rows of each batch: a rehearsal on the CPU
(Pallas in interpret mode there), never a measurement.

Rows and heads are the token cells' own: the first train batch of
``joyai-llm-flash.train-causal-mtp-docs`` (latent attention: 32 one-head
groups scoring at 192 and carrying 128, 8,208 row slots) and of
``sdar-30b-a3b-chat.train-blockdiff-docs`` (32 query heads in 4 groups of
128, 16,400 row slots), as the program's loader builds them from the
benchmark's samples, and the pair lists the program builds from them; q,
k, v and the output's cotangent are bfloat16 draws from ``--seed``. Per
cell, kernel and head block (1, the rule's, and the blocks that give each
of ``--query-heads``, where they divide the heads and fit the kernels'
VMEM): the median wall time of ``--reps`` calls after one warm-up call
(each ends in ``block_until_ready``), and whether the outputs equal those
of one key-value head a step bit for bit. The head block is a parameter
of the private kernel calls; ``block_attention`` takes it from the rule.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "benchmark"))

CELLS = ("joyai-llm-flash.train-causal-mtp-docs", "sdar-30b-a3b-chat.train-blockdiff-docs")


def cell_case(workload: str, seed: int):
    """(heads ``(hq, hkv, dk, dv)``, the batch's row integers ``doc, blk, cpy``)."""
    import cell as cellmod
    from hydragnn_tpu.api import prepare_loaders_and_config
    from hydragnn_tpu.data.tokens import COPY, INDEX

    c = cellmod.load_cell(workload)
    arch = c.run_config["NeuralNetwork"]["Architecture"]
    train, _, _, _ = prepare_loaders_and_config(c.run_config, c.fam.program_samples(c.fam.generate(c.traffic, seed)))
    b = train.peek_batch()
    index = b.nodes[:, INDEX]
    if arch["model_type"] == "LatentAttentionMoE":
        hq = arch["num_attention_heads"]
        heads = (hq, hq, arch["qk_nope_head_dim"] + arch["qk_rope_head_dim"], arch["v_head_dim"])
        return heads, (b.node_graph, index, b.nodes[:, COPY])
    heads = (arch["num_attention_heads"], arch["num_key_value_heads"], arch["head_dim"], arch["head_dim"])
    return heads, (b.node_graph, index // arch["block_length"], b.nodes[:, COPY])


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--query-heads", default="4,16", help="more query heads a grid step to time, besides 1 kv head and the rule's")
    ap.add_argument("--reps", type=int, default=10)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--rows", type=int, default=0)
    ap.add_argument("--out", default=os.path.join(ROOT, "chiprun_out", "ab_attention.json"))
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from hydragnn_tpu.ops import block_attention as ba

    interpret = jax.default_backend() != "tpu"
    extra = [int(h) for h in args.query_heads.split(",") if h]
    static = ("scale", "kvb")

    @functools.partial(jax.jit, static_argnames=static)
    def fwd(q, k, v, m, p, scale, kvb):
        return ba._forward(q, k, v, m, m.T, p, scale, ba.TILE, interpret, kvb)

    @functools.partial(jax.jit, static_argnames=static)
    def dq(q, k, v, do, lse, delta, m, p, scale, kvb):
        return ba._dq(q, k, v, do, lse, delta, m, m.T, p, scale, ba.TILE, interpret, kvb)

    @functools.partial(jax.jit, static_argnames=static)
    def dkv(q, k, v, do, lse, delta, m, p, scale, kvb):
        return ba._dkv(q, k, v, do, lse, delta, m, m.T, p, scale, ba.TILE, interpret, kvb)

    rows_out, ok = [], True
    for workload in CELLS:
        (hq, hkv, dk, dv), row_ints = cell_case(workload, args.seed)
        if args.rows:
            row_ints = tuple(a[:args.rows] for a in row_ints)
        qmeta, (pairs_q, pairs_k) = ba.attention_plan(*row_ints)
        n, group, scale = qmeta.shape[0], hq // hkv, dk ** -0.5
        keys = jax.random.split(jax.random.PRNGKey(args.seed), 4)
        q, k = (jax.random.normal(kk, (h, n, dk), jnp.bfloat16) for kk, h in zip(keys[:2], (hq, hkv)))
        v = jax.random.normal(keys[2], (hkv, n, dv), jnp.bfloat16)
        do = jax.random.normal(keys[3], (hq, n, dv), jnp.bfloat16)
        rule = ba.kv_heads_per_step(hq, hkv, dk, dv)
        blocks = sorted({1, rule} | {h // group for h in extra if h % group == 0 and hkv % (h // group) == 0
                                     and ba._vmem_bytes(h, h // group, ba.TILE, dk, dv) <= ba._VMEM_LIMIT})
        first = {}
        for kvb in blocks:
            kw = {"scale": scale, "kvb": kvb}
            o, lse = jax.block_until_ready(fwd(q, k, v, qmeta, pairs_q, **kw))
            delta = (do.astype(jnp.float32) * o.astype(jnp.float32)).sum(-1, keepdims=True)
            calls = {"block_attention_fwd": (fwd, (q, k, v, qmeta, pairs_q)),
                     "block_attention_dq": (dq, (q, k, v, do, lse, delta, qmeta, pairs_q)),
                     "block_attention_dkv": (dkv, (q, k, v, do, lse, delta, qmeta, pairs_k))}
            for name, (f, ops) in calls.items():
                out = jax.block_until_ready(f(*ops, **kw))
                times = []
                for _ in range(args.reps):
                    t0 = time.perf_counter()
                    jax.block_until_ready(f(*ops, **kw))
                    times.append(time.perf_counter() - t0)
                host = [np.asarray(x) for x in jax.tree_util.tree_leaves(out)]
                same = None
                if name in first:
                    same = all(np.array_equal(a, b) for a, b in zip(host, first[name]))
                    ok = ok and same
                else:
                    first[name] = host
                row = {"cell": workload, "kernel": name, "kv_heads_per_step": kvb, "query_heads_per_step": kvb * group,
                       "rule": kvb == rule, "rows": n, "active_pairs": int(pairs_q[4][0]),
                       "grid_steps": hkv // kvb * int(pairs_q[0].shape[0]),
                       "median_ms": 1e3 * statistics.median(times), "min_ms": 1e3 * min(times),
                       "max_ms": 1e3 * max(times), "equal_to_one_kv_head": same}
                rows_out.append(row)
                print(json.dumps(row), flush=True)
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump({"device": jax.devices()[0].device_kind, "reps": args.reps, "seed": args.seed, "rows": rows_out}, f,
                  indent=1)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
