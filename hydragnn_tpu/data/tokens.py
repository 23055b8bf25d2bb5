"""Token documents as graphs without edges: the block-diffusion noising
and the next-token layout.

A document of ``n`` tokens becomes ONE graph of ``2n`` nodes and no edge:
the noised copy first, then the clean copy (BD3-LM's training layout,
arXiv:2503.09573; SDAR, arXiv:2510.06303). What mixes among a graph's
nodes is their ORDER, not an edge list: ``models/token_stack.py`` computes
its attention mask from three integers a row, which are the node features

  x[:, 0]  token id as the model sees it (the mask id where noised)
  x[:, 1]  index of the token in its own document (the same in both copies)
  x[:, 2]  1 in the noised copy, 0 in the clean copy

all int32, so that neither min-max normalisation nor a float cast touches
them (``Dataset.format: "token_documents"`` in ``data/ingest.py``). The
targets are ``node_targets[name]`` (int32 ``[2n, 1]``: the clean token id)
and ``node_targets[name + "_weight"]`` (float32 ``[2n, 1]``: ``1/t`` on the
masked rows of the noised copy, 0 everywhere else), which is what
``models/base.py:model_loss`` takes for ``loss_function_type:
"cross_entropy"``.

The noise: every block of ``block_length`` tokens draws its own masking
rate ``t`` and each of its tokens is replaced by ``mask_id`` with
probability ``t`` (the linear schedule of MDLM, arXiv:2406.07524, whose
loss weight is ``1/t``). The rates of a document's blocks are a stratified
draw over ``[t_min, t_max]`` (one rate from each of as many equal strata as
the document has blocks, in a random order), clipped from below so that the
weight is bounded by ``1/t_min``.

Next-token training (``models/token_stack.py:LatentStack``) takes ONE copy:
a document of ``n`` tokens is a graph of ``n`` nodes (token, index, copy 0),
and head ``d`` of ``head_names`` has the target ``t[i + 1 + d]`` with the
weight 1, or 0 (and the id 0) where the document has no such token
(:func:`next_token_samples`).
"""

from __future__ import annotations

from typing import List, Sequence

import numpy as np

from hydragnn_tpu.data.dataset import GraphSample

TOKEN, INDEX, COPY = 0, 1, 2  # the columns of a sample's ``x``


def block_rates(num_blocks: int, rng: np.random.Generator, t_min: float, t_max: float) -> np.ndarray:
    """One masking rate a block: stratum ``k`` of ``num_blocks`` equal
    strata of ``[t_min, t_max]`` gives one uniform draw, and the strata are
    dealt to the blocks in a random order."""
    u = (rng.permutation(num_blocks) + rng.random(num_blocks)) / num_blocks
    return t_min + (t_max - t_min) * u


def noise_document(
    tokens: np.ndarray,
    rng: np.random.Generator,
    block_length: int,
    mask_id: int,
    t_min: float = 0.1,
    t_max: float = 1.0,
    head_name: str = "token",
) -> GraphSample:
    """One document (int ids, ``[n]``) as a ``GraphSample`` of ``2n`` nodes."""
    tokens = np.asarray(tokens).astype(np.int32).reshape(-1)
    n = int(tokens.shape[0])
    if n == 0 or n % block_length:
        raise ValueError(f"a document's length ({n}) must be a positive multiple of the block length {block_length}")
    t = np.repeat(block_rates(n // block_length, rng, t_min, t_max), block_length)
    masked = rng.random(n) < t
    index = np.arange(n, dtype=np.int32)
    x = np.empty((2 * n, 3), np.int32)
    x[:n, TOKEN] = np.where(masked, mask_id, tokens)
    x[n:, TOKEN] = tokens
    x[:n, INDEX] = index
    x[n:, INDEX] = index
    x[:n, COPY] = 1
    x[n:, COPY] = 0
    weight = np.zeros((2 * n, 1), np.float32)
    weight[:n, 0] = np.where(masked, 1.0 / t, 0.0)
    return GraphSample(
        x=x,
        edge_index=np.zeros((2, 0), np.int32),
        node_targets={head_name: np.concatenate([tokens, tokens])[:, None], head_name + "_weight": weight},
    )


def block_diffusion_samples(
    documents: Sequence[np.ndarray],
    seed: int,
    block_length: int,
    mask_id: int,
    t_min: float = 0.1,
    t_max: float = 1.0,
    head_name: str = "token",
) -> List[GraphSample]:
    """The transform a user calls on token documents before
    ``run_training(config, samples=...)``: each document noised once, from
    ``seed`` and its place in the list."""
    return [
        noise_document(doc, np.random.default_rng([int(seed), i]), block_length, mask_id, t_min, t_max, head_name)
        for i, doc in enumerate(documents)
    ]


def next_token_document(tokens: np.ndarray, head_names: Sequence[str] = ("token", "token_mtp")) -> GraphSample:
    """One document (int ids, ``[n]``) as a ``GraphSample`` of ``n`` nodes,
    one head a name: head ``d`` predicts the token ``1 + d`` further on."""
    tokens = np.asarray(tokens).astype(np.int32).reshape(-1)
    n = int(tokens.shape[0])
    if n < 2:
        raise ValueError(f"a document needs at least two tokens for a next token, got {n}")
    x = np.zeros((n, 3), np.int32)
    x[:, TOKEN] = tokens
    x[:, INDEX] = np.arange(n, dtype=np.int32)
    targets = {}
    for d, name in enumerate(head_names):
        ahead = 1 + d
        target = np.zeros((n, 1), np.int32)
        weight = np.zeros((n, 1), np.float32)
        target[: n - ahead, 0] = tokens[ahead:]
        weight[: n - ahead, 0] = 1.0
        targets[name], targets[name + "_weight"] = target, weight
    return GraphSample(x=x, edge_index=np.zeros((2, 0), np.int32), node_targets=targets)


def next_token_samples(documents: Sequence[np.ndarray], head_names: Sequence[str] = ("token", "token_mtp")) -> List[GraphSample]:
    """The transform a user calls on token documents before
    ``run_training(config, samples=...)`` for next-token training with
    ``len(head_names) - 1`` more prediction depths."""
    return [next_token_document(doc, head_names) for doc in documents]
