"""Operations and bytes from the configuration's shapes: the yardstick for
``train_step_mfu`` and ``pallas_roofline_share``.

Everything is counted on REAL nodes, edges and graphs (padding is the
program's choice and counts for nothing), for the cheapest algebraic form
of the published layer that this framework could run (PNA's pre-network
is one linear layer, so it is priced at node level: x_i W_i + x_j W_j),
multiply-add = 2 operations. A training step is priced at 3x the forward
pass (backward = 2x forward, the usual convention); recomputation counts
for nothing. Small terms (BatchNorm, activations, pooling) are priced at
a few operations per element so that they are not forgotten, not because
they matter.

``kernel_bytes`` is the least HBM traffic of a conv layer's fused
gather -> edge operation -> scatter chain, which is what the Pallas
kernels exist to approach: the node table read once and the edge ids
read once, the per-edge operand the layer cannot avoid reading (SchNet's
filter), and the node-level result written once; activations in
bfloat16 (2 bytes, the cell's stated precision), ids and results in 4
bytes. The backward pass moves the same tables twice (the cotangent in,
the gradient out). It is a floor: a share of it cannot pass 100%.
"""

from __future__ import annotations

from typing import Any, Dict, List

ACT_BYTES, ID_BYTES, OUT_BYTES = 2, 4, 4


def widths(arch: Dict[str, Any], input_dim: int) -> List[tuple]:
    h = int(arch["hidden_dim"])
    return [(input_dim if i == 0 else h, h) for i in range(int(arch["num_conv_layers"]))]


def heads_flops(arch, voi, n: float, g: float) -> float:
    h = int(arch["hidden_dim"])
    heads = arch["output_heads"]
    total = 0.0
    gs = heads["graph"]
    shared_dim = int(gs["dim_sharedlayers"])
    d = h
    for _ in range(int(gs["num_sharedlayers"])):
        total += g * d * shared_dim * 2
        d = shared_dim
    for typ in voi["type"]:
        cfg = heads["graph"] if typ == "graph" else heads["node"]
        rows = g if typ == "graph" else n
        d = shared_dim if typ == "graph" else h
        for width in list(cfg["dim_headlayers"])[: int(cfg["num_headlayers"])] + [1]:
            total += rows * d * int(width) * 2
            d = int(width)
    return total


def model(name: str):
    """``costmodels/<name>.py``: ``forward(arch, voi, real)`` -> operations by
    part, ``kernel(arch, voi, real)`` -> {"bytes", "flops"}, where ``real`` is the
    dictionary of real counts an epoch that the cell's family makes
    (``reference_run``'s ``real``); a model reads the keys it prices. A new
    configuration adds a file."""
    import importlib

    return importlib.import_module(f"costmodels.{name}")


def train_step_flops(name: str, run_config: Dict[str, Any], real: Dict[str, float]) -> float:
    nn = run_config["NeuralNetwork"]
    return 3.0 * sum(model(name).forward(nn["Architecture"], nn["Variables_of_interest"], real).values())


def kernel_floor(name: str, run_config: Dict[str, Any], real: Dict[str, float]) -> Dict[str, float]:
    nn = run_config["NeuralNetwork"]
    return model(name).kernel(nn["Architecture"], nn["Variables_of_interest"], real)
