"""PNA (HydraGNN PNAStack): operations and bytes from shapes (see ``cost.py`` for the rules)."""

from typing import Dict

from cost import ACT_BYTES, ID_BYTES, OUT_BYTES, heads_flops, widths


def forward(arch, voi, real: Dict[str, float]) -> Dict[str, float]:
    n, e, g = real["nodes_per_epoch"], real["edges_per_epoch"], real["graphs_per_epoch"]
    conv = agg = 0.0
    for fin, out in widths(arch, len(voi["input_node_features"])):
        conv += n * (2 * fin) * fin * 2  # pre network, node level
        conv += n * (17 * fin) * out * 2  # post network
        agg += e * fin * 6  # shift, square, sum, sum of squares, max, min
        agg += n * 16 * fin * 2  # scalers
    bn = n * int(arch["hidden_dim"]) * 8 * int(arch["num_conv_layers"])
    return {"conv_matmul": conv, "edge_aggregation": agg, "batchnorm": bn,
            "heads": heads_flops(arch, voi, n, g)}


def kernel(arch, voi, real: Dict[str, float]) -> Dict[str, float]:
    """Gather of the sender table + the four statistics, per layer."""
    n, e = real["nodes_per_epoch"], real["edges_per_epoch"]
    fwd_bytes = fwd_flops = 0.0
    for fin, _ in widths(arch, len(voi["input_node_features"])):
        fwd_bytes += n * fin * ACT_BYTES + 2 * e * ID_BYTES + 4 * n * fin * OUT_BYTES
        fwd_flops += e * fin * 6
    return {"bytes": 3 * fwd_bytes, "flops": 3 * fwd_flops}
