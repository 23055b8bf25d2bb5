"""SchNet (HydraGNN SCFStack): operations and bytes from shapes (see ``cost.py`` for the rules)."""

from typing import Dict

from cost import ACT_BYTES, ID_BYTES, OUT_BYTES, heads_flops, widths


def forward(arch, voi, real: Dict[str, float]) -> Dict[str, float]:
    n, e, g = real["nodes_per_epoch"], real["edges_per_epoch"], real["graphs_per_epoch"]
    f, gauss = int(arch["num_filters"]), int(arch["num_gaussians"])
    filt = conv = msg = 0.0
    for fin, out in widths(arch, len(voi["input_node_features"])):
        filt += e * gauss * 4  # smearing
        filt += e * (gauss * f + f * f) * 2 + e * f * 4  # two dense layers, softplus, cutoff
        conv += n * fin * f * 2 + n * f * out * 2  # lin1, lin2
        msg += e * f * 2  # filter product and sum
    bn = n * int(arch["hidden_dim"]) * 8 * int(arch["num_conv_layers"])
    return {"filter_network": filt, "conv_matmul": conv, "edge_aggregation": msg,
            "batchnorm": bn, "heads": heads_flops(arch, voi, n, g)}


def kernel(arch, voi, real: Dict[str, float]) -> Dict[str, float]:
    """Gather of W1 x, product with the per-edge filter, sum per receiver."""
    n, e = real["nodes_per_epoch"], real["edges_per_epoch"]
    f = int(arch["num_filters"])
    fwd_bytes = fwd_flops = 0.0
    for _ in widths(arch, len(voi["input_node_features"])):
        fwd_bytes += n * f * ACT_BYTES + 2 * e * ID_BYTES + e * f * ACT_BYTES + n * f * OUT_BYTES
        fwd_flops += e * f * 2
    return {"bytes": 3 * fwd_bytes, "flops": 3 * fwd_flops}
