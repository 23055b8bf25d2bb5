"""``cost.py`` against counts made by hand for a two-layer model of each
configuration, and the peaks table's refusal of an unknown device."""

import pytest

import cost
import peaks
from costmodels import pna, schnet


def _cfg(model_type, **arch):
    return {"NeuralNetwork": {
        "Architecture": dict({
            "model_type": model_type, "hidden_dim": 16, "num_conv_layers": 2,
            "output_heads": {
                "graph": {"num_sharedlayers": 2, "dim_sharedlayers": 16, "num_headlayers": 2, "dim_headlayers": [16, 8]},
                "node": {"num_headlayers": 2, "dim_headlayers": [16, 8], "type": "mlp"}},
        }, **arch),
        "Variables_of_interest": {"input_node_features": [0], "type": ["graph", "node", "node", "node"]},
    }}


N, E, G = 10, 40, 2
REAL = {"nodes_per_epoch": N, "edges_per_epoch": E, "graphs_per_epoch": G}
# heads, by hand: trunk 2 x (G*16*16*2) = 2048; graph head G*(16*16+16*8+8*1)*2 = 1568;
# three node heads N*(16*16+16*8+8*1)*2 = 7840 each
HEADS = 2048 + 1568 + 3 * 7840
BN = N * 16 * 8 * 2


def test_pna_two_layers_by_hand():
    # layer 0 (1 -> 16): pre N*2*1*1*2 = 40, post N*17*1*16*2 = 5440, edges E*1*6 = 240, scalers N*16*1*2 = 320
    # layer 1 (16 -> 16): pre N*32*16*2 = 10240, post N*272*16*2 = 87040, edges E*16*6 = 3840, scalers N*256*2 = 5120
    parts = pna.forward(*_split(_cfg("PNA")), REAL)
    assert parts["conv_matmul"] == 40 + 5440 + 10240 + 87040
    assert parts["edge_aggregation"] == 240 + 320 + 3840 + 5120
    assert parts["batchnorm"] == BN and parts["heads"] == HEADS
    assert cost.train_step_flops("pna", _cfg("PNA"), REAL) == 3 * (102760 + 9520 + BN + HEADS)
    # kernel floor: layer 0 N*1*2 + 2E*4 + 4N*1*4 = 500 B, layer 1 N*16*2 + 2E*4 + 4N*16*4 = 3200 B
    floor = cost.kernel_floor("pna", _cfg("PNA"), REAL)
    assert floor == {"bytes": 3 * 3700, "flops": 3 * (240 + 3840)}


def test_schnet_two_layers_by_hand():
    cfg = _cfg("SchNet", num_filters=16, num_gaussians=10)
    parts = schnet.forward(*_split(cfg), REAL)
    # per layer: smearing E*10*4 = 1600, two dense E*(10*16+16*16)*2 = 33280, softplus+cutoff E*16*4 = 2560
    assert parts["filter_network"] == 2 * (1600 + 33280 + 2560)
    # lin1 + lin2: layer 0 N*1*16*2 + N*16*16*2 = 5440, layer 1 2 * N*16*16*2 = 10240
    assert parts["conv_matmul"] == 5440 + 10240
    assert parts["edge_aggregation"] == 2 * E * 16 * 2
    assert cost.train_step_flops("schnet", cfg, REAL) == 3 * (74880 + 15680 + 2560 + BN + HEADS)
    # kernel floor per layer: N*16*2 + 2E*4 + E*16*2 + N*16*4 = 2560 B; E*16*2 = 1280 operations
    assert cost.kernel_floor("schnet", cfg, REAL) == {"bytes": 3 * 2 * 2560, "flops": 3 * 2 * 1280}


def _split(cfg):
    nn = cfg["NeuralNetwork"]
    return nn["Architecture"], nn["Variables_of_interest"]


def test_unknown_device_is_an_error():
    assert peaks.lookup("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(SystemExit):
        peaks.lookup("TPU v99")
