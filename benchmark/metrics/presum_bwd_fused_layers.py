"""PNA conv layers whose gather-statistics backward runs as the one
`gather_stats_bwd` kernel (regather, tie counts, grad_v and the sender
scatter in VMEM): the flight manifest's
`model.gather_presum.backward_fused_layers`, which the program computes at
set-up for the train loader's slots with the rule its dispatch uses. A
program that does not report it gives no reading."""

META = {"layer": "kernels (ops/segment_pallas.py, ops/fused_conv.py)", "unit": "x", "better": "higher",
        "source": "program_counter", "moves": "train_graphs_per_s"}


def read(ctx):
    model = ctx["manifest"].get("model") or {}
    return (model.get("gather_presum") or {}).get("backward_fused_layers")
