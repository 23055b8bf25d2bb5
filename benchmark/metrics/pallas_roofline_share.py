"""For the conv layers' gather -> edge operation -> scatter chain: the
least time the chip could take (the larger of ``cost.kernel_floor``'s
bytes over the table's HBM rate and its operations over the bf16 peak,
both from shapes and real edges; the bytes bound applies in both
configurations) over the summed device time of the Pallas custom calls in
the traced steps, per chip."""

META = {"layer": "kernels (ops/segment_pallas.py, ops/fused_conv.py)", "unit": "%", "better": "higher",
        "source": "device_trace", "moves": "train_graphs_per_s"}


def read(ctx):
    import cost
    import peaks

    tr = ctx["trace"]
    if not tr or not tr.get("pallas_s") or not ctx["traced_epochs"]:
        return None
    chips = ctx["cell"].chips
    floor = cost.kernel_floor(ctx["cell"].cost_model, ctx["cell"].run_config, ctx["real"])
    pk = peaks.lookup(ctx["device"]["kind"])
    least = max(floor["bytes"] / pk["hbm_bytes_s"], floor["flops"] / pk["bf16_flops"]) / chips
    return 100.0 * least * ctx["traced_epochs"] / tr["pallas_s"]
