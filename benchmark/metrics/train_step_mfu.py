"""Operations of forward and backward from the configuration's shapes on
real nodes and edges (``cost.py``; recomputation counts for nothing) times
the optimizer steps of the window's untraced epochs, over their seconds,
the chips and the table's bf16 peak. The whole step's share of the chip:
it bounds every kernel's roofline share from above in what it can claim."""

META = {"layer": "model (models/, graph/segment.py)", "unit": "%", "better": "higher", "source": "host_clock",
        "moves": "train_graphs_per_s"}


def read(ctx):
    import cost
    import peaks

    if ctx["rehearse"] or not ctx["quiet_epochs"]:
        return None
    per_epoch = cost.train_step_flops(ctx["cell"].cost_model, ctx["cell"].run_config, ctx["real"])
    seconds = sum(ctx["epoch_seconds"][i] for i in ctx["quiet_epochs"])
    peak = peaks.lookup(ctx["device"]["kind"])["bf16_flops"] * ctx["cell"].chips
    return 100.0 * per_epoch * len(ctx["quiet_epochs"]) / (seconds * peak)
