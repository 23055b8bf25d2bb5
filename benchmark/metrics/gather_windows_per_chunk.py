"""Table windows a sender chunk of the windowed one-hot gathers needs
(`bcast_gather`, `gather_stats`), the mean over the train split's built
batches: the flight manifest's `pad_plans.train.gather_windows`, which the
program counts at set-up from its loader's batches with the arithmetic of
its window plan. 1 is every chunk's ids inside one window; each window
more is one more DMA and one-hot product of that chunk."""

META = {"layer": "kernels (ops/segment_pallas.py, ops/fused_conv.py)", "unit": "x", "better": "lower",
        "source": "program_counter", "moves": "train_graphs_per_s"}


def read(ctx):
    plan = ((ctx["manifest"].get("pad_plans") or {}).get("train")) or {}
    windows = plan.get("gather_windows") or {}
    return windows.get("mean")
