"""Per traced epoch, the time the fullest chip runs nothing while the
program is inside ``epoch.test`` (dispatch, the per-head gather on the
host) or ``epoch.head_quality`` (the per-head error metrics)."""

META = {"layer": "train loop (train/loop.py)", "unit": "ms", "better": "lower", "source": "program_span",
        "moves": "train_graphs_per_s"}


def read(ctx):
    import program_spans

    return program_spans.idle_under_ms(ctx, ("epoch.test", "epoch.head_quality"))
