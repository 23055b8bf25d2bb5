"""Per traced epoch, the time the fullest chip runs nothing while the
program is inside its ``epoch.train`` span (``train/loop.py``: the batch
stack, the diagnostics sample, the dispatch, the read-back that waits);
``program_spans.py`` cuts the idle intervals at the spans' boundaries."""

META = {"layer": "train loop (train/loop.py)", "unit": "ms", "better": "lower", "source": "program_span",
        "moves": "train_graphs_per_s"}


def read(ctx):
    import program_spans

    return program_spans.idle_under_ms(ctx, ("epoch.train",))
