"""The time the fullest chip runs nothing while the program is inside
``epoch.checkpoint`` (``save_model`` and ``save_train_meta``), per
checkpoint written inside the traced window."""

META = {"layer": "train loop (train/loop.py)", "unit": "ms", "better": "lower", "source": "program_span",
        "moves": "train_graphs_per_s"}


def read(ctx):
    import program_spans

    return program_spans.idle_under_ms(ctx, ("epoch.checkpoint",), per="instance")
