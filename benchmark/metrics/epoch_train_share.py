"""Share of the window's untraced epochs spent under the program's
``epoch.train`` span, over their ``epoch`` spans (the flight record's
``phases``); the rest is validation, test, diagnostics, flight record and
checkpoint."""

META = {"layer": "train loop (train/loop.py)", "unit": "%", "better": "higher", "source": "program_span",
        "moves": "train_graphs_per_s"}


def read(ctx):
    import program_spans

    phases = program_spans.epoch_phases(ctx["flight"])
    train = total = 0.0
    for i in ctx["quiet_epochs"]:
        row = phases.get(i) or {}
        if "epoch.train" not in row or "epoch" not in row:
            return None
        train += float(row["epoch.train"]["s"])
        total += float(row["epoch"]["s"])
    return 100.0 * train / total if total > 0 else None
