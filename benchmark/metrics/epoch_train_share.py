"""Share of the window's untraced epochs spent in the train dispatch
(the flight record's ``hw.train_wall_s``); the rest is validation, test,
diagnostics, flight record and checkpoint."""

META = {"layer": "train loop (train/loop.py)", "unit": "%", "better": "higher", "source": "program_span",
        "moves": "train_graphs_per_s"}


def read(ctx):
    train = total = 0.0
    for i in ctx["quiet_epochs"]:
        hw = (ctx["epochs"].get(i) or {}).get("hw") or {}
        wall = hw.get("train_wall_s")
        if wall is None:
            return None
        train += float(wall)
        total += ctx["epoch_seconds"][i]
    return 100.0 * train / total if total > 0 else None
