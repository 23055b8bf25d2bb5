"""Share of the traced window's idle time (fullest chip) that lies under
no span below ``epoch``: what the program's spans cannot name."""

META = {"layer": "train loop (train/loop.py)", "unit": "%", "better": "lower", "source": "program_span",
        "moves": "train_graphs_per_s"}


def read(ctx):
    import program_spans

    t = program_spans.of(ctx)
    if not t or not t["idle_s"]:
        return None
    return 100.0 * t["unattributed_idle_s"] / t["idle_s"]
