"""Device busy time inside the traced train dispatches over the number of
optimizer steps in them (the fullest-loaded chip on a mesh)."""

META = {"layer": "model (models/, graph/segment.py)", "unit": "ms", "better": "lower", "source": "device_trace",
        "moves": "train_graphs_per_s"}


def read(ctx):
    tr = ctx["trace"]
    if not tr or not tr.get("train_busy_s") or not ctx["traced_steps"]:
        return None
    return 1e3 * tr["train_busy_s"] / ctx["traced_steps"]
