"""Per optimizer step, the time of collective operations during which no
other operation runs on that chip (mean over the chips)."""

META = {"layer": "parallel (parallel/partitioner.py, parallel/sharded.py)", "unit": "ms", "better": "lower",
        "source": "device_trace", "moves": "train_graphs_per_s"}


def read(ctx):
    tr = ctx["trace"]
    if not tr or tr.get("collective_exposed_s") is None or not ctx["traced_steps"]:
        return None
    if not tr.get("collective_s"):
        return None  # no collective ran: nothing to read
    return 1e3 * tr["collective_exposed_s"] / ctx["traced_steps"]
