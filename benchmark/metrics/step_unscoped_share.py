"""The traced train steps' device time that no ``op_name`` names (or no
program's HLO holds), over all of it, split by ``device_scopes.py``."""

META = {"layer": "model (models/, graph/segment.py)", "unit": "%", "better": "lower", "source": "device_trace",
        "moves": "train_graphs_per_s"}


def read(ctx):
    import device_scopes

    got = device_scopes.of(ctx)
    if got is None:
        return None
    return 100.0 * got["regions"]["unscoped"]["s"] / got["total_s"]
