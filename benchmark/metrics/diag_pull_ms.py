"""Device time of the program's ``diagnostics`` scope (the per-head pulls
and their arithmetic; not the pull that makes the update) per diagnosed
step of the traced epochs, split by ``device_scopes.py``."""

META = {"layer": "model (models/, graph/segment.py)", "unit": "ms", "better": "lower", "source": "device_trace",
        "moves": "train_graphs_per_s"}


def read(ctx):
    import device_scopes

    got, n = device_scopes.of(ctx), device_scopes.diagnosed_steps(ctx)
    if got is None or not n:
        return None
    return 1e3 * got["regions"]["diagnostics"]["s"] / n
