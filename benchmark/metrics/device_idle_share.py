"""1 minus the union of device-operation intervals over the traced
window, on the fullest-loaded chip."""

META = {"layer": "device (TPU v5e)", "unit": "%", "better": "lower", "source": "device_trace",
        "moves": "train_graphs_per_s"}


def read(ctx):
    tr = ctx["trace"]
    if not tr or not tr.get("window_s"):
        return None
    return 100.0 * (1.0 - tr["busy_max_s"] / tr["window_s"])
