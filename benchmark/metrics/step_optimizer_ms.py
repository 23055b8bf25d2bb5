"""Device time of the program's ``optimizer`` scope (``tx.update``, the update's landing and its non-finite guard)
per traced train step: the events ``step_device_ms`` reads, split by
``device_scopes.py``."""

META = {"layer": "train loop (train/loop.py)", "unit": "ms", "better": "lower", "source": "device_trace",
        "moves": "train_graphs_per_s"}


def read(ctx):
    import device_scopes

    return device_scopes.per_step_ms(ctx, "optimizer")
