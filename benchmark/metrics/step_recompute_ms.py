"""Device time of the forward computed again inside the backward (``rematted_computation``: ``nn.remat``)
per traced train step: the events ``step_device_ms`` reads, split by
``device_scopes.py``."""

META = {"layer": "model (models/, graph/segment.py)", "unit": "ms", "better": "lower", "source": "device_trace",
        "moves": "train_graphs_per_s"}


def read(ctx):
    import device_scopes

    return device_scopes.per_step_ms(ctx, "recompute")
