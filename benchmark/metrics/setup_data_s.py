"""Host seconds to make the samples from the seed plus the seconds inside
``prepare_loaders_and_config`` (normalization, radius graphs, split,
loaders and their pad plans)."""

META = {"layer": "data (data/ingest.py, data/loader.py, graph/batch.py)", "unit": "s", "better": "lower",
        "source": "host_clock", "moves": "setup_s"}


def read(ctx):
    return ctx["setup"]["data_s"]
