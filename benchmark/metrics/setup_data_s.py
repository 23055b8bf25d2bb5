"""Seconds to make the samples from the seed (the benchmark's clock around
the family's ``generate`` and ``program_samples``) plus the program's own
``setup.data`` span from the flight record's ``setup`` event
(normalization, radius graphs, split, loaders and their pad plans)."""

META = {"layer": "data (data/ingest.py, data/loader.py, graph/batch.py)", "unit": "s", "better": "lower",
        "source": "program_span", "moves": "setup_s"}


def read(ctx):
    import program_spans

    phases = program_spans.setup_phases(ctx["flight"]) or {}
    if "setup.data" not in phases:
        return None
    return ctx["setup"]["generate_s"] + float(phases["setup.data"]["s"])
