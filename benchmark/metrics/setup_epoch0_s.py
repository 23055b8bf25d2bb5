"""Seconds of the program's ``epoch`` span of epoch 0: tracing, lowering
and compiling (or reading from the cache) the train, diagnostics and eval
programs, and running them once."""

META = {"layer": "train loop (train/loop.py)", "unit": "s", "better": "lower", "source": "program_span",
        "moves": "setup_s"}


def read(ctx):
    import program_spans

    first = program_spans.epoch_phases(ctx["flight"]).get(0) or {}
    return float(first["epoch"]["s"]) if "epoch" in first else None
