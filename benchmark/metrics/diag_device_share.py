"""Device time of the operations inside ``jit_diagnostics_step`` programs
(the per-head gradient diagnostics, a whole gradient program of its own)
over the device's busy time in the traced window."""

META = {"layer": "model (models/, graph/segment.py)", "unit": "%", "better": "lower", "source": "device_trace",
        "moves": "train_graphs_per_s"}


def read(ctx):
    import program_spans

    t = program_spans.of(ctx)
    if not t or not t["busy_s"] or program_spans.DIAGNOSTICS not in t["programs"]:
        return None
    return 100.0 * t["programs"][program_spans.DIAGNOSTICS]["device_s"] / t["busy_s"]
