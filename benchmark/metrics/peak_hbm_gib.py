"""``memory_stats()["peak_bytes_in_use"]`` after the window and before the
reference runs, the fullest chip."""

META = {"layer": "device (TPU v5e)", "unit": "GiB", "better": "lower", "source": "program_counter",
        "moves": "train_graphs_per_s"}


def read(ctx):
    peak = ctx["device"].get("memory_peak_bytes")
    if not peak or ctx["rehearse"]:
        return None
    return peak / 2**30
