"""Real (unpadded) graphs through an optimizer step in the window, over
the window's whole wall time (the benchmark's own clock, epoch boundary
to epoch boundary); on a mesh the total of all chips."""

META = {"kind": "end_to_end", "unit": "graphs/s", "better": "higher", "source": "host_clock"}


def read(ctx):
    return ctx["window"]["graphs"] / ctx["window"]["seconds"]
