"""Process start to the window's start: imports, data from the seed, the
program's preparation and loaders, compiles (or cache reads), warm-up
epochs."""

META = {"kind": "end_to_end", "unit": "s", "better": "lower", "source": "host_clock"}


def read(ctx):
    return ctx["setup"]["seconds"]
