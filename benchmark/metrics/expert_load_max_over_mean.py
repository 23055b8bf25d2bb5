"""The hottest held expert's assignments over a balanced router's share an
expert (rows x experts a token / experts), the worst layer of an epoch's
last train step, averaged over the window's epochs: the program's own
counter ``moe.load_max_over_mean`` in the flight record's ``epoch`` event.
1 is a balanced router; the grouped product's tiles fill up as it grows."""

META = {"layer": "model (models/, graph/segment.py)", "unit": "x", "better": "lower", "source": "program_counter",
        "moves": "train_graphs_per_s"}


def read(ctx):
    w = ctx["window"]
    seen = [ctx["epochs"][i].get("moe.load_max_over_mean") for i in range(w["first"], w["last"]) if i in ctx["epochs"]]
    seen = [v for v in seen if v is not None]
    if not seen:
        return None
    return sum(seen) / len(seen)
