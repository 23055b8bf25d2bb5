"""The hottest of ALL the router's experts (held here or not) over a
balanced router's share an expert (real rows x experts a token / experts),
the worst expert layer of an epoch's last train step, averaged over the
window's epochs: the program's counter ``moe.routed_load_max_over_mean`` in
the flight record's ``epoch`` event, which exists where the router has a
balancing bias. 1 is a balanced router; this is what the bias holds down,
and the held experts' rounds follow it."""

META = {"layer": "model (models/, graph/segment.py)", "unit": "x", "better": "lower", "source": "program_counter",
        "moves": "train_graphs_per_s"}


def read(ctx):
    w = ctx["window"]
    seen = [ctx["epochs"][i].get("moe.routed_load_max_over_mean") for i in range(w["first"], w["last"]) if i in ctx["epochs"]]
    seen = [v for v in seen if v is not None]
    if not seen:
        return None
    return sum(seen) / len(seen)
