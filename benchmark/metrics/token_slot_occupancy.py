"""Real rows over the row slots of an epoch's train batches: what share of
the padded row axis, which every projection, the router and the head walk,
carries a token of either copy. Slots from the flight manifest's pad plan,
rows counted by the benchmark from its own documents."""

META = {"layer": "data (data/ingest.py, data/loader.py, graph/batch.py)", "unit": "%", "better": "higher",
        "source": "program_counter", "moves": "train_graphs_per_s"}


def read(ctx):
    plan = ((ctx["manifest"].get("pad_plans") or {}).get("train")) or {}
    stack = int((ctx["manifest"].get("mesh") or {}).get("device_stack") or 1)
    slots = (plan.get("pad_nodes") or 0) * (plan.get("num_batches") or 0) * stack
    real = ctx["real"].get("rows_per_epoch")
    if not slots or not real:
        return None
    return 100.0 * real / slots
