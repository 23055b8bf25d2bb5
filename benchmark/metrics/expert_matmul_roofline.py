"""For the grouped product of the held experts (``jax.lax.ragged_dot``, whose
kernels a device trace carries as ``ragged-dot...``): the least time the
chip could take for the three products of every layer, forward and
backward, over the traced steps (``costmodels/<model>.expert_kernel``: the
held assignments' rows in and out, the held experts' matrices once a layer;
operations on the held assignments) over the kernels' summed device time
(``roofline.py``). The held assignments are the PROGRAM's own count in the
traced epochs (``moe.held_assignments`` of their ``epoch`` events: all
layers, the epoch's last train step, taken for each of its steps), not the
reference's count at the initial weights: the router moves."""

META = {"layer": "kernels (ops/segment_pallas.py, ops/fused_conv.py)", "unit": "%", "better": "higher",
        "source": "device_trace", "moves": "train_graphs_per_s"}

KERNELS = "ragged-dot"


def read(ctx):
    import roofline

    traced = getattr(ctx["taps"], "traced", None)
    if not traced:
        return None
    held = [ctx["epochs"][i].get("moe.held_assignments") for i in range(*traced) if i in ctx["epochs"]]
    held = [h for h in held if h is not None]
    if not held:
        return None
    steps = ctx["real"].get("steps_per_epoch", 1)
    real = dict(ctx["real"], held_assignments_per_epoch=steps * sum(held) / len(held))
    return roofline.kernel_share(ctx, KERNELS, "expert_kernel", real=real)
