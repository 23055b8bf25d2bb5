"""A kernel family's share of its roofline from a traced run, for the
per-layer metrics named ``<kernel>_roofline``: the least time the chip
could take (the larger of the floor's bytes over the table's HBM rate and
its operations over the bf16 peak, from ``costmodels/<model>.<floor_name>``
on the family's real counts) over the summed device time of the Pallas
kernels whose name in the trace starts with ``prefix``
(``trace_reduce.py``'s ``pallas:<name>`` categories: every custom call,
XLA's own grouped product among them). ``real``: the counts to price the
floor on, where a reader has better ones than the family's. None where the
trace holds no such kernel or the cost model no such floor."""

import cost
import peaks


def kernel_share(ctx, prefix: str, floor_name: str, real=None):
    tr = ctx["trace"]
    if not tr or not ctx["traced_epochs"]:
        return None
    seconds = sum(v for k, v in (tr.get("by_category_s") or {}).items() if k.startswith("pallas:" + prefix))
    floor_fn = getattr(cost.model(ctx["cell"].cost_model), floor_name, None)
    if not seconds or floor_fn is None:
        return None
    nn = ctx["cell"].run_config["NeuralNetwork"]
    floor = floor_fn(nn["Architecture"], nn["Variables_of_interest"], real or ctx["real"])
    pk = peaks.lookup(ctx["device"]["kind"])
    least = max(floor["bytes"] / pk["hbm_bytes_s"], floor["flops"] / pk["bf16_flops"]) / ctx["cell"].chips
    return 100.0 * least * ctx["traced_epochs"] / seconds
