"""The block-diffusion mixture-of-experts decoder (``reference/sdar_moe.py``):
operations and bytes from shapes (see ``cost.py`` for the rules).

``real`` is the family's (``families/token_documents.py``): real rows (both
copies of every document), tokens (one copy), the query-key pairs the mask
allows and the assignments to the experts held, all an epoch. Everything is
priced in its cheapest form: the projections and the router on every row,
attention on the allowed pairs only, the experts on the held assignments
only, and the head on the tokens only (the clean copy carries no loss).
"""

from typing import Dict

ACT_BYTES = 2  # bfloat16, the cell's stated precision


def _shape(arch):
    return (int(arch["hidden_dim"]), int(arch["num_attention_heads"]), int(arch["num_key_value_heads"]),
            int(arch["head_dim"]), int(arch["num_conv_layers"]))


def forward(arch, voi, real: Dict[str, float]) -> Dict[str, float]:
    hidden, heads, kv, d, layers = _shape(arch)
    rows, tokens = real["rows_per_epoch"], real["tokens_per_epoch"]
    pairs, held = real["allowed_pairs_per_epoch"], real["held_assignments_per_epoch"]
    proj = hidden * heads * d * 2 + hidden * kv * d * 2  # q and o, k and v
    return {
        "projections": layers * rows * proj * 2,
        "router": layers * rows * hidden * int(arch["num_experts"]) * 2,
        "attention": layers * pairs * heads * d * 4,  # q k^T and p v
        "experts": held * 3 * hidden * int(arch["moe_intermediate_size"]) * 2,  # held: all layers
        "head": tokens * hidden * int(arch["vocab_size"]) * 2,
        "norms_rotary_softmax": layers * rows * (hidden * 8 + (heads + kv) * d * 10) + layers * pairs * heads * 6,
    }


def attention_kernel(arch, voi, real: Dict[str, float]) -> Dict[str, float]:
    """Forward and backward of the block-attention kernels over a step's
    layers: q, k, v in and o out once (the backward: those and do in, dq, dk,
    dv out: twice the forward), products on the allowed pairs."""
    hidden, heads, kv, d, layers = _shape(arch)
    rows, pairs = real["rows_per_epoch"], real["allowed_pairs_per_epoch"]
    fwd_bytes = layers * rows * (2 * heads + 2 * kv) * d * ACT_BYTES
    fwd_flops = layers * pairs * heads * d * 4
    return {"bytes": 3 * fwd_bytes, "flops": 3 * fwd_flops}


def expert_kernel(arch, voi, real: Dict[str, float]) -> Dict[str, float]:
    """The three grouped products of a layer, forward and backward: the
    held assignments' rows in and out, the gate and up results once, the
    held experts' matrices read once a layer."""
    hidden, _, _, _, layers = _shape(arch)
    width, held_experts = int(arch["moe_intermediate_size"]), int(arch["experts_held"])
    held = real["held_assignments_per_epoch"]
    steps = real.get("steps_per_epoch", 1)
    weights = steps * layers * held_experts * 3 * hidden * width * ACT_BYTES
    fwd_bytes = held * (2 * hidden + 3 * width) * ACT_BYTES + weights
    fwd_flops = held * 3 * hidden * width * 2
    return {"bytes": 3 * fwd_bytes, "flops": 3 * fwd_flops}


def kernel(arch, voi, real: Dict[str, float]) -> Dict[str, float]:
    """All Pallas kernels of the step, for ``pallas_roofline_share``, which
    sums every custom call's time and takes the larger of the summed bytes'
    and the summed operations' bound: never more than the two families'
    own bounds one after the other, so the share errs low, not high."""
    a, e = attention_kernel(arch, voi, real), expert_kernel(arch, voi, real)
    return {"bytes": a["bytes"] + e["bytes"], "flops": a["flops"] + e["flops"]}
