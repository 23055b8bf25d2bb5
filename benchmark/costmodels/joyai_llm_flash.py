"""The latent-attention mixture-of-experts decoder with a prediction depth
(``reference/joyai_llm_flash.py``): operations and bytes from shapes (see
``cost.py`` for the rules).

``real`` is the family's (``families/causal_documents.py``): real rows (one
copy of every document), the rows with a target at each head, the
query-key pairs the document-causal mask allows and the assignments to the
experts held (all expert layers), all an epoch. Everything is priced in its
cheapest form: the latent projections, the router and the shared expert on
every row, attention on the allowed pairs only at the query-key width for
the scores and the value width for the sum, the routed experts on the held
assignments only, the dense layer and ``W_eh`` on every row, and each head
on the rows that have its target. Attention layers: the main stack's and
the prediction depth's; expert layers: the main stack's but the dense ones,
and the depth's.
"""

from typing import Dict

ACT_BYTES = 2  # bfloat16, the cell's stated precision


def _shape(arch):
    hidden, heads = int(arch["hidden_dim"]), int(arch["num_attention_heads"])
    d_qk = int(arch["qk_nope_head_dim"]) + int(arch["qk_rope_head_dim"])
    depth = int(arch.get("num_nextn_predict_layers", 0))
    attention_layers = int(arch["num_conv_layers"]) + depth
    expert_layers = attention_layers - int(arch.get("first_k_dense_replace", 0))
    return hidden, heads, d_qk, int(arch["v_head_dim"]), attention_layers, expert_layers, depth


def forward(arch, voi, real: Dict[str, float]) -> Dict[str, float]:
    hidden, heads, d_qk, d_v, att_layers, moe_layers, depth = _shape(arch)
    rows, pairs, held = real["rows_per_epoch"], real["allowed_pairs_per_epoch"], real["held_assignments_per_epoch"]
    q_rank, kv_rank, rope = int(arch["q_lora_rank"]), int(arch["kv_lora_rank"]), int(arch["qk_rope_head_dim"])
    nope = d_qk - rope
    # W_dq, W_uq, W_dkv, W_ukv, W_o
    proj = hidden * q_rank + q_rank * heads * d_qk + hidden * (kv_rank + rope) + kv_rank * heads * (nope + d_v) + heads * d_v * hidden
    expert = 3 * hidden * int(arch["moe_intermediate_size"])
    dense = int(arch.get("first_k_dense_replace", 0)) * 3 * hidden * int(arch.get("intermediate_size") or 0)
    head_rows = real["main_rows_per_epoch"] + (real["mtp_rows_per_epoch"] if depth else 0)
    return {
        "projections": att_layers * rows * proj * 2,
        "attention": att_layers * pairs * heads * (d_qk + d_v) * 2,  # q k^T and p v
        "router": moe_layers * rows * hidden * int(arch["num_experts"]) * 2,
        "shared_experts": moe_layers * rows * int(arch.get("n_shared_experts", 0)) * expert * 2,
        "routed_experts": held * expert * 2,  # held: all expert layers
        "dense": rows * dense * 2,
        "eh_proj": depth * rows * 2 * hidden * hidden * 2,
        "heads": head_rows * hidden * int(arch["vocab_size"]) * 2,
        "norms_rotary_softmax": att_layers * rows * (hidden * 8 + heads * (2 * d_qk + d_v) * 4) + att_layers * pairs * heads * 6,
    }


def attention_kernel(arch, voi, real: Dict[str, float]) -> Dict[str, float]:
    """Forward and backward of the block-attention kernels over a step's
    attention layers: q and k at the query-key width, v and o at the value
    width in and out once (the backward: those and do in, dq, dk, dv out:
    twice the forward), products on the allowed pairs."""
    hidden, heads, d_qk, d_v, att_layers, _, _ = _shape(arch)
    rows, pairs = real["rows_per_epoch"], real["allowed_pairs_per_epoch"]
    fwd_bytes = att_layers * rows * heads * (2 * d_qk + 2 * d_v) * ACT_BYTES
    fwd_flops = att_layers * pairs * heads * (d_qk + d_v) * 2
    return {"bytes": 3 * fwd_bytes, "flops": 3 * fwd_flops}


def expert_kernel(arch, voi, real: Dict[str, float]) -> Dict[str, float]:
    """The three grouped products of the routed experts, forward and
    backward: the held assignments' rows in and out, the gate and up
    results once, the held experts' matrices read once an expert layer."""
    hidden, _, _, _, _, moe_layers, _ = _shape(arch)
    width, held_experts = int(arch["moe_intermediate_size"]), int(arch["experts_held"])
    held = real["held_assignments_per_epoch"]
    steps = real.get("steps_per_epoch", 1)
    weights = steps * moe_layers * held_experts * 3 * hidden * width * ACT_BYTES
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
