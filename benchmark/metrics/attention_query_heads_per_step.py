"""Query heads one grid step of the block-attention kernels
(`block_attention_fwd`, `_dq`, `_dkv`) works: the flight manifest's
`model.token_stack.attention_grid.query_heads_per_step`, which the program
computes at set-up from the stack's own heads and widths with the rule its
kernels use. One mask tile and one grid step serve that many heads; a
program that does not report its grid gives no reading."""

META = {"layer": "kernels (ops/segment_pallas.py, ops/fused_conv.py)", "unit": "x", "better": "higher",
        "source": "program_counter", "moves": "train_graphs_per_s"}


def read(ctx):
    stack = ((ctx["manifest"].get("model") or {}).get("token_stack")) or {}
    return (stack.get("attention_grid") or {}).get("query_heads_per_step")
