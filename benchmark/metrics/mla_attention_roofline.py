"""For the block-attention kernels (``block_attention_fwd``, ``_dq``,
``_dkv``, by the names the trace carries) in a latent-attention cell, where
queries and keys are ``qk_nope_head_dim + qk_rope_head_dim`` wide and values
``v_head_dim``: the least time the chip could take for forward and backward
over the traced steps (``costmodels/<model>.attention_kernel``: q, k, v, o
once and the products on the query-key pairs the document-causal mask
allows, at the two widths) over the kernels' summed device time
(``roofline.py``). Recomputation (the layers' remat runs the forward twice,
the backward recomputes the probabilities) counts for nothing in the floor
and for all of it in the time."""

META = {"layer": "kernels (ops/segment_pallas.py, ops/fused_conv.py)", "unit": "%", "better": "higher",
        "source": "device_trace", "moves": "train_graphs_per_s"}


def read(ctx):
    import roofline

    return roofline.kernel_share(ctx, "block_attention", "attention_kernel")
