"""Message-passing conv layers as Flax modules over masked segment ops.

Each layer reimplements the semantics of the torch_geometric conv the
reference plugs into its ``Base.get_conv`` slot (reference:
hydragnn/models/*Stack.py), redesigned for TPU: dense matmuls feed the MXU,
edge aggregation is an XLA segment reduction, and every op is mask-correct
under static padding. Message direction matches PyG: sender j -> receiver i,
aggregation groups by receiver.

Call convention: ``conv(x, ctx)`` where ``ctx`` is an EdgeContext holding
senders/receivers/masks and optional edge features, so one chassis drives
every flavor (mirrors Base._conv_args, reference hydragnn/models/Base.py:111-115).
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import flax.linen as nn

from hydragnn_tpu.graph import segment as S


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class EdgeContext:
    """Edge structure handed to every conv layer by the chassis."""

    senders: jnp.ndarray  # [E] int32
    # CONTRACT: receivers must be sorted ascending (batch_graphs
    # canonicalizes receiver-major edge order; radius_graph_in_forward
    # emits it) — every conv passes indices_are_sorted=True to its
    # segment reductions, and a violated hint silently corrupts sums
    # on TPU rather than erroring.
    receivers: jnp.ndarray  # [E] int32, sorted ascending
    edge_mask: jnp.ndarray  # [E] bool
    node_mask: jnp.ndarray  # [N] bool
    edge_attr: Optional[jnp.ndarray] = None  # [E, De]
    edge_weight: Optional[jnp.ndarray] = None  # [E] distances (SchNet)
    # argsort(senders), computed ONCE per step by the chassis: lets every
    # layer's sender-gather backward run as a SORTED segment sum (the
    # Pallas CSR kernel on TPU) instead of XLA's unsorted scatter-add
    sender_perm: Optional[jnp.ndarray] = None  # [E] int32
    # per-node count of REAL incoming edges, computed once per step by
    # the chassis WITHOUT a scatter (receivers are sorted, so it is a
    # searchsorted difference; padding edges point at a padding node and
    # never inflate a real node's count). Layers that need degree (PNA
    # scalers/has, MFC dispatch) read this instead of paying the [E,1]
    # count scatter XLA otherwise emits (~6 ms at E=699k, r03 trace).
    in_degree: Optional[jnp.ndarray] = None  # [N] float32
    # dense per-node edge-slot map (loader-emitted — graph/batch.py):
    # lets PNA run its aggregations as DENSE [N, D, H] reductions (one
    # fused XLA pass fwd, broadcasts bwd) instead of scatter/segment
    # ops. dense_edge_attr is FLAT [N*D, De]; dense_sender_perm is
    # argsort of the flattened dense senders, computed once per step by
    # the chassis for the sender-gather backward (like sender_perm).
    dense_senders: Optional[jnp.ndarray] = None  # [N, D] int32
    dense_mask: Optional[jnp.ndarray] = None  # [N, D] bool
    dense_edge_attr: Optional[jnp.ndarray] = None  # [N*D, De]
    dense_sender_perm: Optional[jnp.ndarray] = None  # [N*D] int32
    # loader-emitted per-node-block position windows (graph/batch.py:
    # _block_windows; block size derived from the window shape — see
    # GraphBatch.sender_win): when present, sender gathers ride the
    # windowed kernels in BOTH directions — no cotangent permute in
    # the backward
    sender_win: Optional[jnp.ndarray] = None  # [2, n_blocks] int32
    dense_sender_win: Optional[jnp.ndarray] = None  # [2, n_blocks] int32
    # loader-emitted edge occupancy (GraphBatch.edge_occupancy): index
    # after the last slot that can hold a REAL edge. Handed to the fused
    # kernel as its chunk-loop bound so fully-padded tail chunks (bucket
    # ladders, _mask_out filler) cost zero DMAs/MXU work. None = process
    # the full pad (externally-built batches; always correct).
    edge_occ: Optional[jnp.ndarray] = None  # [] int32
    # static: run-aligned edge layout factor (GraphBatch.run_align).
    # K > 0 guarantees every K-group of edge slots shares one receiver
    # (or is batch tail), so segment reductions pre-reduce K-fold with
    # one fused pass (_run_groups) before the serial scatter/segment op.
    run_align: int = 0
    # static: Architecture.fused_conv — route the gather -> edge-network
    # -> scatter chain through the single fused Pallas kernel
    # (ops/fused_conv.py) where the knob/backend allow; layers fall back
    # to the composed segment-op paths otherwise.
    fused_conv: bool = False
    # static: Architecture.conv_bf16 — flow the conv hot path's
    # activation streams (x, gathered sender windows, receiver tables,
    # per-edge scale) in bfloat16 with f32 MXU accumulation, halving the
    # dominant HBM byte streams (ISSUE 10). Applies to BOTH the fused
    # kernel and the composed fallback so the two stay within the
    # documented tolerance of each other; the inter-layer stream is
    # restored to the caller dtype on return (BN + relu run f32).
    conv_bf16: bool = False


def _local_kernels(n_rows: int) -> bool:
    """Trace-time gate for the local-window gather/scatter pair: the
    kernels carry a fixed per-call cost (window plan + grid setup) that
    only pays off when the serial alternative is large — measured on
    v5e: 811k-row flagship wins big, 61k-row qm9 dense LOSES 8.2 vs
    3.4 ms scan-step (tools/ab_qm9.py). Below the threshold the
    permuted-sorted path is faster."""
    from hydragnn_tpu.ops.segment_pallas import (
        local_kernel_active,
        local_min_rows,
    )

    return n_rows >= local_min_rows() and local_kernel_active()


def gather_presum_layers(cfg, rows: int, edges: int, run_align: int, dtype=None) -> dict:
    """How many of a PNA model's conv layers run the fused gather-
    statistics kernels on a batch of ``rows`` node and ``edges`` edge
    slots laid out with ``run_align``, asking what :class:`PNAConv` asks
    at trace time: the forward (``gather_stats``) by its gate, the
    backward (``gather_stats_bwd``) where the kernel's accumulator also
    fits (``ops/segment_pallas.py:presum_bwd_fits``). Set-up arithmetic
    for the flight manifest; the loader always emits the sender windows."""
    from hydragnn_tpu.ops.segment_pallas import (
        gather_presum_layout_ok,
        local_kernel_active,
        presum_bwd_fits,
    )

    widths = [cfg.input_dim] + [cfg.hidden_dim] * (cfg.num_conv_layers - 1)
    if cfg.node_head_type == "conv":
        # a conv head's layers take the encoder's width, then their own
        hidden = list(cfg.node_dim_headlayers[: cfg.node_num_headlayers])
        widths += ([cfg.hidden_dim] + hidden) * list(cfg.output_type).count("node")
    fwd = []
    if run_align and not cfg.use_edge_attr and local_kernel_active():
        fwd = [w for w in widths if gather_presum_layout_ok(edges, w, run_align)]
    dt = jnp.float32 if dtype is None else dtype
    bwd = [w for w in fwd if presum_bwd_fits(rows, w, run_align, dt)]
    return {"conv_layers": len(widths), "forward_fused_layers": len(fwd),
            "backward_fused_layers": len(bwd)}


def _fused_active(ctx: EdgeContext) -> bool:
    """Trace-time gate for the fused conv kernel (ops/fused_conv.py):
    the config knob (EdgeContext.fused_conv <- Architecture.fused_conv)
    AND the shared HYDRAGNN_PALLAS knob/backend contract. Receivers are
    sorted by the EdgeContext contract, so no shape check is needed —
    narrow widths lane-pad inside the op."""
    if not ctx.fused_conv:
        return False
    from hydragnn_tpu.ops.fused_conv import fused_conv_active

    return fused_conv_active()


def _gather_scatter(
    x: jnp.ndarray,
    ctx: EdgeContext,
    n: int,
    scale: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """``sum_e mask_e * (x[send_e] * scale_e?)`` grouped by receiver —
    ONE fused Pallas kernel (gather + optional per-edge scale + scatter
    all in VMEM, no [E, H] HBM intermediate) when active, else the
    composed gather + masked segment sum the layers always used.
    Returns x.dtype. ``ctx.conv_bf16`` rounds the streamed operands to
    bf16 in BOTH paths (accumulation stays f32 — the segment-sum family
    contract); the result is cast back to the incoming dtype."""
    xd = x.dtype
    if ctx.conv_bf16:
        x = x.astype(jnp.bfloat16)
        if scale is not None:
            scale = scale.astype(jnp.bfloat16)
    if _fused_active(ctx):
        from hydragnn_tpu.ops.fused_conv import fused_conv

        return fused_conv(
            x, ctx.senders, ctx.receivers, ctx.edge_mask, n,
            scale=scale, win=ctx.sender_win, real_edges=ctx.edge_occ,
        ).astype(xd)
    vals = _gather_senders(x, ctx)
    if scale is not None:
        vals = vals * scale
    return _segment_sum_edges(vals, ctx, n).astype(xd)


def _run_presum(vals: jnp.ndarray, ctx: EdgeContext) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Pre-reduce masked edge values over the K-aligned run groups:
    one fused [E/K, K, H] reshape-sum (accumulated f32 — the family
    contract) replaces K-1 of every K rows the downstream segment sum
    would otherwise scatter serially (XLA's TPU scatter loops per ROW
    at ~6-9 ms per 699k-row pass regardless of width; docs/PERF.md).
    Returns (summed [E/K, H] f32, receivers[::K]) — valid because the
    run-aligned layout guarantees each K-group lies within one node's
    receiver-run or the batch tail, and masked slots contribute 0."""
    K = ctx.run_align
    vf = jnp.where(ctx.edge_mask[:, None], vals, 0).astype(jnp.float32)
    v8 = vf.reshape(-1, K, vals.shape[-1]).sum(axis=1)
    return v8, ctx.receivers[::K]


def _segment_sum_edges(vals: jnp.ndarray, ctx: EdgeContext, n: int) -> jnp.ndarray:
    """Masked sum of per-edge values into receiver rows — pre-reduced
    K-fold on run-aligned batches, the plain masked sorted segment sum
    otherwise. Returns the values' dtype."""
    if ctx.run_align:
        v8, recv8 = _run_presum(vals, ctx)
        return S.segment_sum_sorted(
            v8, recv8, n, grad_dtype=vals.dtype
        ).astype(vals.dtype)
    return S.segment_sum(
        vals, ctx.receivers, n, mask=ctx.edge_mask, indices_are_sorted=True
    )


def _edge_count(ctx: EdgeContext, n: int) -> jnp.ndarray:
    """Real in-degree: the loader-precomputed field, else a masked count."""
    if ctx.in_degree is not None:
        return ctx.in_degree
    return S.segment_count(ctx.receivers, n, mask=ctx.edge_mask, indices_are_sorted=True)


def sorted_in_degree(receivers: jnp.ndarray, num_nodes: int) -> jnp.ndarray:
    """Per-node incoming-edge count from SORTED receivers — two
    searchsorted passes instead of XLA's per-row [E,1] count scatter.
    Valid when masked edges cannot point at real nodes (the loader
    contract: padding edges target a padding node)."""
    bounds = jnp.searchsorted(
        receivers, jnp.arange(num_nodes + 1, dtype=receivers.dtype), side="left"
    )
    return (bounds[1:] - bounds[:-1]).astype(jnp.float32)


def _gather_senders(x: jnp.ndarray, ctx: EdgeContext) -> jnp.ndarray:
    """x[ctx.senders] with the fastest available backward: the
    local-window kernel pair when the loader emitted block windows AND
    the kernels lower here (no cotangent permute at all), else the
    permuted sorted segment sum via the chassis ``sender_perm``."""
    if ctx.sender_win is not None and _local_kernels(ctx.senders.shape[0]):
        return S.gather_rows_local(x, ctx.senders, ctx.sender_win, x.shape[0])
    if ctx.sender_perm is not None:
        return S.gather_rows_permuted(x, ctx.senders, ctx.sender_perm, x.shape[0])
    return x[ctx.senders]


class GINConv(nn.Module):
    """GIN with a 2-layer MLP, trainable eps initialized to 100.0
    (reference: hydragnn/models/GINStack.py:25-36)."""

    out_dim: int

    @nn.compact
    def __call__(self, x: jnp.ndarray, ctx: EdgeContext) -> jnp.ndarray:
        eps = self.param("eps", lambda _: jnp.asarray(100.0, jnp.float32))
        agg = _gather_scatter(x, ctx, x.shape[0])
        h = (1.0 + eps) * x + agg
        h = nn.Dense(self.out_dim)(h)
        h = nn.relu(h)
        h = nn.Dense(self.out_dim)(h)
        return h


class SAGEConv(nn.Module):
    """GraphSAGE, mean aggregation: W_l(mean_j x_j) + W_r x_i
    (reference: hydragnn/models/SAGEStack.py:15-19; PyG SAGEConv defaults)."""

    out_dim: int

    @nn.compact
    def __call__(self, x: jnp.ndarray, ctx: EdgeContext) -> jnp.ndarray:
        n = x.shape[0]
        total = _gather_scatter(x, ctx, n)
        cnt = _edge_count(ctx, n)
        agg = total / jnp.maximum(cnt, 1.0)[:, None].astype(total.dtype)
        return nn.Dense(self.out_dim)(agg) + nn.Dense(self.out_dim, use_bias=False)(x)


class MFConv(nn.Module):
    """Molecular-fingerprint conv: degree-indexed weight matrices
    (reference: hydragnn/models/MFCStack.py:21-28; PyG MFConv).

    out_i = W_l[deg_i](sum_j x_j) + W_r[deg_i](x_i), degree clamped to
    ``max_degree``. The per-degree dispatch is a gather over a stacked
    weight tensor followed by a batched matmul — no data-dependent Python
    loop, so the whole thing stays one fused XLA computation.
    """

    out_dim: int
    max_degree: int

    @nn.compact
    def __call__(self, x: jnp.ndarray, ctx: EdgeContext) -> jnp.ndarray:
        n, fin = x.shape
        ndeg = self.max_degree + 1
        agg = _gather_scatter(x, ctx, n)
        deg = jnp.clip(_edge_count(ctx, n).astype(jnp.int32), 0, self.max_degree)

        # init parity with the reference: PyG MFConv holds one torch
        # Linear per degree — lins_l with kaiming-uniform weights
        # (var 1/(3 fan_in)) + uniform(-1/sqrt(fan_in), .) bias, lins_r
        # with bias=False. batch_axis=0 keeps fan_in = fin for the
        # stacked per-degree weights (otherwise jax counts ndeg*fin).
        # With flax's lecun_normal + zero bias the same training budget
        # lands ~0.28 MAE on the deterministic dataset vs the 0.20 bar.
        init = nn.initializers.variance_scaling(
            1.0 / 3.0, "fan_in", "uniform", batch_axis=0
        )
        bound = 1.0 / float(fin) ** 0.5

        def bias_init(key, shape, dtype=jnp.float32):
            return jax.random.uniform(key, shape, dtype, -bound, bound)

        w_l = self.param("w_l", init, (ndeg, fin, self.out_dim))
        b_l = self.param("b_l", bias_init, (ndeg, self.out_dim))
        w_r = self.param("w_r", init, (ndeg, fin, self.out_dim))

        out = jnp.einsum("ni,nio->no", agg, w_l[deg]) + b_l[deg]
        out = out + jnp.einsum("ni,nio->no", x, w_r[deg])
        return out


class CGConv(nn.Module):
    """Crystal-graph conv, aggr="add", dimension-preserving
    (reference: hydragnn/models/CGCNNStack.py:19-49; PyG CGConv).

    z_ij = [x_i, x_j, e_ij];  out_i = x_i + sum_j sigmoid(W_f z) * softplus(W_s z)

    Fused path (TPU / interpret — the PNA message-elimination idea
    applied to the gate): each Dense over the concat splits exactly into
    a receiver part (a NODE-level matmul, bias folded in), a sender
    part (the only true edge-level matmul), and an edge-attr part —
    ``W z = x_i W[:F] + x_j W[F:2F] + e W[2F:]``. The [E, 2F+De] concat
    never exists, and the whole gather -> two-branch MLP ->
    sigmoid*softplus -> scatter chain runs in ONE Pallas kernel
    (ops/fused_conv.py) with the receiver parts gathered in-VMEM from
    node-blocked tables. The params stay the ORIGINAL ``nn.Dense``
    children (the fused path slices the same kernels), so off-TPU the
    layer computes — and initializes — bit-identically to the
    pre-fusion form."""

    out_dim: int  # must equal input dim; CGConv preserves width

    @nn.compact
    def __call__(self, x: jnp.ndarray, ctx: EdgeContext) -> jnp.ndarray:
        n, fin = x.shape
        h = self.out_dim
        use_edge = ctx.edge_attr is not None
        # conv_bf16 rounds the streamed operands (x, receiver tables,
        # edge features) to bf16 in both branches of this layer; params
        # stay f32 (param_dtype default), so the knob changes only the
        # hot-path byte streams, not initialization or the checkpoint.
        cdt = jnp.bfloat16 if ctx.conv_bf16 else None
        dense_f = nn.Dense(h, dtype=cdt)  # gate (Dense_0)
        dense_s = nn.Dense(h, dtype=cdt)  # core (Dense_1)
        xc = x.astype(jnp.bfloat16) if ctx.conv_bf16 else x
        if not _fused_active(ctx):
            xi = S.gather_rows(xc, ctx.receivers, n, True)
            xj = _gather_senders(xc, ctx)
            z = [xi, xj]
            if use_edge:
                z.append(ctx.edge_attr.astype(xc.dtype))
            z = jnp.concatenate(z, axis=-1)
            gate = jax.nn.sigmoid(dense_f(z))
            core = jax.nn.softplus(dense_s(z))
            agg = _segment_sum_edges(gate * core, ctx, n).astype(x.dtype)
            return x + agg

        # materialize the children's params on a dummy row (same shapes
        # and RNG draws as the concat form), then decompose their
        # kernels for the fused kernel's branch layout
        de = ctx.edge_attr.shape[-1] if use_edge else 0
        zdim = 2 * fin + de
        dummy = jnp.zeros((1, zdim), x.dtype)
        dense_f(dummy)
        dense_s(dummy)
        wf = dense_f.variables["params"]["kernel"].astype(xc.dtype)
        bf = dense_f.variables["params"]["bias"].astype(xc.dtype)
        ws = dense_s.variables["params"]["kernel"].astype(xc.dtype)
        bs = dense_s.variables["params"]["bias"].astype(xc.dtype)

        # receiver-side parts as node-level matmuls (bias folded in)
        af = xc @ wf[:fin] + bf
        ac = xc @ ws[:fin] + bs
        cf = cs = None
        if use_edge:
            ea = ctx.edge_attr.astype(xc.dtype)
            cf = ea @ wf[2 * fin :]
            cs = ea @ ws[2 * fin :]

        from hydragnn_tpu.ops.fused_conv import fused_conv

        agg = fused_conv(
            xc, ctx.senders, ctx.receivers, ctx.edge_mask, n,
            branches=(
                (wf[fin : 2 * fin], None, af, cf),
                (ws[fin : 2 * fin], None, ac, cs),
            ),
            acts=("sigmoid", "softplus"),
            win=ctx.sender_win,
            real_edges=ctx.edge_occ,
        ).astype(x.dtype)
        return x + agg


class GATv2Conv(nn.Module):
    """GATv2 multi-head attention conv
    (reference: hydragnn/models/GATStack.py:91-101; PyG GATv2Conv with
    heads=6, negative_slope=0.05, dropout=0.25, add_self_loops=True).

    Self-loops are appended in-graph for real nodes (static shape: E + N
    edges), matching PyG's add_self_loops on the un-padded graph.
    """

    out_dim: int  # per-head output width
    heads: int = 6
    negative_slope: float = 0.05
    dropout: float = 0.25
    concat: bool = True

    @nn.compact
    def __call__(
        self, x: jnp.ndarray, ctx: EdgeContext, deterministic: bool = True
    ) -> jnp.ndarray:
        n = x.shape[0]
        h, d = self.heads, self.out_dim

        senders = jnp.concatenate([ctx.senders, jnp.arange(n, dtype=ctx.senders.dtype)])
        receivers = jnp.concatenate([ctx.receivers, jnp.arange(n, dtype=ctx.receivers.dtype)])
        emask = jnp.concatenate([ctx.edge_mask, ctx.node_mask])

        x_l = nn.Dense(h * d)(x).reshape(n, h, d)  # source transform
        x_r = nn.Dense(h * d)(x).reshape(n, h, d)  # target transform
        feat = x_l[senders] + x_r[receivers]  # [E', h, d]
        feat = nn.leaky_relu(feat, self.negative_slope)
        att = self.param("att", nn.initializers.lecun_normal(), (1, h, d))
        logits = (feat * att).sum(-1)  # [E', h]
        alpha = S.segment_softmax(logits, receivers, n, mask=emask[:, None])
        alpha = nn.Dropout(self.dropout, deterministic=deterministic)(alpha)
        msg = x_l[senders] * alpha[..., None]  # [E', h, d]
        out = S.segment_sum(msg, receivers, n, mask=emask)
        if self.concat:
            out = out.reshape(n, h * d)
            out = out + self.param("bias", nn.initializers.zeros, (h * d,))
        else:
            out = out.mean(axis=1)
            out = out + self.param("bias", nn.initializers.zeros, (d,))
        return out


class PNAConv(nn.Module):
    """Principal Neighbourhood Aggregation conv
    (reference: hydragnn/models/PNAStack.py:19-54; PyG PNAConv with
    aggregators [mean,min,max,std], scalers [identity,amplification,
    attenuation,linear], towers=1, pre/post_layers=1, divide_input=False).

    TPU-first message elimination: the pre-aggregation network is ONE
    linear layer (pre_layers=1), so the per-edge message decomposes
    exactly as

        msg_e = W @ [x_i, x_j, e_ij] + b
              = (x_i @ W_i + b) + x_j @ W_j + e_ij @ W_e
              =       a[recv_e] +  bsend[send_e] + c_e

    with ``a``/``bsend`` computed as NODE-level matmuls. Every PNA
    aggregator then needs only segment reductions of v_e = bsend[send_e]
    (+ c_e) over receivers: mean(msg) = a + mean(v), max(msg) = a +
    max(v), min likewise, and std(msg) = std(v) because variance is
    shift-invariant. The [E, 3H] concat, the [E, *] pre-Dense matmul,
    and the [E, H] message array — plus all their backward mirrors —
    never exist; the only edge-width intermediate is the single gather
    ``v``. This is the r03 answer to the measured HBM-bound profile
    (161 GB/step at 995 GFLOPs — docs/PERF.md): attack bytes, not
    roofline fraction. The torch path cannot do this: PyG materializes
    messages per edge by design (torch_geometric MessagePassing).

    ``avg_deg_lin``/``avg_deg_log`` are precomputed on host from the
    train-set degree histogram (reference: hydragnn/utils/model.py:92-109,
    config_utils.py:54-58) so the layer itself is purely static.
    """

    out_dim: int
    avg_deg_lin: float
    avg_deg_log: float
    edge_dim: Optional[int] = None

    @nn.compact
    def __call__(self, x: jnp.ndarray, ctx: EdgeContext) -> jnp.ndarray:
        n, fin = x.shape
        use_edge = (
            self.edge_dim is not None and self.edge_dim > 0 and ctx.edge_attr is not None
        )
        # pre_nn (pre_layers=1) as explicit slices of one kernel so the
        # receiver/sender parts apply at node level. Init matches
        # nn.Dense(fin) on the concat: lecun_normal with fan_in = zdim.
        zdim = (3 if use_edge else 2) * fin
        w = self.param("pre_kernel", nn.initializers.lecun_normal(), (zdim, fin))
        b_pre = self.param("pre_bias", nn.initializers.zeros, (fin,))
        w = w.astype(x.dtype)
        a = x @ w[:fin] + b_pre.astype(x.dtype)  # receiver part [N, fin]
        bsend = x @ w[fin : 2 * fin]  # sender part [N, fin]

        # DENSE path (loader-emitted slot map): aggregations become
        # [N, D, fin] reshape reductions — one fused XLA pass forward,
        # pure broadcasts backward — skipping every scatter/segment op
        # (XLA's TPU scatter-extremum alone is ~7-9 ms per pass at
        # E=699k; docs/PERF.md r03). The sender gather and its
        # permuted-CSR backward are unchanged in structure.
        dense = ctx.dense_senders is not None and (
            not use_edge or ctx.dense_edge_attr is not None
        )
        if dense:
            nslots = ctx.dense_senders.shape[1]
            flat = ctx.dense_senders.reshape(-1)
            if ctx.dense_sender_win is not None and _local_kernels(flat.shape[0]):
                v = S.gather_rows_local(bsend, flat, ctx.dense_sender_win, n)
            else:
                v = S.gather_rows_permuted(bsend, flat, ctx.dense_sender_perm, n)
            if use_edge:
                v = v + nn.Dense(fin)(ctx.dense_edge_attr) @ w[2 * fin :]
            v3 = v.reshape(n, nslots, fin)
            m3 = ctx.dense_mask[:, :, None]
            # one fused read of v3 computes sum, sumsq, max and min —
            # accumulation in f32 like the family kernel contract
            vf = jnp.where(m3, v3, 0).astype(jnp.float32)
            vsum = vf.sum(axis=1)
            vsumsq = (vf * vf).sum(axis=1)
            neg = jnp.finfo(v.dtype).min
            vmax = jnp.where(m3, v3, neg).max(axis=1)
            vmin = jnp.where(m3, v3, -neg).min(axis=1)
            cnt = (
                ctx.in_degree
                if ctx.in_degree is not None
                else ctx.dense_mask.sum(axis=1).astype(jnp.float32)
            )
            # empty-clean from the fill value itself (like the CSR
            # path's both-cleanup): cnt/in_degree counts the padding
            # NODE's masked edges by design, so it cannot be the gate
            max_v = jnp.where(vmax <= neg, 0, vmax).astype(v.dtype)
            min_v = jnp.where(vmin >= -neg, 0, vmin).astype(v.dtype)
        else:
            # CSR path: the ONLY edge-width intermediate is v_e =
            # bsend[send_e] (+ edge term); the sender gather's backward
            # is a sorted segment sum via the chassis-provided argsort.
            # Aggregation is ONE fused op: sum + sumsq (family kernel)
            # and the [v,-v] scatter-max forward, with the two-kernel
            # fused backward emitting the complete grad_v in one pass
            # (hydragnn_tpu/ops/segment_pallas.py:pna_aggregate).
            # indices_are_sorted: the data pipeline emits edges
            # receiver-major sorted (data/radius_graph.py:_cap_and_sort;
            # batch_graphs canonicalizes), which also enables the Pallas
            # CSR kernels on TPU.
            from hydragnn_tpu.ops import pna_aggregate
            from hydragnn_tpu.ops.segment_pallas import (
                gather_presum_eligible,
                gather_presum_stats,
            )

            if (
                ctx.run_align
                and not use_edge
                and gather_presum_eligible(
                    bsend, ctx.senders, ctx.sender_win, ctx.run_align
                )
            ):
                # Fused gather + K-group pre-reduction (r05): the kernel
                # keeps v = bsend[senders] in VMEM and emits the four
                # statistics at E/K rows directly — the [E, H] v array
                # and its 4-6 full re-reads (the "fwd reduce_sum" block
                # of the r05 trace) never touch HBM. The backward is the
                # closed-form derivative of the identical composition
                # (ops/segment_pallas.py:_presum_stats_ref), one
                # gather_stats_bwd kernel where its accumulator fits
                # VMEM (gather_presum_layers counts those). use_edge
                # keeps the unfused path: the edge term breaks the
                # pure-gather structure. fin % 128 == 0 by eligibility,
                # so no lane split is needed in the slicing below.
                K = ctx.run_align
                v = bsend  # dtype source for the shared tail
                stats8, both8 = gather_presum_stats(
                    bsend, ctx.senders, ctx.edge_mask, ctx.sender_win, n, K
                )
                recv8 = ctx.receivers[::K]
                pair = S.segment_sum_sorted(
                    stats8, recv8, n, grad_dtype=bsend.dtype
                )
                vsum, vsumsq = pair[:, :fin], pair[:, fin : 2 * fin]
                both = S.segment_max(
                    both8, recv8, n, indices_are_sorted=True, empty_value=0.0
                )
                cnt = _edge_count(ctx, n)
            else:
                v = _gather_senders(bsend, ctx)
                if use_edge:
                    v = v + nn.Dense(fin)(ctx.edge_attr) @ w[2 * fin :]
                if ctx.run_align:
                    # Run-aligned pre-reduction (graph/batch.py run_align):
                    # every aggregation statistic first collapses K-fold
                    # with fused elementwise passes, then the segment ops
                    # run on E/K rows — the serial scatter-max that
                    # dominated the r04 trace (6 x ~9 ms at E=699k) costs
                    # 1/K, and the fused K1/K2 backward kernels are
                    # replaced by plain AD through broadcasts + the
                    # E/K-scale segment VJPs.
                    K = ctx.run_align
                    m = ctx.edge_mask[:, None]
                    # Narrow widths run at LANE width on TPU: a [E', fin<8]
                    # elementwise chain uses ~fin/128 of each VPU tile
                    # (conv_0's fin=1 backward measured 7 GB/s, r04 trace);
                    # zero columns ride along and are sliced off after the
                    # segment ops.
                    lane_w = fin
                    if fin % 128 and jax.default_backend() == "tpu":
                        lane_w = (fin + 127) // 128 * 128
                        v = jnp.concatenate(
                            [v, jnp.zeros((v.shape[0], lane_w - fin), v.dtype)], axis=1
                        )
                    # The statistics composition is SHARED with the
                    # fused kernel's contract (_presum_stats_ref is
                    # also what its custom VJP recompute targets), so
                    # fused and fallback configs cannot silently
                    # diverge. It runs one pass per statistic — an r05
                    # experiment packed (vf | vf^2) and (max | -min)
                    # into E-level lane-concats hoping XLA would fuse
                    # them into the reshape-reduce; it materialized the
                    # f32 [E', 2W] concats instead (110 ms/step vs
                    # 77.8, +27 GB/step), same failure mode as r04's
                    # [msg,-msg] concat. The E/K-level concats inside
                    # _presum_stats_ref are bandwidth-trivial.
                    from hydragnn_tpu.ops.segment_pallas import (
                        _presum_stats_ref,
                    )

                    stats8, both8 = _presum_stats_ref(v, ctx.edge_mask, K)
                    recv8 = ctx.receivers[::K]
                    pair = S.segment_sum_sorted(
                        stats8, recv8, n, grad_dtype=v.dtype
                    )
                    vsum, vsumsq = pair[:, :fin], pair[:, lane_w : lane_w + fin]
                    both = S.segment_max(
                        both8, recv8, n, indices_are_sorted=True, empty_value=0.0
                    )
                    both = jnp.concatenate(
                        [both[:, :fin], both[:, lane_w : lane_w + fin]], axis=-1
                    )
                    cnt = _edge_count(ctx, n)
                else:
                    vsum, vsumsq, cnt, both = pna_aggregate(
                        v, ctx.receivers, n, mask=ctx.edge_mask, indices_are_sorted=True
                    )
                    if ctx.in_degree is not None:
                        # chassis-precomputed degree (searchsorted over the
                        # sorted receivers): the aggregate's own count scatter
                        # then has no consumer and XLA dead-code-eliminates it
                        cnt = ctx.in_degree
            max_v = both[:, :fin]
            min_v = -both[:, fin:]
        # mean/var formed in f32 (both paths accumulate f32); cast back
        # to the compute dtype only after the cancellation
        safe_cnt = jnp.maximum(cnt, 1.0)[:, None]
        has = (cnt > 0.0)[:, None]
        mean_v = vsum / safe_cnt
        mean = jnp.where(has, a.astype(jnp.float32) + mean_v, 0.0)
        # PyG 'std': sqrt(relu(mean(x^2) - mean(x)^2) + eps); the a-shift
        # cancels exactly, so this is the variance of v alone — and for
        # empty receivers sqrt(eps), digit-identical to the message form
        var = jax.nn.relu(vsumsq / safe_cnt - mean_v * mean_v)
        std = jnp.sqrt(var + 1e-5)
        has_c = has.astype(v.dtype)
        aggs = [
            mean.astype(v.dtype),
            (a + min_v) * has_c,
            (a + max_v) * has_c,
            std.astype(v.dtype),
        ]
        agg = jnp.concatenate(aggs, axis=-1)  # [N, 4*fin]

        # Padding-node slots: cnt counts their masked edges (thousands at
        # flagship scale), and an ungated 'linear' scaler would amplify
        # the padding rows by ~deg/avg_deg — bounded-magnitude garbage
        # only because downstream consumers mask padding nodes. Gate on
        # node_mask so padding rows scale by exactly 1 (r03 advisor).
        deg = jnp.where(
            ctx.node_mask, jnp.maximum(cnt, 1.0), 1.0
        ).astype(v.dtype)
        log_deg = jnp.log(deg + 1.0)[:, None]
        amplification = log_deg / self.avg_deg_log
        attenuation = self.avg_deg_log / log_deg
        linear = deg[:, None] / self.avg_deg_lin
        scaled = jnp.concatenate(
            [agg, agg * amplification, agg * attenuation, agg * linear], axis=-1
        )  # [N, 16*fin]

        out = jnp.concatenate([x, scaled], axis=-1)
        return nn.Dense(self.out_dim)(out)  # post_nn, post_layers=1


class CFConv(nn.Module):
    """SchNet continuous-filter conv
    (reference: hydragnn/models/SCFStack.py:48-62; PyG schnet.CFConv).

    W_ij = filter_mlp(gaussian(d_ij)) * cosine_cutoff(d_ij)
    out_i = W2( sum_j W1(x_j) * W_ij )
    Expects ``ctx.edge_weight`` (distances) and ``ctx.edge_attr``
    (Gaussian-smeared distances) prepared by the SchNet chassis hook.
    """

    out_dim: int
    num_filters: int
    num_gaussians: int
    cutoff: float

    @nn.compact
    def __call__(self, x: jnp.ndarray, ctx: EdgeContext) -> jnp.ndarray:
        if ctx.edge_weight is None or ctx.edge_attr is None:
            raise ValueError("CFConv requires edge_weight and edge_attr")
        d = ctx.edge_weight
        # init parity with the reference: the filter MLP is plain torch
        # Linear init (kaiming-uniform a=sqrt(5) -> var 1/(3 fan_in));
        # lin1/lin2 are xavier-uniform with zero bias (PyG
        # CFConv.reset_parameters). At the CI accuracy thresholds this
        # scale difference vs flax's lecun_normal default is measurable.
        torch_init = nn.initializers.variance_scaling(1.0 / 3.0, "fan_in", "uniform")
        xavier = nn.initializers.xavier_uniform()

        def torch_bias(fan_in):
            bound = 1.0 / float(fan_in) ** 0.5

            def init(key, shape, dtype=jnp.float32):
                return jax.random.uniform(key, shape, dtype, -bound, bound)

            return init

        w = nn.Dense(
            self.num_filters,
            kernel_init=torch_init,
            bias_init=torch_bias(self.num_gaussians),
        )(ctx.edge_attr)
        w = shifted_softplus(w)
        w = nn.Dense(
            self.num_filters,
            kernel_init=torch_init,
            bias_init=torch_bias(self.num_filters),
        )(w)
        c = 0.5 * (jnp.cos(d * jnp.pi / self.cutoff) + 1.0)
        c = jnp.where(d <= self.cutoff, c, 0.0)
        w = w * c[:, None]

        h = nn.Dense(self.num_filters, use_bias=False, kernel_init=xavier)(x)
        # fused path: gather + per-edge filter product + scatter in one
        # kernel — the [E, F] message array never touches HBM
        agg = _gather_scatter(h, ctx, x.shape[0], scale=w).astype(x.dtype)
        return nn.Dense(self.out_dim, kernel_init=xavier)(agg)


def shifted_softplus(x: jnp.ndarray) -> jnp.ndarray:
    return jax.nn.softplus(x) - jnp.log(2.0)


def gaussian_smearing(
    d: jnp.ndarray, start: float, stop: float, num_gaussians: int
) -> jnp.ndarray:
    """PyG GaussianSmearing: RBF expansion of distances
    (reference usage: hydragnn/models/SCFStack.py:42,70)."""
    offset = jnp.linspace(start, stop, num_gaussians)
    coeff = -0.5 / float((stop - start) / (num_gaussians - 1)) ** 2
    diff = d[:, None] - offset[None, :]
    return jnp.exp(coeff * diff * diff)


def avg_degree_stats(deg_histogram) -> Tuple[float, float]:
    """(avg_deg_lin, avg_deg_log) from a train-set degree histogram,
    mirroring PyG PNAConv's init-time computation."""
    import numpy as np

    hist = np.asarray(deg_histogram, dtype=np.float64)
    total = max(hist.sum(), 1.0)
    degrees = np.arange(len(hist), dtype=np.float64)
    lin = float((hist * degrees).sum() / total)
    log = float((hist * np.log(degrees + 1.0)).sum() / total)
    return max(lin, 1e-6), max(log, 1e-6)
