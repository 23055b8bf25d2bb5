"""Statically-padded graph batches — the TPU-native PyG ``Batch`` equivalent.

The reference feeds ragged PyG ``Data`` objects through a collate that
concatenates nodes/edges and keeps a ``batch`` vector (torch_geometric
collate, consumed at reference hydragnn/models/Base.py:244-275). Ragged
shapes recompile under ``jit``, so here a batch is padded to static
``(num_nodes, num_edges, num_graphs)`` with explicit masks:

  - one *padding graph* slot absorbs all padding nodes/edges (jraph-style),
  - padding edges point at a padding node, so segment reductions stay clean,
  - targets are a dict-of-heads ``{head_name: values}`` replacing the
    reference's ragged ``data.y`` + ``y_loc`` offset table
    (reference: hydragnn/preprocess/serialized_dataset_loader.py:262-303,
    hydragnn/train/train_validate_test.py:218-281) — per-head values carry
    their own masks, which eliminates the index gymnastics while keeping
    loss parity.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Sequence

import jax
import numpy as np
import jax.numpy as jnp


def _round_up(x: int, multiple: int) -> int:
    return ((x + multiple - 1) // multiple) * multiple


def _block_windows(
    ids: np.ndarray,
    perm: np.ndarray,
    num_rows: int,
    target_rows: Optional[int] = None,
) -> np.ndarray:
    """Host-side per-node-block position windows [2, n_blocks] for the
    local-window kernels: every position p with ``ids[p] // B == i``
    satisfies ``win[0, i] <= p < win[1, i]``, where B is derived from
    (num_rows, n_blocks) by the SAME formula the kernel uses
    (ops/segment_pallas.py:local_block_rows) — the block size rides
    the window shape. ``perm`` must be a stable argsort of ``ids``.
    ``target_rows`` sizes blocks to the batch's typical graph so large
    graphs don't re-scan their edge window once per 128-row block
    (docs/PERF.md r04).

    Windows are ALWAYS emitted (a data-dependent None would make the
    pytree structure vary per batch — breaking device_stack stacking
    and flapping the jit cache). Tightness, not validity, depends on
    locality: batches from :func:`batch_graphs` are graph-contiguous,
    bounding the kernel's scan at a small multiple of a sorted
    layout's; a pathologically shuffled node order degrades to
    wide windows — slower, never wrong (the one-hot match filters
    strays). The giant-graph path strips windows before GSPMD sharding
    (parallel/edge_sharded.py:place_giant_batch)."""
    from hydragnn_tpu.ops.segment_pallas import BN, local_block_rows

    t = target_rows or BN
    n_blocks = max(1, (max(num_rows, 1) + t - 1) // t)
    b_eff = local_block_rows(num_rows, n_blocks)
    lo = np.zeros(n_blocks, dtype=np.int64)
    hi = np.zeros(n_blocks, dtype=np.int64)
    if ids.size:
        sblk = ids[perm] // b_eff  # sorted ids -> sorted block ids
        starts = np.searchsorted(sblk, np.arange(n_blocks), side="left")
        ends = np.searchsorted(sblk, np.arange(n_blocks), side="right")
        ne = ends > starts
        if ne.any():
            # nonempty block segments tile the sorted array contiguously,
            # so reduceat over their starts reduces exactly [start, end)
            lo[ne] = np.minimum.reduceat(perm, starts[ne])
            hi[ne] = np.maximum.reduceat(perm, starts[ne]) + 1
    return np.stack([lo, hi]).astype(np.int32)


class BatchInvariantError(AssertionError):
    """A loader-layout contract was violated (GraphBatch.check_invariants).

    Subclasses AssertionError for caller compatibility, but is raised
    explicitly so the checks survive ``python -O`` (graftlint HG007)."""


def _invariant(cond, message: str) -> None:
    if not cond:
        raise BatchInvariantError(message)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class GraphBatch:
    """A fixed-shape batch of graphs.

    Attributes:
      nodes: [N, F] node features (float32; int32 for token documents).
      senders / receivers: [E] int32 edge endpoints (message flows
        sender -> receiver, matching PyG's edge_index[0] -> edge_index[1]).
      edge_attr: [E, De] edge features, or None.
      pos: [N, 3] node positions, or None.
      node_graph: [N] int32 graph id of each node (PyG ``batch`` vector).
      n_node / n_edge: [G] int32 per-graph counts (padding slots are 0).
      node_mask: [N] bool, True for real nodes.
      edge_mask: [E] bool, True for real edges.
      graph_mask: [G] bool, True for real graphs.
      graph_targets: {name: [G, d]} graph-level targets.
      node_targets: {name: [N, d]} node-level targets.
    """

    nodes: jnp.ndarray
    senders: jnp.ndarray
    receivers: jnp.ndarray
    node_graph: jnp.ndarray
    n_node: jnp.ndarray
    n_edge: jnp.ndarray
    node_mask: jnp.ndarray
    edge_mask: jnp.ndarray
    graph_mask: jnp.ndarray
    edge_attr: Optional[jnp.ndarray] = None
    pos: Optional[jnp.ndarray] = None
    graph_targets: Dict[str, jnp.ndarray] = dataclasses.field(default_factory=dict)
    node_targets: Dict[str, jnp.ndarray] = dataclasses.field(default_factory=dict)
    # Dense per-node edge-slot map (host-emitted, free: receivers are
    # already receiver-major sorted so node n's edges are contiguous).
    # Lets aggregations run as DENSE [N, D, H] reshape reductions — one
    # fused XLA pass forward, pure broadcasts backward — instead of
    # scatter/segment ops (XLA's TPU scatter-extremum is row-bound:
    # ~7-9 ms per pass at E=699k, docs/PERF.md r03). D is the dataset
    # max in-degree (static across batches); padding slots carry
    # mask=False and point at a padding edge/node.
    dense_senders: Optional[jnp.ndarray] = None  # [N, D] int32
    dense_mask: Optional[jnp.ndarray] = None  # [N, D] bool
    dense_edge_attr: Optional[jnp.ndarray] = None  # [N, D, De]
    # Host-precomputed edge-structure derivatives, pure functions of
    # senders/receivers. The model chassis (models/base.py:_conv_args)
    # consumes these instead of recomputing argsort/searchsorted inside
    # the jitted step every iteration — at flagship scale (E=699k) the
    # in-step sorts are serial row-bound ops worth ~ms/step (r03 trace,
    # docs/PERF.md). Batches built outside batch_graphs/pad_batch may
    # leave them None; the chassis falls back to in-jit computation.
    sender_perm: Optional[jnp.ndarray] = None  # [E] int32, stable argsort(senders)
    in_degree: Optional[jnp.ndarray] = None  # [N] f32, edge count per receiver
    dense_sender_perm: Optional[jnp.ndarray] = None  # [N*D] int32
    # Per-node-block edge-position windows for the local-window Pallas
    # kernels (ops/segment_pallas.py:segment_sum_local_pallas): every
    # edge e with senders[e] // B == i lies in [win[0,i], win[1,i]),
    # where B = local_block_rows(num_nodes, win.shape[1]) — the block
    # size is DERIVED from the window shape, identically by the emitter
    # (_block_windows) and the kernel; external producers must use the
    # same derivation. Tight for batched graphs (graph g's senders
    # live in g's contiguous node block); lets the sender-gather
    # backward scatter WITHOUT the [E, H] cotangent permute.
    # batch_graphs ALWAYS emits them (pathological id layouts just get
    # wide, slow-but-correct windows); None only for externally-built
    # batches and the GSPMD-sharded giant-graph path, which strips
    # them.
    sender_win: Optional[jnp.ndarray] = None  # [2, n_blocks] int32
    dense_sender_win: Optional[jnp.ndarray] = None  # [2, n_blocks] int32
    # Edge OCCUPANCY: scalar int32 — the index AFTER the last edge slot
    # that can carry a real (unmasked) edge. Everything at position >=
    # edge_occupancy is pure padding (the batch_graphs sentinel tail;
    # run_align keeps its masked self-loops interleaved BELOW this
    # bound, so the bound is int(adeg.sum()) there, tot_edges otherwise;
    # _mask_out filler batches advertise 0). The fused conv kernel
    # clamps its chunk loop at ceil(edge_occupancy / CE), so tail
    # padding costs zero DMAs and zero MXU work — device cost scales
    # with real edges, not the pad plan (ISSUE 10). Carried as a scalar
    # ARRAY (not static) so bucket-ladder batches with different
    # occupancies share one jit cache entry and device_stack stacking
    # works. None on externally-built batches — consumers then process
    # the full pad (slower, never wrong).
    edge_occupancy: Optional[jnp.ndarray] = None  # [] int32
    # Real (unmasked) node count, for pad-waste accounting in the
    # bench/ledger layers (obs/introspect.py, bench.py). None on
    # externally-built batches.
    n_real_nodes: Optional[jnp.ndarray] = None  # [] int32
    # STATIC (pytree meta): run-aligned edge layout factor. When K > 0,
    # every node's receiver-run is padded to a multiple of K with MASKED
    # self-loop edges (sender = receiver = the node), so every K-group
    # of edge slots lies within one node's run (or the batch tail) and
    # segment reductions can PRE-REDUCE each group with one fused
    # elementwise pass — shrinking the serial scatter/segment work K-fold
    # (XLA's TPU scatter loops per ROW; docs/PERF.md r03/r04). Downstream
    # contracts that change under K > 0: masked edges may target REAL
    # nodes (always as self-loops), so consumers MUST apply edge_mask —
    # all in-tree convs do; in_degree counts REAL edges only (either way).
    run_align: int = dataclasses.field(default=0, metadata=dict(static=True))

    @property
    def num_nodes(self) -> int:
        return self.nodes.shape[0]

    @property
    def num_edges(self) -> int:
        return self.senders.shape[0]

    @property
    def num_graphs(self) -> int:
        return self.n_node.shape[0]

    def replace(self, **kwargs) -> "GraphBatch":
        return dataclasses.replace(self, **kwargs)

    def check_invariants(self) -> None:
        """Validate the loader contracts the model chassis SILENTLY
        relies on (r03 advisor): raises :class:`BatchInvariantError`
        (an AssertionError subclass) with a named violation. Host-side debug helper — call it on batches built
        outside :func:`batch_graphs`/:func:`pad_batch` (which maintain
        these by construction); never inside jit.

          1. receivers sorted ascending (segment reductions pass
             indices_are_sorted=True — a violated hint silently corrupts
             sums on TPU);
          2. every masked edge targets a padding node (the degree
             shortcut counts edges without consulting the mask);
          3. sender_perm is a stable argsort of senders, in_degree
             matches the receiver bincount, and the block windows cover
             every edge position of their id block.
        """
        import numpy as np_

        recv = np_.asarray(self.receivers)
        send = np_.asarray(self.senders)
        emask = np_.asarray(self.edge_mask)
        nmask = np_.asarray(self.node_mask)
        _invariant(
            np_.all(recv[:-1] <= recv[1:]), "receivers not sorted ascending"
        )
        masked_idx = np_.flatnonzero(~emask)
        if masked_idx.size:
            to_real = nmask[recv[masked_idx]]
            if self.run_align:
                # run-aligned layout: masked edges at real nodes must be
                # SELF-LOOPS (they then cannot corrupt any masked
                # aggregation, and sender locality is preserved)
                bad = to_real & (send[masked_idx] != recv[masked_idx])
                _invariant(
                    not bad.any(),
                    "masked edge targets a real node without being a "
                    "self-loop (run_align contract)",
                )
            else:
                _invariant(
                    not to_real.any(),
                    "masked edge targets a REAL node (degree shortcut + "
                    "dense map assume padding edges only ever point at "
                    "padding nodes)",
                )
        if self.edge_occupancy is not None:
            occ = int(np_.asarray(self.edge_occupancy))
            real_pos = np_.flatnonzero(emask)
            _invariant(
                not real_pos.size or int(real_pos.max()) < occ,
                "unmasked edge at position >= edge_occupancy (the fused "
                "kernel skips all chunks past the occupancy bound)",
            )
            _invariant(
                int(np_.asarray(self.n_real_nodes)) == int(nmask.sum()),
                "n_real_nodes != node_mask.sum()",
            )
        if self.sender_perm is not None:
            sp = np_.asarray(self.sender_perm)
            _invariant(
                np_.all(send[sp][:-1] <= send[sp][1:]),
                "sender_perm does not sort senders",
            )
        if self.in_degree is not None:
            deg = np_.asarray(self.in_degree)
            real = recv[emask]
            ref = np_.bincount(real, minlength=real.max() + 1 if real.size else 0)
            _invariant(
                np_.array_equal(deg[: ref.shape[0]], ref)
                and not deg[ref.shape[0]:].any(),
                "in_degree != bincount(real receivers)",
            )
        for ids, win, label in (
            (send, self.sender_win, "sender_win"),
            (
                None
                if self.dense_senders is None
                else np_.asarray(self.dense_senders).reshape(-1),
                self.dense_sender_win,
                "dense_sender_win",
            ),
        ):
            if win is None or ids is None:
                continue
            from hydragnn_tpu.ops.segment_pallas import local_block_rows

            w = np_.asarray(win)
            b_eff = local_block_rows(self.num_nodes, w.shape[1])
            blk = ids // b_eff
            pos = np_.arange(ids.shape[0])
            lo, hi = w[0][blk], w[1][blk]
            _invariant(
                np_.all((pos >= lo) & (pos < hi)),
                f"{label} does not cover every position of its id block",
            )


def batch_graphs(
    graphs: Sequence[Dict[str, Any]],
    n_node_pad: Optional[int] = None,
    n_edge_pad: Optional[int] = None,
    n_graph_pad: Optional[int] = None,
    node_multiple: int = 16,
    edge_multiple: int = 8,
    dense_slots: Optional[int] = None,
    run_align: int = 0,
    win_block_rows: Optional[int] = None,
) -> GraphBatch:
    """Concatenate a list of single graphs and pad to static shapes.

    Each graph is a dict with keys ``x`` [n, F], ``senders``/``receivers``
    [e] (or ``edge_index`` [2, e]), optional ``edge_attr``, ``pos``,
    ``graph_targets`` {name: [d]}, ``node_targets`` {name: [n, d]}.
    All numpy; this runs on host in the input pipeline.

    ``run_align=K`` (K > 1) emits the run-aligned edge layout: each
    node's receiver-run padded to a multiple of K with masked self-loop
    edges (see GraphBatch.run_align). Mutually exclusive with
    ``dense_slots`` — they are alternative answers to the same
    scatter-cost problem, dense for tight degree distributions,
    run-align for wide ones.
    """
    if not graphs:
        raise ValueError("graphs must be non-empty")
    n_graphs = len(graphs)
    tot_nodes = sum(int(np.asarray(g["x"]).shape[0]) for g in graphs)
    tot_edges = sum(_num_edges(g) for g in graphs)

    # Field presence must be homogeneous — a silently dropped optional field
    # is worse than an error here.
    for key in ("edge_attr", "pos"):
        present = [g.get(key) is not None for g in graphs]
        if any(present) and not all(present):
            raise ValueError(f"field '{key}' present on some graphs but not others")
    gt_names = sorted(graphs[0].get("graph_targets", {}).keys())
    nt_names = sorted(graphs[0].get("node_targets", {}).keys())
    for g in graphs:
        if sorted(g.get("graph_targets", {}).keys()) != gt_names:
            raise ValueError("graph_targets keys differ across graphs")
        if sorted(g.get("node_targets", {}).keys()) != nt_names:
            raise ValueError("node_targets keys differ across graphs")

    # One extra padding graph absorbs padding nodes/edges; at least one
    # padding node/edge must exist for them to point at. node_multiple
    # defaults to 16 = ops.segment_pallas.ALIGN so the CSR-broadcast
    # kernel never re-pads (copies) the node table per call.
    if n_graph_pad is None:
        n_graph_pad = n_graphs + 1
    if n_node_pad is None:
        n_node_pad = _round_up(tot_nodes + 1, node_multiple)
    if n_edge_pad is None:
        n_edge_pad = max(_round_up(tot_edges + 1, edge_multiple), 1)
    if n_graph_pad <= n_graphs:
        raise ValueError(
            f"n_graph_pad={n_graph_pad} must exceed num real graphs {n_graphs} "
            "(one slot is reserved for the padding graph)"
        )
    # Padding edges only need a padding *node* to point at, so an exact-fit
    # edge capacity is fine; the node side must strictly exceed.
    if n_node_pad <= tot_nodes or n_edge_pad < tot_edges:
        raise ValueError(
            f"padded sizes (nodes {n_node_pad}, edges {n_edge_pad}) too small "
            f"for real totals (nodes {tot_nodes}, edges {tot_edges})"
        )

    x0 = _as_2d(graphs[0]["x"])
    nodes = np.zeros((n_node_pad, x0.shape[1]), dtype=x0.dtype)
    senders = np.full((n_edge_pad,), tot_nodes, dtype=np.int32)
    receivers = np.full((n_edge_pad,), tot_nodes, dtype=np.int32)
    node_graph = np.full((n_node_pad,), n_graphs, dtype=np.int32)
    n_node = np.zeros((n_graph_pad,), dtype=np.int32)
    n_edge = np.zeros((n_graph_pad,), dtype=np.int32)
    node_mask = np.zeros((n_node_pad,), dtype=bool)
    edge_mask = np.zeros((n_edge_pad,), dtype=bool)
    graph_mask = np.zeros((n_graph_pad,), dtype=bool)

    has_edge_attr = graphs[0].get("edge_attr") is not None
    has_pos = graphs[0].get("pos") is not None
    edge_attr = None
    pos = None
    if has_edge_attr:
        de = _as_2d(graphs[0]["edge_attr"]).shape[1]
        edge_attr = np.zeros((n_edge_pad, de), dtype=np.float32)
    if has_pos:
        pos = np.zeros((n_node_pad, np.asarray(graphs[0]["pos"]).shape[-1]), dtype=np.float32)

    g_targets: Dict[str, list] = {}
    n_targets: Dict[str, Any] = {}
    for name in nt_names:
        t0 = _as_2d(graphs[0]["node_targets"][name])
        n_targets[name] = np.zeros((n_node_pad, t0.shape[1]), dtype=t0.dtype)

    node_off, edge_off = 0, 0
    for gi, g in enumerate(graphs):
        x = _as_2d(g["x"])
        n, e = x.shape[0], _num_edges(g)
        s, r = _edge_endpoints(g)
        nodes[node_off : node_off + n] = x
        senders[edge_off : edge_off + e] = s + node_off
        receivers[edge_off : edge_off + e] = r + node_off
        node_graph[node_off : node_off + n] = gi
        n_node[gi], n_edge[gi] = n, e
        node_mask[node_off : node_off + n] = True
        edge_mask[edge_off : edge_off + e] = True
        graph_mask[gi] = True
        if has_edge_attr:
            edge_attr[edge_off : edge_off + e] = _as_2d(g["edge_attr"])
        if has_pos:
            pos[node_off : node_off + n] = np.asarray(g["pos"], dtype=np.float32)
        for name in gt_names:
            g_targets.setdefault(name, []).append(
                np.asarray(g["graph_targets"][name], dtype=np.float32).reshape(-1)
            )
        for name in nt_names:
            n_targets[name][node_off : node_off + n] = _as_2d(g["node_targets"][name])
        node_off += n
        edge_off += e

    graph_targets = {}
    for name, rows in g_targets.items():
        d = rows[0].shape[0]
        arr = np.zeros((n_graph_pad, d), dtype=np.float32)
        arr[:n_graphs] = np.stack(rows)
        graph_targets[name] = arr

    # Canonical RECEIVER-MAJOR edge order: segment reductions may then
    # assume indices_are_sorted (better XLA lowering; enables the Pallas
    # CSR family kernel on TPU) regardless of the featurizer's emission
    # order (the radius pipeline is already receiver-sorted; SMILES is
    # sender-major). Stable sort; padding receivers (= tot_nodes
    # sentinel) stay at the tail. Aggregation is order-invariant, so
    # results are unchanged.
    if not np.all(receivers[:-1] <= receivers[1:]):
        perm = np.argsort(receivers, kind="stable")
        senders = senders[perm]
        receivers = receivers[perm]
        edge_mask = edge_mask[perm]
        if has_edge_attr:
            edge_attr = edge_attr[perm]

    # Index after the last slot that can hold a real edge (see
    # GraphBatch.edge_occupancy). Receiver-major sort puts the sentinel
    # tail last, so this is tot_edges here; the run_align relayout
    # interleaves its masked self-loops below int(adeg.sum()) and
    # overwrites it below.
    edge_occ = tot_edges

    if run_align and run_align > 1:
        if dense_slots:
            raise ValueError("run_align and dense_slots are mutually exclusive")
        K = int(run_align)
        if n_edge_pad % K:
            raise ValueError(f"n_edge_pad={n_edge_pad} not a multiple of run_align={K}")
        # Real edges occupy [0, tot_edges): real receivers < tot_nodes
        # strictly, padding receivers == tot_nodes, and the sort is
        # receiver-major. Re-lay runs on K-aligned starts; pad slots are
        # masked SELF-LOOPS at their node (receivers stay sorted, sender
        # locality preserved, and a self-loop cannot corrupt any masked
        # aggregation). The tail keeps the padding-node sentinel.
        deg = np.bincount(receivers[:tot_edges], minlength=n_node_pad)
        adeg = ((deg + K - 1) // K) * K * (deg > 0)
        total = int(adeg.sum())
        if total > n_edge_pad:
            raise ValueError(
                f"run_align={K} needs {total} edge slots > n_edge_pad={n_edge_pad}; "
                "size the pad from the ALIGNED per-sample counts "
                "(data/loader.py:_aligned_edge_counts — GraphLoader does "
                "this automatically)"
            )
        rs = np.zeros(n_node_pad + 1, dtype=np.int64)
        rs[1:] = np.cumsum(adeg)
        row_ptr = np.zeros(n_node_pad + 1, dtype=np.int64)
        row_ptr[1:] = np.cumsum(deg)
        r = receivers[:tot_edges]
        new_pos = rs[r] + (np.arange(tot_edges) - row_ptr[r])
        fill = np.repeat(np.arange(n_node_pad, dtype=np.int32), adeg)
        new_recv = np.full(n_edge_pad, tot_nodes, dtype=np.int32)
        new_recv[:total] = fill
        new_send = new_recv.copy()
        new_mask = np.zeros(n_edge_pad, dtype=bool)
        new_send[new_pos] = senders[:tot_edges]
        new_mask[new_pos] = True
        if has_edge_attr:
            new_ea = np.zeros_like(edge_attr)
            new_ea[new_pos] = edge_attr[:tot_edges]
            edge_attr = new_ea
        senders, receivers, edge_mask = new_send, new_recv, new_mask
        edge_occ = total

    dense_senders = dense_mask = dense_edge_attr = dense_sender_perm = None
    if dense_slots is not None and dense_slots > 0:
        # receiver-major sorted + only padding edges masked (targeting a
        # padding node), so node n's real edges occupy the contiguous
        # range [row_ptr[n], row_ptr[n] + deg[n])
        deg = np.bincount(receivers[edge_mask], minlength=n_node_pad)
        dmax = int(deg.max(initial=0))
        if dmax > dense_slots:
            raise ValueError(
                f"dense_slots={dense_slots} < batch max in-degree {dmax}"
            )
        row_ptr = np.zeros(n_node_pad, dtype=np.int64)
        row_ptr[1:] = np.cumsum(deg)[:-1]
        slot = np.arange(dense_slots, dtype=np.int64)[None, :]
        dense_mask = slot < deg[:, None]
        # host-side slot->edge positions (a local temporary: consumers
        # only ever need the gathered senders / edge features)
        dense_edge_pos = np.where(
            dense_mask, row_ptr[:, None] + slot, n_edge_pad - 1
        ).astype(np.int32)
        dense_senders = senders[dense_edge_pos]
        if has_edge_attr:
            dense_edge_attr = edge_attr[dense_edge_pos]
        dense_sender_perm = np.argsort(
            dense_senders.reshape(-1), kind="stable"
        ).astype(np.int32)

    # Stable argsort matches jnp.argsort's tie-breaking, so the sorted
    # segment-sum reduction order (hence bf16 numerics) is identical to
    # the previous in-jit computation.
    sender_perm = np.argsort(senders, kind="stable").astype(np.int32)
    # Counts REAL edges per receiver. Real-node values match
    # models/convs.py:sorted_in_degree (masked edges never target a real
    # node except as run_align self-loop padding, excluded here by the
    # mask); padding-node rows are 0 rather than the masked-tail count —
    # strictly cleaner for every consumer (PNA has-gate, MFC dispatch).
    in_degree = np.bincount(
        receivers[edge_mask], minlength=n_node_pad
    ).astype(np.float32)
    # ``win_block_rows`` must be BATCH-INDEPENDENT for a fixed pad plan
    # (the loader derives it once from dataset-wide stats): window
    # shapes are part of the pytree structure, and a per-batch
    # data-dependent target would break device_stack stacking and flap
    # the jit cache. Default BN keeps standalone callers stable.
    sender_win = _block_windows(senders, sender_perm, n_node_pad, win_block_rows)
    dense_sender_win = (
        _block_windows(
            dense_senders.reshape(-1), dense_sender_perm, n_node_pad, win_block_rows
        )
        if dense_sender_perm is not None
        else None
    )

    return GraphBatch(
        nodes=jnp.asarray(nodes),
        senders=jnp.asarray(senders),
        receivers=jnp.asarray(receivers),
        node_graph=jnp.asarray(node_graph),
        n_node=jnp.asarray(n_node),
        n_edge=jnp.asarray(n_edge),
        node_mask=jnp.asarray(node_mask),
        edge_mask=jnp.asarray(edge_mask),
        graph_mask=jnp.asarray(graph_mask),
        edge_attr=jnp.asarray(edge_attr) if edge_attr is not None else None,
        pos=jnp.asarray(pos) if pos is not None else None,
        graph_targets={k: jnp.asarray(v) for k, v in graph_targets.items()},
        node_targets={k: jnp.asarray(v) for k, v in n_targets.items()},
        dense_senders=jnp.asarray(dense_senders) if dense_senders is not None else None,
        dense_mask=jnp.asarray(dense_mask) if dense_mask is not None else None,
        dense_edge_attr=jnp.asarray(dense_edge_attr) if dense_edge_attr is not None else None,
        sender_perm=jnp.asarray(sender_perm),
        in_degree=jnp.asarray(in_degree),
        dense_sender_perm=(
            jnp.asarray(dense_sender_perm) if dense_sender_perm is not None else None
        ),
        sender_win=jnp.asarray(sender_win) if sender_win is not None else None,
        dense_sender_win=(
            jnp.asarray(dense_sender_win) if dense_sender_win is not None else None
        ),
        edge_occupancy=jnp.asarray(np.int32(edge_occ)),
        n_real_nodes=jnp.asarray(np.int32(tot_nodes)),
        run_align=int(run_align) if run_align and run_align > 1 else 0,
    )


def pad_batch(batch: GraphBatch, n_node: int, n_edge: int, n_graph: int) -> GraphBatch:
    """Pad an existing GraphBatch up to larger static shapes."""
    dn = n_node - batch.num_nodes
    de = n_edge - batch.num_edges
    dg = n_graph - batch.num_graphs
    if dn < 0 or de < 0 or dg < 0:
        raise ValueError("target shape smaller than current batch")
    if batch.run_align and n_edge % batch.run_align:
        raise ValueError(
            f"n_edge={n_edge} must stay a multiple of run_align="
            f"{batch.run_align} (the model reshapes edges into K-groups)"
        )
    if dn == de == dg == 0:
        return batch

    def pad0(a, amount, value=0):
        if a is None:
            return None
        widths = [(0, amount)] + [(0, 0)] * (a.ndim - 1)
        return jnp.pad(a, widths, constant_values=value)

    # New padding nodes/edges must point at a *padding* slot. If this
    # dimension grows, the first new slot is one; otherwise reuse the
    # existing padding slot at the end (batch_graphs always reserves one).
    if dg > 0:
        pad_graph_id = batch.num_graphs
    else:
        if bool(batch.graph_mask[-1]):
            raise ValueError("cannot pad nodes: batch has no padding graph slot")
        pad_graph_id = batch.num_graphs - 1
    if dn > 0:
        pad_node_id = batch.num_nodes
    else:
        if bool(batch.node_mask[-1]):
            raise ValueError("cannot pad edges: batch has no padding node slot")
        pad_node_id = batch.num_nodes - 1
    # Precomputed edge-structure derivatives extend without a re-sort:
    # appended padding edges sit at the tail with sender/receiver value
    # pad_node_id >= every existing value (real ids < tot_nodes <=
    # pad_node_id), and stable argsort tie-breaks old-index-first — so
    # the stable argsort of the padded array is exactly
    # concat(old_perm, arange(old_E, new_E)). in_degree only gains the
    # de new edges, all targeting pad_node_id (a padding slot).
    sender_perm = batch.sender_perm
    if sender_perm is not None:
        sender_perm = jnp.concatenate(
            [sender_perm, jnp.arange(batch.num_edges, n_edge, dtype=sender_perm.dtype)]
        )
    # in_degree counts REAL edges only; appended padding edges are
    # masked, so only zero-extension is needed
    in_degree = batch.in_degree
    if in_degree is not None:
        in_degree = pad0(in_degree, dn)
    dense_sender_perm = batch.dense_sender_perm
    if dense_sender_perm is not None and batch.dense_senders is not None:
        old_flat = batch.dense_senders.size
        new_flat = old_flat + dn * batch.dense_senders.shape[1]
        dense_sender_perm = jnp.concatenate(
            [
                dense_sender_perm,
                jnp.arange(old_flat, new_flat, dtype=dense_sender_perm.dtype),
            ]
        )

    def _extend_win(win, n_appended, old_len, new_len):
        """dn == 0: block boundaries are unchanged (the kernel derives
        the block size from (num_segments, n_blocks), both fixed), so
        only the pad-node block's window widens to cover the appended
        tail positions. dn > 0 changes the derived block size —
        callers rebuild windows on host instead (below)."""
        if win is None:
            return None
        from hydragnn_tpu.ops.segment_pallas import local_block_rows

        if n_appended <= 0:
            return win
        b_eff = local_block_rows(batch.num_nodes, win.shape[1])
        b = pad_node_id // b_eff
        empty = win[0, b] == win[1, b]
        lo = jnp.where(empty, old_len, jnp.minimum(win[0, b], old_len))
        win = win.at[0, b].set(lo.astype(win.dtype))
        return win.at[1, b].set(new_len)

    if dn > 0 and (batch.sender_win is not None or batch.dense_sender_win is not None):
        # growing the node axis changes the derived block size; rebuild
        # the plans on host, PRESERVING the original block granularity
        # (derived back from the old window shape). pad_batch with
        # node growth therefore requires concrete (host) arrays —
        # strip the windows first to pad under a trace (the GSPMD
        # giant path already does).
        import numpy as _np

        from hydragnn_tpu.ops.segment_pallas import local_block_rows

        if isinstance(batch.senders, jax.core.Tracer):
            raise ValueError(
                "pad_batch cannot grow the node axis of a TRACED batch "
                "carrying window plans (the block size must be re-derived "
                "on host); replace(sender_win=None, dense_sender_win=None) "
                "before padding under jit/vmap"
            )
        if batch.sender_win is not None and sender_perm is not None:
            target = local_block_rows(batch.num_nodes, batch.sender_win.shape[1])
            sender_win = jnp.asarray(
                _block_windows(
                    _np.asarray(pad0(batch.senders, de, pad_node_id)),
                    _np.asarray(sender_perm),
                    n_node,
                    target,
                )
            )
        else:
            # a window without its perm (exotic external batch): the
            # consumers' fallback chain handles a None window correctly
            sender_win = None
        if (
            batch.dense_sender_win is not None
            and batch.dense_senders is not None
            and dense_sender_perm is not None
        ):
            target = local_block_rows(
                batch.num_nodes, batch.dense_sender_win.shape[1]
            )
            new_dense = _np.asarray(
                pad0(batch.dense_senders, dn, pad_node_id)
            ).reshape(-1)
            dense_sender_win = jnp.asarray(
                _block_windows(new_dense, _np.asarray(dense_sender_perm), n_node, target)
            )
        else:
            dense_sender_win = None
    else:
        sender_win = _extend_win(batch.sender_win, de, batch.num_edges, n_edge)
        dense_sender_win = batch.dense_sender_win
        if dense_sender_win is not None and batch.dense_senders is not None:
            dense_sender_win = _extend_win(
                dense_sender_win,
                dn * batch.dense_senders.shape[1],
                batch.dense_senders.size,
                batch.dense_senders.size + dn * batch.dense_senders.shape[1],
            )
    return batch.replace(
        nodes=pad0(batch.nodes, dn),
        senders=pad0(batch.senders, de, pad_node_id),
        receivers=pad0(batch.receivers, de, pad_node_id),
        node_graph=pad0(batch.node_graph, dn, pad_graph_id),
        n_node=pad0(batch.n_node, dg),
        n_edge=pad0(batch.n_edge, dg),
        node_mask=pad0(batch.node_mask, dn, False),
        edge_mask=pad0(batch.edge_mask, de, False),
        graph_mask=pad0(batch.graph_mask, dg, False),
        edge_attr=pad0(batch.edge_attr, de),
        pos=pad0(batch.pos, dn),
        graph_targets={k: pad0(v, dg) for k, v in batch.graph_targets.items()},
        node_targets={k: pad0(v, dn) for k, v in batch.node_targets.items()},
        # new dense rows are all-padding slots: mask False, senders at a
        # padding node, positions at the (old) last edge slot
        dense_senders=pad0(batch.dense_senders, dn, pad_node_id),
        dense_mask=pad0(batch.dense_mask, dn, False),
        dense_edge_attr=pad0(batch.dense_edge_attr, dn),
        sender_perm=sender_perm,
        in_degree=in_degree,
        dense_sender_perm=dense_sender_perm,
        sender_win=sender_win,
        dense_sender_win=dense_sender_win,
    )


def _as_2d(a) -> np.ndarray:
    """[n] or [n, d] as [n, d] float32. An int32 array stays int32: token
    ids and indices (``data/tokens.py``), an embedding's input and a
    cross-entropy's target, which no cast to a float may touch; any other
    dtype (numpy's default int64 among them) is a feature and becomes
    float32 as before."""
    a = np.asarray(a)
    if a.dtype != np.int32:
        a = a.astype(np.float32, copy=False)
    return a[:, None] if a.ndim == 1 else a


def _num_edges(g: Dict[str, Any]) -> int:
    if "senders" in g:
        return int(np.asarray(g["senders"]).shape[0])
    return int(np.asarray(g["edge_index"]).shape[1])


def _edge_endpoints(g: Dict[str, Any]):
    if "senders" in g:
        return np.asarray(g["senders"]), np.asarray(g["receivers"])
    ei = np.asarray(g["edge_index"])
    return ei[0], ei[1]
