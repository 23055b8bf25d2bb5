"""The model chassis: shared message-passing encoder + multi-head decoders.

TPU-native re-design of the reference's ``Base`` class (reference:
hydragnn/models/Base.py:22-378): one conv stack with interleaved
BatchNorm+ReLU, masked global mean pooling, then N decoder heads — graph
heads share a dense trunk (Base.py:168-177) with per-head MLPs, node heads
come in three flavors ``mlp`` / ``mlp_per_node`` / ``conv``
(Base.py:205-235) — and a weighted multi-task loss with normalized weights
(Base.py:69-80,304-321).

Differences by design:
  - all shapes static, all reductions masked (padding-graph slots never
    contribute to pooling, BN stats, or the loss);
  - targets are a dict-of-heads on the GraphBatch instead of the ragged
    ``data.y``/``y_loc`` contract — per-head selection happens in the data
    layer (see hydragnn_tpu/data), not with index lists in the hot loop
    (reference: hydragnn/train/train_validate_test.py:218-281);
  - the reference's conv-type node head applies every hidden conv to the
    encoder output ``x`` (Base.py:267-271), which only type-checks when all
    widths match; here the layers chain (x -> h1 -> h2 -> out), the sane
    reading of the same architecture;
  - ``freeze_conv`` (Base.py:117-121) is honored by the optimizer via a
    parameter-label mask rather than requires_grad (see train/optimizer.py).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import flax.linen as nn

from hydragnn_tpu.data.tokens import COPY
from hydragnn_tpu.graph import segment as S
from hydragnn_tpu.graph.batch import GraphBatch
from hydragnn_tpu.models import convs as C
from hydragnn_tpu.models.layers import MLP, MaskedBatchNorm

# The token stack (models/token_stack.py) is imported where a configuration
# names it, with its kernels: the conv models load none of it.
TOKEN_MODELS = ("BlockDiffusionMoE", "LatentAttentionMoE")
KNOWN_MODELS = ("GIN", "PNA", "GAT", "MFC", "CGCNN", "SAGE", "SchNet") + TOKEN_MODELS


@dataclasses.dataclass(frozen=True, eq=True)
class ModelConfig:
    """Static (hashable) model configuration; a Flax module attribute."""

    model_type: str
    input_dim: int
    hidden_dim: int
    output_dim: Tuple[int, ...]
    output_type: Tuple[str, ...]  # each "graph" | "node"
    output_names: Tuple[str, ...]
    task_weights: Tuple[float, ...]
    num_conv_layers: int = 16
    loss_function_type: str = "mse"
    # graph-head config (reference config_heads["graph"])
    graph_num_sharedlayers: int = 0
    graph_dim_sharedlayers: int = 0
    graph_num_headlayers: int = 0
    graph_dim_headlayers: Tuple[int, ...] = ()
    # node-head config (reference config_heads["node"])
    node_num_headlayers: int = 0
    node_dim_headlayers: Tuple[int, ...] = ()
    node_head_type: str = "mlp"  # mlp | mlp_per_node | conv
    num_nodes: Optional[int] = None  # required for mlp_per_node
    # edge features
    edge_dim: Optional[int] = None
    # model-specific knobs
    gat_heads: int = 6
    gat_negative_slope: float = 0.05
    dropout: float = 0.25
    max_neighbours: Optional[int] = None  # MFC max_degree
    pna_avg_deg_lin: float = 1.0
    pna_avg_deg_log: float = 1.0
    num_gaussians: Optional[int] = None
    num_filters: Optional[int] = None
    radius: Optional[float] = None
    # SchNet: rebuild the interaction graph inside the forward pass from
    # positions (the reference's RadiusInteractionGraph, SCFStack.py:63-76)
    # instead of consuming host-precomputed edges. Static-shape neighbor
    # search; see ops/dynamic_radius.py for the O(N^2) trade.
    inforward_radius: bool = False
    freeze_conv: bool = False
    initial_bias: Optional[float] = None
    # Architecture.fused_conv (default on): run each conv layer's
    # gather -> edge-network -> scatter chain as ONE Pallas kernel
    # where the backend/knob support it (ops/fused_conv.py); layers
    # fall back to the composed segment-op paths elsewhere, so the
    # knob only ever selects between numerically-matching paths.
    fused_conv: bool = True
    # Architecture.conv_bf16 (default off): stream the conv hot path's
    # activation bytes (x, gathered sender windows, receiver tables,
    # per-edge scale) in bfloat16 with f32 MXU accumulation — halves
    # the dominant HBM traffic on the bandwidth-bound profile
    # (docs/PERF.md r08). Params and the inter-layer BN+relu stream
    # stay f32; numerics are tolerance-bounded vs the f32 path
    # (tests/test_conv_traffic.py pins the bound).
    conv_bf16: bool = False
    # Architecture.conv_residency (default off): opt IN to the
    # multi-layer VMEM-resident conv stack (ops/fused_conv.py:
    # fused_conv_stack) where a consumer can use it. The chassis
    # encoder interleaves MaskedBatchNorm between conv layers, which
    # breaks cross-layer residency by construction — the knob is
    # threaded for external/headless stacks and recorded in the flight
    # manifest; docs/PERF.md r08 documents the VMEM-budget decision
    # rule and this limitation honestly.
    conv_residency: bool = False
    # SyncBatchNorm equivalent: name of the mapped device axis to psum
    # batch statistics over (reference: SyncBatchNorm convert,
    # hydragnn/utils/distributed.py:227-228). None = per-device stats,
    # matching DDP's default non-synced BatchNorm.
    bn_axis_name: Optional[str] = None
    # The token stack (models/token_stack.py; hidden_dim and
    # num_conv_layers are its width and depth): grouped-query attention
    # under the block-diffusion mask, a mixture of experts of which
    # ``experts_held`` from ``expert_offset`` live here, the vocabulary held.
    num_attention_heads: Optional[int] = None
    num_key_value_heads: Optional[int] = None
    head_dim: Optional[int] = None
    num_experts: Optional[int] = None
    num_experts_per_tok: Optional[int] = None
    moe_intermediate_size: Optional[int] = None
    experts_held: Optional[int] = None
    expert_offset: int = 0
    vocab_size: Optional[int] = None
    rope_theta: float = 1e6
    rms_norm_eps: float = 1e-6
    block_length: int = 4
    # LatentAttentionMoE (models/token_stack.py:LatentStack), next-token
    # training: latent attention (queries through a ``q_lora_rank`` latent,
    # keys and values through a ``kv_lora_rank`` latent; heads score at
    # ``qk_nope_head_dim + qk_rope_head_dim`` and carry values of
    # ``v_head_dim``; rotary embedding over the rope dimensions only, its
    # pairs interleaved), ``first_k_dense_replace`` leading dense layers of
    # width ``intermediate_size``, then expert layers with
    # ``n_shared_experts`` shared experts beside the routed ones; and
    # ``num_nextn_predict_layers`` more prediction depths (one vocabulary
    # head each, behind the main one). The expert layer's router (both
    # stacks): ``scoring_func`` "softmax" or "sigmoid" over all experts, the
    # chosen experts' scores renormalised to sum 1 and multiplied by
    # ``routed_scaling_factor``; with ``bias_update_speed`` > 0 a balancing
    # bias (``batch_stats``) joins the scores for the choice only and moves
    # by that much against each expert's load after every train step.
    q_lora_rank: Optional[int] = None
    kv_lora_rank: Optional[int] = None
    qk_nope_head_dim: Optional[int] = None
    qk_rope_head_dim: Optional[int] = None
    v_head_dim: Optional[int] = None
    first_k_dense_replace: int = 0
    intermediate_size: Optional[int] = None
    n_shared_experts: int = 0
    scoring_func: str = "softmax"
    routed_scaling_factor: float = 1.0
    bias_update_speed: float = 0.0
    num_nextn_predict_layers: int = 0

    @property
    def is_token_stack(self) -> bool:
        return self.model_type in TOKEN_MODELS

    @property
    def token_objective(self) -> Optional[str]:
        """``"block_diffusion"`` (a noised and a clean copy of every
        document, the loss over the noised copy's tokens) or
        ``"next_token"`` (one copy, each head's loss a mean over the rows
        that have its target); None for the conv models."""
        if not self.is_token_stack:
            return None
        return "block_diffusion" if self.model_type == "BlockDiffusionMoE" else "next_token"

    @property
    def has_batch_norm(self) -> bool:
        """Whether the stack holds BatchNorm statistics, which the end of
        training recalibrates (train/loop.py)."""
        return not self.is_token_stack

    def manifest_block(self, rows: int) -> Dict[str, Any]:
        """What the flight record's manifest says of the model beyond its
        ``config``, for a train batch of ``rows`` node slots: nothing for
        the conv models."""
        if not self.is_token_stack:
            return {}
        from hydragnn_tpu.models.token_stack import manifest_block

        return manifest_block(self, rows)

    def epoch_counters(self, train_samples) -> Optional[Callable[[Any], Dict[str, Any]]]:
        """``batch_stats -> {name: value}`` for the flight record's ``epoch``
        event, where the stack counts something of its own; else None."""
        if not self.is_token_stack:
            return None
        from hydragnn_tpu.models.token_stack import epoch_counters

        return epoch_counters(self, train_samples)

    def _check_token_stack(self) -> None:
        need = ["num_attention_heads", "num_experts", "num_experts_per_tok", "moe_intermediate_size",
                "experts_held", "vocab_size"]
        if self.model_type == "BlockDiffusionMoE":
            need += ["num_key_value_heads", "head_dim"]
            heads = 1
        else:
            need += ["q_lora_rank", "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim"]
            need += ["intermediate_size"] if self.first_k_dense_replace else []
            heads = 1 + self.num_nextn_predict_layers
        missing = [k for k in need if getattr(self, k) is None]
        if missing:
            raise ValueError(f"{self.model_type} requires Architecture keys {missing}")
        if self.output_type != ("node",) * heads or self.loss_function_type != "cross_entropy":
            raise ValueError(
                f"{self.model_type} has {heads} node head(s) over the vocabulary with "
                'Training.loss_function_type "cross_entropy"; got heads '
                f"{self.output_type} and {self.loss_function_type!r}"
            )
        if self.model_type == "BlockDiffusionMoE" and self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("num_attention_heads must be a multiple of num_key_value_heads")
        if not 0 <= self.expert_offset <= self.num_experts - self.experts_held:
            raise ValueError(
                f"experts {self.expert_offset}..{self.expert_offset + self.experts_held} "
                f"are not among the router's {self.num_experts}"
            )
        if self.scoring_func not in ("softmax", "sigmoid"):
            raise ValueError(f'scoring_func is "softmax" or "sigmoid", got {self.scoring_func!r}')
        if not 0 <= self.first_k_dense_replace < self.num_conv_layers:
            raise ValueError("first_k_dense_replace must leave at least one expert layer")

    def __post_init__(self):
        if self.model_type not in KNOWN_MODELS:
            raise ValueError(f"Unknown model_type: {self.model_type}")
        if self.is_token_stack:
            self._check_token_stack()
        if len(self.output_dim) != len(self.output_type) or len(self.output_dim) != len(
            self.output_names
        ):
            raise ValueError("output_dim/output_type/output_names length mismatch")
        if len(self.task_weights) != len(self.output_dim):
            raise ValueError(
                "Inconsistent number of loss weights and tasks: "
                f"{len(self.task_weights)} VS {len(self.output_dim)}"
            )
        if self.node_head_type == "mlp_per_node" and not self.num_nodes:
            raise ValueError("num_nodes must be positive integer for mlp_per_node")
        if self.inforward_radius and (self.radius is None or self.max_neighbours is None):
            # an implicit cap default would silently diverge from the
            # (uncapped-by-default) host pipeline's edge set
            raise ValueError(
                "radius_graph_in_forward requires explicit radius and max_neighbours"
            )
        if self.model_type == "CGCNN" and self.hidden_dim != self.input_dim:
            raise ValueError("CGCNN preserves width: hidden_dim must equal input_dim")
        if self.model_type == "CGCNN" and self.node_head_type == "conv" and "node" in self.output_type:
            raise ValueError("CGCNN does not support conv-type node heads")

    @property
    def num_heads(self) -> int:
        return len(self.output_dim)

    @property
    def normalized_weights(self) -> Tuple[float, ...]:
        total = sum(abs(w) for w in self.task_weights)
        return tuple(w / total for w in self.task_weights)

    @property
    def use_edge_attr(self) -> bool:
        return self.edge_dim is not None and self.edge_dim > 0

    @property
    def encoder_out_dim(self) -> int:
        return self.hidden_dim


class HydraModel(nn.Module):
    """Encoder + multi-head decoder. Forward returns one output per head:
    [G, dim] for graph heads, [N, dim] for node heads (matching the
    reference forward contract, Base.py:244-275)."""

    cfg: ModelConfig

    def _make_conv(self, out_dim: int, concat: bool = True, name: Optional[str] = None) -> nn.Module:
        cfg = self.cfg
        mt = cfg.model_type
        if mt == "GIN":
            return C.GINConv(out_dim, name=name)
        if mt == "SAGE":
            return C.SAGEConv(out_dim, name=name)
        if mt == "MFC":
            if cfg.max_neighbours is None:
                raise ValueError("MFC requires max_neighbours")
            return C.MFConv(out_dim, max_degree=cfg.max_neighbours, name=name)
        if mt == "CGCNN":
            return C.CGConv(out_dim, name=name)
        if mt == "PNA":
            return C.PNAConv(
                out_dim,
                avg_deg_lin=cfg.pna_avg_deg_lin,
                avg_deg_log=cfg.pna_avg_deg_log,
                edge_dim=cfg.edge_dim,
                name=name,
            )
        if mt == "GAT":
            return C.GATv2Conv(
                out_dim,
                heads=cfg.gat_heads,
                negative_slope=cfg.gat_negative_slope,
                dropout=cfg.dropout,
                concat=concat,
                name=name,
            )
        if mt == "SchNet":
            if not (cfg.num_gaussians and cfg.num_filters and cfg.radius):
                raise ValueError(
                    "SchNet requires num_gaussians, num_filters, and radius"
                )
            return C.CFConv(
                out_dim,
                num_filters=cfg.num_filters,
                num_gaussians=cfg.num_gaussians,
                cutoff=cfg.radius,
                name=name,
            )
        raise ValueError(mt)

    def _conv_args(self, batch: GraphBatch) -> C.EdgeContext:
        """Build the EdgeContext (reference: Base._conv_args Base.py:111-115
        and SCFStack._conv_args SCFStack.py:63-76)."""
        cfg = self.cfg
        edge_attr = batch.edge_attr if cfg.use_edge_attr else None
        edge_weight = None
        if cfg.model_type == "SchNet":
            if cfg.inforward_radius:
                if batch.pos is None:
                    raise ValueError(
                        "radius_graph_in_forward requires node positions; "
                        "this batch has pos=None"
                    )
                # in-forward interaction graph (reference: SCFStack.py:74
                # RadiusInteractionGraph) — nearest-K within the cutoff,
                # rebuilt from positions on every forward
                from hydragnn_tpu.ops.dynamic_radius import radius_graph_in_forward

                if batch.pos.shape[0] > 20_000:
                    # trace-time (static shape): the builder computes an
                    # all-pairs O(N_pad^2) distance matrix — molecular
                    # batches only; supercell-scale pads would allocate
                    # gigabytes in HBM before XLA fails opaquely
                    import warnings

                    warnings.warn(
                        "radius_graph_in_forward is O(N_pad^2): node pad "
                        f"{batch.pos.shape[0]} implies ~"
                        f"{batch.pos.shape[0] ** 2 * 12 / 1e9:.1f} GB of "
                        "pairwise temporaries (the [N,N,3] displacement "
                        "tensor dominates); precompute edges on host for "
                        "graphs this large "
                        "(Architecture.radius_graph_in_forward=false)",
                        RuntimeWarning,
                        stacklevel=2,
                    )

                senders, receivers, edge_weight, edge_mask = radius_graph_in_forward(
                    batch.pos,
                    batch.node_graph,
                    batch.node_mask,
                    cfg.radius,
                    cfg.max_neighbours,
                )
                edge_attr = C.gaussian_smearing(
                    edge_weight, 0.0, cfg.radius, cfg.num_gaussians
                )
                return C.EdgeContext(
                    senders=senders,
                    receivers=receivers,
                    edge_mask=edge_mask,
                    node_mask=batch.node_mask,
                    edge_attr=edge_attr,
                    edge_weight=edge_weight,
                    fused_conv=cfg.fused_conv,
                    conv_bf16=cfg.conv_bf16,
                    # in-forward edges are rebuilt per step with their
                    # own mask layout; no host occupancy bound applies
                )
            if cfg.use_edge_attr and batch.edge_attr is not None:
                edge_weight = jnp.linalg.norm(batch.edge_attr, axis=-1)
            elif batch.pos is not None:
                # The reference recomputes a radius interaction graph in the
                # forward pass (SCFStack.py:74). Dynamic neighbor search does
                # not jit; the data pipeline already builds the same radius
                # graph, so distances over the provided edges are equivalent.
                diff = batch.pos[batch.receivers] - batch.pos[batch.senders]
                edge_weight = jnp.linalg.norm(diff, axis=-1)
            else:
                raise ValueError("SchNet requires edge_attr or node positions")
            edge_attr = C.gaussian_smearing(
                edge_weight, 0.0, cfg.radius, cfg.num_gaussians
            )
        return C.EdgeContext(
            senders=batch.senders,
            receivers=batch.receivers,
            edge_mask=batch.edge_mask,
            node_mask=batch.node_mask,
            edge_attr=edge_attr,
            edge_weight=edge_weight,
            # argsort(senders), reused by every layer's sender-gather
            # backward (convs._gather_senders) — the sorted segment sum
            # beats XLA's unsorted scatter-add ~2x at flagship shapes.
            # The loader precomputes it on host (graph/batch.py) because
            # the in-step argsort is a serial row-bound op (~ms at
            # E=699k); recompute only for externally-built batches.
            sender_perm=(
                batch.sender_perm
                if batch.sender_perm is not None
                else jnp.argsort(batch.senders)
            ),
            in_degree=(
                batch.in_degree
                if batch.in_degree is not None
                else C.sorted_in_degree(batch.receivers, batch.num_nodes)
            ),
            dense_senders=batch.dense_senders,
            dense_mask=batch.dense_mask,
            dense_edge_attr=(
                batch.dense_edge_attr.reshape(-1, batch.dense_edge_attr.shape[-1])
                if batch.dense_edge_attr is not None
                else None
            ),
            dense_sender_perm=(
                batch.dense_sender_perm
                if batch.dense_sender_perm is not None
                else (
                    jnp.argsort(batch.dense_senders.reshape(-1))
                    if batch.dense_senders is not None
                    else None
                )
            ),
            sender_win=batch.sender_win,
            dense_sender_win=batch.dense_sender_win,
            edge_occ=batch.edge_occupancy,
            run_align=batch.run_align,
            fused_conv=cfg.fused_conv,
            conv_bf16=cfg.conv_bf16,
        )

    def _apply_conv(self, conv, x, ctx, train: bool):
        if isinstance(conv, C.GATv2Conv):
            return conv(x, ctx, deterministic=not train)
        return conv(x, ctx)

    @nn.compact
    def __call__(
        self,
        batch: GraphBatch,
        train: bool = False,
        bn_train: Optional[bool] = None,
    ) -> List[jnp.ndarray]:
        """``train`` drives dropout; ``bn_train`` (default = ``train``)
        drives BatchNorm batch-vs-running statistics separately, so
        BatchNorm recalibration can run batch-stats forward passes with
        dropout off (hydragnn_tpu/train/state.py:make_stats_step)."""
        cfg = self.cfg
        if cfg.is_token_stack:
            from hydragnn_tpu.models.token_stack import LatentStack, TokenStack

            if cfg.model_type == "LatentAttentionMoE":
                return LatentStack(cfg, name="tokens")(batch)
            return [TokenStack(cfg, name="tokens")(batch)]
        bn = train if bn_train is None else bn_train
        ctx = self._conv_args(batch)
        x = batch.nodes
        n = x.shape[0]

        # ---- encoder: conv -> BN -> ReLU, x num_conv_layers ----
        # GAT widens hidden layers by `heads` with concat=True except the
        # last layer (reference: GATStack._init_conv GATStack.py:35-46).
        is_gat = cfg.model_type == "GAT"
        for layer in range(cfg.num_conv_layers):
            last = layer == cfg.num_conv_layers - 1
            concat = not last if is_gat else True
            width = cfg.hidden_dim
            bn_width = (
                cfg.hidden_dim * cfg.gat_heads if (is_gat and not last) else cfg.hidden_dim
            )
            # Explicit names make the encoder stack addressable by the
            # optimizer's freeze_conv mask (reference: Base._freeze_conv
            # Base.py:117-121 freezes self.convs only, not batch norms).
            conv = self._make_conv(width, concat=concat, name=f"conv_{layer}")
            x = self._apply_conv(conv, x, ctx, train)
            x = MaskedBatchNorm(bn_width, axis_name=cfg.bn_axis_name)(x, mask=batch.node_mask, train=bn)
            x = nn.relu(x)

        # ---- masked global mean pool (reference: Base.py:256-258) ----
        x_graph = S.segment_mean(
            x, batch.node_graph, batch.num_graphs, mask=batch.node_mask
        )

        # ---- decoders ----
        outputs: List[jnp.ndarray] = []
        graph_shared = None
        if "graph" in cfg.output_type:
            dims = (cfg.graph_dim_sharedlayers,) * cfg.graph_num_sharedlayers
            graph_shared = MLP(dims, relu_last=True, name="graph_shared")(x_graph)

        for ihead in range(cfg.num_heads):
            if cfg.output_type[ihead] == "graph":
                dims = tuple(cfg.graph_dim_headlayers[: cfg.graph_num_headlayers]) + (
                    cfg.output_dim[ihead],
                )
                outputs.append(MLP(dims, name=f"graph_head_{ihead}")(graph_shared))
            else:
                outputs.append(self._node_head(ihead, x, batch, ctx, train, bn))
        return outputs

    def _node_head(self, ihead, x, batch: GraphBatch, ctx, train: bool, bn: Optional[bool] = None):
        bn = train if bn is None else bn
        cfg = self.cfg
        nht = cfg.node_head_type
        dims_hidden = tuple(cfg.node_dim_headlayers[: cfg.node_num_headlayers])
        out_dim = cfg.output_dim[ihead]
        if nht == "mlp":
            return MLP(dims_hidden + (out_dim,), name=f"node_head_{ihead}")(x)
        if nht == "mlp_per_node":
            return PerNodeMLP(
                num_nodes=cfg.num_nodes,
                hidden_dims=dims_hidden,
                out_dim=out_dim,
                name=f"node_head_{ihead}",
            )(x, batch)
        if nht == "conv":
            # conv head: hidden convs + BN + ReLU, then output conv + BN
            # (reference: Base._init_node_conv Base.py:130-163).
            is_gat = cfg.model_type == "GAT"
            h = x
            for li, dim in enumerate(dims_hidden):
                conv = self._make_conv(dim, concat=True)
                bn_width = dim * cfg.gat_heads if is_gat else dim
                h = self._apply_conv(conv, h, ctx, train)
                h = MaskedBatchNorm(bn_width, axis_name=cfg.bn_axis_name)(h, mask=batch.node_mask, train=bn)
                h = nn.relu(h)
            conv = self._make_conv(out_dim, concat=False)
            h = self._apply_conv(conv, h, ctx, train)
            h = MaskedBatchNorm(out_dim, axis_name=cfg.bn_axis_name)(h, mask=batch.node_mask, train=bn)
            return h
        raise ValueError(
            f"Unknown head NN structure for node features {nht}; currently only "
            "support 'mlp', 'mlp_per_node' or 'conv'"
        )

    # ---- loss (reference: Base.loss_hpweighted Base.py:304-321) ----

    def graph_loss(
        self, outputs: List[jnp.ndarray], batch: GraphBatch
    ) -> Tuple[jnp.ndarray, List[jnp.ndarray]]:
        return model_loss(self.cfg, outputs, batch)


class PerNodeMLP(nn.Module):
    """One MLP per intra-graph node position (reference: MLPNode with
    ``mlp_per_node``, Base.py:327-375). Requires every graph to have
    exactly ``num_nodes`` nodes. Implemented as stacked per-position
    weights gathered by node position — a batched matmul, no Python loop."""

    num_nodes: int
    hidden_dims: Tuple[int, ...]
    out_dim: int

    @nn.compact
    def __call__(self, x: jnp.ndarray, batch: GraphBatch) -> jnp.ndarray:
        n = x.shape[0]
        starts = jnp.concatenate(
            [jnp.zeros((1,), jnp.int32), jnp.cumsum(batch.n_node)[:-1].astype(jnp.int32)]
        )
        pos = jnp.arange(n, dtype=jnp.int32) - starts[batch.node_graph]
        pos = jnp.clip(pos, 0, self.num_nodes - 1)

        dims = (x.shape[1],) + tuple(self.hidden_dims) + (self.out_dim,)
        init = nn.initializers.lecun_normal()
        h = x
        for li in range(len(dims) - 1):
            w = self.param(f"w_{li}", init, (self.num_nodes, dims[li], dims[li + 1]))
            b = self.param(f"b_{li}", nn.initializers.zeros, (self.num_nodes, dims[li + 1]))
            h = jnp.einsum("ni,nio->no", h, w[pos]) + b[pos]
            if li < len(dims) - 2:
                h = nn.relu(h)
        return h


def masked_loss(
    kind: str, pred: jnp.ndarray, target: jnp.ndarray, mask: jnp.ndarray
) -> jnp.ndarray:
    """Masked mean-reduced loss, matching the reference's selection
    (reference: hydragnn/utils/model.py loss_function_selection)."""
    m = mask.astype(pred.dtype)[:, None]
    denom = jnp.maximum(m.sum() * pred.shape[1], 1.0)
    diff = (pred - target) * m
    if kind == "mse":
        return (diff * diff).sum() / denom
    if kind == "mae":
        return jnp.abs(diff).sum() / denom
    if kind == "rmse":
        return jnp.sqrt((diff * diff).sum() / denom)
    raise ValueError(f"Unknown loss function type: {kind}")


def token_cross_entropy(logp: jnp.ndarray, weight: jnp.ndarray, mask: jnp.ndarray, counted: jnp.ndarray) -> jnp.ndarray:
    """``-sum(weight * log p(target))`` over the real rows, divided by the
    number of real rows that are ``counted`` (block diffusion: the tokens of
    the step, one copy's rows; next-token training: the rows that have the
    head's target). ``logp`` [N] is the vocabulary head's first column; a
    row of weight 0 (unmasked, clean copy, past a document's end, padding)
    adds nothing, and its ``logp`` is not looked at (so a NaN there stays
    out)."""
    w = jnp.where(mask, weight, 0.0)
    tokens = jnp.maximum((mask & counted).sum().astype(jnp.float32), 1.0)
    return -jnp.where(w > 0, w * logp, 0.0).sum() / tokens


def model_loss(
    cfg: ModelConfig, outputs: List[jnp.ndarray], batch: GraphBatch
) -> Tuple[jnp.ndarray, List[jnp.ndarray]]:
    """Weighted multi-task loss over masked heads
    (reference: Base.loss_hpweighted Base.py:304-321)."""
    weights = cfg.normalized_weights
    tasks_loss = []
    total = 0.0
    for ihead in range(cfg.num_heads):
        name = cfg.output_names[ihead]
        if cfg.output_type[ihead] == "graph":
            target = batch.graph_targets[name]
            mask = batch.graph_mask
        else:
            target = batch.node_targets[name]
            mask = batch.node_mask
        if cfg.loss_function_type == "cross_entropy":
            # integer targets and a weight a node (data/tokens.py); the head
            # has already picked the target's log-probability
            if cfg.output_type[ihead] != "node" or name + "_weight" not in batch.node_targets:
                raise ValueError(
                    f'head {name!r}: "cross_entropy" is for a node head over a vocabulary '
                    f"whose batch carries node_targets[{name + '_weight'!r}]"
                )
            logp = outputs[ihead][:, 0]
            weight = batch.node_targets[name + "_weight"][:, 0]
            if cfg.token_objective == "block_diffusion":
                counted = batch.nodes[:, COPY] == 1
            else:
                counted = weight > 0
            head_loss = token_cross_entropy(logp, weight, mask, counted)
        elif cfg.loss_function_type in ("mse", "mae", "rmse"):
            head_loss = masked_loss(cfg.loss_function_type, outputs[ihead], target, mask)
        else:
            raise ValueError(f"head {name!r}: unknown loss function type {cfg.loss_function_type!r}")
        tasks_loss.append(head_loss)
        total = total + weights[ihead] * head_loss
    return total, tasks_loss


def cast_floats(tree, dtype):
    """Cast float32 leaves to ``dtype`` (ints/bools untouched)."""
    return jax.tree_util.tree_map(
        lambda x: x.astype(dtype)
        if hasattr(x, "dtype") and x.dtype == jnp.float32
        else x,
        tree,
    )


def train_loss_closure(model: HydraModel, compute_dtype, batch_stats, batch: GraphBatch, dropout_rng):
    """``params -> (loss, (tasks[H], mutated))``: the train-mode forward
    (mixed-precision casts, dropout rng, BatchNorm statistics as mutated
    output) and the weighted multi-task loss. The ONE forward/loss
    closure: every step body of ``train/state.py`` differentiates it, and
    the diagnosed step and the diagnostics observer linearise it
    (``obs/introspect.py:linearize_heads``)."""

    def loss_fn(params):
        if compute_dtype is not None:
            apply_params = cast_floats(params, compute_dtype)
            apply_batch = cast_floats(batch, compute_dtype)
        else:
            apply_params, apply_batch = params, batch
        outputs, mutated = model.apply(
            {"params": apply_params, "batch_stats": batch_stats},
            apply_batch,
            train=True,
            mutable=["batch_stats"],
            rngs={"dropout": dropout_rng},
        )
        # loss in f32 against the ORIGINAL (uncast) targets
        outputs = [o.astype(jnp.float32) for o in outputs]
        total, tasks = model_loss(model.cfg, outputs, batch)
        return total, (jnp.stack(tasks), mutated)

    return loss_fn
