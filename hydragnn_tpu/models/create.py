"""Model factory: config dict -> (HydraModel, initialized variables).

Mirrors the reference factory's dispatch and per-model requirements
(reference: hydragnn/models/create.py:29-214): PNA needs the train-set
degree histogram (create.py:104), MFC needs max_neighbours (create.py:142),
SchNet needs num_gaussians/num_filters/radius (create.py:188-190), GAT uses
heads=6 and negative_slope=0.05 (create.py:122-124). Parameters are
initialized from a fixed PRNG seed, the analog of the reference's
``torch.manual_seed(0)`` (create.py:83).
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from hydragnn_tpu.graph.batch import GraphBatch
from hydragnn_tpu.models.base import HydraModel, ModelConfig
from hydragnn_tpu.models.convs import avg_degree_stats


def model_config_from_dict(
    config: Dict[str, Any], bn_axis_name: Optional[str] = None
) -> ModelConfig:
    """Build a static ModelConfig from the reference-shaped config dict
    (the ``NeuralNetwork`` section, after update_config inference).

    ``bn_axis_name`` is the mapped device axis the caller will bind (via
    shard_map) — required for ``Architecture.SyncBatchNorm`` to take
    effect; it is ignored when the config does not request SyncBN
    (reference: SyncBatchNorm convert, hydragnn/utils/distributed.py:
    227-228, default injected at config_utils.py:82-83)."""
    arch = config["Architecture"]
    training = config.get("Training", {})
    heads_cfg = arch.get("output_heads", {})
    graph_cfg = heads_cfg.get("graph", {})
    node_cfg = heads_cfg.get("node", {})

    pna_lin, pna_log = 1.0, 1.0
    if arch.get("pna_deg") is not None:
        pna_lin, pna_log = avg_degree_stats(arch["pna_deg"])

    model_type = arch["model_type"]
    if arch.get("radius_graph_in_forward") and arch.get("periodic_boundary_conditions"):
        # the in-forward builder is plain Euclidean; silently dropping
        # cross-boundary images would train on physically wrong graphs
        raise ValueError(
            "radius_graph_in_forward does not support periodic_boundary_conditions; "
            "use host-precomputed edges for PBC datasets"
        )
    input_dim = int(arch["input_dim"])
    hidden_dim = int(arch["hidden_dim"])
    if model_type == "CGCNN":
        # CGCNN preserves width; hidden == input (reference CGCNNStack.py:30-40)
        hidden_dim = input_dim

    return ModelConfig(
        model_type=model_type,
        input_dim=input_dim,
        hidden_dim=hidden_dim,
        output_dim=tuple(int(d) for d in arch["output_dim"]),
        output_type=tuple(arch["output_type"]),
        output_names=tuple(config["Variables_of_interest"]["output_names"])
        if "Variables_of_interest" in config
        else tuple(f"head_{i}" for i in range(len(arch["output_dim"]))),
        task_weights=tuple(float(w) for w in arch["task_weights"]),
        num_conv_layers=int(arch["num_conv_layers"]),
        loss_function_type=training.get("loss_function_type", "mse"),
        graph_num_sharedlayers=int(graph_cfg.get("num_sharedlayers", 0)),
        graph_dim_sharedlayers=int(graph_cfg.get("dim_sharedlayers", 0)),
        graph_num_headlayers=int(graph_cfg.get("num_headlayers", 0)),
        graph_dim_headlayers=tuple(graph_cfg.get("dim_headlayers", ())),
        node_num_headlayers=int(node_cfg.get("num_headlayers", 0)),
        node_dim_headlayers=tuple(node_cfg.get("dim_headlayers", ())),
        node_head_type=node_cfg.get("type", "mlp"),
        num_nodes=arch.get("num_nodes"),
        edge_dim=arch.get("edge_dim"),
        max_neighbours=arch.get("max_neighbours"),
        pna_avg_deg_lin=pna_lin,
        pna_avg_deg_log=pna_log,
        num_gaussians=arch.get("num_gaussians"),
        num_filters=arch.get("num_filters"),
        radius=arch.get("radius"),
        inforward_radius=bool(arch.get("radius_graph_in_forward", False)),
        fused_conv=bool(arch.get("fused_conv", True)),
        conv_bf16=bool(arch.get("conv_bf16", False)),
        conv_residency=bool(arch.get("conv_residency", False)),
        freeze_conv=bool(arch.get("freeze_conv_layers", False)),
        initial_bias=arch.get("initial_bias"),
        bn_axis_name=bn_axis_name if arch.get("SyncBatchNorm") else None,
        **{
            key: arch[key]
            for key in (
                "num_attention_heads", "num_key_value_heads", "head_dim", "num_experts",
                "num_experts_per_tok", "moe_intermediate_size", "experts_held", "expert_offset",
                "vocab_size", "rope_theta", "rms_norm_eps", "block_length", "q_lora_rank", "kv_lora_rank",
                "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "first_k_dense_replace",
                "intermediate_size", "n_shared_experts", "scoring_func", "routed_scaling_factor",
                "bias_update_speed", "num_nextn_predict_layers",
            )
            if arch.get(key) is not None
        },
    )


def create_model_config(
    config: Dict[str, Any],
    example_batch: GraphBatch,
    seed: int = 0,
    verbosity: int = 0,
    bn_axis_name: Optional[str] = None,
) -> Tuple[HydraModel, Dict[str, Any]]:
    cfg = model_config_from_dict(config, bn_axis_name=bn_axis_name)
    return create_model(cfg, example_batch, seed=seed)


def create_model(
    cfg: ModelConfig, example_batch: GraphBatch, seed: int = 0
) -> Tuple[HydraModel, Dict[str, Any]]:
    """Instantiate and initialize; returns (model, variables) where
    variables = {'params': ..., 'batch_stats': ...}."""
    if cfg.model_type == "PNA" and cfg.pna_avg_deg_lin <= 0:
        raise AssertionError("PNA requires degree input.")
    if cfg.node_head_type == "mlp_per_node" and "node" in cfg.output_type:
        # mlp_per_node requires every graph to have exactly num_nodes nodes
        # (reference: Base.py:209-212 + node_features_reshape); validate on
        # the concrete example batch rather than silently clipping.
        import numpy as np

        n_node = np.asarray(example_batch.n_node)
        gmask = np.asarray(example_batch.graph_mask)
        if not np.all(n_node[gmask] == cfg.num_nodes):
            raise ValueError(
                "mlp_per_node requires every graph to have exactly "
                f"num_nodes={cfg.num_nodes} nodes; got {sorted(set(n_node[gmask]))}"
            )
    model = HydraModel(cfg)
    rngs = {"params": jax.random.PRNGKey(seed), "dropout": jax.random.PRNGKey(seed + 1)}
    if cfg.is_token_stack:
        # Under jit nothing of the forward pass survives (no initial value
        # hangs on it), so a stack of real size is initialised without being
        # run: its dense CPU attention path would not fit any host.
        variables = jax.jit(functools.partial(model.init, train=False))(rngs, example_batch)
    else:
        variables = model.init(rngs, example_batch, train=False)
    if cfg.initial_bias is not None:
        variables = _set_initial_bias(variables, cfg)
    return model, variables


def _set_initial_bias(variables, cfg: ModelConfig):
    """Fill the final bias of each graph head with a large initial value
    (UQ option; reference: Base._set_bias Base.py:123-128)."""
    import flax

    params = flax.core.unfreeze(variables["params"])
    for ihead in range(cfg.num_heads):
        if cfg.output_type[ihead] != "graph":
            continue
        head = params.get(f"graph_head_{ihead}")
        if head is None:
            continue
        last = sorted(
            (k for k in head if k.startswith("Dense_")), key=lambda k: int(k.split("_")[1])
        )[-1]
        head[last]["bias"] = jnp.full_like(head[last]["bias"], cfg.initial_bias)
    new_vars = dict(variables)
    new_vars["params"] = params
    return new_vars
