"""Edge-sharded message passing: one giant graph split across chips.

The reference cannot partition a single graph across ranks — a graph
must fit one device, and large-graph scaling is handled purely on the
data side (SURVEY §5: out-of-core reads, DDStore fetches). This module
is the TPU-native headroom beyond that parity point: the EDGE set of one
huge graph is sharded over the ``data`` mesh axis, every device computes
messages for its edge shard against replicated node features, reduces
them into per-node partials with a local segment-sum, and one ``psum``
over ICI combines the partials — the GNN analog of sequence-parallel
attention (partition the quadratic axis, all-reduce the contraction).

Memory per chip: O(E/D + N) instead of O(E + N); compute per chip:
O(E/D) message FLOPs. Works under ``jit`` with static shapes: pad the
edge list to a multiple of the mesh size and mask.
"""

from __future__ import annotations

from functools import partial
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from hydragnn_tpu.parallel.mesh import DATA_AXIS

from jax import shard_map


def shard_edges(
    senders: np.ndarray,
    receivers: np.ndarray,
    edge_data: Optional[np.ndarray],
    num_devices: int,
) -> Tuple[np.ndarray, np.ndarray, Optional[np.ndarray], np.ndarray]:
    """Host-side: pad the edge list to a multiple of ``num_devices`` and
    return (senders, receivers, edge_data, edge_mask) ready to place with
    a ``P(DATA_AXIS)`` sharding. Padding edges point at node 0 and are
    masked out."""
    e = senders.shape[0]
    e_pad = ((e + num_devices - 1) // num_devices) * num_devices
    pad = e_pad - e
    mask = np.concatenate([np.ones(e, bool), np.zeros(pad, bool)])
    senders = np.concatenate([senders, np.zeros(pad, senders.dtype)])
    receivers = np.concatenate([receivers, np.zeros(pad, receivers.dtype)])
    if edge_data is not None:
        edge_data = np.concatenate(
            [edge_data, np.zeros((pad,) + edge_data.shape[1:], edge_data.dtype)]
        )
    return senders, receivers, edge_data, mask


def edge_sharded_aggregate(
    mesh: Mesh,
    message_fn: Callable[..., jnp.ndarray],
    nodes: jnp.ndarray,
    senders: jnp.ndarray,
    receivers: jnp.ndarray,
    edge_mask: jnp.ndarray,
    edge_data: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """Aggregated messages [N, H] for one edge-sharded graph.

    ``message_fn(x_i, x_j[, edge_data]) -> [e_local, H]`` computes the
    per-edge messages on each device's shard; the result is the masked
    segment-sum over receivers, psum-combined across the mesh. ``nodes``
    is replicated; ``senders``/``receivers``/``edge_mask``/``edge_data``
    are sharded on their leading axis.
    """
    num_nodes = nodes.shape[0]
    has_edge_data = edge_data is not None

    def local(nodes, snd, rcv, msk, *ed):
        x_i = nodes[rcv]
        x_j = nodes[snd]
        msg = message_fn(x_i, x_j, *ed)
        msg = jnp.where(msk[:, None], msg, 0)
        part = jax.ops.segment_sum(msg, rcv, num_nodes)
        return jax.lax.psum(part, DATA_AXIS)

    in_specs = [P(), P(DATA_AXIS), P(DATA_AXIS), P(DATA_AXIS)]
    args = [nodes, senders, receivers, edge_mask]
    if has_edge_data:
        in_specs.append(P(DATA_AXIS))
        args.append(edge_data)

    fn = shard_map(
        local,
        mesh=mesh,
        in_specs=tuple(in_specs),
        out_specs=P(),
        check_vma=False,
    )
    return fn(*args)


def place_edge_shards(mesh: Mesh, *arrays):
    """Device-put edge arrays with leading-axis sharding over the mesh."""
    sh = NamedSharding(mesh, P(DATA_AXIS))
    return tuple(jax.device_put(a, sh) if a is not None else None for a in arrays)


def edge_axis_shardings(mesh: Mesh, batch):
    """Per-leaf shardings for a GraphBatch holding ONE giant graph:
    every leaf whose leading axis is the edge axis (senders, receivers,
    edge_attr, edge_mask) is sharded ``P(data)``; node/graph leaves stay
    replicated. Matching is a heuristic on the leading dim: node and edge
    pads MAY coincide, in which case node arrays get edge-style sharding
    too — that only changes layout (XLA inserts the gathers), never
    results."""
    e = batch.senders.shape[0]
    rep = NamedSharding(mesh, P())
    edge = NamedSharding(mesh, P(DATA_AXIS))

    def pick(x):
        if hasattr(x, "shape") and getattr(x, "ndim", 0) >= 1 and x.shape[0] == e:
            return edge
        return rep

    return jax.tree_util.tree_map(pick, batch)


def place_giant_batch(mesh: Mesh, batch):
    """Place one giant-graph batch with its edge arrays sharded over the
    mesh and everything else replicated. A plain jitted train/eval step
    over inputs placed this way is partitioned by XLA's SPMD pass: each
    device computes messages for its edge shard, the partial-aggregate
    all-reduce rides ICI, and gradients get the matching collectives
    automatically — the full-model generalization of
    :func:`edge_sharded_aggregate`, with no hand-written comm. Memory per
    chip: O(E/D) edge buffers + O(N) node buffers.

    The edge pad is rounded up to a mesh multiple first (a ``P(data)``
    placement requires divisibility); the extra slots are masked padding.

    The loader's local-window plans (``sender_win``/``dense_sender_win``)
    are stripped: they index GLOBAL edge positions, and the local-window
    kernel has no partitioning rule — the model then falls back to the
    sorted-permute path, whose ops all partition."""
    batch = batch.replace(sender_win=None, dense_sender_win=None)
    d = int(mesh.shape[DATA_AXIS])
    e = batch.senders.shape[0]
    if e % d:
        from hydragnn_tpu.graph.batch import pad_batch

        batch = pad_batch(
            batch,
            n_node=batch.nodes.shape[0],
            n_edge=((e + d - 1) // d) * d,
            n_graph=batch.graph_mask.shape[0],
        )
    return jax.device_put(batch, edge_axis_shardings(mesh, batch))


def _lead_entry(batch_axes):
    """PartitionSpec entry for the stacked batch's leading device axis."""
    if not batch_axes:
        return None
    return batch_axes[0] if len(batch_axes) == 1 else tuple(batch_axes)


def place_dp_edge_batch(mesh: Mesh, batch, batch_axes=(DATA_AXIS,)):
    """Place a device-stacked batch ([D_data, ...] leaves from
    ``GraphLoader(device_stack=D_data)``) on a composed mesh carrying an
    ``edge`` axis: axis 0 shards over the batch axes (``data``, or
    ``data × fsdp`` under the Partitioner); leaves whose SECOND axis is
    the edge axis additionally shard it over ``edge``. Companion of
    :func:`make_dp_edge_train_step`."""
    d_edge = int(mesh.shape["edge"])
    e = batch.senders.shape[1]
    if e % d_edge:
        raise ValueError(
            f"the edge-axis size ({d_edge}) must divide the stacked edge "
            f"pad ({e}); build the loader with edge_multiple={d_edge} "
            "(or a multiple of it)"
        )

    lead = _lead_entry(batch_axes)
    dp = NamedSharding(mesh, P(lead))
    dp_edge = NamedSharding(mesh, P(lead, "edge"))

    # Edge leaves are selected by GraphBatch field NAME, not by shape:
    # a node- or graph-axis leaf whose pad coincidentally equals the edge
    # pad must stay data-sharded only.
    import dataclasses as _dc

    edge_fields = {"senders", "receivers", "edge_mask", "edge_attr", "sender_perm"}
    shardings = {}
    for f in _dc.fields(batch):
        v = getattr(batch, f.name)
        if f.metadata.get("static"):
            # static pytree meta (run_align): pass the value through —
            # it is part of the treedef, not a shardable leaf
            shardings[f.name] = v
            continue
        sh = dp_edge if f.name in edge_fields else dp
        shardings[f.name] = jax.tree_util.tree_map(lambda _: sh, v)
    return jax.device_put(batch, type(batch)(**shardings))


def make_dp_edge_train_step(
    model, tx, mesh: Mesh, batch_axes=(DATA_AXIS,), state_sharding_fn=None
):
    """Data-parallel x edge-sharded training on a composed mesh carrying
    an ``edge`` axis: sub-batches vmap over the leading batch axis (each
    holding its own graphs) while every sub-batch's edge arrays shard
    over the edge axis — GSPMD partitions both (the giant-graph analog of
    composing DP with sequence parallelism). Parameters stay replicated
    by default; ``state_sharding_fn`` (the Partitioner's FSDP layout)
    pins an fsdp-sharded parameter/optimizer layout instead — GSPMD then
    all-gathers parameters into the vmapped forward and reduce-scatters
    the state update, composing edge sharding with FSDP.

    Returns jitted ``(state, batch[D_data-leading]) -> (state, loss,
    tasks)`` matching ``make_sharded_train_step``'s contract."""
    import optax

    from hydragnn_tpu.models.base import model_loss
    from hydragnn_tpu.ops.segment_pallas import xla_segment_ops

    from hydragnn_tpu.parallel.sharded import _state_sharding

    def train_step(state, batch):
        # this step vmaps the model over the data axis; the Pallas
        # segment ops' custom_partitioning wrapper has no vmap batching
        # rule, so trace the whole body on the XLA segment path (the
        # GSPMD giant-graph path — plain jit, no vmap — keeps the
        # kernel via its partitioning rule; see ops/segment_pallas.py)
        with xla_segment_ops():
            return _body(state, batch)

    def _body(state, batch):
        rng, dropout_rng = jax.random.split(state.rng)
        d_data = batch.graph_mask.shape[0]

        def loss_fn(params):
            def per_shard(batch_d, rng_d):
                outputs, mutated = model.apply(
                    {"params": params, "batch_stats": state.batch_stats},
                    batch_d,
                    train=True,
                    mutable=["batch_stats"],
                    rngs={"dropout": rng_d},
                )
                total, tasks = model_loss(model.cfg, outputs, batch_d)
                n = batch_d.graph_mask.sum().astype(jnp.float32)
                return total, jnp.stack(tasks), mutated["batch_stats"], n

            rngs = jax.random.split(dropout_rng, d_data)
            # axis_name binds SyncBatchNorm's psum, like shard_map's mesh
            losses, tasks, stats, ns = jax.vmap(
                per_shard, axis_name=DATA_AXIS
            )(batch, rngs)
            # Gradient target is the UNWEIGHTED mean over shards — the
            # shard_map step pmean's per-device grads (DDP semantics,
            # sharded.py); reported metrics stay real-graph-weighted.
            loss_grad = losses.mean()
            w = ns / jnp.maximum(ns.sum(), 1.0)
            loss_rep = (losses * w).sum()
            tasks_rep = (tasks * w[:, None]).sum(axis=0)
            new_stats = jax.tree_util.tree_map(lambda s: s.mean(axis=0), stats)
            return loss_grad, (loss_rep, tasks_rep, new_stats)

        (_, (loss, tasks, new_stats)), grads = jax.value_and_grad(
            loss_fn, has_aux=True
        )(state.params)
        updates, opt_state = tx.update(grads, state.opt_state, state.params)
        params = optax.apply_updates(state.params, updates)
        new_state = state.replace(
            step=state.step + 1,
            params=params,
            batch_stats=new_stats,
            opt_state=opt_state,
            rng=rng,
        )
        # pin the state layout (see sharded.py: without it the batch's
        # (data, edge) sharding can propagate into params, churning
        # layouts across donated steps); a caller-supplied layout (the
        # Partitioner's FSDP sharding) wins over the replicated default
        new_state = jax.lax.with_sharding_constraint(
            new_state,
            _state_sharding(
                mesh, new_state, zero1=False, state_sharding_fn=state_sharding_fn
            ),
        )
        return new_state, loss, tasks

    return jax.jit(train_step, donate_argnums=(0,))


def make_dp_edge_eval_step(model, mesh: Mesh, with_outputs: bool = False):
    """Eval companion of :func:`make_dp_edge_train_step`: the vmapped
    eval forward over the stacked batch axis, edge arrays sharded over
    the mesh's ``edge`` axis by the batch placement. With
    ``with_outputs`` the per-head outputs come back flattened over the
    device axis ([D*G, d] / [D*N, d]) so ``test_epoch``'s mask
    flattening aligns — the same contract as ``make_sharded_eval_step``."""
    import jax.numpy as _jnp

    from hydragnn_tpu.models.base import model_loss as _model_loss
    from hydragnn_tpu.ops.segment_pallas import xla_segment_ops

    def eval_step(state, batch):
        with xla_segment_ops():
            return _body(state, batch)

    def _body(state, batch):
        def per_shard(batch_d):
            outputs = model.apply(
                {"params": state.params, "batch_stats": state.batch_stats},
                batch_d,
                train=False,
            )
            loss, tasks = _model_loss(model.cfg, outputs, batch_d)
            n = batch_d.graph_mask.sum().astype(_jnp.float32)
            return loss, _jnp.stack(tasks), n, tuple(outputs)

        losses, tasks, ns, outputs = jax.vmap(per_shard, axis_name=DATA_AXIS)(
            batch
        )
        w = ns / _jnp.maximum(ns.sum(), 1.0)
        loss = (losses * w).sum()
        tasks = (tasks * w[:, None]).sum(axis=0)
        if with_outputs:
            flat = [o.reshape((-1,) + o.shape[2:]) for o in outputs]
            return loss, tasks, flat
        return loss, tasks

    if with_outputs:
        eval_step.__name__ = "eval_step_outputs"
    return jax.jit(eval_step)


def make_dp_edge_stats_step(model, mesh: Mesh):
    """BatchNorm-recalibration companion of
    :func:`make_dp_edge_train_step` (see train.state.make_stats_step):
    vmapped train-mode forward updating only the running statistics,
    averaged over the stacked sub-batches."""
    from hydragnn_tpu.ops.segment_pallas import xla_segment_ops

    def bn_stats_step(state, batch):
        with xla_segment_ops():
            def per_shard(batch_d):
                _, mutated = model.apply(
                    {"params": state.params, "batch_stats": state.batch_stats},
                    batch_d,
                    train=False,
                    bn_train=True,
                    mutable=["batch_stats"],
                )
                return mutated["batch_stats"]

            stats = jax.vmap(per_shard, axis_name=DATA_AXIS)(batch)
            new_stats = jax.tree_util.tree_map(lambda s: s.mean(axis=0), stats)
            return state.replace(batch_stats=new_stats)

    return jax.jit(bn_stats_step)


def edge_sharded_gin_layer(
    mesh: Mesh,
    nodes: jnp.ndarray,
    senders: jnp.ndarray,
    receivers: jnp.ndarray,
    edge_mask: jnp.ndarray,
    w1: jnp.ndarray,
    b1: jnp.ndarray,
    w2: jnp.ndarray,
    b2: jnp.ndarray,
    eps: float = 100.0,
) -> jnp.ndarray:
    """One GIN conv over an edge-sharded giant graph: the neighbor sum is
    computed edge-parallel; the (1+eps)x + sum MLP stays node-replicated
    (node count is the small axis by assumption). Demonstrates how a full
    conv composes with :func:`edge_sharded_aggregate`."""
    agg = edge_sharded_aggregate(
        mesh,
        lambda x_i, x_j: x_j,
        nodes,
        senders,
        receivers,
        edge_mask,
    )
    h = (1.0 + eps) * nodes + agg
    h = jax.nn.relu(h @ w1 + b1)
    return h @ w2 + b2
