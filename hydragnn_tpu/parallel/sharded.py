"""Data-parallel (ZeRO-1 / FSDP-capable) train/eval steps over a device mesh.

TPU-native replacement for DDP (reference: hydragnn/utils/distributed.py:
220-233 wraps the model; gradient all-reduce happens inside torch's
backward). Here the structure is explicit and compiler-friendly:

  - the loader yields batches with a leading device axis [D, ...] whose
    edge indices are LOCAL to each sub-batch (no cross-device gathers in
    the segment ops — the analog of each DDP rank owning its own graphs);
  - ``shard_map`` runs the per-device forward+backward; gradients are
    ``pmean``-ed over the batch axes (DDP's all-reduce, riding ICI);
  - BatchNorm running stats are ``pmean``-ed so the replicated state stays
    consistent (plain DDP keeps per-rank stats and saves rank 0's; the
    in-forward statistics stay per-device unless ``SyncBatchNorm`` sets
    ``bn_axis_name``, matching reference semantics);
  - the optimizer update runs under ``jit`` outside shard_map; the
    state layout is pinned by a sharding constraint: replicated by
    default, optimizer-state leaves sharded over the data axis with
    ``zero1=True`` (ZeRO stage 1 — XLA inserts the reduce-scatter /
    all-gather pattern; reference: ZeroRedundancyOptimizer,
    hydragnn/utils/optimizer.py:43-113), or an arbitrary caller-supplied
    layout via ``state_sharding_fn`` — how the ``Partitioner``
    (parallel/partitioner.py) threads its FSDP parameter+optimizer
    sharding through the SAME step.

The ``batch_axes`` parameter generalizes every step to composed meshes:
the batch's leading device axis shards over that tuple of mesh axes
(``("data",)`` classic DP; ``("data", "fsdp")`` under the Partitioner's
FSDP layout) and gradients/metrics reduce over all of them.
"""

from __future__ import annotations

import warnings
from typing import Any, Callable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from hydragnn_tpu.graph.batch import GraphBatch
from hydragnn_tpu.models.base import HydraModel, model_loss
from hydragnn_tpu.obs.spans import scope
from hydragnn_tpu.parallel.mesh import DATA_AXIS
from hydragnn_tpu.train.state import TrainState

from jax import shard_map

# graftsync: thread-safe=GIL-atomic one-way False->True latch; a race costs one duplicate warning
_warned_zero1_replicated = False


def _lead_spec(batch_axes: Sequence[str]):
    """PartitionSpec entry for the batch leading axis."""
    if not batch_axes:
        return None
    return batch_axes[0] if len(batch_axes) == 1 else tuple(batch_axes)


def _axes_arg(batch_axes: Sequence[str]):
    """axis_name argument for pmean/psum over the batch axes."""
    return batch_axes[0] if len(batch_axes) == 1 else tuple(batch_axes)


def _device_index(batch_axes: Sequence[str], mesh: Mesh) -> jnp.ndarray:
    """Flat per-device index over the batch axes (dropout decorrelation)."""
    idx = jnp.zeros((), jnp.int32)
    for a in batch_axes:
        idx = idx * mesh.shape[a] + jax.lax.axis_index(a)
    return idx


def _zero1_leaf_shardings(
    mesh: Mesh, opt_state, report: Optional[List[str]] = None
):
    """ZeRO-1 layout for the optimizer tree: leaves sharded on their
    first axis when it divides the data-axis size, else replicated —
    recording each non-scalar replicated fallback's path into ``report``
    (the silent-replication fix: the fallback is now observable)."""
    n = mesh.shape[DATA_AXIS]
    rep = NamedSharding(mesh, P())

    def leaf(path, x):
        if (
            hasattr(x, "ndim")
            and x.ndim >= 1
            and x.shape[0] > 0
            and x.shape[0] % n == 0
        ):
            return NamedSharding(mesh, P(DATA_AXIS))
        if report is not None and getattr(x, "ndim", 0) >= 1:
            report.append("opt_state" + jax.tree_util.keystr(path))
        return rep

    return jax.tree_util.tree_map_with_path(leaf, opt_state)


def _zero1_sharding(
    mesh: Mesh, state: TrainState, warn: bool = False
) -> TrainState:
    """Per-leaf shardings for the TrainState: params/batch_stats/rng
    replicated, optimizer-state leaves sharded on their first axis when it
    divides the data-axis size (ZeRO-1), else replicated. With
    ``warn=True`` (placement time, never inside a trace) a replicated
    fallback logs ONE loud rank-0 warning naming the leaf paths."""
    global _warned_zero1_replicated
    rep = NamedSharding(mesh, P())
    report: List[str] = []
    opt = _zero1_leaf_shardings(mesh, state.opt_state, report)
    if (
        warn
        and report
        and not _warned_zero1_replicated
        and jax.process_index() == 0
    ):
        _warned_zero1_replicated = True
        shown = ", ".join(report[:8]) + (", ..." if len(report) > 8 else "")
        warnings.warn(
            f"ZeRO-1: {len(report)} optimizer leaf(ves) have a first axis "
            f"not divisible by the data-axis size {mesh.shape[DATA_AXIS]} "
            f"and stay fully REPLICATED on every device: {shown}. Recorded "
            "in the flight manifest as parallel.replicated_leaves.",
            RuntimeWarning,
            stacklevel=3,
        )
    return TrainState(
        step=rep,
        params=jax.tree_util.tree_map(lambda _: rep, state.params),
        batch_stats=jax.tree_util.tree_map(lambda _: rep, state.batch_stats),
        opt_state=opt,
        rng=rep,
    )


def _replicated_state_sharding(mesh: Mesh, state: TrainState) -> TrainState:
    rep = NamedSharding(mesh, P())
    return jax.tree_util.tree_map(lambda _: rep, state)


def _state_sharding(
    mesh: Mesh,
    state: TrainState,
    zero1: bool,
    state_sharding_fn: Optional[Callable[[TrainState], TrainState]] = None,
    warn: bool = False,
) -> TrainState:
    """The run's state layout — single source of truth shared by initial
    placement and the per-step output constraint. ``state_sharding_fn``
    (the Partitioner's FSDP layout) overrides the built-in rules."""
    if state_sharding_fn is not None:
        return state_sharding_fn(state)
    if zero1:
        return _zero1_sharding(mesh, state, warn=warn)
    return _replicated_state_sharding(mesh, state)


def place_state(
    mesh: Mesh,
    state: TrainState,
    zero1: bool = False,
    state_sharding_fn: Optional[Callable[[TrainState], TrainState]] = None,
) -> TrainState:
    """Place a host-built TrainState onto the mesh with the chosen layout."""
    sh = _state_sharding(mesh, state, zero1, state_sharding_fn, warn=True)
    return jax.tree_util.tree_map(
        lambda x, s: jax.device_put(x, s), state, sh
    )


def make_sharded_train_step(
    model: HydraModel,
    tx: optax.GradientTransformation,
    mesh: Mesh,
    zero1: bool = False,
    compute_dtype=None,
    remat: bool = False,
    batch_axes: Tuple[str, ...] = (DATA_AXIS,),
    state_sharding_fn: Optional[Callable[[TrainState], TrainState]] = None,
) -> Callable[[TrainState, GraphBatch], Tuple[TrainState, jnp.ndarray, jnp.ndarray]]:
    """Jitted ``(state, batch[D-leading]) -> (state, loss, tasks)``.

    ``batch`` leaves carry a leading device axis equal to the product of
    the ``batch_axes`` mesh sizes (GraphLoader(device_stack=D) output).
    ``compute_dtype=jnp.bfloat16`` enables mixed precision exactly like
    the single-device step: bf16 forward/backward, f32 master params /
    grads / BN stats / loss. ``remat=True`` checkpoints the per-device
    forward (see train.state.make_train_step). ``state_sharding_fn``
    pins a caller-owned state layout (the Partitioner's FSDP sharding:
    params + optimizer leaves over the ``fsdp`` axis — XLA turns the
    replicated-in / sharded-out constraint pair into the all-gather /
    reduce-scatter FSDP pattern)."""
    from hydragnn_tpu.models.base import cast_floats

    axes = _axes_arg(batch_axes)
    lead = _lead_spec(batch_axes)

    def per_device_grads(params, batch_stats, dropout_rng, batch: GraphBatch):
        # Each device sees its own sub-batch (leading axis stripped by
        # shard_map's lead-axis in_spec).
        batch = jax.tree_util.tree_map(lambda x: x[0], batch)
        dropout_rng = jax.random.fold_in(
            dropout_rng, _device_index(batch_axes, mesh)
        )

        def loss_fn(p):
            if compute_dtype is not None:
                ap = cast_floats(p, compute_dtype)
                ab = cast_floats(batch, compute_dtype)
            else:
                ap, ab = p, batch
            outputs, mutated = model.apply(
                {"params": ap, "batch_stats": batch_stats},
                ab,
                train=True,
                mutable=["batch_stats"],
                rngs={"dropout": dropout_rng},
            )
            # loss in f32 against the original (uncast) targets
            outputs = [o.astype(jnp.float32) for o in outputs]
            total, tasks = model_loss(model.cfg, outputs, batch)
            return total, (jnp.stack(tasks), mutated)

        lf = jax.checkpoint(loss_fn) if remat else loss_fn
        (loss, (tasks, mutated)), grads = jax.value_and_grad(lf, has_aux=True)(
            params
        )
        # DDP-equivalent gradient mean over the batch axes (ICI collective).
        grads = jax.lax.pmean(grads, axes)
        new_stats = jax.lax.pmean(mutated["batch_stats"], axes)
        # Real-graph-weighted global loss for reporting.
        n = batch.graph_mask.sum().astype(jnp.float32)
        n_tot = jnp.maximum(jax.lax.psum(n, axes), 1.0)
        loss_g = jax.lax.psum(loss * n, axes) / n_tot
        tasks_g = jax.lax.psum(tasks * n, axes) / n_tot
        return grads, new_stats, loss_g, tasks_g

    sharded_grads = shard_map(
        per_device_grads,
        mesh=mesh,
        in_specs=(P(), P(), P(), P(lead)),
        out_specs=(P(), P(), P(), P()),
        check_vma=False,
    )

    def train_step(state: TrainState, batch: GraphBatch):
        rng, dropout_rng = jax.random.split(state.rng)
        grads, new_stats, loss, tasks = sharded_grads(
            state.params, state.batch_stats, dropout_rng, batch
        )
        with scope("optimizer"):
            updates, opt_state = tx.update(grads, state.opt_state, state.params)
            params = optax.apply_updates(state.params, updates)
        new_state = state.replace(
            step=state.step + 1,
            params=params,
            batch_stats=new_stats,
            opt_state=opt_state,
            rng=rng,
        )
        # Pin the documented layout (replicated, ZeRO-1, or the
        # Partitioner's FSDP sharding): without the constraint XLA may
        # propagate an input sharding into the updated params, which
        # both changes layout across steps (recompile + donation churn)
        # and leaves params unreadable from host code.
        new_state = jax.lax.with_sharding_constraint(
            new_state, _state_sharding(mesh, new_state, zero1, state_sharding_fn)
        )
        return new_state, loss, tasks

    return jax.jit(train_step, donate_argnums=(0,))


def make_sharded_stats_step(
    model: HydraModel, mesh: Mesh, batch_axes: Tuple[str, ...] = (DATA_AXIS,)
) -> Callable[[TrainState, GraphBatch], TrainState]:
    """Sharded BatchNorm recalibration (see train.state.make_stats_step):
    train-mode forward over the device mesh updating only the running
    statistics (psum-synchronized by the BN layer's axis_name)."""
    axes = _axes_arg(batch_axes)
    lead = _lead_spec(batch_axes)

    def per_device(params, batch_stats, batch: GraphBatch):
        batch = jax.tree_util.tree_map(lambda x: x[0], batch)
        # dropout off, BN in batch-stats mode (see make_stats_step)
        _, mutated = model.apply(
            {"params": params, "batch_stats": batch_stats},
            batch,
            train=False,
            bn_train=True,
            mutable=["batch_stats"],
        )
        return jax.lax.pmean(mutated["batch_stats"], axes)

    fn = shard_map(
        per_device,
        mesh=mesh,
        in_specs=(P(), P(), P(lead)),
        out_specs=P(),
        check_vma=False,
    )

    def bn_stats_step(state: TrainState, batch: GraphBatch):
        new_stats = fn(state.params, state.batch_stats, batch)
        return state.replace(batch_stats=new_stats)

    return jax.jit(bn_stats_step)


def make_sharded_eval_step(
    model: HydraModel,
    mesh: Mesh,
    with_outputs: bool = False,
    batch_axes: Tuple[str, ...] = (DATA_AXIS,),
) -> Callable[..., Any]:
    """Jitted sharded eval. With ``with_outputs`` the per-head outputs come
    back concatenated over devices ([D*G, d] / [D*N, d]) so the host-side
    ``test_epoch`` collection can flatten masks to match."""
    axes = _axes_arg(batch_axes)
    lead = _lead_spec(batch_axes)

    def per_device(params, batch_stats, batch: GraphBatch):
        batch = jax.tree_util.tree_map(lambda x: x[0], batch)
        outputs = model.apply(
            {"params": params, "batch_stats": batch_stats}, batch, train=False
        )
        loss, tasks = model_loss(model.cfg, outputs, batch)
        tasks = jnp.stack(tasks)
        n = batch.graph_mask.sum().astype(jnp.float32)
        n_tot = jnp.maximum(jax.lax.psum(n, axes), 1.0)
        loss_g = jax.lax.psum(loss * n, axes) / n_tot
        tasks_g = jax.lax.psum(tasks * n, axes) / n_tot
        if with_outputs:
            return loss_g, tasks_g, tuple(outputs)
        return loss_g, tasks_g

    out_specs: Any = (P(), P())
    if with_outputs:
        out_specs = (P(), P(), tuple(P(lead) for _ in range(model.cfg.num_heads)))

    fn = shard_map(
        per_device,
        mesh=mesh,
        in_specs=(P(), P(), P(lead)),
        out_specs=out_specs,
        check_vma=False,
    )

    def eval_step(state: TrainState, batch: GraphBatch):
        res = fn(state.params, state.batch_stats, batch)
        if with_outputs:
            loss, tasks, outputs = res
            return loss, tasks, list(outputs)
        return res

    if with_outputs:
        eval_step.__name__ = "eval_step_outputs"
    return jax.jit(eval_step)
