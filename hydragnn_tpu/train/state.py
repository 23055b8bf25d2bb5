"""Train state and jitted step functions.

The reference hot loop (hydragnn/train/train_validate_test.py:333-371) does
zero_grad -> head indexing -> H2D copy -> forward -> loss -> backward ->
step per batch. Here the whole step is ONE jitted function over a
``TrainState`` pytree: forward + weighted multi-task loss + grad + optax
update + BatchNorm running-stat update, compiled once (fixed batch shapes
come from the loader's pad plan). Head indexing does not exist — targets
are already a dict-of-heads on the batch.

The name of each function handed to ``jax.jit`` is its compiled program's
name (``jit_train_scan_epoch``, ``jit_train_step``, ``jit_eval_scan``,
``jit_eval_step``, ``jit_eval_step_outputs``, ``jit_bn_stats_step``,
``jit_diagnostics_step``; ``parallel/`` uses the same ones): a profiler trace's ``XLA Modules`` line
tells the programs apart by it, and ``benchmark/program_spans.py`` reads it.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from flax import struct

from hydragnn_tpu.graph.batch import GraphBatch
from hydragnn_tpu.models.base import HydraModel, model_loss, train_loss_closure
from hydragnn_tpu.obs.introspect import head_diagnostics, linearize_heads, make_diagnostics_step


class TrainState(struct.PyTreeNode):
    step: jnp.ndarray
    params: Any
    batch_stats: Any
    opt_state: Any
    rng: jnp.ndarray


def create_train_state(
    variables: Dict[str, Any], tx: optax.GradientTransformation, seed: int = 0
) -> TrainState:
    # The jitted step donates the state's buffers; copy so the caller's
    # ``variables`` stay usable after the first step (e.g. re-init paths).
    params = jax.tree_util.tree_map(jnp.copy, variables["params"])
    batch_stats = jax.tree_util.tree_map(jnp.copy, variables.get("batch_stats", {}))
    return TrainState(
        step=jnp.zeros((), jnp.int32),
        params=params,
        batch_stats=batch_stats,
        opt_state=tx.init(params),
        rng=jax.random.PRNGKey(seed),
    )


def create_eval_state(
    variables: Dict[str, Any], tx: optax.GradientTransformation, seed: int = 0
) -> TrainState:
    """TrainState with the full checkpoint SCHEMA but no device-side
    optimizer state: opt leaves are host zero-arrays shaped by
    ``jax.eval_shape(tx.init)``. Restoring a checkpoint for eval through
    this target never materializes the optimizer on any device — required
    for ZeRO-1-trained runs whose optimizer state cannot fit un-sharded."""
    import numpy as np

    params = jax.tree_util.tree_map(jnp.copy, variables["params"])
    batch_stats = jax.tree_util.tree_map(jnp.copy, variables.get("batch_stats", {}))
    opt_shapes = jax.eval_shape(tx.init, params)
    opt_state = jax.tree_util.tree_map(
        lambda s: np.zeros(s.shape, s.dtype), opt_shapes
    )
    return TrainState(
        step=jnp.zeros((), jnp.int32),
        params=params,
        batch_stats=batch_stats,
        opt_state=opt_state,
        rng=jax.random.PRNGKey(seed),
    )


def _land(state: TrainState, rng, loss, tasks, mutated, grads, updates, opt_state, consec=None):
    """The step's new state from its update. Without ``consec``:
    ``(state, loss, tasks)``. With it (the non-finite guard, the device
    half of ``hydragnn_tpu/resilience/sentry.py``): a cheap on-device
    ``isfinite(loss) & isfinite(global_norm(grads))`` check decides
    whether the update LANDS. A bad batch leaves params, optimizer state,
    BatchNorm statistics and the step counter at their previous values
    (one fused ``where`` over the state, no host sync), and the return is
    ``(state, loss, tasks, consec, bad)``: ``consec`` the consecutive-bad
    counter (int32 device scalar, threaded by the caller across steps),
    ``bad`` this step's flag as float32 (0.0/1.0); reported loss and task
    losses are zeroed on bad steps so the epoch's weighted metrics (which
    also zero the batch's count) stay clean."""
    params = optax.apply_updates(state.params, updates)
    if consec is None:
        new_state = state.replace(
            step=state.step + 1,
            params=params,
            batch_stats=mutated["batch_stats"],
            opt_state=opt_state,
            rng=rng,
        )
        return new_state, loss, tasks
    bad = jnp.logical_not(jnp.isfinite(loss) & jnp.isfinite(optax.global_norm(grads)))

    def keep(new, old):
        return jax.tree_util.tree_map(lambda a, b: jnp.where(bad, b, a), new, old)

    new_state = state.replace(
        step=state.step + jnp.where(bad, 0, 1).astype(state.step.dtype),
        params=keep(params, state.params),
        batch_stats=keep(mutated["batch_stats"], state.batch_stats),
        opt_state=keep(opt_state, state.opt_state),
        rng=rng,
    )
    return (
        new_state,
        jnp.where(bad, 0.0, loss),
        jnp.where(bad, jnp.zeros_like(tasks), tasks),
        jnp.where(bad, consec + 1, 0).astype(jnp.int32),
        bad.astype(jnp.float32),
    )


def _train_step_body(
    model: HydraModel,
    tx: optax.GradientTransformation,
    compute_dtype=None,
    remat: bool = False,
    guarded: bool = False,
) -> Callable[..., Tuple]:
    """The un-jitted per-batch training body shared by the jitted
    single-step path and the scan-over-epoch path: ``(state, batch) ->
    (state, loss, tasks)``, or ``guarded`` (:func:`_land`'s non-finite
    guard) ``(state, batch, consec) -> (state, loss, tasks, consec, bad)``;
    with all-finite inputs the two compute the same."""

    def step(state: TrainState, batch: GraphBatch, consec: Optional[jnp.ndarray]):
        rng, dropout_rng = jax.random.split(state.rng)
        loss_fn = train_loss_closure(model, compute_dtype, state.batch_stats, batch, dropout_rng)
        lf = jax.checkpoint(loss_fn) if remat else loss_fn
        (loss, (tasks, mutated)), grads = jax.value_and_grad(lf, has_aux=True)(
            state.params
        )
        updates, opt_state = tx.update(grads, state.opt_state, state.params)
        return _land(state, rng, loss, tasks, mutated, grads, updates, opt_state, consec)

    # two defs of one name: the name is the compiled program's (module docstring)
    if guarded:

        def train_step(state: TrainState, batch: GraphBatch, consec: jnp.ndarray):
            return step(state, batch, consec)

    else:

        def train_step(state: TrainState, batch: GraphBatch):
            return step(state, batch, None)

    return train_step


def make_train_step(
    model: HydraModel,
    tx: optax.GradientTransformation,
    compute_dtype=None,
    remat: bool = False,
    guard_nonfinite: bool = False,
    diagnostics: bool = False,
) -> Callable[..., Tuple]:
    """Returns jitted ``(state, batch) -> (state, loss, tasks_loss)``.

    ``compute_dtype=jnp.bfloat16`` enables mixed precision: params and
    batch features are cast to bf16 for the forward/backward (MXU-native
    on TPU), while the master params, optimizer state, BatchNorm
    statistics, and the loss stay float32.

    ``remat=True`` (config ``Training.remat``) checkpoints the forward:
    activations are recomputed during the backward pass instead of held in
    HBM — the standard FLOPs-for-memory trade for deep conv stacks or
    large padded graphs. No reference analog (torch would use
    ``torch.utils.checkpoint``; the reference never does).

    ``guard_nonfinite=True`` (config ``Training.nonfinite_guard``)
    returns the GUARDED step instead — signature ``(state, batch,
    consec) -> (state, loss, tasks_loss, consec, bad)`` — which skips
    any batch producing a non-finite loss or gradient norm (see
    :func:`_land`; the host policy lives in
    ``hydragnn_tpu/resilience/sentry.py``). With all-finite inputs it
    computes exactly what the unguarded step computes.

    ``diagnostics=True`` additionally returns the jitted per-head
    diagnostics OBSERVER — ``(train_step, diag_step)`` — a separate
    executable over the same loss (per-head gradient norms, inter-task
    cosine conflict matrix, update-to-param ratio; see
    ``hydragnn_tpu/obs/introspect.py``) that a per-step loop dispatches on
    sampled steps before the train step, which then repeats the forward
    and the gradient. A loop that scans its epochs does not pair the two:
    it runs :func:`make_diagnosed_first_step` as the epoch's first train
    step."""
    body = _train_step_body(
        model, tx, compute_dtype=compute_dtype, remat=remat, guarded=guard_nonfinite
    )
    step = jax.jit(body, donate_argnums=(0,))
    if diagnostics:
        return step, make_diagnostics_step(
            model, tx, compute_dtype=compute_dtype, remat=remat
        )
    return step


def make_diagnosed_first_step(
    model: HydraModel,
    tx: optax.GradientTransformation,
    compute_dtype=None,
    remat: bool = False,
    guard_nonfinite: bool = False,
) -> Callable[..., Tuple]:
    """The epoch's first train step, run ONCE by the program that
    diagnoses it (``Training.diagnostics`` under the whole-epoch scan).

    One linearisation of the stacked task losses serves the diagnostics
    (H head pulls) and the update (the pull with the task weights as
    cotangent, which is the train step's backward), so the sampled step is
    not observed and then repeated. Its batch is cut from the stack inside
    the program, ``stacked[order[0]]``, as the scan body cuts its own:
    ``order`` is a device value, so the shuffle moving ``order[0]``
    compiles nothing. Training's numbers are the plain step's to rounding.

    Returns jitted ``(state, stacked, order) -> (state, (loss, tasks[H],
    count), diagnostics)``, and with ``guard_nonfinite`` ``(state,
    stacked, order, consec) -> (state, (loss, tasks[H], count, bad),
    consec, diagnostics)`` with :func:`_land`'s guard. The state is
    donated; the tuple is what ``make_scan_epoch``'s programs take as
    ``first``; ``diagnostics`` is ``obs/introspect.py:head_diagnostics``'
    dictionary. The composition is this module's (the closure, ``tx.update``
    and :func:`_land` of every other step body); ``obs/`` lends the two
    pure functions ``linearize_heads`` and ``head_diagnostics``, which its
    observer calls around the same closure.
    The compiled program keeps the observer's name,
    ``jit_diagnostics_step``: it is the program that carries the
    diagnostics (``benchmark/program_spans.py`` finds them by it)."""

    def step(state: TrainState, stacked: GraphBatch, order: jnp.ndarray, consec):
        i = order[0]
        batch = jax.tree_util.tree_map(lambda x: x[i], stacked)
        rng, dropout_rng = jax.random.split(state.rng)
        loss_fn = train_loss_closure(model, compute_dtype, state.batch_stats, batch, dropout_rng)
        weights, lean = model.cfg.normalized_weights, model.cfg.is_token_stack
        loss, tasks, mutated, head_grads, grads = linearize_heads(
            loss_fn, state.params, weights, remat=remat, last_from_total=lean
        )
        updates, opt_state = tx.update(grads, state.opt_state, state.params)
        diagnostics = head_diagnostics(tasks, head_grads, grads, state.params, updates, weights)
        count = batch.graph_mask.sum().astype(jnp.float32)
        landed = _land(state, rng, loss, tasks, mutated, grads, updates, opt_state, consec)
        if consec is None:
            state, loss, tasks = landed
            return state, (loss, tasks, count), diagnostics
        state, loss, tasks, consec, bad = landed
        return state, (loss, tasks, count * (1.0 - bad), bad), consec, diagnostics

    if guard_nonfinite:

        def diagnostics_step(state, stacked, order, consec):
            return step(state, stacked, order, consec)

    else:

        def diagnostics_step(state, stacked, order):
            return step(state, stacked, order, None)

    return jax.jit(diagnostics_step, donate_argnums=(0,))


def _after_first(first: Tuple, rest: Tuple) -> Tuple:
    """The epoch's per-step outputs: the first step's in front of the
    scanned steps'."""
    return tuple(jnp.concatenate([f[None], r]) for f, r in zip(first, rest))


def make_scan_epoch(
    model: HydraModel,
    tx: optax.GradientTransformation,
    compute_dtype=None,
    remat: bool = False,
    guard_nonfinite: bool = False,
) -> Callable[..., Tuple]:
    """Whole-epoch training as ONE dispatch: ``lax.scan`` of the train
    step over device-resident stacked batches.

    Per-step dispatch costs a host->device round trip; scanning the
    epoch inside one jitted program amortizes it to one
    dispatch per epoch. Requires every batch of the epoch stacked on a
    leading axis and resident in HBM (GraphLoader.stacked_device_batches),
    so it suits datasets that fit on-device. Since the scan-eligibility
    work (train/loop.py:_scan_auto_eligible) this is the DEFAULT
    dispatch mode on a single-device mesh with a stackable loader; the
    streaming per-step path remains for everything else.

    Returns jitted ``(state, stacked_batches, order) -> (state, losses[B],
    tasks[B, H], counts[B])`` where ``order`` is an int32 permutation of
    the batch axis (the per-epoch reshuffle, device-side gather) and
    ``counts`` the real-graph count per batch for weighted averaging.

    ``guard_nonfinite=True`` scans the GUARDED step body instead — the
    same on-device non-finite skip the per-step path gets
    (:func:`_land`), with the consecutive-bad counter
    threaded through the scan carry. Signature then becomes
    ``(state, stacked, order, consec0) -> (state, losses, tasks, counts,
    bads[B], consec_end)`` where bad steps contribute zero loss/count
    (the ``NonFiniteSentry.observe_scan`` contract).

    Both take one more argument, ``first``: the per-step outputs of
    :func:`make_diagnosed_first_step`, which has run ``order[0]`` on the
    state handed in. The scan then runs ``order[1:]`` and returns the same
    ``B``-long arrays with ``first`` in front (a second compiled program
    of the same name, one step shorter).
    """
    body = _train_step_body(
        model, tx, compute_dtype=compute_dtype, remat=remat, guarded=guard_nonfinite
    )
    if guard_nonfinite:

        def train_scan_epoch_guarded(
            state: TrainState, stacked: GraphBatch, order: jnp.ndarray,
            consec: jnp.ndarray, first: Optional[Tuple] = None,
        ):
            def scan_body(carry, i: jnp.ndarray):
                state, consec = carry
                batch = jax.tree_util.tree_map(lambda x: x[i], stacked)
                state, loss, tasks, consec, bad = body(state, batch, consec)
                cnt = batch.graph_mask.sum().astype(jnp.float32) * (1.0 - bad)
                return (state, consec), (loss, tasks, cnt, bad)

            (state, consec), outs = jax.lax.scan(
                scan_body, (state, consec), order if first is None else order[1:]
            )
            losses, tasks, counts, bads = outs if first is None else _after_first(first, outs)
            return state, losses, tasks, counts, bads, consec

        return jax.jit(train_scan_epoch_guarded, donate_argnums=(0,))

    def train_scan_epoch(
        state: TrainState, stacked: GraphBatch, order: jnp.ndarray, first: Optional[Tuple] = None
    ):
        # Scan over the PERMUTATION, dynamic-indexing one batch out of the
        # closed-over stack per iteration: a full permuted copy of the
        # train split as scan xs would double the feature's HBM footprint.
        def scan_body(state: TrainState, i: jnp.ndarray):
            batch = jax.tree_util.tree_map(lambda x: x[i], stacked)
            new_state, loss, tasks = body(state, batch)
            return new_state, (loss, tasks, batch.graph_mask.sum().astype(jnp.float32))

        state, outs = jax.lax.scan(scan_body, state, order if first is None else order[1:])
        losses, tasks, counts = outs if first is None else _after_first(first, outs)
        return state, losses, tasks, counts

    return jax.jit(train_scan_epoch, donate_argnums=(0,))


def make_scan_eval(
    model: HydraModel,
) -> Callable[..., Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]]:
    """Whole-split evaluation as ONE dispatch: ``lax.scan`` of the eval
    step over device-resident stacked batches (the eval-side companion of
    :func:`make_scan_epoch`; same HBM-residency requirement). Returns
    jitted ``(state, stacked) -> (losses[B], tasks[B, H], counts[B])``."""

    def scan_body(state: TrainState, batch: GraphBatch):
        outputs = model.apply(
            {"params": state.params, "batch_stats": state.batch_stats},
            batch,
            train=False,
        )
        loss, tasks = model_loss(model.cfg, outputs, batch)
        return state, (loss, jnp.stack(tasks), batch.graph_mask.sum().astype(jnp.float32))

    def eval_scan(state: TrainState, stacked: GraphBatch):
        _, (losses, tasks, counts) = jax.lax.scan(scan_body, state, stacked)
        return losses, tasks, counts

    return jax.jit(eval_scan)


def make_stats_step(model: HydraModel) -> Callable[[TrainState, GraphBatch], TrainState]:
    """Jitted BatchNorm-recalibration step: a train-mode forward that
    updates ONLY the running statistics (params untouched, no grads).

    Used after training to re-estimate the running stats at the final
    parameters: the in-training EMA trails the last few noisy batches
    (and BN's train-mode batch-feedback can leave it far from the
    stationary statistics — observed as train-mode metrics converging
    while eval-mode metrics diverge), so a few frozen-parameter passes
    make eval faithful."""

    def bn_stats_step(state: TrainState, batch: GraphBatch):
        # dropout OFF (train=False), BatchNorm in batch-stats mode
        # (bn_train=True): eval statistics must be estimated under the
        # same deterministic forward eval itself uses
        _, mutated = model.apply(
            {"params": state.params, "batch_stats": state.batch_stats},
            batch,
            train=False,
            bn_train=True,
            mutable=["batch_stats"],
        )
        return state.replace(batch_stats=mutated["batch_stats"])

    return jax.jit(bn_stats_step)


def make_eval_step(
    model: HydraModel, with_outputs: bool = False
) -> Callable[..., Any]:
    """Returns jitted ``(state, batch) -> (loss, tasks_loss[, outputs])``
    using running BatchNorm statistics (train=False), the analog of the
    reference's ``model.eval()`` validate/test passes
    (train_validate_test.py:374-443)."""

    def eval_step(state: TrainState, batch: GraphBatch):
        outputs = model.apply(
            {"params": state.params, "batch_stats": state.batch_stats},
            batch,
            train=False,
        )
        loss, tasks = model_loss(model.cfg, outputs, batch)
        if with_outputs:
            return loss, jnp.stack(tasks), outputs
        return loss, jnp.stack(tasks)

    if with_outputs:
        eval_step.__name__ = "eval_step_outputs"
    return jax.jit(eval_step)
