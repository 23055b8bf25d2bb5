"""Epoch driver: train / validate / test with plateau LR and early stop.

TPU-native re-design of the reference epoch loop (reference:
hydragnn/train/train_validate_test.py:37-215). Semantics kept:

  - per-epoch seeded reshuffle (``loader.set_epoch`` = the reference's
    ``sampler.set_epoch``, :113-115);
  - loss accumulation weighted by the real graph count of each batch
    (``data.num_graphs`` weighting, :364-367) — here the count comes from
    ``graph_mask`` so padding never dilutes the average;
  - ``ReduceLROnPlateau(factor=0.5, patience=5, min_lr=1e-5)`` stepped on
    the validation loss (reference constructs it at run_training.py:94-96);
  - ``EarlyStopping(patience=10, min_delta=0)`` gated by config
    ``Training.EarlyStopping`` / ``Training.patience`` (:53-56,103-106,
    utils/model.py:128-143);
  - cross-process metric reduction (mean) replacing the torch.distributed
    all-reduce (:284-289); prediction gathering replacing the padded
    all-gather (:292-330).

Device-sync discipline: per-batch losses are accumulated as device scalars
and materialized once per epoch, so the hot loop never blocks on D2H.
"""

from __future__ import annotations

import contextlib
import json
import os
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from hydragnn_tpu.graph.batch import GraphBatch
from hydragnn_tpu.models.base import HydraModel, ModelConfig
from hydragnn_tpu.obs.spans import count, drain, drain_counts, span, span_iter
from hydragnn_tpu.train.optimizer import current_learning_rate, set_learning_rate
from hydragnn_tpu.train.state import (
    TrainState,
    make_eval_step,
    make_scan_epoch,
    make_scan_eval,
    make_stats_step,
    make_train_step,
)
from hydragnn_tpu.utils.print_utils import print_distributed, iterate_tqdm
from hydragnn_tpu.utils import knobs
from hydragnn_tpu.utils.time_utils import Timer


class EarlyStopping:
    """Patience counter on validation loss (reference:
    hydragnn/utils/model.py:128-143)."""

    def __init__(self, patience: int = 10, min_delta: float = 0.0):
        self.patience = patience
        self.min_delta = min_delta
        self.count = 0
        self.min_loss = float("inf")

    def __call__(self, val_loss: float) -> bool:
        if val_loss < self.min_loss:
            self.min_loss = val_loss
            self.count = 0
        elif val_loss > self.min_loss + self.min_delta:
            self.count += 1
            if self.count >= self.patience:
                return True
        return False


class ReduceLROnPlateau:
    """Torch-semantics plateau scheduler acting on the injected dynamic
    learning rate (reference uses torch.optim.lr_scheduler.ReduceLROnPlateau
    with factor=0.5, patience=5, min_lr=1e-5, run_training.py:94-96)."""

    def __init__(
        self,
        factor: float = 0.5,
        patience: int = 5,
        min_lr: float = 1e-5,
        threshold: float = 1e-4,
    ):
        self.factor = factor
        self.patience = patience
        self.min_lr = min_lr
        self.threshold = threshold
        self.best = float("inf")
        self.num_bad_epochs = 0

    def step(self, state: TrainState, val_loss: float) -> TrainState:
        if val_loss < self.best * (1.0 - self.threshold):
            self.best = val_loss
            self.num_bad_epochs = 0
        else:
            self.num_bad_epochs += 1
        if self.num_bad_epochs > self.patience:
            self.num_bad_epochs = 0
            lr = max(current_learning_rate(state.opt_state) * self.factor, self.min_lr)
            state = state.replace(opt_state=set_learning_rate(state.opt_state, lr))
        return state


def _reduce_mean_across_processes(values: np.ndarray) -> np.ndarray:
    """Mean across processes (reference reduce_values_ranks,
    train_validate_test.py:284-289); identity in single-process runs."""
    if jax.process_count() > 1:
        from jax.experimental import multihost_utils

        return np.asarray(multihost_utils.process_allgather(values)).mean(axis=0)
    return values


def _finalize_weighted(
    weighted_losses, weighted_tasks, counts
) -> Tuple[float, np.ndarray, float]:
    """Count-weighted mean of per-batch metrics (already multiplied by
    their counts), mean-reduced across processes — the reference's
    num_graphs weighting + all-reduce
    (train_validate_test.py:284-289,364-367). Third: this process's
    count itself (the real graphs the metrics are over)."""
    graphs = float(jnp.stack(counts).sum())
    total = max(graphs, 1.0)
    avg_loss = float(jnp.stack(weighted_losses).sum()) / total
    avg_tasks = np.asarray(jnp.stack(weighted_tasks).sum(axis=0)) / total
    avg_loss = float(_reduce_mean_across_processes(np.asarray([avg_loss]))[0])
    avg_tasks = _reduce_mean_across_processes(avg_tasks)
    return avg_loss, avg_tasks, graphs


def _named_tasks(names: Sequence[str], values) -> Dict[str, float]:
    """Per-task loss array -> {head_name: loss}. Zip-truncating: a
    zero-length array (preempted epoch finalize) yields {}."""
    return {n: float(v) for n, v in zip(names, np.asarray(values).reshape(-1))}


class _MetricAccum:
    """Accumulates per-batch (loss, tasks, graph_mask) as raw device
    arrays; ``finalize`` does ALL the weighting math in one stacked
    computation at the epoch boundary. The hot loop therefore dispatches
    ZERO extra device ops per step — no ``graph_mask.sum()``, no
    ``loss * n`` multiplies — and syncs exactly once per epoch (the
    step-span tracer pins this: no ``block_until_ready`` outside the
    sampled window)."""

    def __init__(self):
        self._losses: List[jnp.ndarray] = []
        self._tasks: List[jnp.ndarray] = []
        self._ns: List[jnp.ndarray] = []
        self._bads: List[Optional[jnp.ndarray]] = []
        self.graphs = 0.0  # real graphs counted, known after finalize

    def add(
        self,
        loss: jnp.ndarray,
        tasks: jnp.ndarray,
        n: jnp.ndarray,
        bad: Optional[jnp.ndarray] = None,
    ) -> None:
        """``n``: the batch's ``graph_mask`` (preferred — summed in one
        stacked op at finalize) or an already-reduced scalar count.
        ``bad``: the guarded step's 0/1 flag; a bad batch's count is
        zeroed at finalize (its loss/tasks are already zeroed on
        device by the guarded step)."""
        self._losses.append(loss)
        self._tasks.append(tasks)
        self._ns.append(n)
        self._bads.append(bad)

    def finalize(self) -> Tuple[float, np.ndarray]:
        if not self._ns:
            # zero batches ran (e.g. preemption before the first step);
            # the caller's preempt path discards these values
            return 0.0, np.zeros(0, np.float32)
        losses = jnp.stack(self._losses)
        tasks = jnp.stack(self._tasks)
        first = jnp.asarray(self._ns[0])
        if first.ndim:
            # graph masks (any stacked shape): one fused count reduction
            counts = (
                jnp.stack([jnp.asarray(m) for m in self._ns])
                .reshape(len(self._ns), -1)
                .sum(axis=1)
                .astype(jnp.float32)
            )
        else:
            counts = jnp.stack(self._ns).astype(jnp.float32)
        if any(b is not None for b in self._bads):
            bads = jnp.stack(
                [
                    jnp.zeros((), jnp.float32) if b is None else b
                    for b in self._bads
                ]
            )
            counts = counts * (1.0 - bads)
        avg_loss, avg_tasks, self.graphs = _finalize_weighted(
            [(losses * counts).sum()],
            [(tasks * counts[:, None]).sum(axis=0)],
            [counts.sum()],
        )
        return avg_loss, avg_tasks


def train_epoch(
    loader,
    state: TrainState,
    train_step,
    verbosity: int = 0,
    profiler=None,
    spans=None,
    hooks=None,
    diag=None,
    incidents=None,
) -> Tuple[TrainState, float, np.ndarray]:
    """One training epoch; returns (state, avg_loss, avg_tasks_loss[H]).

    ``spans`` (hydragnn_tpu/obs/spans.py:StepSpans) decomposes the
    epoch's wall time into data-wait / host-dispatch / sampled device
    time; the default disabled spans keep the loop's plain async shape
    (identity iterator, direct step call).

    ``hooks`` (hydragnn_tpu/resilience/hooks.py:TrainHooks) adds the
    fault-tolerance hot-loop duties at batch granularity: preemption
    check (graceful mid-epoch stop), watchdog heartbeat, fault
    injection, and — when its non-finite sentry is active — the
    GUARDED step call ``train_step(state, batch, consec)`` whose
    skipped batches contribute zero weight to the epoch metrics.

    ``diag`` (hydragnn_tpu/obs/introspect.py:HeadDiagnostics) samples
    the per-head gradient diagnostics every K steps. It must run
    BEFORE the train step consumes the state: the jitted step donates
    the state's buffers, so the sampled step is the last moment this
    state is usable from Python (the runtime serializes the in-flight
    diagnostics read against the donating write). Non-sampled steps pay
    one counter increment; no host sync happens until the epoch
    boundary."""
    if spans is None:
        from hydragnn_tpu.obs import StepSpans

        spans = StepSpans.disabled()
    sentry = hooks.sentry if hooks is not None else None
    acc = _MetricAccum()
    steps = 0
    # ``spans`` puts each step under train.loader_wait and train.step
    with span("epoch.train"):
        for batch in spans.timed_iter(iterate_tqdm(loader, verbosity, desc="train")):
            if hooks is not None:
                if hooks.preempted:
                    break
                batch = hooks.before_step(batch)
            if diag is not None:
                with span("train.diag_sample"):
                    diag.maybe_sample(state, batch)
            if sentry is not None:
                state, loss, task_losses, consec, bad = spans.step(
                    train_step, state, batch, sentry.consec
                )
                sentry.observe(consec, bad)
                acc.add(loss, task_losses, batch.graph_mask, bad=bad)
            else:
                state, loss, task_losses = spans.step(train_step, state, batch)
                # the raw mask, NOT mask.sum(): the accumulator defers every
                # metric reduction to ONE stacked dispatch at epoch end, so
                # the steady-state step is exactly one host->device dispatch
                acc.add(loss, task_losses, batch.graph_mask)
            steps += 1
            if profiler is not None:
                profiler.step()
            if incidents is not None:
                # drives any OPEN incident's bounded profiler capture at
                # step granularity (obs/triggers.py:IncidentRecorder.tick);
                # a recorder with no open incident returns immediately
                incidents.tick()
        with span("train.sync"):
            avg_loss, avg_tasks = acc.finalize()
    count("graphs", acc.graphs)
    count("steps", steps)
    return state, avg_loss, avg_tasks


def _finalize_scan(losses, tasks, counts) -> Tuple[float, np.ndarray, float]:
    """Weighted finalize for per-batch metric arrays coming out of a
    scan ([B], [B, H], [B])."""
    return _finalize_weighted(
        [(losses * counts).sum()],
        [(tasks * counts[:, None]).sum(axis=0)],
        [counts.sum()],
    )


def _landing_checked(cached, fresh, ecache, key, expected_delta, label):
    """Wrap a CACHED (deserialized) donated executable with a one-time
    landing check: the first real execution's output ``state.step`` must
    equal input ``step + expected_delta`` (1 for a per-step executable,
    num_batches for a scan-epoch one). A round-trip that dropped
    donation metadata produces an optimizer update that never lands —
    the exact silent-staleness failure mode the exec-cache donation gate
    exists for (utils/exec_cache.py module docstring) — so a failed
    check EVICTS the entry (``donation_check_failed``) and replays the
    step through the fresh jitted ``fresh`` on a pre-copy of the inputs
    (the cached executable may have consumed the donated originals)."""
    holder = {"fn": cached, "checked": False}

    def _copy(tree):
        return jax.tree_util.tree_map(
            lambda x: x.copy() if hasattr(x, "copy") else x, tree
        )

    def step(*args):
        if holder["checked"]:
            return holder["fn"](*args)
        saved = _copy(args)
        in_step = int(jax.device_get(args[0].step))
        try:
            out = holder["fn"](*args)
            out_step = int(jax.device_get(out[0].step))
            if out_step != in_step + expected_delta:
                raise RuntimeError(
                    f"cached {label} executable landed step {out_step}, "
                    f"expected {in_step + expected_delta}"
                )
            holder["checked"] = True
            return out
        except Exception:
            ecache._evict(key, "donation_check_failed")
            ecache._miss(key, "donation_check_failed", label=label)
            holder["fn"] = fresh
            holder["checked"] = True
            return fresh(*saved)

    return step


def train_epoch_scan(
    loader, state: TrainState, scan_fn, epoch: int, diag=None, sentry=None
) -> Tuple[TrainState, float, np.ndarray]:
    """One training epoch as a single device dispatch (``Training.
    scan_epoch``): lax.scan over the loader's device-resident stacked
    batches, shuffled device-side by an epoch-seeded permutation of the
    batch axis (sample-to-batch membership reshuffles only when the
    loader's ``scan_reshuffle_every`` is set — see
    ``GraphLoader.stacked_device_batches``). Same weighted-metric
    semantics as ``train_epoch``.

    ``diag`` (obs/introspect.py:HeadDiagnostics): sampled ONCE per epoch
    on the first scheduled batch, BEFORE the donating scan consumes the
    state — scan mode has no step granularity, so per-epoch is the
    sampling floor. ``sentry``: when the scan_fn is the GUARDED variant
    (make_scan_epoch(guard_nonfinite=True)), the per-step bad flags and
    the carry's consecutive counter are handed to it, device-resident."""
    # the four places the chip is known to wait inside this call
    with span("epoch.train"):
        with span("train.stack"):
            stacked = loader.stacked_device_batches(epoch)
            nb = len(loader)
            if loader.shuffle:
                order = np.random.default_rng(loader.seed + epoch).permutation(nb)
            else:
                order = np.arange(nb)
            order_dev = jnp.asarray(order, dtype=jnp.int32)
        if diag is not None:
            with span("train.diag_sample"):
                # DEVICE-scalar index: a Python-int index would bake the batch
                # position into the gather executable and recompile every epoch
                # (the shuffle moves order[0]), tripping the zero-unexpected-
                # recompile contract the compile monitor enforces
                i0 = jnp.asarray(order[0], dtype=jnp.int32)
                first = jax.tree_util.tree_map(lambda x: x[i0], stacked)
                diag.maybe_sample(state, first)
        with span("train.dispatch"):
            if sentry is not None:
                state, losses, tasks, counts, bads, consec = scan_fn(
                    state, stacked, order_dev, sentry.consec
                )
                sentry.observe_scan(bads, consec)
            else:
                state, losses, tasks, counts = scan_fn(state, stacked, order_dev)
        with span("train.sync"):
            avg_loss, avg_tasks, graphs = _finalize_scan(losses, tasks, counts)
    count("graphs", graphs)
    count("steps", nb)
    return state, avg_loss, avg_tasks


def evaluate_epoch(
    loader, state: TrainState, eval_step, verbosity: int = 0, desc: str = "validate"
) -> Tuple[float, np.ndarray]:
    acc = _MetricAccum()
    for batch in span_iter(iterate_tqdm(loader, verbosity, desc=desc), "validate.loader_wait"):
        with span("validate.dispatch"):
            loss, task_losses = eval_step(state, batch)
            acc.add(loss, task_losses, batch.graph_mask)
    with span("validate.sync"):
        return acc.finalize()


def evaluate_epoch_scan(loader, state: TrainState, scan_eval_fn) -> Tuple[float, np.ndarray]:
    """Whole-split evaluation in one dispatch (``Training.scan_epoch``'s
    eval-side companion); same weighted-metric semantics as
    ``evaluate_epoch``."""
    with span("validate.dispatch"):
        losses, tasks, counts = scan_eval_fn(state, loader.stacked_device_batches())
    with span("validate.sync"):
        return _finalize_scan(losses, tasks, counts)[:2]


def test_epoch(
    loader,
    state: TrainState,
    eval_step_with_outputs,
    cfg: ModelConfig,
    verbosity: int = 0,
    return_samples: bool = True,
) -> Tuple[float, np.ndarray, List[np.ndarray], List[np.ndarray]]:
    """Full test pass; optionally collects per-head (true, predicted) value
    arrays over real (unpadded) entries — the reference ``test()`` contract
    (train_validate_test.py:399-443). Multi-process runs concatenate values
    across processes (the reference's padded all-gather, :292-330)."""
    acc = _MetricAccum()
    true_values: List[List[np.ndarray]] = [[] for _ in range(cfg.num_heads)]
    pred_values: List[List[np.ndarray]] = [[] for _ in range(cfg.num_heads)]
    for batch in span_iter(iterate_tqdm(loader, verbosity, desc="test"), "test.loader_wait"):
        with span("test.dispatch"):
            loss, task_losses, outputs = eval_step_with_outputs(state, batch)
            acc.add(loss, task_losses, batch.graph_mask)
        if return_samples:
            # Stacked multi-device batches carry a leading device axis on
            # masks/targets ([D, G]) while sharded eval outputs come back
            # device-concatenated ([D*G, d]); flattening aligns both.
            # ``local_view`` reduces multi-host global arrays to this
            # process's rows (same order as its local sub-batches), so the
            # cross-process concat below sees each sample exactly once.
            from hydragnn_tpu.parallel.mesh import local_view

            with span("test.gather"):
                gmask = local_view(batch.graph_mask).reshape(-1)
                nmask = local_view(batch.node_mask).reshape(-1)
                for ihead in range(cfg.num_heads):
                    name = cfg.output_names[ihead]
                    if cfg.output_type[ihead] == "graph":
                        t = local_view(batch.graph_targets[name])
                        tv = t.reshape(-1, t.shape[-1])[gmask]
                        p = local_view(outputs[ihead])
                        pv = p.reshape(-1, p.shape[-1])[gmask]
                    else:
                        t = local_view(batch.node_targets[name])
                        tv = t.reshape(-1, t.shape[-1])[nmask]
                        p = local_view(outputs[ihead])
                        pv = p.reshape(-1, p.shape[-1])[nmask]
                    true_values[ihead].append(tv)
                    pred_values[ihead].append(pv)
    with span("test.sync"):
        avg_loss, avg_tasks = acc.finalize()

    trues: List[np.ndarray] = []
    preds: List[np.ndarray] = []
    if return_samples:
        with span("test.allgather"):
            for ihead in range(cfg.num_heads):
                tv = np.concatenate(true_values[ihead]) if true_values[ihead] else np.zeros((0, 1))
                pv = np.concatenate(pred_values[ihead]) if pred_values[ihead] else np.zeros((0, 1))
                if jax.process_count() > 1:
                    tv = _allgather_varlen(tv)
                    pv = _allgather_varlen(pv)
                trues.append(tv)
                preds.append(pv)
    return avg_loss, avg_tasks, trues, preds


def _allgather_varlen(arr: np.ndarray) -> np.ndarray:
    """Cross-process concat of per-process arrays with different row
    counts: exchange sizes, pad to the max, all-gather, trim — the
    reference's padded variable-length all-gather
    (train_validate_test.py:292-330). Row counts differ because each
    process's shard holds different samples (node heads: different atom
    counts)."""
    from jax.experimental import multihost_utils

    n = np.asarray([arr.shape[0]], dtype=np.int64)
    counts = np.asarray(multihost_utils.process_allgather(n)).reshape(-1)
    n_max = int(counts.max())
    padded = np.zeros((n_max,) + arr.shape[1:], dtype=arr.dtype)
    padded[: arr.shape[0]] = arr
    gathered = np.asarray(multihost_utils.process_allgather(padded))
    return np.concatenate([gathered[p, : counts[p]] for p in range(len(counts))])


def _stack_refusal(loader, keep: bool = False) -> Optional[str]:
    """Materialize ``loader``'s device-resident stack (the loader caches
    it, so epoch 0 does not pay twice), or with ``keep`` the batches it
    iterates (``keep_on_device``). None when it did; else why not,
    for the two anticipated causes — batches of unlike shape cannot
    stack (ValueError), a split too large for device memory cannot be
    resident. Anything else is a fault and raises."""
    try:
        with span("setup.stack_splits"):
            if keep:
                loader.keep_on_device()
            else:
                loader.stacked_device_batches(0)
    except (ValueError, jax.errors.JaxRuntimeError) as exc:
        if not (isinstance(exc, ValueError) or "RESOURCE_EXHAUSTED" in str(exc)):
            raise
        return f"{type(exc).__name__}: {str(exc)[:160]}"
    return None


def _keep_test_split_on_device(
    test_loader, use_scan: bool, caller_step: bool
) -> Tuple[bool, str]:
    """Where the train split scans, the test loader's batches are built
    once, at set-up, and stay on the device (``GraphLoader.keep_on_device``):
    ``test_epoch`` then iterates them with no host batching and no
    transfer in, every epoch. Same pass, same values; only where the
    batches come from changes, and the choice is made from what the loop
    knows: a per-step run streams its splits and keeps doing so, a
    caller-supplied (sharded) step keeps its loader's placement as it is,
    ``run_prediction`` makes one pass and never comes here. Returns
    (kept, reason) for the manifest's ``dispatch_mode.test_split``."""
    if not use_scan:
        return False, "the train split is dispatched per step"
    if caller_step:
        return False, "caller-supplied eval_step_out"
    if not hasattr(test_loader, "keep_on_device"):
        return False, "the test loader cannot keep its batches"
    refusal = _stack_refusal(test_loader, keep=True)  # a loader that shuffles refuses
    if refusal is not None:
        return False, f"keeping failed: {refusal}"
    return True, "scan dispatch: test batches built once, kept on the device"


def _scan_auto_eligible(
    loader, config: Dict[str, Any], partitioner=None, profiler=None
) -> Tuple[bool, str]:
    """Is the whole-epoch scan dispatch the right DEFAULT here?
    (``Training.scan_epoch`` unset — an explicit true/false always
    wins.) Eligible = a loader that can stack the split device-resident
    + what :func:`scan_dispatch_planned` asks of topology, environment
    and configuration (``config``: the ``NeuralNetwork`` section).
    Returns (eligible, human-readable reason) — the reason lands in the
    flight manifest's ``dispatch_mode`` field either way.

    ``partitioner`` (hydragnn_tpu/parallel/partitioner.py) is the
    authoritative topology signal when given: the scan path trusts
    ``partitioner.single_device`` instead of sniffing the loader's
    mesh shape itself."""
    if not hasattr(loader, "stacked_device_batches") or not hasattr(
        loader, "shuffle"
    ):
        return False, "loader cannot stack device-resident batches"
    if partitioner is None and getattr(loader, "device_stack", 1) != 1:
        return False, "multi-device stacked loader (sharded mesh)"
    try:
        if len(loader) < 1:
            return False, "empty loader"
    except TypeError:
        return False, "unsized loader"
    return scan_dispatch_planned(
        config,
        single_device=partitioner is None or partitioner.single_device,
        profiler=profiler,
    )


def scan_dispatch_planned(
    config: Dict[str, Any], single_device: bool, profiler=None
) -> Tuple[bool, str]:
    """Will a loop-owned run of this ``NeuralNetwork`` configuration train
    through the whole-epoch scan? The part of the dispatch-mode
    resolution that configuration, environment and topology decide,
    WITHOUT a loader: ``train_validate_test`` resolves its mode through
    it, and ``api.create_dataloaders`` asks it before the train loader
    exists, so that the loader a scan will consume is built with its
    membership fixed (``GraphLoader(fixed_membership=True)``). A sharded
    run brings its own step and never scans (``single_device`` false);
    else an explicit ``Training.scan_epoch`` wins; unset, the scan is the
    default in one process unless a feature needs batch granularity. What
    only the loader can say (``_scan_auto_eligible``, ``_stack_refusal``)
    stays with the loop."""
    if not single_device:
        return False, "partitioner mesh is multi-device"
    training = config["Training"]
    scan_cfg = training.get("scan_epoch")
    if scan_cfg is not None:
        return bool(scan_cfg), f"Training.scan_epoch={'true' if scan_cfg else 'false'}"
    if jax.process_count() > 1:
        return False, "multi-process run"
    inject = knobs.active_injections(include_serve=False)
    if inject:
        # deterministic fault injection is step-indexed — it needs the
        # per-step path's batch granularity to fire at the right step
        return False, f"fault injection active ({inject[0]})"
    if (
        knobs.get_float("HYDRAGNN_WATCHDOG_S", 0.0) > 0
        or float(training.get("watchdog_stall_s", 0) or 0) > 0
    ):
        # the watchdog heartbeats at batch granularity; a whole-epoch
        # dispatch would read as a stall
        return False, "hang watchdog active"
    if profiler is not None or "Profile" in config:
        return False, "per-step profiler configured"
    return True, "single-device mesh + device-resident stacked loader"


def train_validate_test(
    model: HydraModel,
    tx,
    state: TrainState,
    train_loader,
    val_loader,
    test_loader,
    config: Dict[str, Any],
    log_name: str = "run",
    verbosity: int = 0,
    create_plots: bool = False,
    plot_init_solution: bool = False,
    plot_hist_solution: bool = False,
    log_dir: str = "./logs/",
    profiler=None,
    train_step=None,
    eval_step=None,
    eval_step_out=None,
    stats_step=None,
    flight=None,
    run_config=None,
    partitioner=None,
    manifest_extra=None,
) -> Tuple[TrainState, Dict[str, Any]]:
    """Train for ``Training.num_epoch`` epochs with validation-driven LR
    plateau + early stopping; returns (final_state, history dict). ``config``
    is the ``NeuralNetwork`` section (reference signature parity,
    train_validate_test.py:37-58). Callers running data-parallel pass the
    sharded step functions (hydragnn_tpu/parallel); defaults are the
    single-device jitted steps.

    Telemetry (hydragnn_tpu/obs, gated by ``HYDRAGNN_TELEMETRY``): the
    run writes a flight record — ``<log_dir>/<log_name>/flight.jsonl``,
    rank 0 — with a start manifest (resolved config, backend, mesh,
    pad plans), per-epoch records carrying the losses plus the
    data-wait / dispatch / device step-time decomposition and compile
    counts, and a final summary. Callers may pass their own ``flight``
    recorder (bench harnesses) and ``run_config`` (the full resolved
    config for the manifest; defaults to the NeuralNetwork section);
    ``manifest_extra`` merges extra caller keys into the run_start
    manifest (the retrain pilot's fine-tune child stamps its
    provenance there — pilot/tune.py).

    ``partitioner`` (hydragnn_tpu/parallel/partitioner.py) is the run's
    sharding authority: the scan-epoch auto-dispatch trusts its
    single-device verdict, and the manifest's ``parallel`` block (mesh
    shape, fsdp factor, per-leaf sharding summary, per-device bytes,
    replicated-leaf fallbacks) comes from it — docs/PARALLELISM.md."""
    with span("setup.step_builders"):
        training = config["Training"]
        num_epoch = int(training["num_epoch"])
        early_stop = bool(training.get("EarlyStopping", False))
        stopper = EarlyStopping(patience=int(training.get("patience", 10))) if early_stop else None
        scheduler = ReduceLROnPlateau()

        cfg = model.cfg
        # Training.mixed_precision: bf16 forward/backward with f32 master
        # params/optimizer/BN stats (MXU-native; absent from the reference,
        # which has no AMP path — SURVEY §2.2 "explicitly absent")
        compute_dtype = (
            jnp.bfloat16 if training.get("mixed_precision") else None
        )
        # Dispatch-mode resolution. ``Training.scan_epoch`` explicit
        # true/false always wins; UNSET defaults to the whole-epoch lax.scan
        # dispatch when eligible (_scan_auto_eligible: single-device mesh +
        # device-resident stacked loader), with automatic fallback to
        # per-step dispatch and the decision recorded in the flight
        # manifest's ``dispatch_mode``.
        scan_fn = scan_eval_fn = None
        loop_owned = train_step is None
        scan_cfg = training.get("scan_epoch")
        scan_auto = scan_cfg is None and loop_owned
        if not loop_owned:
            use_scan, dispatch_reason = False, "caller-supplied train step"
        elif scan_cfg is None:
            use_scan, dispatch_reason = _scan_auto_eligible(
                train_loader, config, partitioner=partitioner, profiler=profiler
            )
            if use_scan:
                # the stack must actually materialize, or the run goes
                # per-step and says why
                refusal = _stack_refusal(train_loader)
                if refusal is not None:
                    use_scan, dispatch_reason = False, f"stacking failed: {refusal}"
        else:
            use_scan, dispatch_reason = scan_dispatch_planned(config, single_device=True)
        if (
            not use_scan
            and getattr(train_loader, "shuffle", False)
            and getattr(train_loader, "fixed_membership", False)
        ):
            # built for a scan (or with cache_device_batches) and iterated
            # per step: the loader keeps its batches and shuffles their order
            dispatch_reason += "; train batches keep their membership, only their order is shuffled"
        # Non-finite guard (hydragnn_tpu/resilience/sentry.py): folded into
        # the loop-owned step in BOTH dispatch modes — per-step via the
        # guarded jitted step, scan via the guarded scan body threading the
        # consecutive-bad counter through the carry. Sharded callers pass
        # their own step and keep their own policy.
        guard_nonfinite = bool(training.get("nonfinite_guard", True)) and loop_owned
        if use_scan:
            scan_fn = make_scan_epoch(
                model,
                tx,
                compute_dtype=compute_dtype,
                remat=bool(training.get("remat", False)),
                guard_nonfinite=guard_nonfinite,
            )
            if eval_step is None:  # a caller-supplied eval_step keeps priority
                scan_eval_fn = make_scan_eval(model)
                # auto mode must not die on an unstackable VAL split —
                # eval falls back to per-step, training stays scanned
                if scan_auto and _stack_refusal(val_loader) is not None:
                    scan_eval_fn = None
        # own_step: the loop built the default single-device PER-STEP train
        # step — the only mode with per-batch (state, batch) pairs on the
        # host (the diagnostics sampler's per-step granularity; scan mode
        # samples once per epoch instead).
        own_step = loop_owned and scan_fn is None
        train_step = train_step or make_train_step(
            model,
            tx,
            compute_dtype=compute_dtype,
            remat=bool(training.get("remat", False)),
            guard_nonfinite=guard_nonfinite,
        )
        eval_step = eval_step or make_eval_step(model)
        test_kept, test_reason = _keep_test_split_on_device(
            test_loader, use_scan, caller_step=eval_step_out is not None
        )
        eval_step_out = eval_step_out or make_eval_step(model, with_outputs=True)
        if stats_step is None and training.get("bn_recalibration", True):
            stats_step = make_stats_step(model)

        # config-driven profiler (reference: Profiler setup from
        # config["Profile"], train_validate_test.py:99-101)
        if profiler is None and "Profile" in config:
            from hydragnn_tpu.utils.profile import Profiler

            profiler = Profiler(prefix=os.path.join(log_dir, log_name, "profile"))
            profiler.setup(config["Profile"])
            if not profiler.enable:
                profiler = None

        history: Dict[str, List] = {
            "train_loss": [],
            "val_loss": [],
            "test_loss": [],
            "train_tasks": [],
            "val_tasks": [],
            "test_tasks": [],
            "lr": [],
        }
    with span("setup.restore"):
        # Per-epoch checkpointing + exact resume (beyond the reference's
        # restore-model-and-start-over: epoch index, plateau scheduler, and
        # early-stop counters survive the restart). The TrainState itself is
        # restored by the caller via Training.continue/startfrom.
        ckpt_every = int(training.get("checkpoint_every", 0))
        ckpt_keep_last = int(training.get("checkpoint_keep_last", 3))
        start_epoch = 0
        resumed_from = None  # set when a continue-run actually loaded meta
        if training.get("continue") == 1:
            from hydragnn_tpu.utils.checkpoint import load_train_meta

            if "startfrom" not in training:
                raise ValueError("Training.continue=1 requires Training.startfrom")
            meta = load_train_meta(training["startfrom"], log_dir)
            if meta is not None:
                # The model file and the meta sidecar are written sequentially
                # (each atomic, the pair not): a crash between them leaves meta
                # one interval older than the weights. The meta carries the
                # optimizer step it described; on mismatch, re-derive the epoch
                # from the restored weights instead of replaying epochs.
                meta_step = meta.get("step")
                state_step = int(jax.device_get(state.step))
                if meta_step is not None and int(meta_step) != state_step:
                    steps_per_epoch = max(len(train_loader), 1)
                    derived = min(num_epoch, state_step // steps_per_epoch)
                    print_distributed(
                        verbosity,
                        f"WARNING: checkpoint meta (step {meta_step}) does not "
                        f"match restored weights (step {state_step}) — the run "
                        "likely crashed between the weight and meta writes; "
                        f"resuming from epoch {derived} derived from the "
                        f"weights, not meta epoch {meta['epoch']}",
                    )
                    # Repair the whole sidecar, not just the epoch: the stale
                    # history would misalign epoch indices for everything
                    # appended after it, and the stale scheduler/stopper
                    # counters describe an older state than the weights (the
                    # weights' own opt_state already carries the live LR).
                    hist = meta.get("history", {})
                    for k, v in hist.items():
                        v = v[:derived]
                        while v and len(v) < derived:
                            v.append(v[-1])  # unknown epochs: carry the last
                        hist[k] = v
                    meta = {
                        "epoch": derived,
                        "step": state_step,
                        "early_stopped": False,
                        "scheduler": {"best": float("inf"), "num_bad_epochs": 0},
                        "stopper": {"count": 0, "min_loss": float("inf")},
                        "history": hist,
                    }
                    # rewrite once so future resumes see a consistent pair —
                    # under the name resume READS from (training["startfrom"]),
                    # which may differ from this run's log_name; also under
                    # log_name so this run's own sidecar starts consistent
                    from hydragnn_tpu.utils.checkpoint import save_train_meta

                    save_train_meta(meta, training["startfrom"], log_dir)
                    if log_name != training["startfrom"]:
                        save_train_meta(meta, log_name, log_dir)
                # an early-stopped run resumes to a no-op (the stop decision
                # is honored, not replayed into extra epochs); a completed or
                # interrupted run continues from its recorded epoch — which
                # also supports the reference's extend-training workflow
                # (continue with a larger num_epoch)
                start_epoch = num_epoch if meta.get("early_stopped") else int(meta["epoch"])
                resumed_from = start_epoch
                scheduler.best = float(meta["scheduler"]["best"])
                scheduler.num_bad_epochs = int(meta["scheduler"]["num_bad_epochs"])
                if stopper is not None and "stopper" in meta:
                    stopper.count = int(meta["stopper"]["count"])
                    stopper.min_loss = float(meta["stopper"]["min_loss"])
                history = meta["history"]

    with span("setup.manifest"):
        # Unified telemetry (hydragnn_tpu/obs): flight record + step spans +
        # compile monitor, all inert when HYDRAGNN_TELEMETRY=0. Created
        # AFTER resume handling so a config error there cannot leak a
        # registered monitor or an empty flight file. The flight record is
        # rank-0 (like checkpoints/tensorboard); spans and the compile
        # monitor run everywhere but only rank 0 persists them.
        from hydragnn_tpu.obs import (
            CompileMonitor,
            FlightRecorder,
            StepSpans,
            telemetry_enabled,
        )

        telemetry_on = telemetry_enabled()
        # Pod-visibility plane (obs/podview.py, docs/OBSERVABILITY.md "Pod
        # visibility"): when the run spans >1 host (real or simulated via
        # HYDRAGNN_PODVIEW*), every host writes its own flight shard —
        # rank 0 keeps the canonical flight.jsonl, host k writes
        # flight.host<k>.jsonl — instead of non-zero ranks staying silent.
        from hydragnn_tpu.obs import podview as _podview

        pv_host, pv_hosts = _podview.host_identity()
        pv_on = telemetry_on and _podview.podview_enabled()
        pv_run_id = _podview.resolve_run_id(log_name)
        pv_monitor = None
        pv_overhead_s = 0.0
        pv_t_run0 = time.perf_counter()
        own_flight = flight is None
        if flight is None:
            if telemetry_on and (pv_host == 0 or pv_on):
                flight_path = _podview.host_flight_path(
                    os.path.join(log_dir, log_name), pv_host
                )
            else:
                flight_path = None
            flight = FlightRecorder(
                flight_path,
                enabled=telemetry_on,
                host=pv_host if pv_on else None,
            )
        if pv_on and pv_host == 0:
            from hydragnn_tpu.obs import get_registry as _get_registry

            pv_monitor = _podview.SkewMonitor(
                os.path.join(log_dir, log_name),
                host=pv_host,
                hosts=pv_hosts,
                run_id=pv_run_id,
                registry=_get_registry(),
            )
        # Pod fault-tolerance plane (resilience/podckpt.py,
        # docs/RESILIENCE.md "Pod recovery"): multi-host runs cut sharded
        # generations with a rank-0 COMMIT marker, exchange heartbeats, and
        # coordinate preemption cuts so every host checkpoints the SAME
        # generation. Single-host runs keep the plain msgpack path only.
        pv_signaler = None
        pod_ckpt_on = False
        if pv_on and pv_hosts > 1:
            from hydragnn_tpu.resilience.podckpt import PodSignaler

            pv_signaler = PodSignaler(
                os.path.join(log_dir, log_name), host=pv_host, hosts=pv_hosts
            )
            pod_ckpt_on = knobs.get_bool("HYDRAGNN_POD_CKPT", True)
        spans = StepSpans() if telemetry_on else StepSpans.disabled()
        cmon = CompileMonitor().start() if telemetry_on else None
        if profiler is not None and getattr(profiler, "on_trace", None) is None:
            profiler.on_trace = lambda path, ep: flight.record(
                "profile_trace", path=path, epoch=ep
            )

        # Incident-grade tracing (obs/trace.py + obs/triggers.py,
        # docs/OBSERVABILITY.md "Tracing and incidents"): sampled sync
        # steps join the request-trace timeline keyed (epoch, step), and —
        # when Training.slo_triggers is on — an SLO trigger engine
        # evaluated at each epoch end (nonfinite burst, loss spike vs
        # rolling median, MFU drop) arms a bounded profiler capture whose
        # evidence lands in an incident bundle under
        # <log_dir>/<log_name>/incidents/<id>/.
        tracer = None
        trig_engine = None
        incidents = None
        if telemetry_on:
            from hydragnn_tpu.obs.trace import Tracer

            tracer = Tracer(flight=flight)
            spans.tracer = tracer
        if telemetry_on and bool(training.get("slo_triggers", False)):
            from hydragnn_tpu.obs import get_registry
            from hydragnn_tpu.obs.triggers import (
                IncidentRecorder,
                TriggerEngine,
                TriggerRule,
            )

            trig_rules = [
                TriggerRule(
                    "train_nonfinite_burst",
                    "nonfinite_burst",
                    "train.nonfinite_skipped",
                    float(training.get("slo_nonfinite_burst", 1)),
                ),
                TriggerRule(
                    "train_loss_spike",
                    "loss_spike",
                    "train_loss",
                    float(training.get("slo_loss_spike_factor", 3.0)),
                ),
                TriggerRule(
                    "train_mfu_drop",
                    "mfu_drop",
                    "mfu",
                    float(training.get("slo_mfu_drop_factor", 0.5)),
                ),
            ]
            if pv_monitor is not None:
                # cross-host skew rules over the gauges the SkewMonitor
                # publishes; the step_skew threshold defaults to the
                # scaling model's skew_tolerance derivation
                trig_rules.append(
                    TriggerRule(
                        "podview_step_skew",
                        "step_skew",
                        "podview.skew_frac",
                        float(
                            training.get("podview_skew_threshold")
                            or pv_monitor.threshold
                        ),
                    )
                )
                trig_rules.append(
                    TriggerRule(
                        "podview_host_stall",
                        "host_stall",
                        "podview.stall_age_s",
                        knobs.get_float("HYDRAGNN_PODVIEW_STALL_S", 120.0),
                    )
                )
            if pv_signaler is not None and pv_signaler.lost_after_s > 0:
                # a peer missing HYDRAGNN_POD_LOST_AFTER_S seconds of
                # heartbeats sets podview.lost_hosts > 0 at the epoch
                # boundary; the incident bundles the heartbeat view
                trig_rules.append(
                    TriggerRule(
                        "podview_host_lost",
                        "host_lost",
                        "podview.lost_hosts",
                        0.5,
                    )
                )
            trig_engine = TriggerEngine(trig_rules, registry=get_registry())
            if jax.process_index() == 0:
                incidents = IncidentRecorder(
                    os.path.join(log_dir, log_name, "incidents"),
                    registry=get_registry(),
                    flight_path=flight.path,
                    podview=pv_monitor,
                )

    with span("setup.introspect"):
        # Model-level introspection (hydragnn_tpu/obs/introspect.py,
        # docs/OBSERVABILITY.md "Model-level diagnostics"): per-head
        # gradient diagnostics sampled every Training.diag_every steps
        # (default: once per epoch), per-head eval MAE/RMSE off the
        # test_epoch gather path, and the hardware-efficiency ledger
        # (compiled-step FLOPs from the LOWERED module — no second compile
        # — turned into per-epoch achieved TFLOP/s + MFU + memory
        # watermark). All inert when HYDRAGNN_TELEMETRY=0 or
        # Training.diagnostics=false; the gradient sampler additionally
        # requires the loop-owned per-step path (sharded callers and the
        # scan path degrade to heads.available=false, never fail).
        # HYDRAGNN_DIAGNOSTICS=0 force-disables introspection regardless of
        # config (the tier-1 suite sets it: dozens of tiny training tests
        # would each pay the diagnostics executable's compile + the ledger
        # lowering; the dedicated introspection tests and the ci.sh smoke
        # opt back in). Production default stays ON.
        introspect_on = (
            telemetry_on
            and bool(training.get("diagnostics", True))
            and knobs.get_bool("HYDRAGNN_DIAGNOSTICS", True)
        )
        head_names = list(cfg.output_names)
        diag = None
        ledger = None
        if introspect_on:
            from hydragnn_tpu.obs.introspect import (
                HardwareLedger,
                HeadDiagnostics,
                make_diagnostics_step,
            )

            if loop_owned:
                # per-step mode: sample every diag_every steps (default once
                # per epoch). Scan mode calls the sampler once per EPOCH
                # (train_epoch_scan), so diag_every converts to an epoch
                # stride there — the sampling floor one dispatch per epoch
                # allows.
                diag_every = int(training.get("diag_every", 0))
                if scan_fn is not None:
                    every = max(1, diag_every // max(len(train_loader), 1))
                else:
                    every = diag_every or max(len(train_loader), 1)
                diag = HeadDiagnostics(
                    make_diagnostics_step(
                        model,
                        tx,
                        compute_dtype=compute_dtype,
                        remat=bool(training.get("remat", False)),
                    ),
                    head_names=head_names,
                    every=every,
                )
            try:
                example = next(iter(train_loader))
                lower_args = (
                    (state, example, jnp.zeros((), jnp.int32))
                    if guard_nonfinite
                    else (state, example)
                )
                # the scan path runs the SAME step body nb times per
                # dispatch, so the per-step lowered cost prices it too
                ledger = HardwareLedger.from_step(train_step, lower_args)
                # useful-vs-padded byte accounting: the XLA cost model above
                # prices padded shapes; the pad-waste fractions + analytic
                # conv-traffic model say how much of that a bucket-ladder
                # batch actually uses (its own guard: this is telemetry and
                # must never take the ledger down with it)
                try:
                    from hydragnn_tpu.obs.introspect import (
                        conv_traffic_model,
                        pad_waste_from_batch,
                    )

                    waste = pad_waste_from_batch(example)
                    ledger.set_conv_traffic(
                        waste,
                        conv_traffic_model(
                            waste["node_pad"],
                            waste["edge_pad"],
                            model.cfg.hidden_dim,
                            model.cfg.num_conv_layers,
                            real_edges=waste["real_edges_mean"],
                        ),
                    )
                except Exception:
                    pass
            except Exception:
                ledger = HardwareLedger.disabled(reason="example_batch_unavailable")

    with span("setup.manifest"):
        # Fault tolerance (hydragnn_tpu/resilience, docs/RESILIENCE.md):
        # preemption handler (SIGTERM/SIGINT -> graceful stop + final
        # checkpoint within Training.preempt_grace_s), non-finite sentry
        # over the guarded loop-owned step (per-step OR the guarded scan
        # body — sharded callers pass their own step and keep their own
        # policy), and the opt-in hang watchdog (Training.watchdog_stall_s
        # or HYDRAGNN_WATCHDOG_S; off by default — it must be sized above
        # the worst expected compile time, and it forces per-step dispatch).
        from hydragnn_tpu.resilience import (
            HangWatchdog,
            NonFiniteSentry,
            PreemptionHandler,
            TrainHooks,
            TrainingPreempted,
        )

        sentry = (
            NonFiniteSentry(
                patience=int(training.get("nonfinite_patience", 16)),
                max_rollbacks=int(training.get("nonfinite_max_rollbacks", 2)),
                lr_factor=float(training.get("nonfinite_rollback_lr_factor", 0.5)),
            )
            if guard_nonfinite
            else None
        )
        preempt = (
            PreemptionHandler(
                grace_s=float(training.get("preempt_grace_s", 30.0))
            ).install()
            if training.get("preempt_handler", True)
            else None
        )
        stall_s = float(
            training.get("watchdog_stall_s", 0)
            or knobs.get_float("HYDRAGNN_WATCHDOG_S", 0.0)
            or 0
        )
        watchdog = HangWatchdog(stall_s, flight=flight).start() if stall_s > 0 else None
        hooks = TrainHooks(preempt=preempt, sentry=sentry, watchdog=watchdog)
        if preempt is not None and pv_signaler is not None:
            # SIGTERM on this host announces the cut generation to the pod
            # (preempt.proposed_gen is kept current at each epoch start)
            preempt.signaler = pv_signaler

        # Spans that close after their epoch's event is written (the end
        # of epoch.record, epoch.checkpoint, epoch itself) wait here under
        # their own epoch number for the next event: the next epoch's, or
        # run_end.
        late_phases: List[Dict[str, Any]] = []
        in_epoch: Optional[int] = None  # whose spans the table is collecting

        def _hold_late() -> None:
            phases = drain()
            if phases:
                late_phases.append({"epoch": in_epoch, "phases": phases})

        def _flush_late() -> Dict[str, Any]:
            held = list(late_phases)
            late_phases.clear()
            return {"phases_late": held} if held else {}

        def _abort_telemetry(exc: BaseException, epochs: int) -> None:
            """Record the failure into the flight record before unwinding —
            a crashed run must still leave a parseable artifact (the r05
            'traceback was the only evidence' failure mode)."""
            hooks.teardown()
            if incidents is not None:
                incidents.finalize()
            flight.error(exc)
            _hold_late()
            flight.end_run(
                status="failed",
                epochs=epochs,
                **_flush_late(),
                triggers=(
                    trig_engine.summary(incidents.capture_s if incidents else 0.0)
                    if trig_engine is not None
                    else None
                ),
            )
            if cmon is not None:
                cmon.stop()
            if own_flight:
                flight.close()

        metrics_path = None
        if jax.process_index() == 0:
            out_dir = os.path.join(log_dir, log_name)
            os.makedirs(out_dir, exist_ok=True)
            metrics_path = os.path.join(out_dir, "metrics.jsonl")
    # rank-0 tensorboard scalars (reference: train_validate_test.py:130-137)
    with span("setup.tensorboard"):
        from hydragnn_tpu.utils.tensorboard import get_summary_writer

        writer = get_summary_writer(log_name, log_dir)

    with span("setup.manifest"):
        # Flight-record manifest: everything needed to interpret (and rerun)
        # this run without the builder's shell history. Recorded AFTER resume
        # handling so start_epoch reflects what will actually execute.
        def _loader_plan(ld) -> Dict[str, Any]:
            return {
                "num_batches": len(ld),
                "num_samples": getattr(ld, "num_samples", None),
                "batch_size": getattr(ld, "batch_size", None),
                "pad_nodes": getattr(ld, "pad_nodes", None),
                "pad_edges": getattr(ld, "pad_edges", None),
                "pad_graphs": getattr(ld, "pad_graphs", None),
                # which plan (data/loader.py): cut to the batches that exist
                # ("fixed_membership") or to the worst case, and the largest
                # (sub-)batch it was cut to
                "plan": getattr(ld, "plan", None),
                "real_nodes_max": getattr(ld, "real_nodes_max", None),
                "real_edges_max": getattr(ld, "real_edges_max", None),
                # the edge layout the loader's AUTO chose under that plan
                "dense_slots": getattr(ld, "dense_slots", None),
                "run_align": getattr(ld, "run_align", None),
            }

        _dev0 = jax.devices()[0]
        # flight ``parallel`` block (docs/PARALLELISM.md): the partitioner's
        # mesh shape, axis names, fsdp factor, per-leaf param/optimizer
        # sharding summary, per-device bytes, and any replicated-leaf
        # fallbacks — computed from the PLACED state so it reports what is
        # actually committed, not what was intended
        if partitioner is not None:
            parallel_block = partitioner.manifest(state=state)
        else:
            parallel_block = {
                "available": False,
                "reason": "caller passed no partitioner",
            }
        if pv_monitor is not None:
            # the committed layout feeds the SkewMonitor's collective-aware
            # cost attribution (compute vs wire split in podview_report.json)
            pv_monitor.set_parallel(parallel_block)
        # graftcheck contract block (lint/ir.py, docs/LINT.md CC rules): the
        # run's OWN train step, lowered and audited for the static contracts
        # the full checker (tools/graftcheck.py) gates in CI — so every
        # recorded run says which contracts its executable passed. Costs one
        # trace, no compile; HYDRAGNN_GRAFTCHECK=0 skips the lowering, and
        # any failure degrades to an all-not_checked block (stamping is
        # telemetry and must never take the run down).
        from hydragnn_tpu.lint.ir import contract_block

        graftcheck_block = contract_block(None)
    with span("setup.drift_reference"):
        # drift reference window (obs/drift.py): per-channel feature stats +
        # per-head target stats over a bounded subsample of the training
        # set, stamped into the manifest so a later serving run can load
        # this flight record as its HYDRAGNN_DRIFT_REF and compare live
        # traffic against what this model actually trained on. Telemetry:
        # a failure degrades to an absent block, never a dead run.
        stats_block = None
        if telemetry_on:
            try:
                from hydragnn_tpu.obs.drift import build_reference

                stats_block = build_reference(
                    list(train_loader.all_samples), head_names=head_names
                )
            except Exception:
                stats_block = None
    with span("setup.graftcheck"):
        if telemetry_on and knobs.get_bool("HYDRAGNN_GRAFTCHECK", True):
            try:
                # peek_batch builds the first batch without counting as an
                # __iter__ draw, so loader wrappers that count epochs
                # (schedulers, fault harnesses) are unperturbed
                _gc_example = (
                    train_loader.peek_batch()
                    if hasattr(train_loader, "peek_batch")
                    else next(iter(train_loader))
                )
                _gc_args = (
                    (state, _gc_example, jnp.zeros((), jnp.int32))
                    if guard_nonfinite
                    else (state, _gc_example)
                )
                _pcfg = partitioner.config if partitioner is not None else None
                graftcheck_block = contract_block(
                    train_step.lower(*_gc_args).as_text(),
                    donated=True,
                    conv_bf16=bool(getattr(cfg, "conv_bf16", False)),
                    edge_pad=int(_gc_example.senders.shape[-1]),
                    data=int(getattr(_pcfg, "data", 1) or 1),
                    fsdp=int(getattr(_pcfg, "fsdp", 1) or 1),
                    zero1=bool(getattr(_pcfg, "zero1", False)),
                    residency_shapes=(
                        [(int(_gc_example.nodes.shape[-2]), int(cfg.hidden_dim))]
                        if getattr(cfg, "conv_residency", False)
                        else None
                    ),
                )
            except Exception:
                pass
    with span("setup.manifest"):
        # lineage left behind by a pod-checkpoint restore earlier in this
        # process (utils/checkpoint.load_existing_model → podckpt); consumed
        # once so only the run that actually restored stamps it
        from hydragnn_tpu.resilience import podckpt as _podckpt

        pod_lineage = _podckpt.consume_last_restore_info()
        flight.start_run(
            {
                "run": log_name,
                "log_dir": log_dir,
                "config": run_config if run_config is not None else {"NeuralNetwork": config},
                "device_kind": getattr(_dev0, "device_kind", str(_dev0)),
                "local_device_count": jax.local_device_count(),
                "mesh": {
                    "device_stack": getattr(train_loader, "device_stack", 1),
                    "process_count": jax.process_count(),
                },
                # pod-visibility identity (obs/podview.py): which host shard
                # this is and the shared run id the merge reader joins on
                "podview": {
                    "enabled": pv_on,
                    "host": pv_host,
                    "hosts": pv_hosts,
                    "run_id": pv_run_id,
                },
                "parallel": parallel_block,
                "pad_plans": {
                    "train": _loader_plan(train_loader),
                    "val": _loader_plan(val_loader),
                    "test": _loader_plan(test_loader),
                },
                "num_epoch": num_epoch,
                "start_epoch": start_epoch,
                "mixed_precision": compute_dtype is not None,
                "scan_epoch": scan_fn is not None,
                # which dispatch mode actually ran, whether it was the
                # automatic default, and why — the satellite contract: a
                # flight record always says which mode executed the epochs
                "dispatch_mode": {
                    "mode": "scan_epoch" if scan_fn is not None else "per_step",
                    "auto": scan_auto,
                    "reason": dispatch_reason,
                    # the test pass iterates batches kept on the device
                    # from set-up, or builds them every epoch, and why
                    "test_split": {
                        "path": "on_device" if test_kept else "rebuilt",
                        "reason": test_reason,
                    },
                },
                "compile_monitor_available": bool(cmon and cmon.available),
                "nonfinite_guard": sentry is not None,
                "preempt_handler": bool(preempt and preempt.available),
                "watchdog_stall_s": stall_s or None,
                "head_names": head_names,
                "diagnostics": {
                    "enabled": diag is not None,
                    "diag_every": diag.every if diag is not None else None,
                },
                # the hardware-efficiency ledger's run-constant half: what
                # one compiled train step costs and what the chip could do
                "hw_cost": ledger.manifest() if ledger is not None else {"available": False},
                # which compiled-IR contracts (docs/LINT.md CC rules) this
                # run's own lowered step passed — the in-run face of
                # tools/graftcheck.py
                "graftcheck": graftcheck_block,
                # the drift reference window serving runs compare live
                # traffic against (obs/drift.py load_reference reads it
                # straight out of this flight record)
                "stats": stats_block,
                # pod-restore lineage (resilience/podckpt.py): set when this
                # process's state came out of a sharded pod checkpoint —
                # which committed generation, the prior pod layout it was
                # cut under, and any generations skipped as torn
                **(
                    {
                        "pod_resume": {
                            "resumed_from_gen": pod_lineage.get("gen"),
                            "step": pod_lineage.get("step"),
                            "prior_hosts": pod_lineage.get("hosts"),
                            "prior_layout": pod_lineage.get("layout"),
                            "fallbacks": pod_lineage.get("fallbacks") or [],
                        }
                    }
                    if pod_lineage is not None
                    else {}
                ),
                # caller-stamped provenance (e.g. the retrain pilot's
                # fine-tune child marks which serving run + spool window it
                # trained from — pilot/tune.py)
                **(manifest_extra or {}),
            }
        )
        if resumed_from is not None:
            # a restarted run announces where it picked up — the supervisor
            # story ("one preempted + one resumed") is then readable from
            # the merged flight record alone
            flight.record("resumed", epoch=resumed_from)
        if pod_lineage is not None:
            flight.record(
                "pod_resume",
                gen=int(pod_lineage.get("gen", -1)),
                prior_hosts=pod_lineage.get("hosts"),
                prior_layout=pod_lineage.get("layout"),
                fallbacks=pod_lineage.get("fallbacks") or [],
            )

    with span("setup.exec_cache"):
        # Persistent AOT executable cache (utils/exec_cache.py): with
        # HYDRAGNN_EXEC_CACHE set — an env var strip_injection_env
        # deliberately preserves, so supervisor auto-resume restarts keep it
        # — the loop-owned train executable (per-step OR scan-epoch) is
        # deserialized from disk instead of recompiled. The loop caches a
        # DONATION-FREE twin of the step (a plain jit of the same body): a
        # deserialized donated executable is unsound inside a full training
        # process on this jax/jaxlib (scrambled output pytrees, runtime
        # aborts — utils/exec_cache.py module docstring), and the failure
        # escapes any same-process probe. Warm loads additionally ride a
        # first-execution landing check: the cached step's output
        # ``state.step`` must equal input ``step + delta`` (1 per-step,
        # num_batches for scan), else the entry is evicted with a
        # ``donation_check_failed`` miss and the fresh jitted step takes
        # over on a saved copy of the inputs.
        # Placed AFTER start_run (the --require-complete validator demands
        # run_start first) and after the ledger lowered the RAW jitted step.
        if loop_owned and start_epoch < num_epoch:
            try:
                from hydragnn_tpu.utils.exec_cache import (
                    ExecCache,
                    abstract_fingerprint,
                    compat_manifest,
                    fingerprint,
                )

                _ecache = ExecCache.from_env(flight=flight, consumer="train")
            except Exception:
                _ecache = None
            if _ecache is not None and _ecache.enabled:
                try:
                    _pc = partitioner.config if partitioner is not None else None
                    _compat = compat_manifest(
                        layout=(_pc.data, _pc.fsdp, _pc.edge) if _pc is not None else (1, 1, 1),
                        compute_dtype=compute_dtype,
                    )
                    # resume bookkeeping (auto_resume_config flips
                    # Training.continue/startfrom on a supervisor restart)
                    # selects WHICH checkpoint restores, not what compiles —
                    # it must not change the key or no resume ever hits
                    _cfg_key = dict(config)
                    _tr_parent = _cfg_key
                    if "Training" not in _tr_parent and isinstance(
                        _cfg_key.get("NeuralNetwork"), dict
                    ):
                        _nn_key = dict(_cfg_key["NeuralNetwork"])
                        _cfg_key["NeuralNetwork"] = _nn_key
                        _tr_parent = _nn_key
                    if isinstance(_tr_parent.get("Training"), dict):
                        _tr_key = dict(_tr_parent["Training"])
                        for _vol in ("continue", "startfrom"):
                            _tr_key.pop(_vol, None)
                        _tr_parent["Training"] = _tr_key
                    _arch = fingerprint(_cfg_key, abstract_fingerprint(state))
                    _is_scan = scan_fn is not None
                    if _is_scan:
                        _stacked0 = train_loader.stacked_device_batches(0)
                        _order0 = jnp.arange(len(train_loader), dtype=jnp.int32)
                        _cargs = (
                            (state, _stacked0, _order0, jnp.zeros((), jnp.int32))
                            if guard_nonfinite
                            else (state, _stacked0, _order0)
                        )
                        _label, _delta, _raw = (
                            "scan_epoch", int(_order0.shape[0]), scan_fn,
                        )
                    else:
                        _example0 = next(iter(train_loader))
                        _cargs = (
                            (state, _example0, jnp.zeros((), jnp.int32))
                            if guard_nonfinite
                            else (state, _example0)
                        )
                        _label, _delta, _raw = "train_step", 1, train_step
                    # the donation-free twin: jit of the same body without
                    # donate_argnums. Costs one extra state-sized buffer
                    # while the cache is enabled; buys executables that
                    # survive the serialize round trip. Donation-ness is
                    # part of the key — the two programs are not the same
                    # executable.
                    _body = getattr(_raw, "__wrapped__", None)
                    _cache_fn = jax.jit(_body) if _body is not None else _raw
                    _donated = _body is None
                    _ckey = fingerprint(
                        _label, _arch, abstract_fingerprint(_cargs), _donated
                    )
                    # marked AFTER arg construction: the eager jnp.arange
                    # / jnp.zeros scalars above cost one tiny compile each
                    # per process and would pollute the zero-compile number
                    if cmon is not None:
                        cmon.mark("exec_cache_build")
                    _exe, _hit, _build_s = _ecache.get_or_compile(
                        _ckey, _cache_fn, _cargs, _compat,
                        donated=_donated, label=_label,
                    )
                    if _hit:
                        _exe = _landing_checked(
                            _exe, _cache_fn, _ecache, _ckey,
                            expected_delta=_delta, label=_label,
                        )
                    if _is_scan:
                        scan_fn = _exe
                    else:
                        train_step = _exe
                    # the scoped zero-compile evidence the fault-injection
                    # smoke pins: how many XLA compiles the build took (0 on
                    # a warm hit) and how long restart-to-ready cost
                    flight.record(
                        "exec_cache",
                        event="train_ready",
                        hit=_hit,
                        compiles=(
                            cmon.count_since("exec_cache_build")
                            if cmon is not None
                            else None
                        ),
                        build_s=round(_build_s, 3),
                        mode="scan_epoch" if scan_fn is not None else "per_step",
                    )
                except Exception as exc:
                    # cache wiring must never take training down: fall back
                    # to the live jitted path and say so in the record
                    flight.record(
                        "exec_cache", event="wiring_failed",
                        error=str(exc)[-200:],
                    )

    with span("setup.manifest"):
        # Visualization (reference: Visualizer wiring, train_validate_test.py:
        # 71-97,90-96: initial-solution scatter, per-epoch histograms, final
        # plots). Plots are rank-0 only.
        visualizer = None
        if create_plots and jax.process_index() == 0:
            from hydragnn_tpu.postprocess.visualizer import Visualizer

            visualizer = Visualizer(
                log_name,
                num_heads=cfg.num_heads,
                head_names=cfg.output_names,
                log_dir=log_dir,
            )
        # all_samples = the full split, not this process's shard; also reused
        # by the final per-node plot dispatch
        viz_nodes_per_graph = (
            [s.num_nodes for s in test_loader.all_samples]
            if visualizer is not None and hasattr(test_loader, "all_samples")
            else None
        )
        if viz_nodes_per_graph is not None:
            # test-set node-count histogram at setup (reference: Visualizer
            # num_nodes_plot wiring, train_validate_test.py:71-97)
            visualizer.num_nodes_plot(viz_nodes_per_graph)
        if visualizer is not None and plot_init_solution:
            try:
                _, _, tv, pv = test_epoch(
                    test_loader, state, eval_step_out, cfg, verbosity, return_samples=True
                )
                visualizer.create_scatter_plots(tv, pv, iepoch=-1)
            except BaseException as exc:
                _abort_telemetry(exc, 0)
                raise

    def _declare_lost(lost, epoch_now: int) -> None:
        """Record each newly-lost peer exactly once: one ``host_lost``
        flight event per host plus the ``podview.lost_host(s)`` gauges
        the podview_host_lost trigger rule reads."""
        fresh = pv_signaler.mark_declared(lost)
        if not fresh:
            return
        from hydragnn_tpu.obs import get_registry

        reg = get_registry()
        reg.gauge("podview.lost_hosts").set(
            float(len(set(pv_signaler.lost_hosts()) | set(lost)))
        )
        for h in fresh:
            reg.gauge("podview.lost_host").set(float(h))
            flight.record(
                "host_lost",
                host=int(h),
                epoch=int(epoch_now),
                lost_after_s=pv_signaler.lost_after_s,
            )

    def _pod_checkpoint(ckpt_state, gen: int) -> None:
        """One sharded generation cut (resilience/podckpt.py): every
        host writes its shard + sha sidecar + manifest; rank 0
        bounded-waits for the peers' manifests, validates them, and
        writes ``gen<N>.COMMIT`` LAST. Runs BEFORE save_train_meta so a
        commit that dies on a lost peer leaves the meta sidecar
        describing the last COMMITTED generation, not this torn one."""
        from hydragnn_tpu.resilience import podckpt
        from hydragnn_tpu.resilience.preempt import PodHostLost

        run_dir = os.path.join(log_dir, log_name)
        pv_signaler.heartbeat(epoch=gen, force=True)
        podckpt.save_pod_shard(
            ckpt_state,
            run_dir,
            gen=gen,
            host=pv_host,
            hosts=pv_hosts,
            step=int(jax.device_get(ckpt_state.step)),
            layout=(
                parallel_block.get("layout")
                if isinstance(parallel_block, dict)
                else None
            ),
        )
        if pv_host != 0:
            # only rank 0 waits at the commit point: the simulated-host
            # CI mode runs hosts sequentially, and a non-zero host
            # blocking here would deadlock it
            return
        commit = podckpt.commit_generation(
            run_dir, gen, pv_hosts, signaler=pv_signaler
        )
        if commit.get("committed"):
            podckpt.prune_generations(run_dir)
            return
        # proceed-and-record: the failed commit is itself flight
        # evidence; a LOST peer additionally raises the typed exit so
        # the supervisor restarts from the last committed generation
        flight.record(
            "error",
            error=(
                f"pod generation {gen} failed to commit: "
                f"lost={commit.get('lost')} bad={commit.get('bad')} "
                f"timeout={commit.get('timeout')}"
            ),
            error_type="PodCommitFailed",
        )
        lost = commit.get("lost") or []
        if lost:
            _declare_lost(lost, gen)
            raise PodHostLost(lost, gen)

    def _write_checkpoint(ckpt_state, epoch_next: int, early_stopped: bool) -> None:
        with span("epoch.checkpoint"):
            from hydragnn_tpu.utils.checkpoint import save_model, save_train_meta

            save_model(ckpt_state, log_name, log_dir, verbosity, keep_last=ckpt_keep_last)
            if pod_ckpt_on:
                _pod_checkpoint(ckpt_state, epoch_next)
            save_train_meta(
                {
                    "epoch": epoch_next,
                    # the optimizer step ties this sidecar to the weight file
                    # it was written with (resume verifies the pair matches)
                    "step": int(jax.device_get(ckpt_state.step)),
                    "early_stopped": early_stopped,
                    "scheduler": {
                        "best": scheduler.best,
                        "num_bad_epochs": scheduler.num_bad_epochs,
                    },
                    "stopper": {
                        "count": stopper.count if stopper else 0,
                        "min_loss": stopper.min_loss if stopper else float("inf"),
                    },
                    "history": history,
                },
                log_name,
                log_dir,
            )

    def _preempt_exit(ckpt_state, epoch: int, coordinated_from=None):
        """Graceful preemption: checkpoint + meta pair for this epoch,
        ``preempt`` + ``run_end{status:"preempted"}`` flight events,
        telemetry closed — all inside the grace window the handler's
        hard-exit timer enforces — then the typed exception the driver's
        run_guard maps to EXIT_PREEMPTED. ``coordinated_from`` marks a
        cut taken on a PEER's announcement rather than our own signal."""
        signum = preempt.signum if preempt is not None else 0
        if signum is None:
            signum = 0
        _write_checkpoint(ckpt_state, epoch, early_stopped=False)
        flight.record(
            "preempt",
            signal=signum,
            epoch=epoch,
            step=int(jax.device_get(ckpt_state.step)),
            **(
                {"coordinated_from": int(coordinated_from)}
                if coordinated_from is not None
                else {}
            ),
        )
        if incidents is not None:
            incidents.finalize()
        _hold_late()
        flight.end_run(
            status="preempted", epochs=epoch - start_epoch, **_flush_late()
        )
        if cmon is not None:
            cmon.stop()
        if own_flight:
            flight.close()
        try:
            writer.flush()
            writer.close()
        except Exception:
            pass
        hooks.teardown()
        raise TrainingPreempted(signum, epoch)

    def _sentry_rollback(cur_state, epoch: int, consec_end: int):
        """K consecutive non-finite steps at the epoch's tail: restore
        the last good checkpoint with a reduced LR instead of
        continuing; give up (typed, fail-fast exit) when the rollback
        budget is spent or there is nothing to roll back to."""
        from hydragnn_tpu.resilience import NonFiniteRollbackExhausted
        from hydragnn_tpu.utils.checkpoint import (
            checkpoint_exists,
            load_existing_model,
        )

        if sentry.exhausted or not checkpoint_exists(log_name, log_dir):
            raise NonFiniteRollbackExhausted(
                f"epoch {epoch} ended with {consec_end} consecutive "
                f"non-finite steps; rollbacks used {sentry.rollbacks}/"
                f"{sentry.max_rollbacks}"
                + (
                    ""
                    if checkpoint_exists(log_name, log_dir)
                    else " and no checkpoint exists to roll back to"
                )
            )
        restored = load_existing_model(cur_state, log_name, log_dir)
        lr = max(
            current_learning_rate(restored.opt_state) * sentry.lr_factor, 1e-8
        )
        restored = restored.replace(
            opt_state=set_learning_rate(restored.opt_state, lr)
        )
        sentry.on_rollback()
        flight.record(
            "rollback",
            epoch=epoch,
            consec=consec_end,
            rollbacks=sentry.rollbacks,
            lr=lr,
        )
        print_distributed(
            verbosity,
            f"non-finite sentry: epoch {epoch} ended with {consec_end} "
            f"consecutive bad steps — rolled back to the last good "
            f"checkpoint (lr -> {lr:g})",
        )
        return restored

    # every span since the entry (api.run_training's, then the ones above)
    flight.record("setup", phases=drain())

    timer = Timer("train_validate_test")
    timer.start()
    epochs_done = start_epoch
    try:
      for epoch in range(start_epoch, num_epoch):
        _hold_late()
        in_epoch = epoch
        drain_counts()  # whatever a rolled-back epoch left
        with span("epoch", epoch=epoch):
            hooks.epoch_start(epoch)
            if hooks.preempted:
                _preempt_exit(state, epoch)
            if pv_signaler is not None:
                # a SIGTERM landing anywhere in this epoch announces the
                # cut at its END boundary, so every host checkpoints the
                # same generation (epoch + 1)
                if preempt is not None:
                    preempt.proposed_gen = epoch + 1
                pv_signaler.heartbeat(epoch=epoch, force=True)
            for loader in (train_loader, val_loader, test_loader):
                if hasattr(loader, "set_epoch"):
                    loader.set_epoch(epoch)
            if profiler is not None:
                profiler.set_current_epoch(epoch)
            if cmon is not None:
                cmon.mark("epoch_start")
            spans.epoch_start(epoch)

            # the profiler context closes an in-flight trace at epoch end even
            # when the epoch has fewer steps than its schedule expects
            t_train0 = time.perf_counter()
            with (profiler if profiler is not None else contextlib.nullcontext()):
                if scan_fn is not None:
                    if incidents is not None:
                        # scan mode is one dispatch per epoch: a single tick
                        # here spans the whole epoch's capture window
                        incidents.tick()
                    state, train_loss, train_tasks = train_epoch_scan(
                        train_loader, state, scan_fn, epoch, diag=diag,
                        sentry=sentry,
                    )
                else:
                    state, train_loss, train_tasks = train_epoch(
                        train_loader,
                        state,
                        train_step,
                        verbosity,
                        profiler=profiler,
                        spans=spans,
                        hooks=hooks,
                        diag=diag,
                        incidents=incidents,
                    )
            # the epoch metrics above already synced at finalize, so this
            # wall time covers every dispatched train step's execution —
            # the denominator of the epoch's achieved-TFLOP/s and MFU
            train_wall_s = time.perf_counter() - t_train0
            if hooks.preempted and pv_signaler is None:
                # mid-epoch graceful stop: this epoch is incomplete, resume
                # re-runs it (the meta pair written here says so). Pod mode
                # instead defers to the epoch's END boundary — the
                # generation the SIGTERM handler announced to the peers —
                # racing the handler's hard-exit grace timer
                _preempt_exit(state, epoch)
            nonfinite = None
            if sentry is not None:
                skipped, consec_end = sentry.epoch_finalize()
                if skipped:
                    from hydragnn_tpu.obs import get_registry

                    get_registry().counter("train.nonfinite_skipped").inc(skipped)
                    nonfinite = {"skipped": skipped, "consec_end": consec_end}
                if sentry.needs_rollback(consec_end):
                    state = _sentry_rollback(state, epoch, consec_end)
                    epochs_done = epoch + 1
                    continue  # the rolled-back epoch consumed its slot
            with span("epoch.validate"):
                if scan_eval_fn is not None:
                    val_loss, val_tasks = evaluate_epoch_scan(val_loader, state, scan_eval_fn)
                else:
                    val_loss, val_tasks = evaluate_epoch(val_loader, state, eval_step, verbosity)
            collect = plot_hist_solution and visualizer is not None
            # introspection reuses the test() gather path for per-head
            # MAE/RMSE — same eval executable, extra host-side gathering
            with span("epoch.test"):
                test_loss, test_tasks, true_values, predicted_values = test_epoch(
                    test_loader,
                    state,
                    eval_step_out,
                    cfg,
                    verbosity,
                    return_samples=collect or introspect_on,
                )
            head_quality = None
            with span("epoch.head_quality"):
                if introspect_on and true_values:
                    from hydragnn_tpu.obs.introspect import per_head_error_metrics

                    head_quality = per_head_error_metrics(
                        true_values, predicted_values, head_names
                    )
                if collect:
                    visualizer.create_error_histograms(
                        true_values, predicted_values, iepoch=epoch
                    )
            with span("epoch.diag_snapshot"):
                diag_snap = diag.epoch_snapshot() if diag is not None else None
            with span("epoch.record"):
                state = scheduler.step(state, val_loss)

                lr = current_learning_rate(state.opt_state)
                history["train_loss"].append(train_loss)
                history["val_loss"].append(val_loss)
                history["test_loss"].append(test_loss)
                history["train_tasks"].append(train_tasks.tolist())
                history["val_tasks"].append(val_tasks.tolist())
                history["test_tasks"].append(test_tasks.tolist())
                history["lr"].append(lr)

                print_distributed(
                    verbosity,
                    f"Epoch: {epoch:02d}, Train Loss: {train_loss:.8f}, "
                    f"Val Loss: {val_loss:.8f}, Test Loss: {test_loss:.8f}",
                )
                if epoch == 0:
                    # post-first-epoch peak = steady-state footprint (weights +
                    # activations + optimizer state); the reference prints peak
                    # GPU memory around the train step (distributed.py:236-243)
                    from hydragnn_tpu.utils.print_utils import print_peak_memory

                    print_peak_memory(verbosity, prefix=f"epoch {epoch}")
                # per-task metrics are keyed by head name everywhere (flight,
                # tensorboard, metrics.jsonl) — a multi-head record is readable
                # without cross-referencing the config's output order
                train_tasks_named = _named_tasks(head_names, train_tasks)
                val_tasks_named = _named_tasks(head_names, val_tasks)
                test_tasks_named = _named_tasks(head_names, test_tasks)
                hw = (
                    ledger.epoch_record(steps=len(train_loader), wall_s=train_wall_s)
                    if ledger is not None
                    else None
                )

                writer.add_scalar("train error", train_loss, epoch)
                writer.add_scalar("validate error", val_loss, epoch)
                writer.add_scalar("test error", test_loss, epoch)
                for name in head_names:
                    if name in train_tasks_named:
                        writer.add_scalar(
                            f"heads/{name}/train_loss", train_tasks_named[name], epoch
                        )
                    if name in val_tasks_named:
                        writer.add_scalar(
                            f"heads/{name}/val_loss", val_tasks_named[name], epoch
                        )
                if metrics_path is not None:
                    with open(metrics_path, "a") as f:
                        f.write(
                            json.dumps(
                                {
                                    "epoch": epoch,
                                    "train_loss": train_loss,
                                    "val_loss": val_loss,
                                    "test_loss": test_loss,
                                    "lr": lr,
                                    "train_tasks": train_tasks_named,
                                    "val_tasks": val_tasks_named,
                                }
                            )
                            + "\n"
                        )

                # per-epoch flight record: losses + the step-time decomposition
                # + compile counts. After the first executed epoch every train
                # step function is compiled; further compiles are the silent
                # recompile class this exists to surface.
                span_snap = None if scan_fn is not None else spans.epoch_snapshot()
                step_time = (
                    dict(span_snap, mode="per_step")
                    if span_snap is not None
                    # scan mode is ONE device dispatch per epoch: its host
                    # side is in ``phases`` (train.stack, train.diag_sample,
                    # train.dispatch, train.sync), its steps exist only on
                    # the device
                    else {"mode": "scan_epoch" if scan_fn is not None else "disabled"}
                )
                counts = drain_counts()
                compiles: Dict[str, Any] = {"available": bool(cmon and cmon.available)}
                if cmon is not None:
                    n_compiles = cmon.count_since("epoch_start")
                    compiles["count"] = n_compiles
                    compiles["seconds"] = round(cmon.seconds_since("epoch_start"), 6)
                    compiles["unexpected"] = bool(
                        cmon.available and epoch > start_epoch and n_compiles > 0
                    )
                # heads: the model-level half of the epoch record — per-head
                # losses always; sampled gradient diagnostics and eval MAE/RMSE
                # when introspection produced them this epoch
                heads: Dict[str, Any] = {"names": head_names, "available": False}
                if diag_snap is not None:
                    heads.update(diag_snap)
                if head_quality is not None:
                    heads["available"] = True
                    heads["mae"] = {n: m["mae"] for n, m in head_quality.items()}
                    heads["rmse"] = {n: m["rmse"] for n, m in head_quality.items()}
                extra: Dict[str, Any] = {}
                if nonfinite:
                    extra["nonfinite"] = nonfinite
                if introspect_on:
                    extra["heads"] = heads
                    extra["hw"] = hw if hw is not None else {"available": False}
                flight.epoch(
                    epoch,
                    train_loss=train_loss,
                    val_loss=val_loss,
                    test_loss=test_loss,
                    lr=lr,
                    train_tasks=train_tasks_named,
                    val_tasks=val_tasks_named,
                    test_tasks=test_tasks_named,
                    step_time=step_time,
                    compiles=compiles,
                    # real graphs through an optimizer step, and steps
                    graphs=int(counts.get("graphs", 0)),
                    steps=int(counts.get("steps", 0)),
                    # the program's spans closed so far this epoch
                    # (obs/spans.py:span; docs/OBSERVABILITY.md "Program
                    # spans"); the ones still open follow as phases_late
                    phases=drain(),
                    **_flush_late(),
                    **extra,
                )

                # pod-visibility (obs/podview.py): append this host's epoch
                # summary to its shard — the lightweight cross-host exchange
                # unit — and, on rank 0, fold every host's summaries into the
                # podview.* skew gauges. Runs BEFORE trigger evaluation so the
                # step_skew / host_stall rules see THIS epoch's skew.
                if pv_on:
                    _t_pv0 = time.perf_counter()
                    pv_summary = {
                        "hosts": pv_hosts,
                        "epoch_s": round(train_wall_s, 6),
                        "data_wait_s": (span_snap or {}).get("data_wait_s"),
                        "dispatch_s": (span_snap or {}).get("dispatch_s"),
                        "steps": (span_snap or {}).get("steps", len(train_loader)),
                        "nonfinite_skipped": (nonfinite or {}).get("skipped", 0),
                        "mfu": hw.get("mfu") if hw is not None else None,
                    }
                    flight.record(
                        "host_epoch",
                        epoch=epoch,
                        host=pv_host,
                        run_id=pv_run_id,
                        **pv_summary,
                    )
                    if pv_monitor is not None:
                        pv_skew = pv_monitor.observe_epoch(
                            epoch, dict(pv_summary, epoch=epoch)
                        )
                        if pv_skew is not None:
                            flight.record("podview", **pv_skew)
                    pv_overhead_s += time.perf_counter() - _t_pv0

                # pod liveness at the epoch boundary (resilience/podckpt.py):
                # refresh this host's beat, then declare any peer whose beats
                # lapsed past HYDRAGNN_POD_LOST_AFTER_S — one host_lost flight
                # event per host, plus the podview.lost_hosts gauge the
                # podview_host_lost trigger rule (evaluated just below) reads
                if pv_signaler is not None:
                    pv_signaler.heartbeat(epoch=epoch + 1, force=True)
                    lost_now = pv_signaler.lost_hosts()
                    if lost_now:
                        # _declare_lost dedupes, so polling every epoch still
                        # yields exactly one event per lost host
                        _declare_lost(lost_now, epoch + 1)

                # SLO trigger evaluation at the epoch boundary: feed the rolling
                # series the rules watch, then let at most one verdict open an
                # incident whose profiler capture runs during the NEXT epoch's
                # ticks (docs/OBSERVABILITY.md "SLO triggers and incidents").
                if trig_engine is not None:
                    trig_engine.observe("train_loss", train_loss)
                    trig_engine.observe("val_loss", val_loss)
                    if hw is not None and hw.get("mfu") is not None:
                        trig_engine.observe("mfu", hw["mfu"])
                    for verdict in trig_engine.evaluate():
                        # the bundle's trigger.json carries the full verdict;
                        # open_incident records the flight "incident" pointer
                        if incidents is not None:
                            incidents.open_incident(verdict, flight=flight)
                from hydragnn_tpu.utils.tensorboard import write_scalar_dict

                if span_snap is not None:
                    write_scalar_dict(writer, span_snap, epoch, prefix="obs/step_time")
                    if compiles.get("count") is not None:
                        writer.add_scalar("obs/compiles", compiles["count"], epoch)
                if diag_snap is not None:
                    for name in head_names:
                        if name in diag_snap.get("grad_norm", {}):
                            writer.add_scalar(
                                f"heads/{name}/grad_norm",
                                diag_snap["grad_norm"][name],
                                epoch,
                            )
                    writer.add_scalar("obs/update_ratio", diag_snap["update_ratio"], epoch)
                if head_quality is not None:
                    for name, m in head_quality.items():
                        if m["mae"] is not None:
                            writer.add_scalar(f"heads/{name}/mae", m["mae"], epoch)
                            writer.add_scalar(f"heads/{name}/rmse", m["rmse"], epoch)
                if hw is not None and hw.get("mfu") is not None:
                    writer.add_scalar("obs/hw/mfu", hw["mfu"], epoch)
                if hw is not None and hw.get("achieved_tflops") is not None:
                    writer.add_scalar(
                        "obs/hw/achieved_tflops", hw["achieved_tflops"], epoch
                    )

                # Prometheus textfile export for training (serve already has
                # one): one atomic train.prom snapshot per epoch, gated by
                # Training.prometheus_dir (docs/OBSERVABILITY.md)
                # rank 0 keeps the legacy train.prom name; any other host (real
                # process or simulated podview host) writes train.host<k>.prom
                # so a second host never clobbers the first
                prom_dir = training.get("prometheus_dir")
                if prom_dir and telemetry_on and (jax.process_index() == 0 or pv_on):
                    from hydragnn_tpu.obs import get_registry
                    from hydragnn_tpu.obs.export import registry_to_prometheus

                    reg = get_registry()
                    reg.gauge("train.epoch").set(epoch)
                    reg.gauge("train.loss").set(train_loss)
                    reg.gauge("train.val_loss").set(val_loss)
                    reg.gauge("train.lr").set(lr)
                    for name, v in train_tasks_named.items():
                        reg.gauge(f"train.head.{name}.loss").set(v)
                    if diag_snap is not None:
                        for name, v in diag_snap.get("grad_norm", {}).items():
                            reg.gauge(f"train.head.{name}.grad_norm").set(v)
                    if hw is not None and hw.get("mfu") is not None:
                        reg.gauge("train.mfu").set(hw["mfu"])
                    registry_to_prometheus(
                        reg,
                        _podview.host_artifact_path(
                            os.path.join(prom_dir, "train.prom"), pv_host
                        ),
                    )

                stop = stopper is not None and stopper(val_loss)
                epochs_done = epoch + 1

            if ckpt_every and (epoch + 1) % ckpt_every == 0:
                _write_checkpoint(state, epoch + 1, early_stopped=False)

            if hooks.preempted:
                # SIGTERM landed during val/test/plots (or, pod mode,
                # anywhere in the epoch): this epoch is complete and
                # recorded, resume continues from the next
                _preempt_exit(state, epoch + 1)

            if pv_signaler is not None:
                req = pv_signaler.preempt_request()
                if (
                    req is not None
                    and int(req.get("host", -1)) != pv_host
                    and epoch + 1 >= int(req.get("gen", 0))
                ):
                    # a PEER announced preemption: cut the same generation
                    # at this boundary so the pod's shards agree and the
                    # supervisor restarts everyone from one COMMIT
                    _preempt_exit(
                        state,
                        epoch + 1,
                        coordinated_from=int(req.get("host", -1)),
                    )

            if stop:
                print_distributed(verbosity, f"Early stopping at epoch {epoch}")
                break
    except TrainingPreempted:
        # _preempt_exit already wrote the checkpoint, the flight
        # events, and tore telemetry down — only the process-global
        # timer still needs closing before the exception unwinds
        timer.stop_if_running()
        raise
    except BaseException as exc:
        # the registry timer is process-global: close its interval or
        # every later train_validate_test in this process raises
        # "Timer already running" (same discipline as run_training's
        # try/finally around its total_training timer)
        timer.stop_if_running()
        _abort_telemetry(exc, epochs_done - start_epoch)
        raise
    timer.stop()
    _hold_late()
    in_epoch = None  # what follows belongs to no epoch

    # A resume that trained zero epochs (e.g. continuing an early-stopped
    # or completed run) must be a pure no-op: re-running BN recalibration
    # would mutate batch_stats and rewriting the checkpoint would change
    # the saved model file without any training having happened.
    ran_epochs = epochs_done > start_epoch
    resumed_noop = training.get("continue") == 1 and not ran_epochs

    try:
        # BatchNorm recalibration: the in-training running-stat EMA trails
        # the last few (noisy, small) batches; with frozen final parameters,
        # two passes over the train set re-estimate faithful eval statistics.
        if (
            stats_step is not None
            and training.get("bn_recalibration", True)
            and not resumed_noop
        ):
            for _ in range(2):
                for b in train_loader:
                    hooks.beat()  # recalibration batches count as liveness
                    state = stats_step(state, b)

        # Final checkpoint+meta pair AFTER BN recalibration: the model file
        # and the loop-state sidecar must describe the same state (a mid-run
        # meta against the final recalibrated weights would make a later
        # continue run replay epochs on the wrong state); an early-stopped
        # run is marked so resume honors the stop instead of training on.
        if ckpt_every and not resumed_noop:
            _write_checkpoint(
                state, epochs_done, early_stopped=bool(stopper and stopper.count >= stopper.patience)
            )

        writer.flush()
        writer.close()

        # Final plots (reference: train_validate_test.py:173-215 rank-0 plots).
        if visualizer is not None:
            _, _, tv, pv = test_epoch(
                test_loader, state, eval_step_out, cfg, verbosity, return_samples=True
            )
            visualizer.create_scatter_plots(tv, pv)
            visualizer.create_plot_global(tv, pv)
            # vector parity grids, per-node diagnostics (fixed-size graphs),
            # and the scalar/vector global-analysis figures (reference:
            # visualizer.py:134-280, 387-613)
            visualizer.create_reference_plot_suite(
                tv, pv, cfg.output_type, viz_nodes_per_graph
            )
            visualizer.plot_history(history)
    except BaseException as exc:
        _abort_telemetry(exc, epochs_done - start_epoch)
        raise

    # run_end summary: the flight record's terminal event — per-process
    # timers, whatever landed in the global metrics registry (loader
    # prefetch accounting, ...), and the whole-run compile count.
    if cmon is not None:
        cmon.stop()
    if incidents is not None:
        # an incident still capturing at run end closes as "truncated"
        incidents.finalize()
    from hydragnn_tpu.obs import get_registry
    from hydragnn_tpu.utils.time_utils import timers_snapshot

    _hold_late()
    flight.end_run(
        status="completed",
        **_flush_late(),
        epochs=epochs_done - start_epoch,
        epochs_total=epochs_done,
        early_stopped=bool(stopper and stopper.count >= stopper.patience),
        best_val_loss=min(history["val_loss"]) if history["val_loss"] else None,
        final_lr=history["lr"][-1] if history["lr"] else None,
        compiles=cmon.snapshot() if cmon is not None else None,
        timers=timers_snapshot(),
        metrics=get_registry().snapshot(),
        # hardware-efficiency rollup: mean/max MFU across epochs and
        # the run's device-memory high-water mark
        hw=ledger.run_summary() if ledger is not None else None,
        triggers=(
            trig_engine.summary(incidents.capture_s if incidents else 0.0)
            if trig_engine is not None
            else None
        ),
        # measured cost of the pod-visibility plane: shard writes +
        # rank-0 skew folds as a fraction of run wall time (the <1%
        # clean-path acceptance gate ci.sh asserts)
        podview=(
            {
                "enabled": True,
                "host": pv_host,
                "hosts": pv_hosts,
                "run_id": pv_run_id,
                "overhead_s": round(pv_overhead_s, 6),
                "overhead_frac": round(
                    pv_overhead_s
                    / max(time.perf_counter() - pv_t_run0, 1e-9),
                    8,
                ),
            }
            if pv_on
            else None
        ),
    )
    if own_flight:
        flight.close()
    hooks.teardown()

    return state, history
