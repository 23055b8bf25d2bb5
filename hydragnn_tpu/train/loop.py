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

import time
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import multihost_utils

from hydragnn_tpu.models.base import HydraModel, ModelConfig
from hydragnn_tpu.obs.introspect import HeadDiagnostics, make_diagnostics_step
from hydragnn_tpu.obs.spans import StepSpans, count, span, span_iter
from hydragnn_tpu.parallel.mesh import local_view
from hydragnn_tpu.resilience import NonFiniteRollbackExhausted, TrainingPreempted
from hydragnn_tpu.train.optimizer import current_learning_rate, set_learning_rate
from hydragnn_tpu.train.run import config_profiler, prepare_run
from hydragnn_tpu.train.state import (
    TrainState,
    make_diagnosed_first_step,
    make_eval_step,
    make_scan_epoch,
    make_scan_eval,
    make_stats_step,
    make_train_step,
)
from hydragnn_tpu.utils import knobs
from hydragnn_tpu.utils.checkpoint import (
    LoopState, checkpoint_exists, load_existing_model, save_model, save_train_meta,
)
from hydragnn_tpu.utils.exec_cache import (
    ExecCache, abstract_fingerprint, compat_manifest, fingerprint,
)
from hydragnn_tpu.utils.print_utils import iterate_tqdm, print_distributed


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
        _sentry_read_back(sentry)
    count("graphs", acc.graphs)
    count("steps", steps)
    return state, avg_loss, avg_tasks


def _sentry_read_back(sentry) -> None:
    """The epoch's one read-back of the guard's flags, the last work of the
    train phase (``Run.close_nonfinite`` takes what it read)."""
    if sentry is not None:
        with span("train.sentry"):
            sentry.epoch_finalize()


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
    """One training epoch as device-resident dispatches (``Training.
    scan_epoch``): lax.scan over the loader's device-resident stacked
    batches, shuffled device-side by an epoch-seeded permutation of the
    batch axis (sample-to-batch membership reshuffles only when the
    loader's ``scan_reshuffle_every`` is set — see
    ``GraphLoader.stacked_device_batches``). Same weighted-metric
    semantics as ``train_epoch``.

    ``scan_fn`` is ONE callable that runs the whole epoch: ``(state,
    stacked, order[, consec]) -> (state, losses[B], tasks[B, H],
    counts[B][, bads[B], consec])``, one entry per step in ``order``'s
    order (``benchmark/taps.py`` wraps it and reads positions 0, 1, 3).
    ``diag`` (obs/introspect.py:HeadDiagnostics) is given where that
    callable is :meth:`DispatchPlan.first_step_epoch`, whose first step
    is the diagnosed one: the device dictionary it returns behind the
    rest is handed to ``diag``, and nothing is dispatched for the
    diagnostics here. ``sentry``: when the scan_fn is the GUARDED variant
    (make_scan_epoch(guard_nonfinite=True)), the per-step bad flags and
    the carry's consecutive counter are handed to it, device-resident."""
    # the places the chip is known to wait inside this call
    with span("epoch.train"):
        with span("train.stack"):
            stacked = loader.stacked_device_batches(epoch)
            nb = len(loader)
            if loader.shuffle:
                order = np.random.default_rng(loader.seed + epoch).permutation(nb)
            else:
                order = np.arange(nb)
            order_dev = jnp.asarray(order, dtype=jnp.int32)
        with span("train.dispatch"):
            consec = () if sentry is None else (sentry.consec,)
            out = scan_fn(state, stacked, order_dev, *consec)
            if diag is not None:
                *out, diagnostics = out
                diag.count_step(diagnostics)
            if sentry is not None:
                state, losses, tasks, counts, bads, consec = out
                sentry.observe_scan(bads, consec)
            else:
                state, losses, tasks, counts = out
        with span("train.sync"):
            avg_loss, avg_tasks, graphs = _finalize_scan(losses, tasks, counts)
        _sentry_read_back(sentry)
    count("graphs", graphs)
    count("steps", nb)
    # steps whose update came from the program that diagnosed them
    count("diagnosed_steps", int(diag is not None))
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


class DispatchPlan:
    """Which programs run an epoch, decided once at set-up: the choice
    (whole-epoch ``lax.scan`` or per step, and why; the non-finite guard;
    where the test split lives, and why; which program diagnoses the
    sampled step, :meth:`open_diagnostics`), the step functions it built or
    was handed, the executable-cache twin of the train program
    (:meth:`wire_exec_cache`) and the manifest's ``dispatch_mode`` block.
    The epoch loop calls :meth:`train`, :meth:`validate` and :meth:`test`
    and never asks which mode it is in.

    ``Training.scan_epoch`` explicit true/false always wins; UNSET
    defaults to the scan when eligible (``_scan_auto_eligible``:
    single-device mesh + device-resident stacked loader), with automatic
    fallback to per-step dispatch. The epoch functions are looked up in
    this module's namespace at call time (``benchmark/taps.py`` replaces
    them for the length of a run)."""

    def __init__(self, model, tx, config, loaders, *, train_step, eval_step, eval_step_out,
                 stats_step, partitioner, profiler, verbosity):
        training = config["Training"]
        self.cfg = model.cfg
        self.train_loader, self.val_loader, self.test_loader = loaders
        train_loader, val_loader, test_loader = loaders
        self.verbosity = verbosity
        # Training.mixed_precision: bf16 forward/backward with f32 master
        # params/optimizer/BN stats (MXU-native; absent from the reference,
        # which has no AMP path — SURVEY §2.2 "explicitly absent")
        self.compute_dtype = jnp.bfloat16 if training.get("mixed_precision") else None
        self.loop_owned = loop_owned = train_step is None
        scan_cfg = training.get("scan_epoch")
        self.auto = scan_cfg is None and loop_owned
        if not loop_owned:
            use_scan, reason = False, "caller-supplied train step"
        elif scan_cfg is None:
            use_scan, reason = _scan_auto_eligible(
                train_loader, config, partitioner=partitioner, profiler=profiler
            )
            if use_scan:
                # the stack must actually materialize, or the run goes
                # per-step and says why
                refusal = _stack_refusal(train_loader)
                if refusal is not None:
                    use_scan, reason = False, f"stacking failed: {refusal}"
        else:
            use_scan, reason = scan_dispatch_planned(config, single_device=True)
        if (
            not use_scan
            and getattr(train_loader, "shuffle", False)
            and getattr(train_loader, "fixed_membership", False)
        ):
            # built for a scan (or with cache_device_batches) and iterated
            # per step: the loader keeps its batches and shuffles their order
            reason += "; train batches keep their membership, only their order is shuffled"
        self.reason = reason
        # Non-finite guard (hydragnn_tpu/resilience/sentry.py): folded into
        # the loop-owned step in BOTH dispatch modes — per-step via the
        # guarded jitted step, scan via the guarded scan body threading the
        # consecutive-bad counter through the carry. Sharded callers pass
        # their own step and keep their own policy.
        self.guard_nonfinite = bool(training.get("nonfinite_guard", True)) and loop_owned
        self._build = build = dict(
            compute_dtype=self.compute_dtype,
            remat=bool(training.get("remat", False)),
            guard_nonfinite=self.guard_nonfinite,
        )
        self.scan_fn = self.scan_eval_fn = None
        # set by open_diagnostics, which set-up calls once telemetry is known
        self.first_step = self.diag_every = None
        # the first epoch without a sample on the first-step path: the plain
        # scan is traced there, not in epoch 0 (Run.record_epoch's ``compiles``)
        self.first_plain_epoch = None
        self.diag_path, self.diag_reason = "off", "introspection not opened"
        if use_scan:
            # the scan after a diagnosed first step is the same jitted function
            # called with ``first``, until wire_exec_cache replaces either
            self.scan_fn = self.scan_rest = make_scan_epoch(model, tx, **build)
            if eval_step is None:  # a caller-supplied eval_step keeps priority
                # auto mode must not die on an unstackable VAL split —
                # eval falls back to per-step, training stays scanned
                if not (self.auto and _stack_refusal(val_loader) is not None):
                    self.scan_eval_fn = make_scan_eval(model)
        self.train_step = train_step or make_train_step(model, tx, **build)
        self.eval_step = eval_step or make_eval_step(model)
        self.test_kept, self.test_reason = _keep_test_split_on_device(
            test_loader, use_scan, caller_step=eval_step_out is not None
        )
        self.eval_step_out = eval_step_out or make_eval_step(model, with_outputs=True)
        self.stats_step = None
        # a stack without BatchNorm has nothing to recalibrate, and the two
        # float32 passes over the train split hand back a copy of the state
        if training.get("bn_recalibration", True) and model.cfg.has_batch_norm:
            self.stats_step = stats_step or make_stats_step(model)

    @property
    def mode(self) -> str:
        return "scan_epoch" if self.scan_fn is not None else "per_step"

    def manifest(self) -> Dict[str, Any]:
        """The run_start manifest's account of the plan: a flight record
        always says which mode executed the epochs."""
        return {
            "mixed_precision": self.compute_dtype is not None,
            "scan_epoch": self.scan_fn is not None,
            "dispatch_mode": {
                "mode": self.mode,
                "auto": self.auto,
                "reason": self.reason,
                # the test pass iterates batches kept on the device
                # from set-up, or builds them every epoch, and why
                "test_split": {
                    "path": "on_device" if self.test_kept else "rebuilt",
                    "reason": self.test_reason,
                },
                # the sampled step's diagnostics come from the epoch's first
                # train step, from an observer program before it, or not at all
                "diagnostics": {"path": self.diag_path, "reason": self.diag_reason},
            },
        }

    def open_diagnostics(self, model, tx, on: bool, head_names, diag_every: int):
        """Which program diagnoses the sampled step; returns the run's
        ``HeadDiagnostics`` or None. Where the loop scans its epochs (one
        device, its own step), the sampled step is the epoch's first train
        step, run once by the program that diagnoses it (``first_step``:
        train/state.py:make_diagnosed_first_step, then the scan over the
        other steps; :meth:`first_step_epoch`). A per-step loop is handed
        batches one at a time and dispatches the observer before the
        sampled step (``observer``: obs/introspect.py:make_diagnostics_step;
        the forward and the gradient run twice there). A caller-supplied
        step is not the loop's to diagnose (``off``)."""
        self.diag_path = "off"
        if not on:
            self.diag_reason = "Training.diagnostics, HYDRAGNN_DIAGNOSTICS or telemetry is off"
            return None
        if not self.loop_owned:
            self.diag_reason = "caller-supplied train step"
            return None
        observer = None
        self.diag_every = self.diag_stride(diag_every)
        if self.scan_fn is not None:
            self.first_step = make_diagnosed_first_step(model, tx, **self._build)
            self.diag_path = "first_step"
            self.diag_reason = "scan dispatch: the sampled step is the epoch's first train step"
        else:
            observer = make_diagnostics_step(
                model, tx, compute_dtype=self.compute_dtype, remat=self._build["remat"]
            )
            self.diag_path = "observer"
            self.diag_reason = "per-step dispatch: an observer program before the sampled step"
        return HeadDiagnostics(observer, head_names, every=self.diag_every)

    def first_step_epoch(self, state, stacked, order, *consec):
        """A scanned epoch whose first step is the diagnosed one: two
        dispatches, both queued before the host blocks. The signature and
        the leading outputs are the plain scan's (``train_epoch_scan``'s
        ``scan_fn``); the diagnostics dictionary comes behind them."""
        state, first, *consec, diagnostics = self.first_step(state, stacked, order, *consec)
        return (*self.scan_rest(state, stacked, order, *consec, first), diagnostics)

    def step_args(self, state, batch) -> tuple:
        """What the per-step train program is lowered with."""
        return (state, batch, jnp.zeros((), jnp.int32)) if self.guard_nonfinite else (state, batch)

    def diag_stride(self, diag_every: int) -> int:
        """Per-step mode samples every ``diag_every`` steps (default once
        per epoch). Scan mode diagnoses an epoch's FIRST step or none
        (:meth:`train`), so diag_every converts to an epoch stride
        there — the sampling floor whole-epoch dispatch allows."""
        nb = max(len(self.train_loader), 1)
        return max(1, diag_every // nb) if self.scan_fn is not None else diag_every or nb

    def step_time(self, spans) -> Tuple[Optional[dict], dict]:
        """(the per-step decomposition or None, the epoch event's
        ``step_time``). Scan mode is one or two device dispatches per
        epoch: its host side is in ``phases`` (train.stack, train.dispatch,
        train.sync), its steps exist only on the device."""
        if self.scan_fn is not None:
            return None, {"mode": "scan_epoch"}
        snap = spans.epoch_snapshot()
        return snap, dict(snap, mode="per_step") if snap is not None else {"mode": "disabled"}

    def wire_exec_cache(self, state, config, partitioner, flight, cmon) -> None:
        """Persistent AOT executable cache (utils/exec_cache.py): with
        HYDRAGNN_EXEC_CACHE set — an env var strip_injection_env
        deliberately preserves, so supervisor auto-resume restarts keep it
        — the loop-owned train executable (per-step OR scan-epoch) is
        deserialized from disk instead of recompiled. The loop caches a
        DONATION-FREE twin of the step (a plain jit of the same body): a
        deserialized donated executable is unsound inside a full training
        process on this jax/jaxlib (scrambled output pytrees, runtime
        aborts — utils/exec_cache.py module docstring), and the failure
        escapes any same-process probe. Warm loads additionally ride a
        first-execution landing check (``_landing_checked``).
        Called AFTER start_run (the --require-complete validator demands
        run_start first) and after the ledger lowered the RAW jitted step."""
        if not self.loop_owned:
            return
        try:
            ecache = ExecCache.from_env(flight=flight, consumer="train")
        except Exception:
            return
        if not ecache.enabled:
            return
        try:
            pc = partitioner.config if partitioner is not None else None
            compat = compat_manifest(
                layout=(pc.data, pc.fsdp, pc.edge) if pc is not None else (1, 1, 1),
                compute_dtype=self.compute_dtype,
            )
            # resume bookkeeping (auto_resume_config flips
            # Training.continue/startfrom on a supervisor restart)
            # selects WHICH checkpoint restores, not what compiles —
            # it must not change the key or no resume ever hits
            cfg_key = dict(config)
            tr_parent = cfg_key
            if "Training" not in tr_parent and isinstance(cfg_key.get("NeuralNetwork"), dict):
                tr_parent = cfg_key["NeuralNetwork"] = dict(cfg_key["NeuralNetwork"])
            if isinstance(tr_parent.get("Training"), dict):
                tr_key = dict(tr_parent["Training"])
                for vol in ("continue", "startfrom"):
                    tr_key.pop(vol, None)
                tr_parent["Training"] = tr_key
            arch = fingerprint(cfg_key, abstract_fingerprint(state))
            is_scan = self.scan_fn is not None
            # where every epoch starts with the diagnosed step, the scanned
            # program that runs is the one after it, a step shorter
            after_first = self.first_step is not None and self.diag_every == 1
            if is_scan:
                nb = len(self.train_loader)
                cargs = (state, self.train_loader.stacked_device_batches(0), jnp.arange(nb, dtype=jnp.int32))
                if self.guard_nonfinite:
                    cargs += (jnp.zeros((), jnp.int32),)
                label, delta, raw = "scan_epoch", nb, self.scan_fn
                if after_first:
                    # the first step's loss, tasks[H], count[, bad]
                    scalar = jnp.zeros((), jnp.float32)
                    first = (scalar, jnp.zeros((self.cfg.num_heads,), jnp.float32), scalar)
                    cargs += (first + ((scalar,) if self.guard_nonfinite else ()),)
                    label, delta = "scan_epoch_after_first", nb - 1
            else:
                cargs = self.step_args(state, next(iter(self.train_loader)))
                label, delta, raw = "train_step", 1, self.train_step
            # the donation-free twin: jit of the same body without
            # donate_argnums. Costs one extra state-sized buffer
            # while the cache is enabled; buys executables that
            # survive the serialize round trip. Donation-ness is
            # part of the key — the two programs are not the same
            # executable.
            body = getattr(raw, "__wrapped__", None)
            cache_fn = jax.jit(body) if body is not None else raw
            donated = body is None
            ckey = fingerprint(label, arch, abstract_fingerprint(cargs), donated)
            # marked AFTER arg construction: the eager jnp.arange
            # / jnp.zeros scalars above cost one tiny compile each
            # per process and would pollute the zero-compile number
            if cmon is not None:
                cmon.mark("exec_cache_build")
            exe, hit, build_s = ecache.get_or_compile(
                ckey, cache_fn, cargs, compat, donated=donated, label=label
            )
            if hit:
                exe = _landing_checked(
                    exe, cache_fn, ecache, ckey, expected_delta=delta, label=label
                )
            if after_first:
                self.scan_rest = exe
            elif is_scan:
                self.scan_fn = exe
            else:
                self.train_step = exe
            # the scoped zero-compile evidence the fault-injection
            # smoke pins: how many XLA compiles the build took (0 on
            # a warm hit) and how long restart-to-ready cost
            flight.record(
                "exec_cache",
                event="train_ready",
                hit=hit,
                compiles=cmon.count_since("exec_cache_build") if cmon is not None else None,
                build_s=round(build_s, 3),
                mode=self.mode,
            )
        except Exception as exc:
            # cache wiring must never take training down: fall back
            # to the live jitted path and say so in the record
            flight.record("exec_cache", event="wiring_failed", error=str(exc)[-200:])

    # -- an epoch's three passes ---------------------------------------------

    def train(self, state, epoch: int, run):
        """One training epoch under the run's instruments; returns
        (state, avg_loss, avg_tasks_loss[H])."""
        if self.scan_fn is not None:
            if run.incidents is not None:
                # scan mode dispatches an epoch at once: a single tick
                # here spans the whole epoch's capture window
                run.incidents.tick()
            epoch_fn, diag = self.scan_fn, None
            if self.first_step is not None:
                if run.diag.due:
                    epoch_fn, diag = self.first_step_epoch, run.diag
                else:
                    run.diag.count_step()  # an epoch without a sample: the plain scan
                    if self.first_plain_epoch is None:
                        self.first_plain_epoch = epoch
            return train_epoch_scan(
                self.train_loader, state, epoch_fn, epoch, diag=diag, sentry=run.sentry
            )
        return train_epoch(
            self.train_loader, state, self.train_step, self.verbosity, profiler=run.profiler,
            spans=run.spans, hooks=run.hooks, diag=run.diag, incidents=run.incidents,
        )

    def validate(self, state):
        if self.scan_eval_fn is not None:
            return evaluate_epoch_scan(self.val_loader, state, self.scan_eval_fn)
        return evaluate_epoch(self.val_loader, state, self.eval_step, self.verbosity)

    def test(self, state, return_samples: bool):
        return test_epoch(
            self.test_loader, state, self.eval_step_out, self.cfg, self.verbosity,
            return_samples=return_samples,
        )

    def recalibrate(self, state, hooks):
        """BatchNorm recalibration: the in-training running-stat EMA trails
        the last few (noisy, small) batches; with frozen final parameters,
        two passes over the train set re-estimate faithful eval statistics."""
        if self.stats_step is not None:
            for _ in range(2):
                for b in self.train_loader:
                    hooks.beat()  # recalibration batches count as liveness
                    state = self.stats_step(state, b)
        return state


class _Plots:
    """Rank-0 plots (reference: Visualizer wiring, train_validate_test.py:
    71-97,173-215: test-set node-count histogram and initial-solution
    scatter at set-up, per-epoch error histograms, final plots). Without
    ``create_plots`` every method returns at once."""

    def __init__(self, cfg, test_loader, log_name, log_dir, create_plots, plot_hist_solution):
        self.visualizer = None
        self.nodes_per_graph = None
        if create_plots and jax.process_index() == 0:
            from hydragnn_tpu.postprocess.visualizer import Visualizer

            self.visualizer = Visualizer(
                log_name, num_heads=cfg.num_heads, head_names=cfg.output_names, log_dir=log_dir
            )
            # all_samples = the full split, not this process's shard; also
            # reused by the final per-node plot dispatch
            if hasattr(test_loader, "all_samples"):
                self.nodes_per_graph = [s.num_nodes for s in test_loader.all_samples]
                self.visualizer.num_nodes_plot(self.nodes_per_graph)
        self.collect = plot_hist_solution and self.visualizer is not None

    def initial(self, plan, state) -> None:
        if self.visualizer is not None:
            _, _, tv, pv = plan.test(state, return_samples=True)
            self.visualizer.create_scatter_plots(tv, pv, iepoch=-1)

    def epoch(self, true_values, predicted_values, epoch: int) -> None:
        if self.collect:
            self.visualizer.create_error_histograms(true_values, predicted_values, iepoch=epoch)

    def final(self, plan, state, history) -> None:
        viz = self.visualizer
        if viz is None:
            return
        _, _, tv, pv = plan.test(state, return_samples=True)
        viz.create_scatter_plots(tv, pv)
        viz.create_plot_global(tv, pv)
        # vector parity grids, per-node diagnostics (fixed-size graphs),
        # and the scalar/vector global-analysis figures (reference:
        # visualizer.py:134-280, 387-613)
        viz.create_reference_plot_suite(tv, pv, plan.cfg.output_type, self.nodes_per_graph)
        viz.plot_history(history)


def _write_checkpoint(run, ckpt_state, epoch_next: int, early_stopped: bool) -> None:
    """The weights, the pod's shard of them, then the loop-state sidecar
    (``LoopState.snapshot``) that describes the same optimizer step."""
    with span("epoch.checkpoint"):
        keep_last = int(run.training.get("checkpoint_keep_last", 3))
        save_model(ckpt_state, run.log_name, run.log_dir, run.verbosity, keep_last=keep_last)
        with span("checkpoint.pod"):
            run.pod.checkpoint(ckpt_state, epoch_next)
        with span("checkpoint.meta"):
            step = int(jax.device_get(ckpt_state.step))
            save_train_meta(
                run.loop_state.snapshot(step, epoch_next, early_stopped), run.log_name, run.log_dir
            )


def _preempt_exit(run, ckpt_state, epoch: int, coordinated_from: Optional[int] = None):
    """Graceful preemption: checkpoint + meta pair for this epoch,
    ``preempt`` + ``run_end{status:"preempted"}`` flight events,
    telemetry closed — all inside the grace window the handler's
    hard-exit timer enforces — then the typed exception the driver's
    run_guard maps to EXIT_PREEMPTED. ``coordinated_from`` marks a
    cut taken on a PEER's announcement rather than our own signal."""
    preempt = run.hooks.preempt
    signum = (preempt.signum if preempt is not None else 0) or 0
    _write_checkpoint(run, ckpt_state, epoch, early_stopped=False)
    fields = {"signal": signum, "epoch": epoch, "step": int(jax.device_get(ckpt_state.step))}
    if coordinated_from is not None:
        fields["coordinated_from"] = int(coordinated_from)
    run.end("preempted", preempted=fields)
    raise TrainingPreempted(signum, epoch)


def _sentry_rollback(run, cur_state, epoch: int, consec_end: int):
    """K consecutive non-finite steps at the epoch's tail: restore
    the last good checkpoint with a reduced LR instead of
    continuing; give up (typed, fail-fast exit) when the rollback
    budget is spent or there is nothing to roll back to."""
    sentry, log_name, log_dir = run.sentry, run.log_name, run.log_dir
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
    lr = max(current_learning_rate(restored.opt_state) * sentry.lr_factor, 1e-8)
    restored = restored.replace(opt_state=set_learning_rate(restored.opt_state, lr))
    sentry.on_rollback()
    run.flight.record(
        "rollback", epoch=epoch, consec=consec_end, rollbacks=sentry.rollbacks, lr=lr
    )
    print_distributed(
        run.verbosity,
        f"non-finite sentry: epoch {epoch} ended with {consec_end} "
        f"consecutive bad steps — rolled back to the last good "
        f"checkpoint (lr -> {lr:g})",
    )
    return restored


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

    It prepares a run, loops over epochs (train, validate, test, record,
    checkpoint, stop?) and ends the run; five parts each own one decision
    (docs/DESIGN.md section 10): :class:`DispatchPlan`, ``LoopState``
    (utils/checkpoint.py), ``PodPlane`` (resilience/pod.py),
    ``Run.record_epoch`` and ``Run.end`` (train/run.py).

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
    training = config["Training"]
    num_epoch = int(training["num_epoch"])
    ckpt_every = int(training.get("checkpoint_every", 0))
    loaders = (train_loader, val_loader, test_loader)
    with span("setup.step_builders"):
        stopper = None
        if training.get("EarlyStopping", False):
            stopper = EarlyStopping(patience=int(training.get("patience", 10)))
        ls = LoopState(ReduceLROnPlateau(), stopper, num_epoch)
        plan = DispatchPlan(
            model, tx, config, loaders, train_step=train_step, eval_step=eval_step,
            eval_step_out=eval_step_out, stats_step=stats_step, partitioner=partitioner,
            profiler=profiler, verbosity=verbosity,
        )
        profiler = config_profiler(profiler, config, log_dir, log_name)
    with span("setup.restore"):
        ls.restore(training, state, len(train_loader), log_name, log_dir, verbosity)
    run = prepare_run(
        plan, ls, model, tx, state, loaders, config, log_name=log_name, log_dir=log_dir,
        verbosity=verbosity, profiler=profiler, flight=flight, run_config=run_config,
        partitioner=partitioner, manifest_extra=manifest_extra,
    )
    try:
        with span("setup.exec_cache"):
            if ls.start_epoch < num_epoch:
                plan.wire_exec_cache(state, config, partitioner, run.flight, run.cmon)
        with span("setup.manifest"):
            plots = _Plots(
                model.cfg, test_loader, log_name, log_dir, create_plots, plot_hist_solution
            )
            if plot_init_solution:
                plots.initial(plan, state)
        run.setup_done()

        for epoch in range(ls.start_epoch, num_epoch):
            run.begin_epoch(epoch)
            with span("epoch", epoch=epoch):
                run.hooks.epoch_start(epoch)
                if run.hooks.preempted:
                    _preempt_exit(run, state, epoch)
                run.pod.epoch_start(epoch)
                for loader in loaders:
                    if hasattr(loader, "set_epoch"):
                        loader.set_epoch(epoch)
                run.mark_epoch_start(epoch)

                t_train0 = time.perf_counter()
                with run.profiling:
                    state, train_loss, train_tasks = plan.train(state, epoch, run)
                # the epoch metrics above already synced at finalize, so this
                # wall time covers every dispatched train step's execution —
                # the denominator of the epoch's achieved-TFLOP/s and MFU
                train_wall_s = time.perf_counter() - t_train0
                if run.hooks.preempted and not run.pod.cuts_at_epoch_end:
                    # mid-epoch graceful stop: this epoch is incomplete, resume
                    # re-runs it (the meta pair written here says so). A pod
                    # instead cuts at the epoch's END boundary, racing the
                    # handler's hard-exit grace timer
                    _preempt_exit(run, state, epoch)
                nonfinite, bad_tail = run.close_nonfinite()
                if bad_tail is not None:
                    state = _sentry_rollback(run, state, epoch, bad_tail)
                    ls.epochs_done = epoch + 1
                    continue  # the rolled-back epoch consumed its slot
                with span("epoch.validate"):
                    val_loss, val_tasks = plan.validate(state)
                with span("epoch.test"):
                    test_loss, test_tasks, true_values, predicted_values = plan.test(
                        state, return_samples=plots.collect or run.introspect_on
                    )
                with span("epoch.head_quality"):
                    head_quality = run.head_quality(true_values, predicted_values)
                    plots.epoch(true_values, predicted_values, epoch)
                with span("epoch.diag_snapshot"):
                    diag_snap = run.diag_snapshot()
                with span("epoch.record"):
                    state = ls.scheduler.step(state, val_loss)
                    run.record_epoch(
                        epoch, state, train=(train_loss, train_tasks), val=(val_loss, val_tasks),
                        test=(test_loss, test_tasks), train_wall_s=train_wall_s,
                        steps=len(train_loader), nonfinite=nonfinite,
                        head_quality=head_quality, diag_snap=diag_snap,
                    )
                    stop = ls.epoch_done(epoch, val_loss)

                if ckpt_every and (epoch + 1) % ckpt_every == 0:
                    _write_checkpoint(run, state, epoch + 1, early_stopped=False)
                if run.hooks.preempted:
                    # SIGTERM landed during val/test/plots (or, in a pod,
                    # anywhere in the epoch): this epoch is complete and
                    # recorded, resume continues from the next
                    _preempt_exit(run, state, epoch + 1)
                peer = run.pod.peer_preempted(epoch + 1)
                if peer is not None:
                    _preempt_exit(run, state, epoch + 1, coordinated_from=peer)
                if stop:
                    print_distributed(verbosity, f"Early stopping at epoch {epoch}")
                    break
        run.loop_done()

        # A resume that trained zero epochs (e.g. continuing an early-stopped
        # or completed run) must be a pure no-op: re-running BN recalibration
        # would mutate batch_stats and rewriting the checkpoint would change
        # the saved model file without any training having happened.
        if not (training.get("continue") == 1 and ls.epochs_done == ls.start_epoch):
            state = plan.recalibrate(state, run.hooks)
            # Final checkpoint+meta pair AFTER BN recalibration: the model file
            # and the loop-state sidecar must describe the same state (a mid-run
            # meta against the final recalibrated weights would make a later
            # continue run replay epochs on the wrong state); an early-stopped
            # run is marked so resume honors the stop instead of training on.
            if ckpt_every:
                _write_checkpoint(run, state, ls.epochs_done, early_stopped=ls.early_stopped)
        plots.final(plan, state, ls.history)
    except TrainingPreempted:
        raise  # _preempt_exit wrote the checkpoint and ended the run
    except BaseException as exc:
        run.end("failed", error=exc)
        raise
    run.end("completed")
    return state, ls.history


# Ballast, not logic. The one-function loop this replaced held some 230 locals in
# its frame. CPython lays frames out in 16 KiB data-stack chunks and frees a chunk
# when the call that opened it returns; with this frame 100+ slots smaller, the deep
# recursion that traces and lowers the diagnostics step in epoch 0 oscillates across
# a chunk boundary and takes 25-60% more host time (PERF.md, PR 29: 7 s of a cell's
# 80 s of set-up on the chip's host; PR 30: the diagnosed first step, traced under
# train.dispatch, lowers in 3.1 s with it and 3.8 s without on the CPU reproducer).
# The frame keeps its old size until that first trace leaves epoch 0 (ROADMAP S4).
train_validate_test.__code__ = train_validate_test.__code__.replace(
    co_stacksize=train_validate_test.__code__.co_stacksize + 160
)
