"""What one training run carries beside its weights: the instruments
(flight record, spans, compile monitor, triggers, diagnostics, hardware
ledger), the resilience actors, the pod plane and the tensorboard
writer — opened by :func:`prepare_run`, one plain function per phase of
set-up, and handed back as one :class:`Run`. The record owns two
decisions ``train_validate_test`` no longer knows: what an epoch writes
and to whom (:meth:`Run.record_epoch`) and how a run ends
(:meth:`Run.end`).
"""

from __future__ import annotations

import contextlib
import json
import os
from typing import Any, Dict, List, Optional

import jax

from hydragnn_tpu.lint.ir import contract_block
from hydragnn_tpu.obs import CompileMonitor, StepSpans, get_registry, telemetry_enabled
from hydragnn_tpu.obs.drift import build_reference
from hydragnn_tpu.obs.export import registry_to_prometheus
from hydragnn_tpu.obs.introspect import (
    HardwareLedger, conv_traffic_model, pad_waste_from_batch, per_head_error_metrics,
)
from hydragnn_tpu.obs.spans import drain, drain_counts, span
from hydragnn_tpu.obs.trace import Tracer
from hydragnn_tpu.resilience import (
    HangWatchdog, NonFiniteSentry, PreemptionHandler, TrainHooks, podckpt,
)
from hydragnn_tpu.resilience.pod import PodPlane
from hydragnn_tpu.train.optimizer import current_learning_rate
from hydragnn_tpu.utils import knobs
from hydragnn_tpu.utils.print_utils import print_distributed, print_peak_memory
from hydragnn_tpu.utils.profile import Profiler
from hydragnn_tpu.utils.tensorboard import get_summary_writer, write_scalar_dict
from hydragnn_tpu.utils.time_utils import Timer, timers_snapshot


def config_profiler(profiler, config: Dict[str, Any], log_dir: str, log_name: str):
    """The caller's profiler, else the config-driven one (reference:
    Profiler setup from config["Profile"], train_validate_test.py:99-101)."""
    if profiler is None and "Profile" in config:
        profiler = Profiler(prefix=os.path.join(log_dir, log_name, "profile"))
        profiler.setup(config["Profile"])
        if not profiler.enable:
            profiler = None
    return profiler


def _named_tasks(names, values) -> Dict[str, float]:
    """Per-task loss array -> {head_name: loss}. Zip-truncating: a
    zero-length array (preempted epoch finalize) yields {}."""
    return {n: float(v) for n, v in zip(names, values.reshape(-1))}


def _loader_plan(ld) -> Dict[str, Any]:
    plan = {"num_batches": len(ld)}
    # pad sizes; which plan (data/loader.py): cut to the batches that exist
    # ("fixed_membership") or to the worst case, and the largest (sub-)batch
    # it was cut to; the edge layout the loader's AUTO chose under that plan
    for key in ("num_samples", "batch_size", "pad_nodes", "pad_edges", "pad_graphs", "plan",
                "real_nodes_max", "real_edges_max", "dense_slots", "run_align"):
        plan[key] = getattr(ld, key, None)
    plan["gather_windows"] = _gather_windows(ld)
    return plan


def _gather_windows(ld) -> Optional[Dict[str, Any]]:
    """How many table windows a sender chunk of the windowed gathers
    needs (``ops/segment_pallas.py:window_counts``), over the batches the
    loader has built and keeps; None where it keeps none or they hold no
    edges. 1 is every chunk's ids inside one window."""
    from hydragnn_tpu.ops.segment_pallas import _BCAST_CE, BW, window_counts

    senders = ld.built_senders() if hasattr(ld, "built_senders") else None
    if senders is None or senders.shape[-1] == 0:
        return None
    counts = window_counts(senders, ld.pad_nodes)
    return {"width": BW, "chunk": _BCAST_CE, "mean": float(counts.mean()), "max": int(counts.max())}


class Run:
    """The record :func:`prepare_run` hands back. Attributes a phase did
    not switch on are None (``cmon``, ``trig_engine``, ``incidents``,
    ``diag``, ``ledger``, ``sentry``) and are tested here, never in the
    epoch loop."""

    def __init__(self, plan, loop_state, training, log_name, log_dir, verbosity, profiler):
        self.plan = plan
        self.loop_state = loop_state
        self.training = training
        self.log_name, self.log_dir, self.verbosity = log_name, log_dir, verbosity
        self.profiler = profiler
        # the profiler context closes an in-flight trace at epoch end even
        # when the epoch has fewer steps than its schedule expects
        self.profiling = profiler if profiler is not None else contextlib.nullcontext()
        self.head_names = list(plan.cfg.output_names)
        self.model_counters = None  # batch_stats -> the model's own counters for the epoch event, or None
        self.timer = Timer("train_validate_test")
        # Spans that close after their epoch's event is written (the end
        # of epoch.record, epoch.checkpoint, epoch itself) wait here under
        # their own epoch number for the next event: the next epoch's, or
        # run_end.
        self._late_phases: List[Dict[str, Any]] = []
        self._in_epoch: Optional[int] = None  # whose spans the table is collecting

    # -- the span table ------------------------------------------------------

    def hold_late(self) -> None:
        phases = drain()
        # counters of the same spans (``checkpoint.leaves``, ``checkpoint.bytes``)
        counts = drain_counts() if self._in_epoch is not None else {}
        if phases or counts:
            late: Dict[str, Any] = {"epoch": self._in_epoch, "phases": phases}
            if counts:
                late["counts"] = counts
            self._late_phases.append(late)

    def _flush_late(self) -> Dict[str, Any]:
        held, self._late_phases = self._late_phases, []
        return {"phases_late": held} if held else {}

    def setup_done(self) -> None:
        """Every span since the entry (api.run_training's, then set-up's)."""
        self.flight.record("setup", phases=drain())
        self.timer.start()

    def begin_epoch(self, epoch: int) -> None:
        self.hold_late()
        self._in_epoch = epoch
        drain_counts()  # whatever a rolled-back epoch left

    def loop_done(self) -> None:
        self.timer.stop()
        self.hold_late()
        self._in_epoch = None  # what follows belongs to no epoch

    def mark_epoch_start(self, epoch: int) -> None:
        if self.profiler is not None:
            self.profiler.set_current_epoch(epoch)
        if self.cmon is not None:
            self.cmon.mark("epoch_start")
        self.spans.epoch_start(epoch)

    # -- an epoch's results ----------------------------------------------------

    def close_nonfinite(self) -> tuple:
        """The sentry's account of the epoch: (``nonfinite`` block or None,
        consecutive bad steps at its tail if they call for a rollback)."""
        if self.sentry is None:
            return None, None
        nonfinite = None
        skipped, consec_end = self.sentry.take_finalized()
        if skipped:
            get_registry().counter("train.nonfinite_skipped").inc(skipped)
            nonfinite = {"skipped": skipped, "consec_end": consec_end}
        return nonfinite, consec_end if self.sentry.needs_rollback(consec_end) else None

    def head_quality(self, true_values, predicted_values):
        """Per-head MAE/RMSE off the test() gather path — same eval
        executable, extra host-side gathering."""
        if self.introspect_on and true_values:
            return per_head_error_metrics(true_values, predicted_values, self.head_names)
        return None

    def diag_snapshot(self):
        return self.diag.epoch_snapshot() if self.diag is not None else None

    def record_epoch(self, epoch, state, *, train, val, test, train_wall_s, steps,
                     nonfinite, head_quality, diag_snap) -> None:
        """Everything one finished epoch writes, and who reads it
        (``train`` / ``val`` / ``test``: (loss, per-task losses)):

        - history (``LoopState``): the caller's return value, the sidecar;
        - the printed line; tensorboard scalars (``train error``,
          ``heads/<name>/*``, ``obs/*``); ``metrics.jsonl`` for scripts;
        - the flight ``epoch`` event: ``benchmark/`` reads ``phases``,
          ``hw.train_wall_s`` and ``compiles``; ``tools/obs_report.py``,
          podview's merge and incident bundles read the rest;
        - ``host_epoch`` / ``podview`` events: ``resilience/pod.py``;
        - the trigger engine's feed: ``obs/triggers.py`` rules -> incidents;
        - ``train.prom``: a Prometheus textfile collector.

        Per-task metrics are keyed by head name everywhere — a multi-head
        record is readable without the config's output order."""
        (train_loss, train_tasks), (val_loss, val_tasks), (test_loss, test_tasks) = train, val, test
        names, flight, writer = self.head_names, self.flight, self.writer
        lr = current_learning_rate(state.opt_state)
        self.loop_state.append(
            train_loss=train_loss, val_loss=val_loss, test_loss=test_loss,
            train_tasks=train_tasks.tolist(), val_tasks=val_tasks.tolist(),
            test_tasks=test_tasks.tolist(), lr=lr,
        )
        print_distributed(
            self.verbosity,
            f"Epoch: {epoch:02d}, Train Loss: {train_loss:.8f}, "
            f"Val Loss: {val_loss:.8f}, Test Loss: {test_loss:.8f}",
        )
        if epoch == 0:
            # post-first-epoch peak = steady-state footprint (weights +
            # activations + optimizer state); the reference prints peak
            # GPU memory around the train step (distributed.py:236-243)
            print_peak_memory(self.verbosity, prefix=f"epoch {epoch}")
        train_named = _named_tasks(names, train_tasks)
        val_named = _named_tasks(names, val_tasks)
        hw = None
        if self.ledger is not None:
            hw = self.ledger.epoch_record(steps=steps, wall_s=train_wall_s)
        mfu = hw.get("mfu") if hw is not None else None

        writer.add_scalar("train error", train_loss, epoch)
        writer.add_scalar("validate error", val_loss, epoch)
        writer.add_scalar("test error", test_loss, epoch)
        for name in names:
            if name in train_named:
                writer.add_scalar(f"heads/{name}/train_loss", train_named[name], epoch)
            if name in val_named:
                writer.add_scalar(f"heads/{name}/val_loss", val_named[name], epoch)
        if self.metrics_path is not None:
            line = {"epoch": epoch, "train_loss": train_loss, "val_loss": val_loss,
                    "test_loss": test_loss, "lr": lr, "train_tasks": train_named,
                    "val_tasks": val_named}
            with open(self.metrics_path, "a") as f:
                f.write(json.dumps(line) + "\n")

        # per-epoch flight record: losses + the step-time decomposition
        # + compile counts. After the first executed epoch every train
        # step function is compiled; further compiles are the silent
        # recompile class this exists to surface.
        span_snap, step_time = self.plan.step_time(self.spans)
        counts = drain_counts()
        cmon = self.cmon
        compiles: Dict[str, Any] = {"available": bool(cmon and cmon.available)}
        if cmon is not None:
            n_compiles = cmon.count_since("epoch_start")
            compiles["count"] = n_compiles
            compiles["seconds"] = round(cmon.seconds_since("epoch_start"), 6)
            # the epochs that trace a train program for the first time
            first_trace = (self.loop_state.start_epoch, self.plan.first_plain_epoch)
            compiles["unexpected"] = bool(
                cmon.available and epoch not in first_trace and n_compiles > 0
            )
        extra: Dict[str, Any] = {}
        if nonfinite:
            extra["nonfinite"] = nonfinite
        if self.model_counters is not None:
            extra.update(self.model_counters(state.batch_stats))
        if self.introspect_on:
            # heads: the model-level half of the epoch record — sampled
            # gradient diagnostics and eval MAE/RMSE when introspection
            # produced them this epoch
            heads: Dict[str, Any] = {"names": names, "available": False}
            if diag_snap is not None:
                heads.update(diag_snap)
            if head_quality is not None:
                heads["available"] = True
                heads["mae"] = {n: m["mae"] for n, m in head_quality.items()}
                heads["rmse"] = {n: m["rmse"] for n, m in head_quality.items()}
                if any("accuracy" in m for m in head_quality.values()):
                    heads["accuracy"] = {n: m.get("accuracy") for n, m in head_quality.items()}
            extra["heads"] = heads
            extra["hw"] = hw if hw is not None else {"available": False}
        flight.epoch(
            epoch,
            train_loss=train_loss,
            val_loss=val_loss,
            test_loss=test_loss,
            lr=lr,
            train_tasks=train_named,
            val_tasks=val_named,
            test_tasks=_named_tasks(names, test_tasks),
            step_time=step_time,
            compiles=compiles,
            # real graphs through an optimizer step, steps, and those of them
            # whose update came from the program that diagnosed them
            graphs=int(counts.get("graphs", 0)),
            steps=int(counts.get("steps", 0)),
            diagnosed_steps=int(counts.get("diagnosed_steps", 0)),
            # the program's spans closed so far this epoch
            # (obs/spans.py:span; docs/OBSERVABILITY.md "Program
            # spans"); the ones still open follow as phases_late
            phases=drain(),
            **self._flush_late(),
            **extra,
        )
        self.pod.epoch_recorded(epoch, {
            "epoch_s": round(train_wall_s, 6),
            "data_wait_s": (span_snap or {}).get("data_wait_s"),
            "dispatch_s": (span_snap or {}).get("dispatch_s"),
            "steps": (span_snap or {}).get("steps", steps),
            "nonfinite_skipped": (nonfinite or {}).get("skipped", 0),
            "mfu": mfu,
        })
        # SLO trigger evaluation at the epoch boundary: feed the rolling
        # series the rules watch, then let at most one verdict open an
        # incident whose profiler capture runs during the NEXT epoch's
        # ticks (docs/OBSERVABILITY.md "SLO triggers and incidents").
        if self.trig_engine is not None:
            self.trig_engine.observe("train_loss", train_loss)
            self.trig_engine.observe("val_loss", val_loss)
            if mfu is not None:
                self.trig_engine.observe("mfu", mfu)
            for verdict in self.trig_engine.evaluate():
                # the bundle's trigger.json carries the full verdict;
                # open_incident records the flight "incident" pointer
                if self.incidents is not None:
                    self.incidents.open_incident(verdict, flight=flight)

        if span_snap is not None:
            write_scalar_dict(writer, span_snap, epoch, prefix="obs/step_time")
            if compiles.get("count") is not None:
                writer.add_scalar("obs/compiles", compiles["count"], epoch)
        grad_norms = diag_snap.get("grad_norm", {}) if diag_snap is not None else {}
        if diag_snap is not None:
            for name in names:
                if name in grad_norms:
                    writer.add_scalar(f"heads/{name}/grad_norm", grad_norms[name], epoch)
            writer.add_scalar("obs/update_ratio", diag_snap["update_ratio"], epoch)
        for name, m in (head_quality or {}).items():
            if m["mae"] is not None:
                writer.add_scalar(f"heads/{name}/mae", m["mae"], epoch)
                writer.add_scalar(f"heads/{name}/rmse", m["rmse"], epoch)
        if mfu is not None:
            writer.add_scalar("obs/hw/mfu", mfu, epoch)
        if hw is not None and hw.get("achieved_tflops") is not None:
            writer.add_scalar("obs/hw/achieved_tflops", hw["achieved_tflops"], epoch)

        # Prometheus textfile export for training (serve already has
        # one): one atomic snapshot per epoch, gated by
        # Training.prometheus_dir (docs/OBSERVABILITY.md)
        prom_dir = self.training.get("prometheus_dir")
        prom_path = self.pod.prom_path(prom_dir) if prom_dir and self.telemetry_on else None
        if prom_path is not None:
            reg = get_registry()
            reg.gauge("train.epoch").set(epoch)
            reg.gauge("train.loss").set(train_loss)
            reg.gauge("train.val_loss").set(val_loss)
            reg.gauge("train.lr").set(lr)
            for name, v in train_named.items():
                reg.gauge(f"train.head.{name}.loss").set(v)
            for name, v in grad_norms.items():
                reg.gauge(f"train.head.{name}.grad_norm").set(v)
            if mfu is not None:
                reg.gauge("train.mfu").set(mfu)
            registry_to_prometheus(reg, prom_path)

    # -- the ending ------------------------------------------------------------

    def end(self, status: str, *, error: Optional[BaseException] = None,
            preempted: Optional[Dict[str, Any]] = None) -> None:
        """The one way a run ends — ``completed``, ``failed`` (``error``:
        what was raised; a crashed run must still leave a parseable
        artifact) or ``preempted`` (``preempted``: the ``preempt`` event's
        fields). ``run_end`` is the record's last event; the preemption
        handler's hard-exit timer stays armed until the record is closed,
        so ``hooks.teardown()`` comes last."""
        ls, cmon, flight = self.loop_state, self.cmon, self.flight
        # the registry timer is process-global: close its interval or every
        # later train_validate_test in this process raises "Timer already
        # running"
        self.timer.stop_if_running()
        if cmon is not None:
            cmon.stop()
        if error is not None:
            flight.error(error)
        if preempted is not None:
            flight.record("preempt", **preempted)
        if self.incidents is not None:
            # an incident still capturing at run end closes as "truncated"
            self.incidents.finalize()
        self.hold_late()
        fields: Dict[str, Any] = {"epochs": ls.epochs_done - ls.start_epoch}
        if status != "preempted":
            fields["triggers"] = (
                self.trig_engine.summary(self.incidents.capture_s if self.incidents else 0.0)
                if self.trig_engine is not None
                else None
            )
        if status == "completed":
            # per-process timers, whatever landed in the global metrics
            # registry (loader prefetch accounting, ...), the whole-run
            # compile count, the hardware-efficiency rollup (mean/max MFU
            # across epochs, the device-memory high-water mark)
            history = ls.history
            fields.update(
                epochs_total=ls.epochs_done,
                early_stopped=ls.early_stopped,
                best_val_loss=min(history["val_loss"]) if history["val_loss"] else None,
                final_lr=history["lr"][-1] if history["lr"] else None,
                compiles=cmon.snapshot() if cmon is not None else None,
                timers=timers_snapshot(),
                metrics=get_registry().snapshot(),
                hw=self.ledger.run_summary() if self.ledger is not None else None,
                podview=self.pod.run_end(),
            )
        flight.end_run(status=status, **self._flush_late(), **fields)
        try:
            self.writer.flush()
            self.writer.close()
        except Exception:
            pass
        if self.own_flight:
            flight.close()
        self.hooks.teardown()


# -- set-up, one function per phase ------------------------------------------


def _open_telemetry(run: Run, flight) -> None:
    """Unified telemetry (hydragnn_tpu/obs): flight record + step spans +
    compile monitor, all inert when HYDRAGNN_TELEMETRY=0. Created AFTER
    resume handling so a config error there cannot leak a registered
    monitor or an empty flight file. The flight record is rank-0 (like
    checkpoints/tensorboard) unless the pod plane is on; spans and the
    compile monitor run everywhere but only rank 0 persists them."""
    on = run.telemetry_on = telemetry_enabled()
    run.pod = PodPlane(run.log_dir, run.log_name, on)
    run.own_flight = flight is None
    run.flight = flight = run.pod.open_flight(flight)
    run.spans = StepSpans() if on else StepSpans.disabled()
    run.cmon = CompileMonitor().start() if on else None
    profiler = run.profiler
    if profiler is not None and getattr(profiler, "on_trace", None) is None:
        profiler.on_trace = lambda path, ep: flight.record("profile_trace", path=path, epoch=ep)
    # Incident-grade tracing (obs/trace.py + obs/triggers.py,
    # docs/OBSERVABILITY.md "Tracing and incidents"): sampled sync
    # steps join the request-trace timeline keyed (epoch, step), and —
    # when Training.slo_triggers is on — an SLO trigger engine
    # evaluated at each epoch end (nonfinite burst, loss spike vs
    # rolling median, MFU drop, the pod plane's rules) arms a bounded
    # profiler capture whose evidence lands in an incident bundle under
    # <log_dir>/<log_name>/incidents/<id>/.
    run.trig_engine = run.incidents = None
    if on:
        run.spans.tracer = Tracer(flight=flight)
    if on and bool(run.training.get("slo_triggers", False)):
        from hydragnn_tpu.obs.triggers import IncidentRecorder, TriggerEngine, TriggerRule

        training = run.training
        rules = [
            TriggerRule("train_nonfinite_burst", "nonfinite_burst", "train.nonfinite_skipped",
                        float(training.get("slo_nonfinite_burst", 1))),
            TriggerRule("train_loss_spike", "loss_spike", "train_loss",
                        float(training.get("slo_loss_spike_factor", 3.0))),
            TriggerRule("train_mfu_drop", "mfu_drop", "mfu",
                        float(training.get("slo_mfu_drop_factor", 0.5))),
        ] + run.pod.trigger_rules(training)
        run.trig_engine = TriggerEngine(rules, registry=get_registry())
        if jax.process_index() == 0:
            run.incidents = IncidentRecorder(
                os.path.join(run.log_dir, run.log_name, "incidents"),
                registry=get_registry(),
                flight_path=flight.path,
                podview=run.pod.monitor,
            )


def _open_introspection(run: Run, model, tx, state, train_loader) -> None:
    """Model-level introspection (hydragnn_tpu/obs/introspect.py,
    docs/OBSERVABILITY.md "Model-level diagnostics"): per-head
    gradient diagnostics sampled every Training.diag_every steps
    (default: once per epoch; DispatchPlan.open_diagnostics says from
    which program), per-head eval MAE/RMSE off the
    test_epoch gather path, and the hardware-efficiency ledger
    (compiled-step FLOPs from the LOWERED module — no second compile
    — turned into per-epoch achieved TFLOP/s + MFU + memory
    watermark). All inert when HYDRAGNN_TELEMETRY=0 or
    Training.diagnostics=false; the gradient sampler additionally
    requires the loop-owned step (sharded callers degrade to
    heads.available=false, never fail).
    HYDRAGNN_DIAGNOSTICS=0 force-disables introspection regardless of
    config (the tier-1 suite sets it: dozens of tiny training tests
    would each pay the diagnostics executable's compile + the ledger
    lowering; the dedicated introspection tests and the ci.sh smoke
    opt back in). Production default stays ON."""
    training, plan = run.training, run.plan
    run.introspect_on = (
        run.telemetry_on
        and bool(training.get("diagnostics", True))
        and knobs.get_bool("HYDRAGNN_DIAGNOSTICS", True)
    )
    run.ledger = None
    # which program diagnoses the sampled step is the plan's to say
    run.diag = plan.open_diagnostics(
        model, tx, run.introspect_on, run.head_names, int(training.get("diag_every", 0))
    )
    if not run.introspect_on:
        return
    try:
        example = next(iter(train_loader))
        # the scan path runs the SAME step body nb times per
        # dispatch, so the per-step lowered cost prices it too
        run.ledger = HardwareLedger.from_step(plan.train_step, plan.step_args(state, example))
        # useful-vs-padded byte accounting: the XLA cost model above
        # prices padded shapes; the pad-waste fractions + analytic
        # conv-traffic model say how much of that a bucket-ladder
        # batch actually uses (its own guard: this is telemetry and
        # must never take the ledger down with it)
        try:
            waste = pad_waste_from_batch(example)
            run.ledger.set_conv_traffic(
                waste,
                conv_traffic_model(
                    waste["node_pad"], waste["edge_pad"], model.cfg.hidden_dim,
                    model.cfg.num_conv_layers, real_edges=waste["real_edges_mean"],
                ),
            )
        except Exception:
            pass
    except Exception:
        run.ledger = HardwareLedger.disabled(reason="example_batch_unavailable")


def _open_resilience(run: Run) -> None:
    """Fault tolerance (hydragnn_tpu/resilience, docs/RESILIENCE.md):
    preemption handler (SIGTERM/SIGINT -> graceful stop + final
    checkpoint within Training.preempt_grace_s), non-finite sentry
    over the guarded loop-owned step (per-step OR the guarded scan
    body — sharded callers pass their own step and keep their own
    policy), and the opt-in hang watchdog (Training.watchdog_stall_s
    or HYDRAGNN_WATCHDOG_S; off by default — it must be sized above
    the worst expected compile time, and it forces per-step dispatch)."""
    training = run.training
    run.sentry = preempt = None
    if run.plan.guard_nonfinite:
        run.sentry = NonFiniteSentry(
            patience=int(training.get("nonfinite_patience", 16)),
            max_rollbacks=int(training.get("nonfinite_max_rollbacks", 2)),
            lr_factor=float(training.get("nonfinite_rollback_lr_factor", 0.5)),
        )
    if training.get("preempt_handler", True):
        preempt = PreemptionHandler(grace_s=float(training.get("preempt_grace_s", 30.0))).install()
    run.stall_s = float(
        training.get("watchdog_stall_s", 0) or knobs.get_float("HYDRAGNN_WATCHDOG_S", 0.0) or 0
    )
    watchdog = HangWatchdog(run.stall_s, flight=run.flight).start() if run.stall_s > 0 else None
    run.hooks = TrainHooks(preempt=preempt, sentry=run.sentry, watchdog=watchdog)
    run.pod.arm(preempt)
    run.metrics_path = None
    if jax.process_index() == 0:
        out_dir = os.path.join(run.log_dir, run.log_name)
        os.makedirs(out_dir, exist_ok=True)
        run.metrics_path = os.path.join(out_dir, "metrics.jsonl")


def _drift_reference(run: Run, train_loader):
    """Drift reference window (obs/drift.py): per-channel feature stats +
    per-head target stats over a bounded subsample of the training
    set, stamped into the manifest so a later serving run can load
    this flight record as its HYDRAGNN_DRIFT_REF and compare live
    traffic against what this model actually trained on. Telemetry:
    a failure degrades to an absent block, never a dead run."""
    if not run.telemetry_on:
        return None
    try:
        return build_reference(list(train_loader.all_samples), head_names=run.head_names)
    except Exception:
        return None


def _graftcheck_block(run: Run, state, train_loader, partitioner):
    """graftcheck contract block (lint/ir.py, docs/LINT.md CC rules): the
    run's OWN train step, lowered and audited for the static contracts
    the full checker (tools/graftcheck.py) gates in CI — so every
    recorded run says which contracts its executable passed. Costs one
    trace, no compile; HYDRAGNN_GRAFTCHECK=0 skips the lowering, and
    any failure degrades to an all-not_checked block (stamping is
    telemetry and must never take the run down)."""
    if not (run.telemetry_on and knobs.get_bool("HYDRAGNN_GRAFTCHECK", True)):
        return contract_block(None)
    try:
        # peek_batch builds the first batch without counting as an
        # __iter__ draw, so loader wrappers that count epochs
        # (schedulers, fault harnesses) are unperturbed
        example = (
            train_loader.peek_batch()
            if hasattr(train_loader, "peek_batch")
            else next(iter(train_loader))
        )
        cfg = run.plan.cfg
        pcfg = partitioner.config if partitioner is not None else None
        return contract_block(
            run.plan.train_step.lower(*run.plan.step_args(state, example)).as_text(),
            donated=True,
            conv_bf16=bool(getattr(cfg, "conv_bf16", False)),
            edge_pad=int(example.senders.shape[-1]),
            data=int(getattr(pcfg, "data", 1) or 1),
            fsdp=int(getattr(pcfg, "fsdp", 1) or 1),
            zero1=bool(getattr(pcfg, "zero1", False)),
            residency_shapes=(
                [(int(example.nodes.shape[-2]), int(cfg.hidden_dim))]
                if getattr(cfg, "conv_residency", False)
                else None
            ),
        )
    except Exception:
        return contract_block(None)


def _start_record(run: Run, loaders, config, run_config, parallel_block, graftcheck_block,
                  stats_block, manifest_extra) -> None:
    """Flight-record manifest: everything needed to interpret (and rerun)
    this run without the builder's shell history. Recorded AFTER resume
    handling so start_epoch reflects what will actually execute."""
    train_loader, val_loader, test_loader = loaders
    ls, flight, diag, ledger = run.loop_state, run.flight, run.diag, run.ledger
    run.model_counters = run.plan.cfg.epoch_counters(train_loader.samples)
    dev0 = jax.devices()[0]
    # lineage left behind by a pod-checkpoint restore earlier in this
    # process (utils/checkpoint.load_existing_model → podckpt); consumed
    # once so only the run that actually restored stamps it
    pod_lineage = podckpt.consume_last_restore_info()
    preempt = run.hooks.preempt
    manifest = {
        "run": run.log_name,
        "log_dir": run.log_dir,
        "config": run_config if run_config is not None else {"NeuralNetwork": config},
        "device_kind": getattr(dev0, "device_kind", str(dev0)),
        "local_device_count": jax.local_device_count(),
        "mesh": {
            "device_stack": getattr(train_loader, "device_stack", 1),
            "process_count": jax.process_count(),
        },
        "podview": run.pod.manifest(),
        "parallel": parallel_block,
        "pad_plans": {
            "train": _loader_plan(train_loader),
            "val": _loader_plan(val_loader),
            "test": _loader_plan(test_loader),
        },
        "num_epoch": ls.num_epoch,
        "start_epoch": ls.start_epoch,
        # mixed_precision, scan_epoch and dispatch_mode: which dispatch
        # mode actually ran, whether it was the automatic default, and why
        **run.plan.manifest(),
        "compile_monitor_available": bool(run.cmon and run.cmon.available),
        "nonfinite_guard": run.sentry is not None,
        "preempt_handler": bool(preempt and preempt.available),
        "watchdog_stall_s": run.stall_s or None,
        "head_names": run.head_names,
        **run.plan.cfg.manifest_block(
            train_loader.pad_nodes, getattr(train_loader, "pad_edges", 0),
            getattr(train_loader, "run_align", 0) or 0, run.plan.compute_dtype,
        ),
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
    }
    if pod_lineage is not None:
        # pod-restore lineage (resilience/podckpt.py): set when this
        # process's state came out of a sharded pod checkpoint —
        # which committed generation, the prior pod layout it was
        # cut under, and any generations skipped as torn
        manifest["pod_resume"] = {
            "resumed_from_gen": pod_lineage.get("gen"),
            "step": pod_lineage.get("step"),
            "prior_hosts": pod_lineage.get("hosts"),
            "prior_layout": pod_lineage.get("layout"),
            "fallbacks": pod_lineage.get("fallbacks") or [],
        }
    # caller-stamped provenance (e.g. the retrain pilot's fine-tune child
    # marks which serving run + spool window it trained from — pilot/tune.py)
    manifest.update(manifest_extra or {})
    flight.start_run(manifest)
    if ls.resumed_from is not None:
        # a restarted run announces where it picked up — the supervisor
        # story ("one preempted + one resumed") is then readable from
        # the merged flight record alone
        flight.record("resumed", epoch=ls.resumed_from)
    if pod_lineage is not None:
        flight.record(
            "pod_resume",
            gen=int(pod_lineage.get("gen", -1)),
            prior_hosts=pod_lineage.get("hosts"),
            prior_layout=pod_lineage.get("layout"),
            fallbacks=pod_lineage.get("fallbacks") or [],
        )


def prepare_run(plan, loop_state, model, tx, state, loaders, config, *, log_name, log_dir,
                verbosity, profiler, flight, run_config, partitioner, manifest_extra) -> Run:
    """Set-up after the dispatch plan and the resume: open every
    instrument, then write ``run_start``. ``setup.manifest`` is entered
    once per stretch of work that has no span of its own (the flight
    record sums it)."""
    run = Run(plan, loop_state, config["Training"], log_name, log_dir, verbosity, profiler)
    train_loader = loaders[0]
    with span("setup.manifest"):
        _open_telemetry(run, flight)
    with span("setup.introspect"):
        _open_introspection(run, model, tx, state, train_loader)
    with span("setup.manifest"):
        _open_resilience(run)
    # rank-0 tensorboard scalars (reference: train_validate_test.py:130-137)
    with span("setup.tensorboard"):
        run.writer = get_summary_writer(log_name, log_dir)
    with span("setup.manifest"):
        # flight ``parallel`` block (docs/PARALLELISM.md): the partitioner's
        # mesh shape, axis names, fsdp factor, per-leaf param/optimizer
        # sharding summary, per-device bytes, and any replicated-leaf
        # fallbacks — computed from the PLACED state so it reports what is
        # actually committed, not what was intended
        if partitioner is not None:
            parallel_block = partitioner.manifest(state=state)
        else:
            parallel_block = {"available": False, "reason": "caller passed no partitioner"}
        run.pod.set_parallel(parallel_block)
    with span("setup.drift_reference"):
        stats_block = _drift_reference(run, train_loader)
    with span("setup.graftcheck"):
        graftcheck_block = _graftcheck_block(run, state, train_loader, partitioner)
    with span("setup.manifest"):
        _start_record(run, loaders, config, run_config, parallel_block, graftcheck_block,
                      stats_block, manifest_extra)
    return run
