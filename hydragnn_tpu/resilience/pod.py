"""The pod plane of one training run: what a run does because it is one
host of several (docs/OBSERVABILITY.md "Pod visibility",
docs/RESILIENCE.md "Pod recovery").

:class:`PodPlane` is the one object ``train/loop.py`` talks to. It joins
the two halves that a multi-host run needs at the same epoch boundaries:
``obs/podview.py`` (host identity, per-host flight shards, the rank-0
:class:`~hydragnn_tpu.obs.podview.SkewMonitor`) and
``resilience/podckpt.py`` (heartbeats, coordinated preemption, sharded
generations with a rank-0 COMMIT). It lives on this side because
``podckpt`` needs jax and flax while ``obs/podview.py`` is stdlib + knobs
only and is read by jax-free tools: ``resilience`` may import ``obs``,
``obs`` never imports this module.

Which parts are live is read from what the process can observe
(``host_identity()``, ``podview_enabled()``, ``HYDRAGNN_POD_CKPT``): on
a one-host run every method returns at once and the loop calls them all
the same.
"""

from __future__ import annotations

import os
import time
from typing import Any, Dict, List, Optional

import jax

from hydragnn_tpu.obs import podview
from hydragnn_tpu.obs.flight import FlightRecorder
from hydragnn_tpu.obs.registry import get_registry
from hydragnn_tpu.resilience import podckpt
from hydragnn_tpu.resilience.preempt import PodHostLost
from hydragnn_tpu.utils import knobs


class PodPlane:
    def __init__(self, log_dir: str, log_name: str, telemetry_on: bool):
        self.host, self.hosts = podview.host_identity()
        self._telemetry_on = telemetry_on
        # on: every host writes its own flight shard — rank 0 keeps the
        # canonical flight.jsonl, host k writes flight.host<k>.jsonl —
        # instead of non-zero ranks staying silent
        self.on = telemetry_on and podview.podview_enabled()
        self.run_id = podview.resolve_run_id(log_name)
        self.run_dir = os.path.join(log_dir, log_name)
        self.flight: Optional[FlightRecorder] = None
        self.layout = None  # the partitioner's layout block, for the shard manifests
        self._preempt = None
        self._overhead_s = 0.0
        self._t_run0 = time.perf_counter()
        self.monitor = None
        if self.on and self.host == 0:
            self.monitor = podview.SkewMonitor(
                self.run_dir, host=self.host, hosts=self.hosts,
                run_id=self.run_id, registry=get_registry(),
            )
        # multi-host runs cut sharded generations with a rank-0 COMMIT
        # marker, exchange heartbeats, and coordinate preemption cuts so
        # every host checkpoints the SAME generation. Single-host runs keep
        # the plain msgpack path only.
        self.signaler = None
        self.ckpt_on = False
        if self.on and self.hosts > 1:
            self.signaler = podckpt.PodSignaler(self.run_dir, host=self.host, hosts=self.hosts)
            self.ckpt_on = knobs.get_bool("HYDRAGNN_POD_CKPT", True)

    # -- set-up --------------------------------------------------------------

    def open_flight(self, flight: Optional[FlightRecorder]) -> FlightRecorder:
        """The caller's recorder, or this host's own shard of the run's."""
        if flight is None:
            path = None
            if self._telemetry_on and (self.host == 0 or self.on):
                path = podview.host_flight_path(self.run_dir, self.host)
            flight = FlightRecorder(
                path, enabled=self._telemetry_on, host=self.host if self.on else None
            )
        self.flight = flight
        return flight

    def trigger_rules(self, training: Dict[str, Any]) -> List[Any]:
        """The cross-host SLO rules (only called under ``slo_triggers``,
        which is what keeps ``obs.triggers`` a lazy import)."""
        from hydragnn_tpu.obs.triggers import TriggerRule

        rules = []
        if self.monitor is not None:
            # over the gauges the SkewMonitor publishes; the step_skew
            # threshold defaults to the scaling model's skew_tolerance
            rules.append(TriggerRule(
                "podview_step_skew", "step_skew", "podview.skew_frac",
                float(training.get("podview_skew_threshold") or self.monitor.threshold),
            ))
            rules.append(TriggerRule(
                "podview_host_stall", "host_stall", "podview.stall_age_s",
                knobs.get_float("HYDRAGNN_PODVIEW_STALL_S", 120.0),
            ))
        if self.signaler is not None and self.signaler.lost_after_s > 0:
            # a peer missing HYDRAGNN_POD_LOST_AFTER_S seconds of
            # heartbeats sets podview.lost_hosts > 0 at the epoch
            # boundary; the incident bundles the heartbeat view
            rules.append(TriggerRule("podview_host_lost", "host_lost", "podview.lost_hosts", 0.5))
        return rules

    def set_parallel(self, parallel_block) -> None:
        """The committed layout feeds the SkewMonitor's collective-aware
        cost attribution and the pod shards' manifests."""
        if isinstance(parallel_block, dict):
            self.layout = parallel_block.get("layout")
        if self.monitor is not None:
            self.monitor.set_parallel(parallel_block)

    def manifest(self) -> Dict[str, Any]:
        """``run_start``'s ``podview`` block: which host shard this is and
        the shared run id the merge reader joins on."""
        return {"enabled": self.on, "host": self.host, "hosts": self.hosts, "run_id": self.run_id}

    def arm(self, preempt) -> None:
        """SIGTERM on this host announces the cut generation to the pod
        (``preempt.proposed_gen`` is kept current at each epoch start)."""
        self._preempt = preempt
        if preempt is not None and self.signaler is not None:
            preempt.signaler = self.signaler

    # -- epoch boundaries ----------------------------------------------------

    @property
    def cuts_at_epoch_end(self) -> bool:
        """A pod defers a mid-epoch preemption to the epoch's END boundary —
        the generation the SIGTERM handler announced to the peers."""
        return self.signaler is not None

    def epoch_start(self, epoch: int) -> None:
        if self.signaler is None:
            return
        # a SIGTERM landing anywhere in this epoch announces the cut at its
        # END boundary, so every host checkpoints the same generation
        if self._preempt is not None:
            self._preempt.proposed_gen = epoch + 1
        self.signaler.heartbeat(epoch=epoch, force=True)

    def epoch_recorded(self, epoch: int, summary: Dict[str, Any]) -> None:
        """After the ``epoch`` event, BEFORE trigger evaluation (so the
        step_skew / host_stall / host_lost rules see THIS epoch): append
        this host's summary to its shard as ``host_epoch`` — the
        cross-host exchange unit — fold every host's into the podview.*
        gauges on rank 0 (``podview`` event), refresh this host's beat and
        declare any peer whose beats lapsed."""
        if self.on:
            t0 = time.perf_counter()
            summary = dict(hosts=self.hosts, **summary)
            self.flight.record(
                "host_epoch", epoch=epoch, host=self.host, run_id=self.run_id, **summary
            )
            if self.monitor is not None:
                skew = self.monitor.observe_epoch(epoch, dict(summary, epoch=epoch))
                if skew is not None:
                    self.flight.record("podview", **skew)
            self._overhead_s += time.perf_counter() - t0
        if self.signaler is not None:
            self.signaler.heartbeat(epoch=epoch + 1, force=True)
            lost_now = self.signaler.lost_hosts()
            if lost_now:
                self._declare_lost(lost_now, epoch + 1)

    def _declare_lost(self, lost, epoch_now: int) -> None:
        """Record each newly-lost peer exactly once (``mark_declared``
        dedupes): one ``host_lost`` flight event per host plus the
        ``podview.lost_host(s)`` gauges the podview_host_lost rule reads."""
        fresh = self.signaler.mark_declared(lost)
        if not fresh:
            return
        reg = get_registry()
        reg.gauge("podview.lost_hosts").set(
            float(len(set(self.signaler.lost_hosts()) | set(lost)))
        )
        for h in fresh:
            reg.gauge("podview.lost_host").set(float(h))
            self.flight.record(
                "host_lost", host=int(h), epoch=int(epoch_now),
                lost_after_s=self.signaler.lost_after_s,
            )

    def checkpoint(self, ckpt_state, gen: int) -> None:
        """One sharded generation cut (resilience/podckpt.py): every
        host writes its shard + sha sidecar + manifest; rank 0
        bounded-waits for the peers' manifests, validates them, and
        writes ``gen<N>.COMMIT`` LAST. Runs BEFORE save_train_meta so a
        commit that dies on a lost peer leaves the meta sidecar
        describing the last COMMITTED generation, not this torn one."""
        if not self.ckpt_on:
            return
        self.signaler.heartbeat(epoch=gen, force=True)
        podckpt.save_pod_shard(
            ckpt_state, self.run_dir, gen=gen, host=self.host, hosts=self.hosts,
            step=int(jax.device_get(ckpt_state.step)), layout=self.layout,
        )
        if self.host != 0:
            # only rank 0 waits at the commit point: the simulated-host
            # CI mode runs hosts sequentially, and a non-zero host
            # blocking here would deadlock it
            return
        commit = podckpt.commit_generation(self.run_dir, gen, self.hosts, signaler=self.signaler)
        if commit.get("committed"):
            podckpt.prune_generations(self.run_dir)
            return
        # proceed-and-record: the failed commit is itself flight
        # evidence; a LOST peer additionally raises the typed exit so
        # the supervisor restarts from the last committed generation
        self.flight.record(
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
            self._declare_lost(lost, gen)
            raise PodHostLost(lost, gen)

    def peer_preempted(self, epoch_next: int) -> Optional[int]:
        """The peer whose announced preemption this host must follow at
        this boundary (cut the same generation, so the pod's shards agree
        and the supervisor restarts everyone from one COMMIT), or None."""
        req = self.signaler.preempt_request() if self.signaler is not None else None
        if (
            req is not None
            and int(req.get("host", -1)) != self.host
            and epoch_next >= int(req.get("gen", 0))
        ):
            return int(req.get("host", -1))
        return None

    # -- artifacts and the run's end -----------------------------------------

    def prom_path(self, prom_dir: str) -> Optional[str]:
        """Where this host's ``train.prom`` goes: rank 0 keeps the legacy
        name, any other host (real process or simulated podview host)
        writes ``train.host<k>.prom`` so a second host never clobbers the
        first; None on a host that writes none."""
        if not (jax.process_index() == 0 or self.on):
            return None
        return podview.host_artifact_path(os.path.join(prom_dir, "train.prom"), self.host)

    def run_end(self) -> Optional[Dict[str, Any]]:
        """``run_end``'s ``podview`` block — the measured cost of the
        plane: shard writes + rank-0 skew folds as a fraction of run wall
        time (the <1% clean-path acceptance gate ci.sh asserts)."""
        if not self.on:
            return None
        return {
            **self.manifest(),
            "overhead_s": round(self._overhead_s, 6),
            "overhead_frac": round(
                self._overhead_s / max(time.perf_counter() - self._t_run0, 1e-9), 8
            ),
        }
