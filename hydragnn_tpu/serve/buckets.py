"""Bucketed pad-plan ladder: the serving-side compile cache.

Training amortizes one worst-case pad plan over an epoch; serving cannot
— a single-graph request padded to the dataset worst case wastes compute
proportional to the size spread, while padding each request to its own
shape recompiles per shape (seconds on XLA:TPU — a latency cliff no
online path can absorb). The middle ground is a small LADDER of padded
shapes ("buckets"), each AOT-compiled once at startup: every request
routes to the smallest bucket whose per-graph caps fit it, so
steady-state traffic never sees a fresh compile and small graphs never
pay the big-graph pad.

The plans themselves come from ``data/loader.py:bucket_pad_plans`` (the
same ``pad_plan_for`` arithmetic every GraphLoader uses), so a bucket
batch obeys exactly the invariants the model chassis assumes of loader
batches.
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence


@dataclasses.dataclass(frozen=True)
class Bucket:
    """One rung of the ladder.

    ``cap_nodes``/``cap_edges`` are PER-GRAPH routing caps; the pad plan
    (``node_pad``, ``edge_pad``, ``graph_pad``) covers any batch of up to
    ``max_batch`` graphs each within the caps, by construction
    (bucket_pad_plans builds it from a synthetic worst-case batch of
    cap-sized graphs)."""

    index: int
    cap_nodes: int
    cap_edges: int
    node_pad: int
    edge_pad: int
    graph_pad: int
    max_batch: int

    def fits_graph(self, num_nodes: int, num_edges: int) -> bool:
        return num_nodes <= self.cap_nodes and num_edges <= self.cap_edges

    def fits_totals(self, tot_nodes: int, tot_edges: int, n_graphs: int) -> bool:
        """Whether a concrete batch fits the PAD PLAN (batch_graphs needs
        one spare node slot and one spare graph slot for padding)."""
        return (
            tot_nodes < self.node_pad
            and tot_edges <= self.edge_pad
            and n_graphs < self.graph_pad
        )


def build_bucket_ladder(
    reference_samples: Sequence,
    max_batch: int,
    num_buckets: int = 3,
    node_multiple: int = 16,
    edge_multiple: int = 8,
) -> List[Bucket]:
    """Size a ladder from a reference sample set (typically the prepared
    dataset the model was trained on — serving traffic is assumed to be
    drawn from a similar size distribution; graphs beyond the top rung
    take the server's oversize fallback path).

    Ascending, deduplicated by pad plan: quantile spacing on a tight size
    distribution can collapse adjacent rungs into one."""
    from hydragnn_tpu.data.loader import bucket_pad_plans

    plans = bucket_pad_plans(
        reference_samples,
        max_batch,
        num_buckets=num_buckets,
        node_multiple=node_multiple,
        edge_multiple=edge_multiple,
    )
    return [
        Bucket(
            index=i,
            cap_nodes=cap_n,
            cap_edges=cap_e,
            node_pad=plan[0],
            edge_pad=plan[1],
            graph_pad=plan[2],
            max_batch=max_batch,
        )
        for i, ((cap_n, cap_e), plan) in enumerate(plans)
    ]


def route(
    buckets: Sequence[Bucket], num_nodes: int, num_edges: int
) -> Optional[Bucket]:
    """Smallest bucket whose per-graph caps fit, or None (oversize —
    the server's fallback path decides what happens next). Buckets are
    ascending, so the first fit is the smallest."""
    for b in buckets:
        if b.fits_graph(num_nodes, num_edges):
            return b
    return None


class BucketCompileCache:
    """AOT-compiled forward executable per bucket.

    ``warmup`` materializes the whole ladder up front; after that,
    :meth:`executable` is a dict lookup — a serving dispatch can only
    recompile by going through the eager fallback, which the server
    counts as a miss.

    With an :class:`~hydragnn_tpu.utils.exec_cache.ExecCache` attached,
    warmup first tries the persistent on-disk executable cache: a disk
    hit deserializes in milliseconds with ZERO XLA compiles (a second
    replica or a post-restart server starts warm), and every live
    compile is stored back so the NEXT process hits. ``compile_warmup``
    counts only LIVE compiles — a fully warm start reports
    ``compile_warmup == 0``, which bench_serve.py and the ci.sh warm
    stage pin."""

    def __init__(
        self,
        forward,
        variables,
        build_warm_batch,
        metrics=None,
        exec_cache=None,
        identity=None,
        compat=None,
    ):
        """``forward`` is the jitted forward fn (variables, batch) ->
        outputs; ``build_warm_batch(bucket)`` builds a structurally
        representative all-padding batch at the bucket's plan.
        ``identity`` is the model-architecture half of the disk-cache
        key (the bucket pad plan is mixed in per bucket); ``compat`` is
        the environment manifest (versions, device_kind, layout) the
        disk cache validates entries against."""
        self._forward = forward
        self._variables = variables
        self._build_warm_batch = build_warm_batch
        self._metrics = metrics
        self._exec_cache = exec_cache
        self._identity = identity
        self._compat = compat or {}
        self._compiled = {}
        # armed by rebind(require_canary=True) after a hot reload: an
        # on-demand compile against the NEW variables must pass the same
        # all-finite gate the reload canary applied to the warm ladder
        self._post_rebind_gate = False

    def _key(self, b: Bucket) -> Optional[str]:
        if self._exec_cache is None or not self._exec_cache.enabled:
            return None
        from hydragnn_tpu.utils.exec_cache import fingerprint

        return fingerprint(
            "serve_bucket",
            self._identity,
            (b.node_pad, b.edge_pad, b.graph_pad, b.max_batch),
        )

    def _load_disk(self, b: Bucket):
        key = self._key(b)
        if key is None:
            return None
        return self._exec_cache.load(key, self._compat, label=f"bucket_{b.index}")

    def _compile(self, b: Bucket):
        lowered = self._forward.lower(self._variables, self._build_warm_batch(b))
        if self._key(b) is None:
            return lowered.compile()
        from hydragnn_tpu.utils.exec_cache import compile_for_store

        return compile_for_store(lowered)

    def _store_disk(self, b: Bucket, exe) -> None:
        key = self._key(b)
        if key is not None:
            self._exec_cache.store(key, exe, self._compat, label=f"bucket_{b.index}")

    def warmup(self, buckets: Sequence[Bucket]) -> None:
        for b in buckets:
            if b.index in self._compiled:
                continue
            exe = self._load_disk(b)
            if exe is not None:
                # disk hit: no XLA compile happened, so compile_warmup
                # stays untouched (the exec-cache hit counter carries it)
                self._compiled[b.index] = exe
                continue
            exe = self._compile(b)
            self._compiled[b.index] = exe
            self._store_disk(b, exe)
            if self._metrics is not None:
                self._metrics.record_compile(hit=False, warmup=True)

    def rebind(self, variables, require_canary: bool = False) -> None:
        """Point future on-demand compiles at new weights (hot reload).
        Existing executables are shape-specialized, not value-
        specialized — they serve the new variables unchanged.
        ``require_canary=True`` additionally routes every FUTURE
        on-demand :meth:`executable` materialization through the
        all-finite gate the reload canary applied to the warm ladder —
        without it, a bucket first compiled after a reload would serve
        the new weights unvetted."""
        self._variables = variables
        if require_canary:
            self._post_rebind_gate = True

    def executable(self, bucket: Bucket):
        """The pre-built executable for ``bucket``; materializes on
        demand — disk cache first, else a live compile (recorded as a
        MISS: this only happens if warmup was skipped)."""
        exe = self._compiled.get(bucket.index)
        if exe is None:
            exe = self._load_disk(bucket)
            hit_disk = exe is not None
            if exe is None:
                exe = self._compile(bucket)
            if self._post_rebind_gate:
                self._canary_gate(exe, bucket)
            self._compiled[bucket.index] = exe
            if not hit_disk:
                self._store_disk(bucket, exe)
                if self._metrics is not None:
                    self._metrics.record_compile(hit=False)
        elif self._metrics is not None:
            self._metrics.record_compile(hit=True)
        return exe

    def _canary_gate(self, exe, bucket: Bucket) -> None:
        """The reload canary's all-finite check, applied to an
        executable materialized AFTER a hot reload: run it on the
        bucket's warm batch against the current (post-reload) variables
        and reject non-finite outputs before it ever serves traffic."""
        import numpy as np

        outs = exe(self._variables, self._build_warm_batch(bucket))
        for i, o in enumerate(outs):
            if not np.all(np.isfinite(np.asarray(o))):
                raise RuntimeError(
                    f"post-reload canary gate: on-demand executable for "
                    f"bucket {bucket.index} produced non-finite outputs "
                    f"(head {i}) against the reloaded weights"
                )

    def __len__(self) -> int:
        return len(self._compiled)
