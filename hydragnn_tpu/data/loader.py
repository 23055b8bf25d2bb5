"""Host-side batching into statically-padded GraphBatches.

Replaces the reference's DistributedSampler + PyG DataLoader stack
(reference: hydragnn/preprocess/load_data.py:226-283). TPU-specific
concerns drive the design:

  - every batch in a loader has the SAME padded (nodes, edges, graphs)
    shape, so the jitted train step compiles exactly once;
  - the pad plan is computed from the dataset up front, not per batch.
    Where batch membership is FIXED (a loader that does not shuffle, or
    one that only permutes the order of the same ``samples[b*bs:(b+1)*bs]``
    chunks: ``cache_device_batches``, or ``fixed_membership`` for the
    scan-epoch path) the batches that will ever exist are known at
    construction, and the plan is the largest of them
    (``plan == "fixed_membership"``). A loader that re-draws membership
    every epoch cannot know its batches, and pads to the worst case:
    the ``batch_size`` largest graphs of the dataset in one batch
    (``plan == "worst_case"``, :func:`pad_plan_for`);
  - per-epoch shuffling is seeded (epoch number = reference
    ``sampler.set_epoch``, train_validate_test.py:113-115);
  - multi-host sharding = stride-sharding the sample list per process
    (DistributedSampler equivalent); multi-device-per-host sharding =
    stacking D equally-shaped sub-batches along a leading device axis.
"""

from __future__ import annotations

import math
import os
import time
from typing import Iterator, List, Optional, Sequence

import jax
import numpy as np

from hydragnn_tpu.graph.batch import GraphBatch, batch_graphs
from hydragnn_tpu.data.dataset import GraphSample, samples_to_graph_dicts
from hydragnn_tpu.utils import knobs


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def _plan_for_totals(
    nodes: int, edges: int, graphs: int, node_multiple: int, edge_multiple: int
) -> tuple:
    """(n_node_pad, n_edge_pad, n_graph_pad) for a batch of ``graphs``
    graphs holding ``nodes`` nodes and ``edges`` edges: one padding node
    and one padding graph beyond them, rounded to the multiples."""
    return (
        _round_up(nodes + 1, node_multiple),
        max(_round_up(edges + 1, edge_multiple), edge_multiple),
        graphs + 1,
    )


def pad_plan_for(
    samples: Sequence[GraphSample],
    batch_size: int,
    node_multiple: int = 16,
    edge_multiple: int = 8,
) -> tuple:
    """Static (n_node_pad, n_edge_pad, n_graph_pad) covering ANY batch of
    ``batch_size`` samples drawn from ``samples``.

    Worst case is the ``batch_size`` largest graphs landing in one batch;
    bounding by that keeps every epoch's batches one compiled shape
    whatever the shuffle draws. The serving ladder
    (:func:`bucket_pad_plans`) rests on this guarantee; a ``GraphLoader``
    whose membership is fixed plans tighter (:func:`_largest_batch`).
    """
    nodes = sorted((s.num_nodes for s in samples), reverse=True)
    edges = sorted((s.num_edges for s in samples), reverse=True)
    return _plan_for_totals(
        sum(nodes[:batch_size]),
        sum(edges[:batch_size]),
        batch_size,
        node_multiple,
        edge_multiple,
    )


class _CapSize:
    """Synthetic (num_nodes, num_edges)-only sample for pad planning."""

    __slots__ = ("num_nodes", "num_edges")

    def __init__(self, num_nodes: int, num_edges: int):
        self.num_nodes = num_nodes
        self.num_edges = num_edges


def bucket_pad_plans(
    samples: Sequence,
    batch_size: int,
    num_buckets: int = 3,
    node_multiple: int = 16,
    edge_multiple: int = 8,
) -> list:
    """Ladder of serving pad plans over the dataset's size distribution.

    Returns an ascending, plan-deduplicated list of
    ``((cap_nodes, cap_edges), (n_node_pad, n_edge_pad, n_graph_pad))``.
    Caps are per-graph quantile cut points (bucket ``i`` covers graphs up
    to the ``(i+1)/num_buckets`` quantile of nodes AND of edges; the last
    bucket's caps are the dataset maxima); each plan is
    :func:`pad_plan_for` over a synthetic worst-case batch of
    ``batch_size`` cap-sized graphs, so ANY batch of up to ``batch_size``
    graphs within the caps fits the plan — the guarantee the serving
    router (hydragnn_tpu/serve/buckets.py) relies on to never trigger a
    fresh compile in steady state.
    """
    if not samples:
        raise ValueError("bucket_pad_plans needs a non-empty sample set")
    if num_buckets < 1:
        raise ValueError(f"num_buckets must be >= 1, got {num_buckets}")
    nodes = np.sort(np.asarray([s.num_nodes for s in samples]))
    edges = np.sort(np.asarray([s.num_edges for s in samples]))
    n = len(nodes)
    plans = []
    seen = set()
    for i in range(num_buckets):
        k = min(n - 1, max(0, math.ceil((i + 1) / num_buckets * n) - 1))
        cap_n, cap_e = int(nodes[k]), int(edges[k])
        plan = pad_plan_for(
            [_CapSize(cap_n, cap_e)] * batch_size,
            batch_size,
            node_multiple,
            edge_multiple,
        )
        if plan in seen:
            continue
        seen.add(plan)
        plans.append(((cap_n, cap_e), plan))
    return plans


class GraphLoader:
    """Iterable over fixed-shape GraphBatches.

    Args:
      samples: the split's samples (edges and targets already built).
      batch_size: graphs per batch (per process, matching the reference's
        per-rank batch size under DDP).
      shuffle: reshuffle each epoch (seeded by ``set_epoch``).
      num_shards / shard_rank: multi-host data sharding (DistributedSampler
        equivalent): this loader only sees samples[shard_rank::num_shards].
      device_stack: if > 1, each yielded batch has a leading device axis of
        this size; batch_size must divide evenly by it. Edge indices stay
        local to each sub-batch (shard_map-ready: no cross-device gathers).
      cache_device_batches: build every batch once (fixed composition) and
        keep it on device; epochs then permute batch ORDER only. Removes
        per-epoch host batching + H2D transfer from the hot loop — the win
        is large when the host->device link is slow — at the cost of
        coarser shuffling (batch membership is fixed after epoch 0). A
        loader that does not shuffle loses nothing by it, and
        ``keep_on_device`` turns it on for one after construction: a
        scanned run does that to its test loader (``train/loop.py``).
      fixed_membership: the run will consume this shuffling loader
        through ``stacked_device_batches`` (scan-epoch dispatch), which
        permutes batch ORDER only. The loader then keeps membership fixed
        on EVERY path — ``__iter__`` draws the same
        ``samples[b*bs:(b+1)*bs]`` chunks in an epoch-seeded order, so a
        run that falls back to per-step dispatch meets no other batch —
        and cuts its pad plan to those batches. Without effect when
        ``scan_reshuffle_every`` asks for membership-level reshuffling.

    ``plan`` says which pad plan the loader got: ``"fixed_membership"``
    (no shuffle, ``cache_device_batches`` or ``fixed_membership``, and
    ``scan_reshuffle_every`` 0: the largest batch that will be built, over
    every shard and sub-batch) or ``"worst_case"`` (:func:`pad_plan_for`).
    ``real_nodes_max`` / ``real_edges_max`` / ``aligned_edges_max`` are
    the sums the plan was cut to.
    """

    def __init__(
        self,
        samples: Sequence[GraphSample],
        batch_size: int,
        shuffle: bool = False,
        seed: int = 0,
        num_shards: int = 1,
        shard_rank: int = 0,
        device_stack: int = 1,
        node_multiple: int = 16,
        edge_multiple: int = 8,
        drop_last: bool = False,
        cache_device_batches: bool = False,
        prefetch: Optional[int] = None,
        scan_reshuffle_every: int = 0,
        dense_slots: bool | int = True,
        run_align: bool | int = True,
        fixed_membership: bool = False,
    ):
        if device_stack > 1 and batch_size % device_stack != 0:
            raise ValueError(
                f"batch_size {batch_size} must be divisible by device_stack {device_stack}"
            )
        self.all_samples = list(samples)
        # DistributedSampler-style equalization: every shard sees exactly
        # ceil(n / num_shards) samples (wrapping around), so every process
        # runs the same number of jitted steps — required for cross-host
        # collectives to stay in lockstep.
        n = len(self.all_samples)
        self.samples = [
            self.all_samples[i] for i in _shard_indices(n, num_shards, shard_rank)
        ]
        self.batch_size = batch_size
        self.shuffle = shuffle
        self.seed = seed
        self.device_stack = device_stack
        self.drop_last = drop_last
        self.cache_device_batches = cache_device_batches
        self.scan_reshuffle_every = scan_reshuffle_every
        # Membership is fixed when every path builds the same
        # samples[b*bs:(b+1)*bs] chunks in every epoch; the batches that
        # will exist are then known here, and the plan is cut to them.
        self.fixed_membership = not shuffle or (
            scan_reshuffle_every == 0 and (cache_device_batches or fixed_membership)
        )
        # an explicit argument wins; HYDRAGNN_NUM_PREFETCH sets the default
        if prefetch is None:
            raw = knobs.get_str("HYDRAGNN_NUM_PREFETCH", "2")
            try:
                prefetch = int(raw)
            except ValueError:
                raise ValueError(
                    f"HYDRAGNN_NUM_PREFETCH must be an integer, got {raw!r}"
                ) from None
        self.prefetch = prefetch
        self._cached_batches: Optional[List[GraphBatch]] = None
        self._stacked: Optional[GraphBatch] = None
        self._stacked_key: Optional[int] = None
        self._sharding = None
        self._global_mesh = None
        self._global_axes = None
        self._placer = None
        self._epoch = 0
        sub = batch_size // device_stack
        # Pad plan from the FULL dataset, not the local shard: all hosts
        # must compile identical batch shapes. The largest (sub-)batch is
        # the ``sub`` largest graphs in one batch where membership is
        # re-drawn (pad_plan_for's worst case), and the largest chunk of
        # ANY shard where it is fixed.
        drawn = None
        if self.fixed_membership:
            take = len(self) * batch_size  # drop_last never draws the rest
            drawn = [_shard_indices(n, num_shards, r)[:take] for r in range(num_shards)]

        self.real_nodes_max = _largest_batch(
            [s.num_nodes for s in self.all_samples], sub, drawn
        )
        self.real_edges_max = _largest_batch(
            [s.num_edges for s in self.all_samples], sub, drawn
        )
        self.aligned_edges_max = None
        self.pad_nodes, self.pad_edges, self.pad_graphs = _plan_for_totals(
            self.real_nodes_max, self.real_edges_max, sub, node_multiple, edge_multiple
        )
        # dense slot count = dataset max in-degree (static across batches
        # AND hosts — derived from the full dataset like the pad plan).
        # True = AUTO: emit the dense map only when the slot inflation
        # (pad_nodes x Dmax vs pad_edges) stays under ~1.35x — tight
        # degree distributions (molecular radius graphs: Dmax ~= mean)
        # win big from dense [N, D, H] aggregation, while wide ones
        # (lattice surfaces: Dmax ~2x mean) pay more in inflated edge
        # passes than the dense reductions save (measured on v5e:
        # flagship BCC 2.07x inflation regressed 5.2k -> 4.3k graphs/s;
        # docs/PERF.md r03). An int pins the slot count unconditionally;
        # False/0 disables the map (pure CSR aggregation).
        if dense_slots is True:
            dmax = max_in_degree(self.all_samples)
            inflation = (
                self.pad_nodes * dmax / max(self.pad_edges, 1) if dmax else None
            )
            self.dense_slots = dmax if dmax and inflation <= 1.35 else None
        elif dense_slots:
            self.dense_slots = int(dense_slots)
        else:
            self.dense_slots = None
        # Run-aligned edge layout (graph/batch.py run_align): pads each
        # node's receiver-run to a multiple of K so segment reductions
        # pre-reduce K-fold before the serial scatter. AUTO (True):
        # K = 8 whenever the dense map is off (they answer the same
        # scatter-cost problem; dense wins for tight degree
        # distributions, run-align for wide ones) and the dataset has
        # edges. The pad plan widens to the ALIGNED largest batch. An int
        # pins K; False/0 disables.
        if run_align is True:
            self.run_align = 8 if self.dense_slots is None else 0
        else:
            self.run_align = int(run_align) if run_align else 0
            if self.run_align > 1 and self.dense_slots is not None:
                raise ValueError(
                    "run_align and dense_slots are mutually exclusive — pass "
                    "dense_slots=False alongside an explicit run_align"
                )
        if self.run_align > 1:
            aligned = _aligned_edge_counts(self.all_samples, self.run_align)
            if aligned is None:
                self.run_align = 0  # no edge_index anywhere — nothing to align
            else:
                self.aligned_edges_max = _largest_batch(aligned, sub, drawn)
                need = max(self.aligned_edges_max + 1, self.pad_edges)
                # Align the edge pad so the Pallas kernel grids divide it
                # evenly at BOTH scales they run on — E rows (gathers /
                # local sums) and E/K rows (pre-reduced segment ops).
                # Otherwise every pallas_call input pays a whole-array
                # pad copy per layer (r05 trace: 6 x 0.63 ms + 2.5 GB of
                # re-written bf16 [E,H] arrays on the flagship, just to
                # add 120 rows). Only at scale: for small batches the
                # in-kernel pad costs microseconds while grid alignment
                # would multiply E_pad (a 176-edge CI batch would pad to
                # 4096), bloating memory and perturbing every
                # accumulation-order-sensitive equivalence test.
                from hydragnn_tpu.ops.segment_pallas import (
                    _BCAST_CE as _bcast_ce,
                    CE as _kernel_ce,
                )

                grid_mult = self.run_align * _kernel_ce
                mult = math.lcm(edge_multiple, self.run_align)
                if need >= 8 * grid_mult:
                    mult = math.lcm(edge_multiple, grid_mult)
                    # The fused gather+stats kernel additionally needs
                    # E % _BCAST_CE == 0 and _BCAST_CE % K == 0
                    # (ops/segment_pallas.py:gather_presum_eligible); a
                    # hand-tuned HYDRAGNN_BCAST_CE outside the lcm would
                    # otherwise silently disable it (ADVICE r5 #1) —
                    # correct fallback, vanished perf, no signal.
                    if _bcast_ce % self.run_align == 0:
                        mult = math.lcm(mult, _bcast_ce)
                    else:
                        import warnings

                        warnings.warn(
                            f"HYDRAGNN_BCAST_CE={_bcast_ce} is not a "
                            f"multiple of run_align={self.run_align}; the "
                            "fused PNA gather+stats kernel stays DISABLED "
                            "for this loader (unfused fallback, correct "
                            "but slower)",
                            RuntimeWarning,
                            stacklevel=2,
                        )
                self.pad_edges = _round_up(need, mult)
        # Local-window block target: sized to the DATASET's mean graph
        # (capped by the [B, H] VMEM accumulator), so one kernel block
        # covers whole graphs and large graphs don't re-scan their edge
        # window per 128-row block (docs/PERF.md r04). Derived from
        # all_samples — like the pad plan — so every batch (and every
        # host) emits identically-shaped windows.
        mean_nodes = int(
            sum(s.num_nodes for s in self.all_samples) / max(len(self.all_samples), 1)
        )
        # cap 512: an r05 A/B at 640 (one block per 572-node large
        # graph, no window re-scan at all) traced 87.0 vs 86.9 ms —
        # the residual re-scan is noise once the r05 pad/dtype fixes
        # landed, and larger blocks cost VMEM for nothing
        self.win_block_rows = min(512, _round_up(max(mean_nodes, 128), 128))
        self._dicts = samples_to_graph_dicts(self.samples)

    @property
    def plan(self) -> str:
        return "fixed_membership" if self.fixed_membership else "worst_case"

    def set_epoch(self, epoch: int) -> None:
        self._epoch = epoch

    def set_sharding(self, sharding) -> None:
        """Sharding for cached device batches (e.g. NamedSharding over the
        data mesh for device_stack > 1, so cached batches live on their
        target devices instead of being resharded from device 0 each step).
        Must be set before the first iteration builds the cache."""
        if sharding is not self._sharding:
            self._cached_batches = None  # rebuild with the new placement
            self._stacked = None
        self._sharding = sharding

    def set_global_mesh(self, mesh, axes=None) -> None:
        """Multi-host mode: assemble each local [device_stack, ...] batch
        into global jax.Arrays sharded over ``mesh``'s batch axes
        (``axes``; default the data axis — the Partitioner passes its
        composed ``(data, fsdp)`` lead axes; leading axis = device_stack
        × process_count). The assembly runs in the prefetch thread so
        cross-host batch formation overlaps compute."""
        if mesh is not self._global_mesh:
            self._cached_batches = None
            self._stacked = None
        self._global_mesh = mesh
        self._global_axes = axes

    def set_placer(self, placer) -> None:
        """Arbitrary per-batch placement callable (the Partitioner's
        ``shard_batch`` for composed meshes whose per-FIELD layouts a
        single uniform sharding cannot express, e.g. the edge axis).
        Overrides ``set_sharding``; must be set before the first
        iteration builds any cache."""
        if placer is not self._placer:
            self._cached_batches = None
            self._stacked = None
        self._placer = placer

    def __len__(self) -> int:
        n = len(self.samples)
        if self.drop_last:
            return n // self.batch_size
        return math.ceil(n / self.batch_size)

    @property
    def num_samples(self) -> int:
        return len(self.samples)

    def peek_batch(self) -> GraphBatch:
        """First batch of the current epoch's order, built and placed
        exactly as ``__iter__`` would build it — WITHOUT counting as an
        epoch iteration. Telemetry consumers (the graftcheck manifest
        stamp in ``train/loop.py``) peek here so loader wrappers that
        count ``__iter__`` draws (epoch schedulers, fault-injection
        harnesses) only ever see real epochs."""
        if self.shuffle and self.fixed_membership:
            first = self._chunks()[0]
        else:
            first = self._order()[: self.batch_size]
        return self._place(self._make_batch(first))

    def _batch_order(self) -> np.ndarray:
        """This epoch's order of the fixed ``samples[b*bs:(b+1)*bs]``
        chunks. ``cache_device_batches`` keeps its draw: an epoch-seeded
        permutation of ALL its batches, a partial one landing anywhere.
        A ``fixed_membership`` loader permutes its full batches (the
        draw of ``train_epoch_scan`` when there is no partial batch)
        and keeps a partial one last, so that ``__iter__`` can cut
        ``_order()`` every ``batch_size``."""
        nb = len(self)
        if not self.shuffle:
            return np.arange(nb)
        rng = np.random.default_rng(self.seed + self._epoch)
        if self.cache_device_batches:
            return rng.permutation(nb)
        full = len(self.samples) // self.batch_size
        return np.append(rng.permutation(full), np.arange(full, nb))

    def _chunks(self) -> list:
        """This epoch's batches of a fixed-membership loader, as indices
        into ``samples``, in the order they are drawn."""
        bs = self.batch_size
        base = np.arange(len(self.samples))
        return [base[b * bs : (b + 1) * bs] for b in self._batch_order()]

    def _order(self) -> np.ndarray:
        """Sample order of the current epoch: ``__iter__`` builds batch
        ``b`` from ``_order()[b*bs:(b+1)*bs]`` (the cached path yields
        ``_chunks()``, the same cut unless a partial batch was drawn
        before the last place)."""
        n = len(self.samples)
        if not self.shuffle:
            return np.arange(n)
        if self.fixed_membership:
            # samples that drop_last never draws close the order
            rest = np.arange(len(self) * self.batch_size, n)
            return np.concatenate(self._chunks() + [rest])
        rng = np.random.default_rng(self.seed + self._epoch)
        return rng.permutation(n)

    def _make_sub_batch(self, idx: Sequence[int]) -> GraphBatch:
        batch = batch_graphs(
            [self._dicts[i] for i in idx],
            n_node_pad=self.pad_nodes,
            n_edge_pad=self.pad_edges,
            n_graph_pad=self.pad_graphs,
            dense_slots=self.dense_slots,
            run_align=self.run_align,
            win_block_rows=self.win_block_rows,
        )
        # HYDRAGNN_DEBUG_BATCH=1 validates the layout contracts the jitted
        # chassis silently relies on (sorted receivers, masked-edge
        # targeting, window coverage) on every host batch — meant for
        # debugging external/custom sample producers; off by default
        # because it walks every edge array on the host per batch.
        if knobs.get_bool("HYDRAGNN_DEBUG_BATCH", False):
            batch.check_invariants()
        return batch

    def _make_batch(self, chunk: Sequence[int]) -> GraphBatch:
        sub = self.batch_size // self.device_stack
        if self.device_stack == 1:
            return self._make_sub_batch(chunk)
        subs = []
        for d in range(self.device_stack):
            part = chunk[d * sub : (d + 1) * sub]
            if len(part) == 0:
                # Partial final batch: an all-padding sub-batch keeps
                # the device axis full; masks zero it out everywhere.
                part = chunk[:1]
                empty = self._make_sub_batch(part)
                subs.append(_mask_out(empty))
            else:
                subs.append(self._make_sub_batch(part))
        return jax.tree_util.tree_map(lambda *xs: np.stack(xs), *subs)

    def _place(self, batch: GraphBatch) -> GraphBatch:
        """Device placement for a freshly-built host batch: global-mesh
        assembly (multi-host), explicit sharding (single-host mesh), or
        pass-through (jit moves it)."""
        if self._global_mesh is not None:
            from hydragnn_tpu.parallel.mesh import DATA_AXIS, globalize_batch

            if self.device_stack == 1:
                # the sharded steps expect a leading device axis even when
                # each process contributes a single sub-batch
                batch = jax.tree_util.tree_map(
                    lambda x: np.asarray(x)[None], batch
                )
            axes = self._global_axes if self._global_axes is not None else DATA_AXIS
            return globalize_batch(self._global_mesh, batch, axes=axes)
        if self._placer is not None:
            return self._placer(batch)
        if self._sharding is not None:
            return jax.device_put(batch, self._sharding)
        return batch

    def _build_cache(self) -> List[GraphBatch]:
        """Every ``samples[b*bs:(b+1)*bs]`` batch, built once and placed
        (on one device ``batch_graphs`` already ends in device arrays)."""
        bs = self.batch_size
        base = np.arange(len(self.samples))
        return [
            self._place(self._make_batch(base[b * bs : (b + 1) * bs]))
            for b in range(len(self))
        ]

    def keep_on_device(self) -> None:
        """``cache_device_batches`` for a loader built without it, and the
        cache built now rather than at the first ``__iter__``. Only for a
        loader that does not shuffle: its batches and their order never
        change, so iterating yields the same batches as before, without
        the host batching and the transfer. Raises what building raises
        (RESOURCE_EXHAUSTED for a split too large to be resident) and
        then leaves the loader as it was."""
        if self.shuffle and not self.cache_device_batches:
            raise ValueError(
                "a shuffling loader fixes its membership at construction: "
                "build it with cache_device_batches=True"
            )
        if self._cached_batches is None:
            self._cached_batches = self._build_cache()
        self.cache_device_batches = True

    def built_senders(self) -> Optional[np.ndarray]:
        """The senders of the batches this loader has built and keeps (the
        device-resident stack, else the kept batches) on the host,
        [..., E]; None where it keeps none or they are not all
        on this process."""
        if self._stacked is not None:
            kept = [self._stacked.senders]
        elif self._cached_batches:
            kept = [b.senders for b in self._cached_batches]
        else:
            return None
        if not all(getattr(s, "is_fully_addressable", True) for s in kept):
            return None
        return np.stack([np.asarray(s) for s in kept])

    def __iter__(self) -> Iterator[GraphBatch]:
        bs = self.batch_size
        nb = len(self)
        if self.cache_device_batches:
            if self._cached_batches is None:
                self._cached_batches = self._build_cache()
            for b in self._batch_order():
                yield self._cached_batches[b]
            return
        # Prefetch accounting into the shared telemetry registry
        # (hydragnn_tpu/obs): build_s is host batching + H2D placement,
        # prefetch_wait_s is time the CONSUMER blocked on the queue (the
        # part the producer thread failed to hide — the loader's share
        # of the train loop's data-wait span). Null counters when
        # telemetry is off; the timing branches are skipped entirely.
        from hydragnn_tpu.obs.registry import get_registry

        _reg = get_registry()
        _obs_on = _reg.enabled
        _c_build = _reg.counter("loader.build_s")
        _c_batches = _reg.counter("loader.batches_built")
        _c_wait = _reg.counter("loader.prefetch_wait_s")
        _c_stalls = _reg.counter("loader.prefetch_stalls")

        # Deterministic stalled-producer fault injection
        # (HYDRAGNN_INJECT_STALL_LOADER, docs/RESILIENCE.md): drives the
        # hang watchdog's data-wait abort path in tests; no-op otherwise.
        from hydragnn_tpu.resilience.inject import maybe_stall_loader

        order = self._order()
        if self.prefetch <= 0:
            for b in range(nb):
                maybe_stall_loader(b)
                t0 = time.perf_counter() if _obs_on else 0.0
                batch = self._place(self._make_batch(order[b * bs : (b + 1) * bs]))
                if _obs_on:
                    _c_build.inc(time.perf_counter() - t0)
                    _c_batches.inc()
                yield batch
            return
        # Background producer thread: batch assembly + H2D transfer
        # overlap with device compute (the reference's HydraDataLoader
        # thread-pool fetcher, hydragnn/preprocess/load_data.py:94-204 —
        # affinity pinning is unnecessary here, XLA owns the host).
        import queue
        import threading

        q: "queue.Queue" = queue.Queue(maxsize=self.prefetch)
        stop = threading.Event()
        sentinel = object()

        def put_stop_aware(item) -> bool:
            while not stop.is_set():
                try:
                    q.put(item, timeout=0.2)
                    return True
                except queue.Full:
                    continue
            return False  # consumer abandoned the generator

        # graftsync: thread-root
        def producer():
            try:
                for b in range(nb):
                    maybe_stall_loader(b)
                    t0 = time.perf_counter() if _obs_on else 0.0
                    batch = self._place(self._make_batch(order[b * bs : (b + 1) * bs]))
                    if _obs_on:
                        _c_build.inc(time.perf_counter() - t0)
                        _c_batches.inc()
                    if not put_stop_aware(batch):
                        return
                put_stop_aware(sentinel)
            except BaseException as exc:  # surfaced to the consumer
                put_stop_aware(exc)

        t = threading.Thread(target=producer, daemon=True)
        t.start()
        try:
            while True:
                if _obs_on:
                    t0 = time.perf_counter()
                    item = q.get()
                    dt = time.perf_counter() - t0
                    _c_wait.inc(dt)
                    if dt > 1e-3:  # the producer was actually behind
                        _c_stalls.inc()
                else:
                    item = q.get()
                if item is sentinel:
                    break
                if isinstance(item, BaseException):
                    raise item
                yield item
        finally:
            stop.set()

    def num_graphs_total(self) -> int:
        return len(self.samples)

    def stacked_device_batches(self, epoch: int = 0) -> GraphBatch:
        """Every batch of an epoch stacked on a new leading axis [B, ...]
        and placed on device — the input for the scan-over-epoch train
        path (train.state.make_scan_epoch). By default batch membership is
        fixed (like ``cache_device_batches``) and per-epoch shuffling
        happens device-side by permuting the batch axis — a deliberate
        divergence from the reference DataLoader(shuffle=True), which
        re-forms batches every epoch. ``scan_reshuffle_every=k`` restores
        membership-level reshuffling by rebuilding the stack host-side
        every k epochs (one extra H2D transfer per rebuild)."""
        k = self.scan_reshuffle_every
        key = (epoch // k) if (self.shuffle and k > 0) else None
        if self._stacked is None or key != self._stacked_key:
            bs = self.batch_size
            if key is None:
                base = np.arange(len(self.samples))
            else:
                # sample-level permutation, seeded like the __iter__ path
                base = np.random.default_rng(self.seed + key).permutation(
                    len(self.samples)
                )
            host = [
                self._make_batch(base[b * bs : (b + 1) * bs]) for b in range(len(self))
            ]
            stacked = jax.tree_util.tree_map(lambda *xs: np.stack(xs), *host)
            self._stacked = jax.device_put(stacked, self._sharding)
            self._stacked_key = key
        return self._stacked


def _shard_indices(n: int, num_shards: int, shard_rank: int) -> np.ndarray:
    """Indices into the full sample list that shard ``shard_rank`` sees:
    stride-sharded, wrapping around to ``ceil(n / num_shards)`` each."""
    if num_shards > 1 and n > 0:
        per_shard = math.ceil(n / num_shards)
        return (shard_rank + np.arange(per_shard) * num_shards) % n
    return np.arange(n)


def _largest_batch(sizes, sub: int, drawn=None) -> int:
    """Largest sum of per-sample ``sizes`` that one (sub-)batch of ``sub``
    samples can hold. ``drawn`` None: any ``sub`` samples can meet, so
    the ``sub`` largest. Else the sample indices each shard draws, in
    order: batches are consecutive chunks of them, and a chunk's
    sub-batches consecutive windows of ``sub``."""
    sizes = np.asarray(sizes, dtype=np.int64)
    if drawn is None:
        return int(np.sort(sizes)[::-1][:sub].sum())
    worst = 0
    for idx in drawn:
        mine = sizes[idx]
        if mine.size:
            mine = np.pad(mine, (0, -mine.size % sub))
            worst = max(worst, int(mine.reshape(-1, sub).sum(axis=1).max()))
    return worst


def _aligned_edge_counts(samples, k: int):
    """Per-sample edge-slot count under run-K alignment
    (sum over nodes of roundup(in_degree, k)), or None when any sample
    lacks an edge_index."""
    import numpy as _np

    out = []
    for s in samples:
        ei = getattr(s, "edge_index", None)
        if ei is None:
            return None
        r = _np.asarray(ei)[1]
        if r.size:
            deg = _np.bincount(r)
            out.append(int((((deg + k - 1) // k) * k * (deg > 0)).sum()))
        else:
            out.append(0)
    return out


def max_in_degree(samples) -> int:
    """Dataset-wide max node in-degree (the static dense-slot count).
    Returns 0 when any sample lacks an edge_index (dense map disabled)."""
    import numpy as _np

    worst = 0
    for s in samples:
        ei = getattr(s, "edge_index", None)
        if ei is None:
            return 0
        r = _np.asarray(ei)[1]
        if r.size:
            worst = max(worst, int(_np.bincount(r).max()))
    return worst


def _block_rows(batch: GraphBatch, win) -> int:
    from hydragnn_tpu.ops.segment_pallas import local_block_rows

    return local_block_rows(batch.num_nodes, win.shape[1])


def _mask_out(batch: GraphBatch) -> GraphBatch:
    """Turn a batch into pure padding (all masks False, counts zero).

    Edges are repointed at the last node slot (always a padding slot —
    ``batch_graphs`` reserves one) to keep the loader-wide invariant
    that masked edges never target a real node: the chassis degree
    shortcut (``models/convs.py:sorted_in_degree``) counts edges
    without consulting the mask."""
    import numpy as _np

    pad_slot = batch.num_nodes - 1
    dense = {}
    if batch.dense_mask is not None:
        dense["dense_mask"] = _np.zeros_like(_np.asarray(batch.dense_mask))
        dense["dense_senders"] = _np.full_like(
            _np.asarray(batch.dense_senders), pad_slot
        )
        if batch.dense_sender_perm is not None:
            # all-equal senders: stable argsort is the identity
            dense["dense_sender_perm"] = _np.arange(
                batch.dense_senders.size, dtype=_np.int32
            )
        if batch.dense_sender_win is not None:
            w = _np.zeros_like(_np.asarray(batch.dense_sender_win))
            w[1, pad_slot // _block_rows(batch, w)] = batch.dense_senders.size
            dense["dense_sender_win"] = w
    derived = {}
    if batch.edge_occupancy is not None:
        # ZERO occupancy: the fused conv kernel's chunk loop clamps at
        # ceil(edge_occupancy / CE), so a filler batch costs no DMAs and
        # no MXU work at all on its device slot (ISSUE 10 satellite)
        derived["edge_occupancy"] = _np.int32(0)
    if batch.n_real_nodes is not None:
        derived["n_real_nodes"] = _np.int32(0)
    if batch.sender_perm is not None:
        derived["sender_perm"] = _np.arange(batch.num_edges, dtype=_np.int32)
    if batch.in_degree is not None:
        # in_degree counts real edges only; a fully-masked batch has none
        derived["in_degree"] = _np.zeros(batch.num_nodes, dtype=_np.float32)
    if batch.sender_win is not None:
        w = _np.zeros_like(_np.asarray(batch.sender_win))
        w[1, pad_slot // _block_rows(batch, w)] = batch.num_edges
        derived["sender_win"] = w
    return batch.replace(
        senders=_np.full_like(_np.asarray(batch.senders), pad_slot),
        receivers=_np.full_like(_np.asarray(batch.receivers), pad_slot),
        **derived,
        node_mask=_np.zeros_like(_np.asarray(batch.node_mask)),
        edge_mask=_np.zeros_like(_np.asarray(batch.edge_mask)),
        graph_mask=_np.zeros_like(_np.asarray(batch.graph_mask)),
        n_node=_np.zeros_like(_np.asarray(batch.n_node)),
        n_edge=_np.zeros_like(_np.asarray(batch.n_edge)),
        **dense,
    )
