"""Unified ``Partitioner``: one sharding story for train, serve, and bench.

Before this module the repo carried three divergent sharding stories —
``parallel/sharded.py`` (data mesh + ZeRO-1 special case),
``parallel/edge_sharded.py`` (giant-graph edge axis), and ``serve/``'s
implicit single-device — and parameters/optimizer state always lived
fully replicated on every chip. The ``Partitioner`` owns all of it:

  - **mesh construction** over the composed ``(data, fsdp, edge)`` axis
    set, with auto-collapse of size-1 axes (a pure-DP run gets the same
    1-D ``("data",)`` mesh ``make_mesh`` built, so nothing recompiles);
  - **input sharding**: the loader's leading device axis ``[D, ...]``
    shards over ``data × fsdp`` (each device owns one sub-batch — the
    openpi ``(batch, fsdp)`` shape), edge-sharded CSR leaves additionally
    shard over ``edge``, pad-plan aware through the existing
    ``place_dp_edge_batch`` arithmetic;
  - **state sharding**: with ``fsdp > 1`` every parameter AND optimizer
    leaf shards its largest ``fsdp``-divisible dimension over the
    ``fsdp`` axis — XLA inserts the all-gather(params) /
    reduce-scatter(grads) pattern around the data-parallel step, which
    IS FSDP/ZeRO-style sharding, unlocking models whose parameters +
    optimizer state exceed one chip's HBM. The legacy ZeRO-1 mode
    (optimizer leaves over ``data``) is the ``fsdp == 1, zero1=True``
    special case of the same layout machinery. Leaves that cannot shard
    are replicated LOUDLY: one rank-0 warning with the leaf paths, and
    ``parallel.replicated_leaves`` in the flight manifest;
  - **step partitioning**: ``shard_init`` / ``shard_train_step`` /
    ``shard_eval_step`` / ``shard_stats_step`` used identically by
    ``train/loop.py``, ``serve/`` (registry warmup + bucket-ladder AOT
    compiles run under this mesh via :meth:`shard_variables` /
    :meth:`shard_inference_batch`), and ``bench_scaling.py`` /
    ``tools/scaling_estimate.py``.

Numerics: the fsdp axis only changes WHERE state bytes live, not what is
computed — the batch still splits over all ``data × fsdp`` devices and
gradients still ``pmean`` over all of them, so an ``(data=2, fsdp=4)``
run computes what the ``data=8`` run computes (modulo collective
reduction order). Correctness is pinned on a forced multi-device CPU
host mesh (``XLA_FLAGS=--xla_force_host_platform_device_count=8``) in
``tests/test_partitioner.py``. The collective set this layout implies —
all-reduce over ``data``/``data×fsdp``, all-gather/reduce-scatter only
over ``fsdp`` (or ``data`` for ZeRO-1), nothing else — is machine-checked
from the compiled step's HLO by graftcheck contract CC003
(docs/LINT.md), so a change here that leaks a new collective fails CI
before it costs wire time. See docs/PARALLELISM.md.
"""

from __future__ import annotations

import dataclasses
import warnings
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from hydragnn_tpu.parallel.mesh import DATA_AXIS

FSDP_AXIS = "fsdp"
EDGE_AXIS = "edge"
# canonical axis order: data outermost (rows of sub-batches), fsdp inside
# it (state shards stay intra-host on multi-host meshes), edge innermost
AXIS_ORDER = (DATA_AXIS, FSDP_AXIS, EDGE_AXIS)


def _leaf_size(x) -> int:
    shape = getattr(x, "shape", None)
    if shape is None:
        return 0
    return int(np.prod(shape)) if len(shape) else 1


def _leaf_bytes(x) -> int:
    if not hasattr(x, "dtype"):
        return 0
    return _leaf_size(x) * int(np.dtype(x.dtype).itemsize)


@dataclasses.dataclass(frozen=True)
class ParallelConfig:
    """Global axis widths of the composed ``(data, fsdp, edge)`` mesh.

    ``data``: sub-batches processed in parallel (DDP width). ``fsdp``:
    parameter/optimizer-state sharding width — the batch ALSO splits over
    this axis, so total sub-batches per step = ``data * fsdp``. ``edge``:
    per-sub-batch edge-array sharding width (giant graphs). ``zero1``:
    the legacy optimizer-state-over-``data`` layout; subsumed by (and
    ignored under) ``fsdp > 1``.
    """

    data: int = 1
    fsdp: int = 1
    edge: int = 1
    zero1: bool = False

    def __post_init__(self):
        for name in ("data", "fsdp", "edge"):
            v = getattr(self, name)
            if not isinstance(v, int) or v < 1:
                raise ValueError(
                    f"Parallel.{name} must be a positive integer, got {v!r}"
                )

    @property
    def num_devices(self) -> int:
        return self.data * self.fsdp * self.edge

    @property
    def single_device(self) -> bool:
        """A plain single-device run: no mesh, no sharded step. The one
        answer that ``Partitioner.single_device`` gives the loop and that
        ``api.create_dataloaders`` needs before a partitioner exists."""
        return self.num_devices == 1

    @classmethod
    def from_config(
        cls, nn_config: Dict[str, Any], device_stack: int = 1, multihost: bool = False
    ) -> "ParallelConfig":
        """Axis widths from a ``NeuralNetwork`` config section and the
        PER-PROCESS batch device axis the loaders are built with
        (``Partitioner.from_config`` says what each means)."""
        par = dict(nn_config.get("Parallel") or {})
        fsdp = int(par.get("fsdp", 1) or 1)
        edge = int(par.get("edge", 1) or 1)
        zero1 = bool(
            nn_config.get("Training", {})
            .get("Optimizer", {})
            .get("use_zero_redundancy", False)
        )
        if device_stack % fsdp:
            raise ValueError(
                f"Parallel.fsdp={fsdp} must divide the batch device axis "
                f"(device_stack={device_stack}); pick an fsdp width that "
                "divides the local data-parallel width"
            )
        nproc = jax.process_count() if multihost else 1
        data = (device_stack // fsdp) * nproc
        if fsdp > 1 and zero1:
            # fsdp shards the optimizer state (and the parameters) over
            # its own axis — the ZeRO-1 special case is subsumed
            zero1 = False
        return cls(data=data, fsdp=fsdp, edge=edge, zero1=zero1)


class Partitioner:
    """Owns the mesh and every sharding decision of a run.

    Construct directly (``Partitioner(data=8)``,
    ``Partitioner(data=2, fsdp=4)``) or from a completed config via
    :meth:`from_config` (the ``NeuralNetwork.Parallel`` section). A
    config whose axes are all 1 yields the SINGLE-DEVICE partitioner:
    ``mesh is None``, every ``shard_*`` method degrades to the plain
    jitted single-device behavior, and callers need no special-casing —
    the "partitioner says single-device" signal the scan-epoch
    eligibility check consumes.
    """

    def __init__(
        self,
        config: Optional[ParallelConfig] = None,
        *,
        data: int = 1,
        fsdp: int = 1,
        edge: int = 1,
        zero1: bool = False,
        devices: Optional[Sequence[Any]] = None,
        multihost: bool = False,
    ):
        if config is None:
            config = ParallelConfig(data=data, fsdp=fsdp, edge=edge, zero1=zero1)
        self.config = config
        self.multihost = bool(multihost)
        self._warned_replicated = False
        self._replicated_leaves: List[str] = []
        self.mesh, self.axis_names = self._build_mesh(devices)

    # -- construction ------------------------------------------------------

    @classmethod
    def from_config(
        cls,
        nn_config: Dict[str, Any],
        device_stack: int = 1,
        multihost: bool = False,
        devices: Optional[Sequence[Any]] = None,
    ) -> "Partitioner":
        """Build from a (completed) ``NeuralNetwork`` config section.

        ``device_stack`` is the PER-PROCESS batch device axis the loaders
        were built with (``data_local * fsdp``); ``Parallel.fsdp`` must
        divide it so fsdp groups never span sub-batch boundaries — on
        multi-host meshes this also keeps every fsdp all-gather
        intra-host. ``Training.Optimizer.use_zero_redundancy`` maps to
        the legacy ZeRO-1 layout and is subsumed when ``fsdp > 1``."""
        return cls(
            ParallelConfig.from_config(nn_config, device_stack, multihost),
            devices=devices,
            multihost=multihost,
        )

    def _ordered_devices(self, per_process: Optional[int] = None) -> List[Any]:
        """Process-major device list; in multihost mode each process
        contributes exactly ``per_process`` devices (its lowest-id ones),
        so every process owns a contiguous block of mesh rows and can
        feed its shard via ``make_array_from_process_local_data``."""
        if not self.multihost:
            return list(jax.devices())
        by_proc: Dict[int, list] = {}
        for d in jax.devices():
            by_proc.setdefault(d.process_index, []).append(d)
        out: List[Any] = []
        for p in sorted(by_proc):
            devs = sorted(by_proc[p], key=lambda d: d.id)
            n = per_process if per_process is not None else len(devs)
            if n > len(devs):
                raise ValueError(
                    f"process {p} has {len(devs)} devices, the mesh needs "
                    f"{n} from each process"
                )
            out.extend(devs[:n])
        return out

    def _build_mesh(self, devices) -> Tuple[Optional[Mesh], Tuple[str, ...]]:
        c = self.config
        total = c.num_devices
        if total == 1 and not self.multihost:
            return None, ()
        if devices is None:
            per_proc = None
            if self.multihost:
                nproc = jax.process_count()
                if total % nproc:
                    raise ValueError(
                        f"{total} mesh devices do not divide evenly over "
                        f"{nproc} processes"
                    )
                per_proc = total // nproc
                if per_proc % (c.fsdp * c.edge):
                    raise ValueError(
                        f"fsdp*edge={c.fsdp * c.edge} must divide the "
                        f"per-process device count {per_proc} so no "
                        "fsdp/edge group spans hosts"
                    )
            devices = self._ordered_devices(per_proc)
        if total > len(devices):
            raise ValueError(
                f"parallel config (data={c.data}, fsdp={c.fsdp}, "
                f"edge={c.edge}) needs {total} devices, have {len(devices)}"
            )
        sizes = [(DATA_AXIS, c.data), (FSDP_AXIS, c.fsdp), (EDGE_AXIS, c.edge)]
        # auto-collapse size-1 axes: the spec/axis machinery only ever
        # names axes that exist, so a pure-DP mesh is exactly the 1-D
        # ("data",) mesh the pre-partitioner code built
        axes = [(n, s) for n, s in sizes if s > 1]
        if not axes:
            axes = [(DATA_AXIS, 1)]  # degenerate multihost: keep one axis
        shape = tuple(s for _, s in axes)
        names = tuple(n for n, _ in axes)
        mesh = Mesh(np.asarray(devices[:total]).reshape(shape), names)
        return mesh, names

    # -- topology ----------------------------------------------------------

    @property
    def single_device(self) -> bool:
        """True when this partitioner describes a plain single-device run
        — the signal scan-epoch eligibility and serve's fast path use
        instead of sniffing meshes themselves (the mesh holds
        ``config.num_devices`` devices, and none is built for one)."""
        return self.config.single_device

    @property
    def num_devices(self) -> int:
        return 1 if self.mesh is None else int(self.mesh.size)

    @property
    def lead_axes(self) -> Tuple[str, ...]:
        """Mesh axes the batch's leading device axis shards over."""
        return tuple(a for a in (DATA_AXIS, FSDP_AXIS) if a in self.axis_names)

    @property
    def lead_spec(self):
        """The PartitionSpec entry for the batch leading axis (a bare
        name, a tuple of names, or None when the batch is unsharded)."""
        ax = self.lead_axes
        if not ax:
            return None
        return ax[0] if len(ax) == 1 else ax

    @property
    def fsdp_factor(self) -> int:
        return self.config.fsdp

    @property
    def device_stack(self) -> int:
        """Sub-batches per PROCESS batch — what ``GraphLoader`` needs."""
        st = self.config.data * self.config.fsdp
        if self.multihost:
            st //= jax.process_count()
        return max(st, 1)

    @property
    def bn_axis_name(self):
        """Axis name(s) SyncBatchNorm reduces over under this mesh: the
        shard_map lead axes for the DP/FSDP step, the vmap's logical
        ``data`` axis for the edge-sharded step, None single-device."""
        if self.mesh is None:
            return None
        if self.config.edge > 1:
            return DATA_AXIS
        ax = self.lead_axes
        if not ax:
            return None
        return ax[0] if len(ax) == 1 else ax

    # -- input sharding ----------------------------------------------------

    def batch_sharding(self) -> Optional[NamedSharding]:
        """Sharding for loader output with a leading device axis [D, ...]."""
        if self.mesh is None:
            return None
        lead = self.lead_spec
        return NamedSharding(self.mesh, P(lead) if lead is not None else P())

    def replicated_sharding(self) -> Optional[NamedSharding]:
        if self.mesh is None:
            return None
        return NamedSharding(self.mesh, P())

    def shard_batch(self, batch):
        """Place one loader batch with this mesh's input layout (edge
        leaves additionally over ``edge`` when that axis exists)."""
        if self.mesh is None:
            return batch
        if self.config.edge > 1:
            from hydragnn_tpu.parallel.edge_sharded import place_dp_edge_batch

            if self.config.data * self.config.fsdp == 1:
                # edge-only mesh over an unstacked loader: the vmapped
                # edge step still wants a leading device axis [1, ...]
                batch = jax.tree_util.tree_map(
                    lambda x: np.asarray(x)[None], batch
                )
            return place_dp_edge_batch(self.mesh, batch, batch_axes=self.lead_axes)
        return jax.device_put(batch, self.batch_sharding())

    def shard_inference_batch(self, batch):
        """Serving-side batch placement: request batches are not
        data-sharded (one coalesced batch at a time) — they replicate on
        the mesh so the fsdp-sharded forward's executable sees one
        committed, deterministic input layout."""
        if self.mesh is None:
            return batch
        return jax.device_put(batch, self.replicated_sharding())

    def attach_loader(self, loader) -> None:
        """Point a ``GraphLoader`` at this mesh: multi-host loaders
        assemble global arrays over the lead axes, single-host loaders
        device_put with the batch sharding (or the per-field edge placer
        when the edge axis exists). Single-device: no-op."""
        if self.mesh is None:
            return
        if self.multihost:
            loader.set_global_mesh(self.mesh, axes=self.lead_spec)
        elif self.config.edge > 1:
            loader.set_placer(self.shard_batch)
        else:
            loader.set_sharding(self.batch_sharding())

    # -- state sharding ----------------------------------------------------

    def _fsdp_dim(self, shape) -> Optional[int]:
        """The dimension an fsdp-sharded leaf splits: the LARGEST one
        divisible by the fsdp width (largest → the biggest per-device
        byte saving; ties → lowest index for determinism)."""
        n = self.config.fsdp
        best = None
        for i, d in enumerate(shape):
            if d > 0 and d % n == 0:
                if best is None or d > shape[best]:
                    best = i
        return best

    def param_spec(self, x) -> P:
        """fsdp PartitionSpec for one parameter/optimizer leaf (``P()``
        when the leaf cannot shard: scalars, no divisible dimension, or
        ``fsdp == 1``)."""
        if self.config.fsdp <= 1 or getattr(x, "ndim", 0) == 0:
            return P()
        dim = self._fsdp_dim(x.shape)
        if dim is None:
            return P()
        return P(*([None] * dim + [FSDP_AXIS]))

    def _map_section(self, prefix: str, tree, report: List[str]):
        """Per-leaf NamedShardings for one state section under the fsdp
        rule, recording un-shardable non-scalar leaves into ``report``."""
        mesh = self.mesh
        rep = NamedSharding(mesh, P())

        def leaf(path, x):
            spec = self.param_spec(x)
            if len(spec) == 0:
                if getattr(x, "ndim", 0) >= 1 and _leaf_size(x) > 1:
                    report.append(prefix + jax.tree_util.keystr(path))
                return rep
            return NamedSharding(mesh, spec)

        return jax.tree_util.tree_map_with_path(leaf, tree)

    def state_sharding(self, state):
        """Per-leaf shardings for a ``TrainState`` — the single source of
        truth shared by initial placement (:meth:`shard_init`) and the
        per-step output constraint inside the partitioned train step."""
        shardings, _ = self._state_sharding_with_report(state)
        return shardings

    def _state_sharding_with_report(self, state):
        mesh = self.mesh
        if mesh is None:
            return None, []
        rep = NamedSharding(mesh, P())
        rep_tree = lambda tree: jax.tree_util.tree_map(lambda _: rep, tree)
        replicated: List[str] = []
        if self.config.fsdp > 1:
            params = self._map_section("params", state.params, replicated)
            opt = self._map_section("opt_state", state.opt_state, replicated)
        elif self.config.zero1 and DATA_AXIS in self.axis_names:
            from hydragnn_tpu.parallel.sharded import _zero1_leaf_shardings

            params = rep_tree(state.params)
            opt = _zero1_leaf_shardings(mesh, state.opt_state, replicated)
        else:
            params = rep_tree(state.params)
            opt = rep_tree(state.opt_state)
        return (
            type(state)(
                step=rep,
                params=params,
                batch_stats=rep_tree(state.batch_stats),
                opt_state=opt,
                rng=rep,
            ),
            replicated,
        )

    def _warn_replicated(self, paths: List[str]) -> None:
        if not paths or self._warned_replicated or jax.process_index() != 0:
            return
        self._warned_replicated = True
        axis = FSDP_AXIS if self.config.fsdp > 1 else DATA_AXIS
        width = self.config.fsdp if self.config.fsdp > 1 else (
            self.mesh.shape[DATA_AXIS] if self.mesh is not None else 1
        )
        shown = ", ".join(paths[:8]) + (", ..." if len(paths) > 8 else "")
        warnings.warn(
            f"Partitioner: {len(paths)} state leaf(ves) have no dimension "
            f"divisible by the {axis!r} axis width {width} and stay fully "
            f"REPLICATED on every device: {shown}. Recorded in the flight "
            "manifest as parallel.replicated_leaves.",
            RuntimeWarning,
            stacklevel=3,
        )

    def shard_init(self, state):
        """Place a host-built ``TrainState`` onto the mesh with this
        partitioner's layout (no-op single-device). Replicated-leaf
        fallbacks warn once, loudly, on rank 0."""
        if self.mesh is None:
            return state
        sh, replicated = self._state_sharding_with_report(state)
        self._replicated_leaves = replicated
        self._warn_replicated(replicated)
        return jax.tree_util.tree_map(
            lambda x, s: jax.device_put(x, s), state, sh
        )

    def shard_variables(self, variables: Dict[str, Any]) -> Dict[str, Any]:
        """Serving-side state placement: ``params`` shard over ``fsdp``
        (a served model bigger than one chip's HBM), everything else
        (batch_stats) replicates. No-op single-device."""
        if self.mesh is None:
            return variables
        rep = self.replicated_sharding()
        replicated: List[str] = []
        out: Dict[str, Any] = {}
        for section, tree in variables.items():
            if section == "params" and self.config.fsdp > 1:
                sh = self._map_section("params", tree, replicated)
            else:
                sh = jax.tree_util.tree_map(lambda _: rep, tree)
            out[section] = jax.tree_util.tree_map(
                lambda x, s: jax.device_put(x, s), tree, sh
            )
        self._replicated_leaves = replicated
        self._warn_replicated(replicated)
        return out

    # -- step partitioning -------------------------------------------------

    def shard_train_step(self, model, tx, compute_dtype=None, remat: bool = False):
        """Jitted ``(state, batch[D-leading]) -> (state, loss, tasks)``
        partitioned for this mesh; the plain single-device jitted step
        when the partitioner is single-device."""
        if self.mesh is None:
            from hydragnn_tpu.train.state import make_train_step

            return make_train_step(
                model, tx, compute_dtype=compute_dtype, remat=remat
            )
        if self.config.edge > 1:
            if compute_dtype is not None:
                raise ValueError(
                    "the edge-sharded train step has no mixed-precision "
                    "path; drop Training.mixed_precision or Parallel.edge"
                )
            from hydragnn_tpu.parallel.edge_sharded import make_dp_edge_train_step

            return make_dp_edge_train_step(
                model,
                tx,
                self.mesh,
                batch_axes=self.lead_axes,
                state_sharding_fn=self.state_sharding,
            )
        from hydragnn_tpu.parallel.sharded import make_sharded_train_step

        return make_sharded_train_step(
            model,
            tx,
            self.mesh,
            zero1=self.config.zero1,
            compute_dtype=compute_dtype,
            remat=remat,
            batch_axes=self.lead_axes,
            state_sharding_fn=self.state_sharding if self.config.fsdp > 1 else None,
        )

    def shard_eval_step(self, model, with_outputs: bool = False):
        if self.mesh is None:
            from hydragnn_tpu.train.state import make_eval_step

            return make_eval_step(model, with_outputs=with_outputs)
        if self.config.edge > 1:
            from hydragnn_tpu.parallel.edge_sharded import make_dp_edge_eval_step

            return make_dp_edge_eval_step(
                model, self.mesh, with_outputs=with_outputs
            )
        from hydragnn_tpu.parallel.sharded import make_sharded_eval_step

        return make_sharded_eval_step(
            model,
            self.mesh,
            with_outputs=with_outputs,
            batch_axes=self.lead_axes,
        )

    def shard_stats_step(self, model):
        if self.mesh is None:
            from hydragnn_tpu.train.state import make_stats_step

            return make_stats_step(model)
        if self.config.edge > 1:
            from hydragnn_tpu.parallel.edge_sharded import make_dp_edge_stats_step

            return make_dp_edge_stats_step(model, self.mesh)
        from hydragnn_tpu.parallel.sharded import make_sharded_stats_step

        return make_sharded_stats_step(
            model, self.mesh, batch_axes=self.lead_axes
        )

    # -- introspection -----------------------------------------------------

    def _shard_factor(self, sharding) -> int:
        """How many ways a leaf under ``sharding`` splits across devices."""
        if self.mesh is None or not isinstance(sharding, NamedSharding):
            return 1
        f = 1
        for entry in sharding.spec:
            if entry is None:
                continue
            for a in entry if isinstance(entry, tuple) else (entry,):
                f *= int(self.mesh.shape[a])
        return f

    def _section_summary(self, tree, sh_tree) -> Dict[str, Any]:
        leaves = jax.tree_util.tree_leaves(tree)
        shs = (
            jax.tree_util.tree_leaves(
                sh_tree, is_leaf=lambda x: isinstance(x, NamedSharding)
            )
            if sh_tree is not None
            else [None] * len(leaves)
        )
        total = per_dev = 0
        sharded = 0
        for x, s in zip(leaves, shs):
            b = _leaf_bytes(x)
            f = self._shard_factor(s)
            total += b
            per_dev += -(-b // f) if f > 1 else b  # ceil-divide real shards
            if f > 1:
                sharded += 1
        return {
            "leaves": len(leaves),
            "sharded": sharded,
            "bytes_global": int(total),
            "bytes_per_device": int(per_dev),
        }

    def layout_fingerprint(self) -> Dict[str, Any]:
        """Compact, JSON-stable identity of the committed layout — what
        the pod checkpoint protocol (resilience/podckpt.py) stamps into
        every shard manifest and COMMIT marker so a restore can tell
        "same layout, place shards directly" from "different layout,
        reassemble leaves elastically", and lineage events can name the
        PRIOR layout a resumed run came from."""
        c = self.config
        fp: Dict[str, Any] = {
            "data": int(c.data),
            "fsdp": int(c.fsdp),
            "edge": int(c.edge),
            "zero1": bool(c.zero1),
            "devices": None if self.mesh is None else int(self.mesh.size),
        }
        try:
            from hydragnn_tpu.obs.podview import host_identity

            _, fp["hosts"] = host_identity()
        except Exception:
            fp["hosts"] = 1
        return fp

    def manifest(self, state=None, variables=None) -> Dict[str, Any]:
        """The flight-record ``parallel`` block: mesh shape and axis
        names, axis widths, and (given a ``state`` or served
        ``variables``) the per-leaf parameter/optimizer sharding summary,
        per-device bytes, and the replicated-leaf fallback list —
        surfaced by ``tools/obs_report.py`` (docs/PARALLELISM.md)."""
        c = self.config
        info: Dict[str, Any] = {
            "available": True,
            "single_device": self.single_device,
            "mesh": None
            if self.mesh is None
            else {
                "shape": {str(k): int(v) for k, v in self.mesh.shape.items()},
                "axis_names": list(self.axis_names),
                "devices": int(self.mesh.size),
            },
            "data": c.data,
            "fsdp": c.fsdp,
            "edge": c.edge,
            "zero1": bool(c.zero1),
            "multihost": self.multihost,
            "device_stack": self.device_stack,
        }
        # pod-visibility identity (obs/podview.py): which host committed
        # this layout and how many peers it expects — the inputs the
        # SkewMonitor's collective-aware cost attribution joins on
        try:
            from hydragnn_tpu.obs.podview import host_identity

            info["process_index"], info["process_count"] = host_identity()
        except Exception:
            pass
        info["layout"] = self.layout_fingerprint()
        if state is not None:
            sh, replicated = self._state_sharding_with_report(state)
            info["params"] = self._section_summary(
                state.params, sh.params if sh is not None else None
            )
            info["opt"] = self._section_summary(
                state.opt_state, sh.opt_state if sh is not None else None
            )
            info["replicated_leaves"] = list(replicated)
        elif variables is not None:
            replicated: List[str] = []
            params = variables.get("params", {})
            sh = (
                self._map_section("params", params, replicated)
                if self.mesh is not None and c.fsdp > 1
                else None
            )
            info["params"] = self._section_summary(params, sh)
            info["replicated_leaves"] = list(replicated)
        return info


def parallel_manifest_summary(par: Dict[str, Any]) -> str:
    """One-line human rendering of a flight ``parallel`` block (used by
    ``tools/obs_report.py``)."""
    mesh = par.get("mesh")
    if not mesh:
        shape = "single-device"
    else:
        shape = "×".join(
            f"{k}{v}" for k, v in (mesh.get("shape") or {}).items()
        )
    parts = [f"mesh={shape}", f"fsdp={par.get('fsdp', 1)}"]
    p = par.get("params")
    if p:
        parts.append(
            f"params {p['sharded']}/{p['leaves']} leaves sharded, "
            f"{p['bytes_per_device']}/{p['bytes_global']} B/device"
        )
    o = par.get("opt")
    if o:
        parts.append(
            f"opt {o['sharded']}/{o['leaves']} sharded, "
            f"{o['bytes_per_device']}/{o['bytes_global']} B/device"
        )
    reps = par.get("replicated_leaves")
    if reps:
        parts.append(f"replicated_leaves={len(reps)}")
    return " ".join(parts)
