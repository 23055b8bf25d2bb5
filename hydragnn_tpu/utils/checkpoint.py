"""Checkpoint / resume.

The reference saves one ``.pk`` file holding model+optimizer state dicts,
written by rank 0 (after ZeRO consolidation), and supports config-driven
continuation (reference: hydragnn/utils/model.py:41-86, config keys
``Training.continue``/``startfrom``). Two TPU-native backends behind the
same single-name "continue" UX:

  - ``msgpack`` (default single-process): the whole ``TrainState``
    pytree (params, batch_stats, optimizer state, step, rng) in one
    flax-msgpack file, written a leaf at a time (``_stream_msgpack``);
    process 0 writes, every process reads. Sharded arrays are
    consolidated to host first (the ZeRO-consolidation analog).
  - ``orbax`` (default multi-process): Orbax sharded checkpoint — every
    host writes its addressable shards in parallel and restore places
    shards directly onto the target sharding, so pod-scale ZeRO-1 state
    never funnels through one host.

``load_existing_model`` auto-detects which backend wrote a run.
"""

from __future__ import annotations

import json
import os
import shutil
from typing import Any, Dict, List, Optional

import jax
import numpy as np
from flax import serialization

from hydragnn_tpu.obs.spans import count, span
from hydragnn_tpu.utils.print_utils import print_distributed

#: On-disk checkpoint format generation, stamped into the meta sidecar
#: and every pod-shard manifest/COMMIT (resilience/podckpt.py). History:
#: 1 = the original UNVERSIONED layout (absent stamp == 1; always
#: accepted), 2 = adds the stamp itself + the pod sharded-generation
#: layout. Readers accept <= CURRENT and refuse newer with a TYPED
#: error — a checkpoint from a future build must fail loudly, not as
#: an incidental KeyError three frames deep.
CHECKPOINT_FORMAT_VERSION = 2


class CheckpointFormatError(RuntimeError):
    """The checkpoint on disk was written by a NEWER format_version
    than this build understands. Typed so supervisors/CLIs can tell an
    upgrade refusal (fail fast, don't retry) from bit-rot (fall back a
    version)."""


def _checkpoint_path(log_name: str, path: str = "./logs/") -> str:
    return os.path.join(path, log_name, f"{log_name}.mp")


def _to_host(x: Any) -> np.ndarray:
    """Fetch one leaf to host. Leaves sharded across non-addressable
    devices (multi-host ZeRO-1 optimizer state) are first all-gathered to
    a replicated layout with an XLA collective — the ZeRO consolidation
    step (reference: consolidate_state_dict, model.py:44-45). A leaf whole
    on every device it lives on is read through a view of one device's
    copy: ``np.asarray`` of an accelerator's array keeps the host copy on
    that array object, which would then live as long as the state does
    (a whole state's host copy during a save, seen on the chip)."""
    if isinstance(x, jax.Array) and not x.is_fully_addressable:
        from jax.sharding import NamedSharding, PartitionSpec

        mesh = x.sharding.mesh
        x = jax.jit(lambda a: a, out_shardings=NamedSharding(mesh, PartitionSpec()))(x)
    if isinstance(x, jax.Array) and x.sharding.is_fully_replicated:
        x = x.addressable_data(0)
    return np.asarray(x)


def _orbax_dir(log_name: str, path: str) -> str:
    return os.path.abspath(os.path.join(path, log_name, f"{log_name}.orbax"))


def _sha256_hex(data: bytes) -> str:
    import hashlib

    return hashlib.sha256(data).hexdigest()


def _versioned_path(log_name: str, path: str, step: int) -> str:
    return os.path.join(path, log_name, f"{log_name}.step{step:010d}.mp")


def list_versioned_checkpoints(log_name: str, path: str = "./logs/"):
    """Retained keep-last-K checkpoint versions, NEWEST first, as
    ``[(step, path)]``."""
    import glob
    import re

    out = []
    pat = re.compile(re.escape(log_name) + r"\.step(\d+)\.mp$")
    for p in glob.glob(os.path.join(path, log_name, f"{log_name}.step*.mp")):
        m = pat.search(os.path.basename(p))
        if m:
            out.append((int(m.group(1)), p))
    return sorted(out, reverse=True)


def validate_checkpoint_file(ckpt_path: str) -> bool:
    """Integrity check for one msgpack checkpoint file: the sha256
    sidecar when present (bit-rot), else parse-validation (a truncated
    msgpack stream — torn write, SIGKILL mid-checkpoint — fails to
    restore). Missing file -> False."""
    if not os.path.isfile(ckpt_path):
        return False
    try:
        with open(ckpt_path, "rb") as f:
            data = f.read()
    except OSError:
        return False
    sidecar = ckpt_path + ".sha256"
    if os.path.exists(sidecar):
        try:
            with open(sidecar) as f:
                want = f.read().strip()
            return _sha256_hex(data) == want
        except OSError:
            return False
    try:
        serialization.msgpack_restore(data)
        return True
    except Exception:
        return False


def _atomic_write(final_path: str, data: bytes) -> None:
    # pid-unique tmp: concurrent simulated pod hosts (resilience/
    # podckpt.py) write the SAME shared targets (latest pointer, meta
    # sidecar); a fixed tmp name would let writer B's os.replace race
    # writer A's and raise on the vanished tmp. Unique tmps make the
    # pair of writes last-writer-wins, each replace still atomic.
    tmp = f"{final_path}.tmp{os.getpid()}"
    with open(tmp, "wb") as f:
        f.write(data)
    os.replace(tmp, final_path)


def _prune_versions(log_name: str, path: str, keep_last: int) -> None:
    for _, p in list_versioned_checkpoints(log_name, path)[keep_last:]:
        for victim in (p, p + ".sha256"):
            try:
                os.remove(victim)
            except OSError:
                pass


def save_model(
    state: Any,
    log_name: str,
    path: str = "./logs/",
    verbosity: int = 0,
    backend: str = "auto",
    keep_last: Optional[int] = None,
) -> str:
    """Write the TrainState under ``<path>/<log_name>/`` (reference:
    rank-0 save, model.py:41-54). ``backend``: "msgpack", "orbax", or
    "auto" (orbax when multi-process — parallel sharded writes).

    ``keep_last=K`` (msgpack backend; config
    ``Training.checkpoint_keep_last``) additionally retains the K most
    recent step-versioned copies (``<log_name>.step<N>.mp`` + sha256
    sidecar, pruned beyond K). Restore validates integrity and falls
    back down the retained set (:func:`load_existing_model`), so a
    checkpoint torn by a crash mid-write never strands the run.

    Spans (children of the span open around the call, ``epoch.checkpoint``
    in the train loop): ``checkpoint.stream`` (the state to the file, with
    :func:`_stream_msgpack`'s ``checkpoint.fetch`` and ``checkpoint.write``
    a leaf) and ``checkpoint.versioned`` (the copy, its sha256, pruning)."""
    if backend == "auto":
        backend = "orbax" if jax.process_count() > 1 else "msgpack"
    if backend == "orbax":
        import orbax.checkpoint as ocp

        ckpt_dir = _orbax_dir(log_name, path)
        with ocp.StandardCheckpointer() as ckptr:
            ckptr.save(ckpt_dir, state, force=True)
        return ckpt_dir
    ckpt_path = _checkpoint_path(log_name, path)
    with span("checkpoint.stream"):
        tree = serialization.to_state_dict(state)
        if jax.process_index() != 0:
            _stream_msgpack(tree, None)  # the gathers of sharded leaves need every process
            return ckpt_path
        os.makedirs(os.path.dirname(ckpt_path), exist_ok=True)
        # atomic replace: a crash mid-write (the exact scenario per-epoch
        # checkpointing exists for) must not destroy the previous good file
        tmp = f"{ckpt_path}.tmp{os.getpid()}"
        with open(tmp, "wb") as f:
            digest = _stream_msgpack(tree, f)
    if keep_last:
        with span("checkpoint.versioned"):
            step = int(jax.device_get(state.step)) if hasattr(state, "step") else 0
            vp = _versioned_path(log_name, path, step)
            vtmp = f"{vp}.tmp{os.getpid()}"
            shutil.copyfile(tmp, vtmp)
            os.replace(vtmp, vp)
            _atomic_write((vp + ".sha256"), digest.encode())
            _prune_versions(log_name, path, int(keep_last))
    # deterministic torn-write fault injection (docs/RESILIENCE.md):
    # under HYDRAGNN_INJECT_KILL_CHECKPOINT the K-th save leaves the
    # latest-pointer file truncated and SIGKILLs the process — the
    # scenario the validation + versioned fallback above recovers
    from hydragnn_tpu.resilience.inject import maybe_kill_checkpoint

    maybe_kill_checkpoint(ckpt_path, tmp)
    os.replace(tmp, ckpt_path)
    return ckpt_path


def _stream_msgpack(tree, out) -> str:
    """Write ``tree`` (``flax.serialization.to_state_dict`` of a state of
    device or host arrays) to the file ``out`` byte for byte as
    ``flax.serialization.to_bytes`` encodes it whole, one leaf at a time: a
    leaf is fetched to the host (:func:`_to_host`), encoded and written
    before the next, so the encoding of the whole state is never held
    (``to_bytes`` holds the host state, every array's bytes and the packed
    buffer at once: for a 5.9 GB state beside a training run's own host
    copies, more than a 40 GiB host has). ``out`` None: fetch only (a
    process that takes part in the gathers and writes nothing). Returns
    the written bytes' sha256.

    Spans (children of ``checkpoint.stream``, :func:`save_model`'s), one
    of each a leaf: ``checkpoint.fetch`` (:func:`_to_host`) and
    ``checkpoint.write`` (the leaf's encoding, hashing and writing);
    counters ``checkpoint.leaves`` and ``checkpoint.bytes`` (written)."""
    import hashlib

    import msgpack

    packer = msgpack.Packer(default=serialization._msgpack_ext_pack, strict_types=True)
    digest = hashlib.sha256()
    written = [0, 0]  # leaves, bytes

    def emit(data: bytes) -> None:
        if out is not None:
            digest.update(data)
            out.write(data)
            written[1] += len(data)

    def walk(node) -> None:
        if type(node) is dict:  # strict_types: what msgpack packs as a map
            emit(packer.pack_map_header(len(node)))
            for key, value in node.items():
                emit(packer.pack(key))
                walk(value)
            return
        with span("checkpoint.fetch"):
            leaf = None if node is None else _to_host(node)  # None is no pytree leaf: packed as nil
        if out is not None:
            with span("checkpoint.write"):
                emit(packer.pack(serialization._chunk_array_leaves_in_place(leaf)))
            written[0] += 1

    walk(tree)
    if out is not None:
        count("checkpoint.leaves", written[0])
        count("checkpoint.bytes", written[1])
    return digest.hexdigest()


def _restore_bytes_into(state: Any, data: bytes) -> Any:
    restored = serialization.from_bytes(state, data)

    # preserve the target's placement: leaves restored as host arrays go
    # back onto the sharding the caller's state carries (ZeRO-1 layouts
    # survive a msgpack resume)
    def _place(tgt, val):
        if isinstance(tgt, jax.Array) and hasattr(tgt, "sharding"):
            return jax.device_put(val, tgt.sharding)
        return val

    return jax.tree_util.tree_map(_place, state, restored)


def load_existing_model(
    state: Any, log_name: str, path: str = "./logs/"
) -> Any:
    """Restore a TrainState from the run's checkpoint. ``state`` is the
    freshly-constructed target (its pytree structure = the schema; with
    sharded leaves, orbax restores shards onto their shardings directly).
    The backend that wrote the run is auto-detected.

    msgpack restores validate integrity first and FALL BACK down the
    retained version set (``save_model(keep_last=...)``): the latest
    pointer file is preferred; if it is truncated/corrupt (torn write —
    e.g. SIGKILL mid-checkpoint), the newest valid ``.step<N>.mp``
    version is restored instead, with a loud warning naming what was
    rejected. Only when every candidate fails does the restore raise.

    Pod-sharded runs (resilience/podckpt.py) are probed FIRST: when the
    run dir holds committed generations, the newest valid one is
    reassembled — elastically, onto whatever layout ``state`` carries —
    and the meta sidecar is reconciled to the committed generation (a
    host may have written a later meta for a generation that never
    committed). Only if every pod generation fails does the restore
    fall through to the msgpack chain below."""
    _check_meta_format(log_name, path)
    run_dir = os.path.join(path, log_name)
    if os.path.isdir(os.path.join(run_dir, "podckpt")):
        from hydragnn_tpu.resilience import podckpt

        restored, info = podckpt.restore_pod_checkpoint(state, run_dir)
        if info is not None:
            reconcile_pod_meta(log_name, path, info)
            return restored
    orbax_dir = _orbax_dir(log_name, path)
    if os.path.isdir(orbax_dir):
        import orbax.checkpoint as ocp

        with ocp.StandardCheckpointer() as ckptr:
            target = jax.tree_util.tree_map(ocp.utils.to_shape_dtype_struct, state)
            return ckptr.restore(orbax_dir, target)
    ckpt_path = _checkpoint_path(log_name, path)
    versioned = [p for _, p in list_versioned_checkpoints(log_name, path)]
    if not versioned:
        # no retained versions: the historical single-file path, raising
        # naturally (FileNotFoundError / parse error) on a bad file
        with open(ckpt_path, "rb") as f:
            return _restore_bytes_into(state, f.read())
    rejected = []
    candidates = [ckpt_path] + [p for p in versioned if p != ckpt_path]
    for p in candidates:
        if not validate_checkpoint_file(p):
            rejected.append(p)
            continue
        with open(p, "rb") as f:
            data = f.read()
        try:
            restored = _restore_bytes_into(state, data)
        except Exception:
            rejected.append(p)
            continue
        if rejected:
            import warnings

            warnings.warn(
                f"checkpoint integrity: rejected {rejected} (truncated/"
                f"corrupt); restored the previous valid checkpoint {p}",
                RuntimeWarning,
                stacklevel=2,
            )
        return restored
    raise ValueError(
        f"no valid checkpoint for run {log_name!r} under {path!r}: "
        f"all candidates failed integrity validation: {rejected}"
    )


def save_train_meta(meta: dict, log_name: str, path: str = "./logs/") -> None:
    """Rank-0 JSON sidecar with host-side training-loop state (epoch,
    scheduler, early-stop counters, history) so a resumed run continues
    exactly where it left off. The reference restores only
    model+optimizer (SURVEY §5: resume "not epoch/scheduler/sampler
    state"); this closes that gap."""
    if jax.process_index() != 0:
        return
    meta = dict(meta)
    meta.setdefault("format_version", CHECKPOINT_FORMAT_VERSION)
    out_dir = os.path.join(path, log_name)
    os.makedirs(out_dir, exist_ok=True)
    _atomic_write(
        os.path.join(out_dir, f"{log_name}.meta.json"),
        json.dumps(meta).encode(),
    )


def _check_meta_format(log_name: str, path: str) -> None:
    """Refuse (typed) a meta sidecar stamped by a future format_version.
    An ABSENT stamp is the legacy layout (format 1) and is accepted —
    old runs must keep resuming under new builds."""
    meta = load_train_meta(log_name, path)
    if not meta:
        return
    fv = meta.get("format_version")
    if fv is not None and int(fv) > CHECKPOINT_FORMAT_VERSION:
        raise CheckpointFormatError(
            f"checkpoint meta for run {log_name!r} was written by "
            f"format_version {fv}; this build understands <= "
            f"{CHECKPOINT_FORMAT_VERSION}"
        )


def reconcile_pod_meta(log_name: str, path: str, info: dict) -> None:
    """Rewrite the meta sidecar to agree with the pod generation that
    actually COMMITTED. A host can write meta for epoch N and die
    before generation N commits (the commit marker is always last); a
    resume would then skip epoch N with generation N-1's weights.
    Truth lives in the COMMIT marker, so the sidecar follows it: epoch
    pinned to the committed gen, history truncated to match, early-stop
    state cleared (its counters described epochs being re-run)."""
    gen = int(info["gen"])
    meta = load_train_meta(log_name, path)
    if meta is None:
        meta = {}
    if int(meta.get("epoch", -1)) == gen and meta.get("early_stopped") is not True:
        return
    meta["epoch"] = gen
    if info.get("step") is not None:
        meta["step"] = int(info["step"])
    meta["early_stopped"] = False
    history = meta.get("history")
    if isinstance(history, dict):
        meta["history"] = {
            k: (v[:gen] if isinstance(v, list) else v) for k, v in history.items()
        }
    save_train_meta(meta, log_name, path)


def load_train_meta(log_name: str, path: str = "./logs/") -> Optional[dict]:
    p = os.path.join(path, log_name, f"{log_name}.meta.json")
    if not os.path.exists(p):
        return None
    with open(p) as f:
        return json.load(f)


class LoopState:
    """The train loop's host-side state — epoch index, plateau scheduler,
    early-stop counters, per-epoch history — and the one place that knows
    the ``<log_name>.meta.json`` sidecar's keys: :meth:`snapshot` writes
    what :meth:`restore` reads (``epoch``, ``step``, ``early_stopped``,
    ``scheduler{best, num_bad_epochs}``, ``stopper{count, min_loss}``,
    ``history``). ``scheduler`` / ``stopper`` are the loop's
    ``ReduceLROnPlateau`` / ``EarlyStopping`` (or None)."""

    HISTORY_KEYS = (
        "train_loss", "val_loss", "test_loss", "train_tasks", "val_tasks", "test_tasks", "lr",
    )

    def __init__(self, scheduler, stopper, num_epoch: int):
        self.scheduler = scheduler
        self.stopper = stopper
        self.num_epoch = num_epoch
        self.history: Dict[str, List] = {k: [] for k in self.HISTORY_KEYS}
        self.start_epoch = 0
        self.epochs_done = 0
        self.resumed_from: Optional[int] = None  # set when a continue-run loaded meta

    @property
    def early_stopped(self) -> bool:
        return bool(self.stopper and self.stopper.count >= self.stopper.patience)

    def restore(self, training: dict, state: Any, steps_per_epoch: int,
                log_name: str, log_dir: str, verbosity: int = 0) -> None:
        """Exact resume under ``Training.continue`` (beyond the reference's
        restore-model-and-start-over: epoch index, plateau scheduler and
        early-stop counters survive the restart). The TrainState itself is
        restored by the caller via Training.continue/startfrom."""
        if training.get("continue") != 1:
            return
        if "startfrom" not in training:
            raise ValueError("Training.continue=1 requires Training.startfrom")
        meta = load_train_meta(training["startfrom"], log_dir)
        if meta is None:
            return
        # The model file and the meta sidecar are written sequentially
        # (each atomic, the pair not): a crash between them leaves meta
        # one interval older than the weights. The meta carries the
        # optimizer step it described; on mismatch, re-derive the epoch
        # from the restored weights instead of replaying epochs.
        meta_step = meta.get("step")
        state_step = int(jax.device_get(state.step))
        if meta_step is not None and int(meta_step) != state_step:
            derived = min(self.num_epoch, state_step // max(steps_per_epoch, 1))
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
            save_train_meta(meta, training["startfrom"], log_dir)
            if log_name != training["startfrom"]:
                save_train_meta(meta, log_name, log_dir)
        # an early-stopped run resumes to a no-op (the stop decision
        # is honored, not replayed into extra epochs); a completed or
        # interrupted run continues from its recorded epoch — which
        # also supports the reference's extend-training workflow
        # (continue with a larger num_epoch)
        self.start_epoch = self.num_epoch if meta.get("early_stopped") else int(meta["epoch"])
        self.epochs_done = self.resumed_from = self.start_epoch
        self.scheduler.best = float(meta["scheduler"]["best"])
        self.scheduler.num_bad_epochs = int(meta["scheduler"]["num_bad_epochs"])
        if self.stopper is not None and "stopper" in meta:
            self.stopper.count = int(meta["stopper"]["count"])
            self.stopper.min_loss = float(meta["stopper"]["min_loss"])
        self.history = meta["history"]

    def snapshot(self, step: int, epoch_next: int, early_stopped: bool) -> dict:
        stopper = self.stopper
        return {
            "epoch": epoch_next,
            # the optimizer step ties this sidecar to the weight file
            # it was written with (resume verifies the pair matches)
            "step": step,
            "early_stopped": early_stopped,
            "scheduler": {
                "best": self.scheduler.best,
                "num_bad_epochs": self.scheduler.num_bad_epochs,
            },
            "stopper": {
                "count": stopper.count if stopper else 0,
                "min_loss": stopper.min_loss if stopper else float("inf"),
            },
            "history": self.history,
        }

    def append(self, **epoch_values) -> None:
        """One epoch's entry in every history list."""
        for k in self.HISTORY_KEYS:
            self.history[k].append(epoch_values[k])

    def epoch_done(self, epoch: int, val_loss: float) -> bool:
        """Count the epoch; True when early stopping says stop."""
        self.epochs_done = epoch + 1
        return self.stopper is not None and self.stopper(val_loss)


def load_existing_model_config(
    state: Any, training_config: dict, path: str = "./logs/"
) -> Any:
    """Config-driven continue (reference: model.py:64-67, keys
    ``Training.continue`` and ``Training.startfrom``)."""
    if "continue" in training_config and training_config["continue"] == 1:
        if "startfrom" not in training_config:
            raise ValueError("Training.continue=1 requires Training.startfrom")
        return load_existing_model(state, training_config["startfrom"], path)
    return state


def checkpoint_exists(log_name: str, path: str = "./logs/") -> bool:
    if (
        os.path.exists(_checkpoint_path(log_name, path))
        or os.path.isdir(_orbax_dir(log_name, path))
        or bool(list_versioned_checkpoints(log_name, path))
    ):
        return True
    if os.path.isdir(os.path.join(path, log_name, "podckpt")):
        from hydragnn_tpu.resilience import podckpt

        return bool(podckpt.list_committed_generations(os.path.join(path, log_name)))
    return False
