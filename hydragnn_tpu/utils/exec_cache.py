"""Persistent AOT executable cache — warm cold-starts across processes.

Every process today re-lowers and re-compiles executables that an
earlier identical process already paid for: each serving replica AOT
compiles its whole bucket ladder, every supervisor auto-resume restart
recompiles the train step, and every bench driver round re-pays
lowering. This module makes those executables durable: a
content-addressed on-disk cache of serialized XLA executables (via
``jax.experimental.serialize_executable``), so a second replica, a
restarted trainer, or a repeated bench round deserializes in
milliseconds instead of compiling in seconds.

Cache anatomy (docs/PERF.md "r09 cold start"):

  - **Entry filename** = sha256 of the *logical identity*: consumer
    kind (``serve`` / ``train_step`` / ``scan_epoch`` / ``bench``),
    model-architecture fingerprint, pad-plan / input-shape fingerprint,
    and compute dtype. Same logical program -> same file.
  - **Compat manifest** stored *inside* the entry: jax / jaxlib /
    libtpu versions, backend, ``device_kind``, and the partitioner
    layout ``(data, fsdp, edge)``. A logical hit whose compat manifest
    mismatches is classified loudly (``version_skew`` /
    ``layout_changed``) instead of silently deserializing an
    executable built for different hardware or sharding.
  - **Integrity**: atomic writes (unique tmp + ``os.replace``) with
    ``.sha256`` sidecars — the checkpoint-integrity pattern
    (``utils/checkpoint.py``). A digest mismatch or unpicklable entry
    is a ``corrupt`` miss that EVICTS the single bad entry and falls
    through to a live compile; it never takes the process down.
  - **LRU size bound**: entries are touched on hit; when the directory
    exceeds ``HYDRAGNN_EXEC_CACHE_MAX_MB`` (default 512) the
    oldest-mtime entries are deleted.

Miss reasons (``absent`` / ``corrupt`` / ``version_skew`` /
``layout_changed`` / ``donation_check_failed`` / ``unavailable``) are
recorded as ``exec_cache`` flight-record events and ServeMetrics
counters — a warm start that silently recompiles is a regression this
observability exists to catch.

EXECUTION DEVICES (jax 0.9.0): ``deserialize_and_load`` takes
``execution_devices`` and, when it is not given, loads the executable
onto EVERY device of the backend — a single-device executable read back
on a host with more than one device then fails at its first call
(``Expected args to execute_sharded_on_local_devices to have N shards``).
Each entry therefore records the ids of the devices its executable was
compiled for, in assignment order, and a load hands exactly those
devices back, so on the CPU backend the executable lands where the
compile did. An entry whose devices do not all exist in this process is
a ``layout_changed`` miss. NOT repaired: on the TPU (libtpu 0.0.34,
four-chip host) a single-device executable compiled for a device other
than the default is loaded onto the DEFAULT device whatever
``execution_devices`` names, reports the named device all the same, and
fails at its first call (``tools/chip_probe.py exec_cache``, PR 21).
Every consumer in the repo compiles for the default device today; one
that pins programs to other chips must not cache them until a load can
carry the device assignment (JAX's own cache passes compile options to
``deserialize_executable``; ``deserialize_and_load`` does not).

DONATION GATE: a deserialized DONATED executable was not trustworthy on
the jax this cache was written against — trivial probes passed while
the real train step, executed inside a full training process,
intermittently corrupted memory. Whether jax 0.9.0 still does that has
not been established, so the rule stands: the repo's consumers NEVER
cache a donated program. The train loop and the bench drivers cache a
donation-free twin of the step (a plain jit of the same body — one
extra state-sized buffer while the cache is on), and serving forwards
are donation-free already. The gate machinery stays as
defense-in-depth for any caller that does pass ``donated=True``:
:func:`donation_roundtrip_ok` — a one-time serialize/deserialize probe
of a tiny donated function whose output must bit-match the fresh
compile, persisted per environment fingerprint in the cache dir — plus
a first-execution landing check in ``train/loop.py`` (the cached step's
output ``step`` must be input ``step + delta``). A failed (or injected:
``HYDRAGNN_INJECT_DONATION_CHECK_FAIL``) check evicts the entry and
falls through to a live compile with a ``donation_check_failed`` miss
reason. A passing probe is necessary, not sufficient.
"""

from __future__ import annotations

import hashlib
import json
import os
import pickle
import sys
import threading
import time
from typing import Any, Callable, Dict, Optional, Tuple
from hydragnn_tpu.utils import knobs

#: every classification a load can record (docs/PERF.md table)
MISS_REASONS = (
    "absent",
    "corrupt",
    "version_skew",
    "layout_changed",
    "donation_check_failed",
    "unavailable",
)

_ENV_DIR = "HYDRAGNN_EXEC_CACHE"
_ENV_MAX_MB = "HYDRAGNN_EXEC_CACHE_MAX_MB"


def _serialize_mod():
    """The serialize_executable module, or None when this jax cannot
    round-trip executables (the cache then misses with reason
    ``unavailable`` and every consumer live-compiles as before)."""
    try:
        from jax.experimental import serialize_executable as se

        if hasattr(se, "serialize") and hasattr(se, "deserialize_and_load"):
            return se
    except ImportError:
        pass
    return None


_COMPILE_LOCK = threading.Lock()


def compile_for_store(lowered):
    """Compile ``lowered`` into an executable that :meth:`ExecCache.store`
    can serialize.

    On the CPU backend (jax 0.9.0) an executable that JAX read back from
    its own persistent compilation cache does not survive a SECOND
    serialization: the reloaded program fails at its first call with
    ``NOT_FOUND: ... Function ... not found``. There the compile is made
    with JAX's cache switched off. The switch is process-wide, so the
    window is held under a lock, and a compile on another thread inside
    it merely misses JAX's cache once. On the TPU a re-serialized
    executable loads and runs (``tools/chip_probe.py reserialize``, PR
    21), so nothing is switched and this is ``lowered.compile()``."""
    import jax

    if jax.default_backend() != "cpu":
        return lowered.compile()
    from jax.experimental.compilation_cache import compilation_cache as cc

    with _COMPILE_LOCK:
        if not jax.config.jax_enable_compilation_cache:
            return lowered.compile()
        jax.config.update("jax_enable_compilation_cache", False)
        cc.reset_cache()
        try:
            return lowered.compile()
        finally:
            jax.config.update("jax_enable_compilation_cache", True)
            cc.reset_cache()


def _execution_devices(compiled):
    """The devices ``compiled`` runs on, in assignment order — read off
    the ``_unloaded_executable`` that ``serialize_executable.serialize``
    itself pickles (no public accessor gives the order; a JAX that
    moves it breaks ``serialize`` too, and :meth:`ExecCache.store`
    reports either as ``store_failed``)."""
    return list(compiled._executable._unloaded_executable.device_list)


def _devices_by_id(device_ids):
    """This process's devices for ``device_ids`` in that order, or None
    when one of them does not exist here."""
    import jax

    by_id = {d.id: d for d in jax.devices()}
    if not device_ids or any(i not in by_id for i in device_ids):
        return None
    return [by_id[i] for i in device_ids]


def _sha256_hex(data: bytes) -> str:
    return hashlib.sha256(data).hexdigest()


def _atomic_write(final_path: str, data: bytes) -> None:
    """Unique-tmp + ``os.replace``: two processes warming the same key
    concurrently each publish a complete file; the loser's replace just
    overwrites the winner's identical bytes (tested in
    tests/test_warm_exec_cache.py concurrent-writer case)."""
    tmp = f"{final_path}.{os.getpid()}.tmp"
    with open(tmp, "wb") as f:
        f.write(data)
    os.replace(tmp, final_path)


# -- identity fingerprints -------------------------------------------------


def _canon(obj: Any, depth: int = 0) -> Any:
    """Canonical, order-stable structure for hashing arbitrary identity
    components (configs, dataclasses, pytrees of arrays)."""
    if depth > 10:
        return repr(obj)
    if obj is None or isinstance(obj, (bool, int, float, str, bytes)):
        return obj
    if isinstance(obj, dict):
        return tuple(
            (str(k), _canon(v, depth + 1)) for k, v in sorted(obj.items(), key=lambda kv: str(kv[0]))
        )
    if isinstance(obj, (list, tuple)):
        return tuple(_canon(v, depth + 1) for v in obj)
    shape = getattr(obj, "shape", None)
    dtype = getattr(obj, "dtype", None)
    if shape is not None and dtype is not None:
        return ("array", tuple(shape), str(dtype))
    if hasattr(obj, "__dataclass_fields__"):
        import dataclasses

        return _canon(dataclasses.asdict(obj), depth + 1)
    return repr(obj)


def fingerprint(*components: Any) -> str:
    """Stable sha256 hex over the canonical form of the components."""
    return _sha256_hex(repr(_canon(components)).encode())


def abstract_fingerprint(tree: Any) -> str:
    """Fingerprint of a pytree's STRUCTURE: leaf paths, shapes, dtypes
    — the pad-plan / architecture identity of a batch, a variables
    tree, or a TrainState, independent of the values it holds."""
    import jax

    leaves = []
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        leaves.append(
            (
                jax.tree_util.keystr(path),
                tuple(getattr(leaf, "shape", ())),
                str(getattr(leaf, "dtype", type(leaf).__name__)),
            )
        )
    return _sha256_hex(repr(tuple(leaves)).encode())


def _versions() -> Dict[str, str]:
    out = {}
    try:
        import jax

        out["jax"] = jax.__version__
    except Exception:
        out["jax"] = "unavailable"
    try:
        import jaxlib

        out["jaxlib"] = getattr(jaxlib, "__version__", "unknown")
    except Exception:
        out["jaxlib"] = "unavailable"
    try:
        import libtpu  # type: ignore

        out["libtpu"] = getattr(libtpu, "__version__", "present")
    except Exception:
        out["libtpu"] = "none"
    return out


def compat_manifest(
    layout: Tuple[int, int, int] = (1, 1, 1),
    compute_dtype: Any = None,
) -> Dict[str, Any]:
    """The environment half of the cache key: everything that makes a
    serialized executable VALID here, beyond its logical program. The
    partitioner layout is included because an executable lowered for
    ``fsdp=4`` shards state differently than one for pure DP
    (docs/PARALLELISM.md)."""
    man: Dict[str, Any] = dict(_versions())
    try:
        import jax

        man["backend"] = jax.default_backend()
        man["device_kind"] = jax.devices()[0].device_kind
    except Exception:
        man["backend"] = man["device_kind"] = "unavailable"
    man["layout"] = tuple(int(x) for x in layout)
    man["compute_dtype"] = str(compute_dtype) if compute_dtype is not None else "f32"
    return man


def environment_fingerprint() -> str:
    """Short fingerprint of the version/backend environment — the key
    the persisted donation-probe verdict is stored under."""
    man = _versions()
    try:
        import jax

        man["backend"] = jax.default_backend()
        man["device_kind"] = jax.devices()[0].device_kind
    except Exception:
        pass
    return _sha256_hex(json.dumps(man, sort_keys=True).encode())[:16]


def _classify_compat(want: Dict[str, Any], got: Dict[str, Any]) -> Optional[str]:
    """None when the entry is valid here, else the loud miss reason."""
    if list(want.get("layout", ())) != list(got.get("layout", ())):
        return "layout_changed"
    for field in ("jax", "jaxlib", "libtpu", "backend", "device_kind", "compute_dtype"):
        if want.get(field) != got.get(field):
            return "version_skew"
    return None


# -- donation gate ---------------------------------------------------------

_DONATION_MEMO: Dict[str, bool] = {}


def donation_roundtrip_ok(cache_dir: Optional[str] = None) -> bool:
    """Whether a donated executable survives the serialize/deserialize
    round trip on THIS jax: a tiny ``donate_argnums=(0,)`` function is
    AOT-compiled, round-tripped, and both are run on fresh inputs —
    the outputs must bit-match. The verdict is memoized per process and
    persisted per environment fingerprint under ``cache_dir`` (warm
    restarts read it back: zero probe compiles).

    ``HYDRAGNN_INJECT_DONATION_CHECK_FAIL=1`` forces a failing verdict
    without touching the persisted one — the deterministic driver for
    the evict-and-recompile path (tests/test_warm_exec_cache.py, ci.sh)."""
    if knobs.is_set("HYDRAGNN_INJECT_DONATION_CHECK_FAIL"):
        return False
    fp = environment_fingerprint()
    if fp in _DONATION_MEMO:
        return _DONATION_MEMO[fp]
    verdict_path = (
        os.path.join(cache_dir, "donation_probe.json") if cache_dir else None
    )
    if verdict_path and os.path.exists(verdict_path):
        try:
            with open(verdict_path) as f:
                stored = json.load(f)
            if fp in stored:
                _DONATION_MEMO[fp] = bool(stored[fp])
                return _DONATION_MEMO[fp]
        except (OSError, json.JSONDecodeError, TypeError):
            pass
    ok = _run_donation_probe()
    _DONATION_MEMO[fp] = ok
    if verdict_path:
        try:
            stored = {}
            if os.path.exists(verdict_path):
                with open(verdict_path) as f:
                    stored = json.load(f)
            stored[fp] = ok
            _atomic_write(verdict_path, json.dumps(stored).encode())
        except (OSError, json.JSONDecodeError, TypeError):
            pass
    return ok


def _run_donation_probe() -> bool:
    se = _serialize_mod()
    if se is None:
        return False
    try:
        import jax
        import jax.numpy as jnp
        import numpy as np

        g = jax.jit(
            lambda s, x: (s + x, (s * x).sum()), donate_argnums=(0,)
        )
        a = jnp.arange(16.0, dtype=jnp.float32).reshape(4, 4)
        compiled = compile_for_store(g.lower(a, a))
        payload, in_tree, out_tree = se.serialize(compiled)
        loaded = se.deserialize_and_load(
            payload, in_tree, out_tree,
            execution_devices=_execution_devices(compiled),
        )
        s1, l1 = compiled(jnp.ones((4, 4), jnp.float32), a)
        s2, l2 = loaded(jnp.ones((4, 4), jnp.float32), a)
        return bool(
            np.array_equal(np.asarray(s1), np.asarray(s2))
            and np.array_equal(np.asarray(l1), np.asarray(l2))
        )
    except Exception:
        return False


# -- the cache -------------------------------------------------------------


class ExecCache:
    """One directory of serialized executables + integrity sidecars.

    Constructed with ``cache_dir=None`` the cache is inert (every
    ``load`` returns None silently, ``store`` is a no-op) so call sites
    need no gate of their own. ``flight`` / ``metrics`` are optional
    sinks for the per-event observability (``exec_cache`` flight events;
    ``ServeMetrics.record_exec_cache``)."""

    def __init__(
        self,
        cache_dir: Optional[str],
        *,
        max_bytes: Optional[int] = None,
        flight=None,
        metrics=None,
        consumer: str = "",
    ):
        self.dir = cache_dir or None
        self.flight = flight
        self.metrics = metrics
        self.consumer = consumer
        if max_bytes is None:
            max_bytes = int(knobs.get_float(_ENV_MAX_MB, 512.0) * 1024 * 1024)
        self.max_bytes = max_bytes
        self.stats: Dict[str, Any] = {
            "hits": 0,
            "misses": 0,
            "stores": 0,
            "evictions": 0,
            "miss_reasons": {},
        }
        if self.dir:
            os.makedirs(self.dir, exist_ok=True)

    @classmethod
    def from_env(cls, **kw) -> "ExecCache":
        """The ``HYDRAGNN_EXEC_CACHE`` directory, or an inert cache.
        The env var (not ``HYDRAGNN_INJECT_*``) deliberately SURVIVES
        supervisor restarts — warm resume is its whole point."""
        return cls(knobs.raw(_ENV_DIR) or None, **kw)

    @property
    def enabled(self) -> bool:
        return self.dir is not None

    def _path(self, key: str) -> str:
        return os.path.join(self.dir, f"{key}.bin")

    # -- events ------------------------------------------------------------

    def _emit(self, event: str, key: str, reason: Optional[str] = None, **extra):
        if self.flight is not None:
            self.flight.record(
                "exec_cache",
                event=event,
                key=key[:16],
                consumer=self.consumer,
                **({"reason": reason} if reason else {}),
                **extra,
            )
        if self.metrics is not None and event in ("hit", "miss"):
            self.metrics.record_exec_cache(hit=(event == "hit"), reason=reason)

    def _miss(self, key: str, reason: str, **extra) -> None:
        self.stats["misses"] += 1
        self.stats["miss_reasons"][reason] = (
            self.stats["miss_reasons"].get(reason, 0) + 1
        )
        self._emit("miss", key, reason, **extra)
        return None

    def _evict(self, key: str, reason: str) -> None:
        path = self._path(key)
        for victim in (path, path + ".sha256"):
            try:
                os.remove(victim)
            except OSError:
                pass
        self.stats["evictions"] += 1
        self._emit("evict", key, reason)
        # loud by design: a corrupt or donation-unsafe entry being
        # dropped is an incident the operator should see without
        # opening the flight record
        print(
            f"exec_cache: evicted entry {key[:16]} ({reason})",
            file=sys.stderr,
        )

    # -- load / store ------------------------------------------------------

    def load(
        self,
        key: str,
        compat: Dict[str, Any],
        *,
        donated: bool = False,
        label: Optional[str] = None,
    ) -> Optional[Callable]:
        """The deserialized executable for ``key``, or None with the
        miss reason recorded. ``donated=True`` routes through the
        donation gate (module docstring) — a failing gate EVICTS the
        entry so a later fixed environment re-stores it fresh."""
        if self.dir is None:
            return None
        se = _serialize_mod()
        if se is None:
            return self._miss(key, "unavailable", label=label)
        path = self._path(key)
        if not os.path.exists(path):
            return self._miss(key, "absent", label=label)
        try:
            with open(path, "rb") as f:
                data = f.read()
        except OSError:
            return self._miss(key, "absent", label=label)
        sidecar = path + ".sha256"
        if os.path.exists(sidecar):
            try:
                with open(sidecar) as f:
                    want = f.read().strip()
            except OSError:
                want = ""
            if _sha256_hex(data) != want:
                self._evict(key, "corrupt")
                return self._miss(key, "corrupt", label=label)
        try:
            entry = pickle.loads(data)
            meta = entry["meta"]
            payload = entry["payload"]
            in_tree = entry["in_tree"]
            out_tree = entry["out_tree"]
        except Exception:
            self._evict(key, "corrupt")
            return self._miss(key, "corrupt", label=label)
        mismatch = _classify_compat(compat, meta.get("compat", {}))
        if mismatch is not None:
            # the entry is fine for the environment that wrote it —
            # loud miss, no eviction (LRU reclaims it eventually)
            return self._miss(key, mismatch, label=label)
        if donated and not donation_roundtrip_ok(self.dir):
            self._evict(key, "donation_check_failed")
            return self._miss(key, "donation_check_failed", label=label)
        devices = _devices_by_id(meta.get("device_ids"))
        if devices is None:
            return self._miss(key, "layout_changed", label=label)
        try:
            exe = se.deserialize_and_load(
                payload, in_tree, out_tree, execution_devices=devices
            )
        except Exception:
            self._evict(key, "corrupt")
            return self._miss(key, "corrupt", label=label)
        try:
            now = time.time()
            os.utime(path, (now, now))  # LRU touch
        except OSError:
            pass
        self.stats["hits"] += 1
        self._emit("hit", key, label=label)
        return exe

    def store(
        self,
        key: str,
        compiled,
        compat: Dict[str, Any],
        *,
        label: Optional[str] = None,
    ) -> bool:
        """Serialize ``compiled`` under ``key``. False (with a
        ``store_failed`` flight event) when this executable cannot be
        serialized — the caller keeps its live executable either way."""
        if self.dir is None:
            return False
        se = _serialize_mod()
        if se is None:
            return False
        try:
            payload, in_tree, out_tree = se.serialize(compiled)
            data = pickle.dumps(
                {
                    "meta": {
                        "compat": dict(compat),
                        "label": label,
                        "t": time.time(),
                        "device_ids": [d.id for d in _execution_devices(compiled)],
                    },
                    "payload": payload,
                    "in_tree": in_tree,
                    "out_tree": out_tree,
                }
            )
        except Exception as exc:
            self._emit("store_failed", key, error=str(exc)[-200:])
            return False
        path = self._path(key)
        try:
            _atomic_write(path, data)
            _atomic_write(path + ".sha256", _sha256_hex(data).encode())
        except OSError as exc:
            self._emit("store_failed", key, error=str(exc)[-200:])
            return False
        self.stats["stores"] += 1
        self._emit("store", key, label=label, bytes=len(data))
        self._enforce_lru()
        return True

    def get_or_compile(
        self,
        key: str,
        jitted,
        lower_args: tuple,
        compat: Dict[str, Any],
        *,
        donated: bool = False,
        label: Optional[str] = None,
    ) -> Tuple[Callable, bool, float]:
        """(executable, was_hit, build_seconds). A miss AOT-compiles
        ``jitted`` against ``lower_args`` and stores the result."""
        t0 = time.perf_counter()
        exe = self.load(key, compat, donated=donated, label=label)
        if exe is not None:
            return exe, True, time.perf_counter() - t0
        compiled = compile_for_store(jitted.lower(*lower_args))
        if not donated or donation_roundtrip_ok(self.dir):
            self.store(key, compiled, compat, label=label)
        return compiled, False, time.perf_counter() - t0

    # -- LRU ---------------------------------------------------------------

    def _enforce_lru(self) -> None:
        if self.dir is None or self.max_bytes <= 0:
            return
        entries = []
        total = 0
        try:
            names = os.listdir(self.dir)
        except OSError:
            return
        for name in names:
            if not name.endswith(".bin"):
                continue
            p = os.path.join(self.dir, name)
            try:
                st = os.stat(p)
            except OSError:
                continue
            size = st.st_size
            try:
                size += os.stat(p + ".sha256").st_size
            except OSError:
                pass
            entries.append((st.st_mtime, size, name[: -len(".bin")]))
            total += size
        entries.sort()  # oldest mtime first
        while total > self.max_bytes and len(entries) > 1:
            mtime, size, key = entries.pop(0)
            self._evict(key, "lru")
            total -= size

    def manifest(self) -> Dict[str, Any]:
        """The flight-manifest block: where the cache lives and what it
        did this process."""
        return {
            "enabled": self.enabled,
            "dir": self.dir,
            "serialize_available": _serialize_mod() is not None,
            **{k: v for k, v in self.stats.items()},
        }
