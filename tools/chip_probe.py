"""Probes of what the attached chip and the installed JAX do, for the
facts the program's bring-up rules rest on. One JSON line per probe;
the exit code is 0 when every probe could be made, whatever it found.

    python tools/chip_probe.py [held_chip] [reserialize] [exec_cache]

- ``held_chip``: this process brings the backend up (and so holds the
  chip), then starts children that want it: plain ``jax.devices()``, the
  same under ``JAX_PLATFORMS=cpu``, ``utils/platform.check_backend``,
  and ``pilot.tune``'s child. Each child's exit code, seconds and last
  stderr lines are printed: does a second process hang, fall to the CPU,
  or fail, and how fast.
- ``reserialize``: an executable that JAX read back from its own
  persistent compilation cache is serialized again
  (``jax.experimental.serialize_executable``), loaded and called. On the
  CPU backend that fails at the first call; ``utils/exec_cache.py:
  compile_for_store`` keeps JAX's cache out of the way where it does.
- ``exec_cache``: a single-device executable compiled for the LAST
  device of the host is serialized and loaded with that device: where
  does raw JAX say it landed, and does its first call work? Then the
  same through ``utils/exec_cache.ExecCache``. On a four-chip TPU host
  both fail at the first call (PR 21): the load goes to the default
  device. Ask again after a JAX or libtpu upgrade.

Run it where the answer matters: ``chiprun -- python tools/chip_probe.py``.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def emit(probe: str, **kw) -> None:
    print(json.dumps({"probe": probe, **kw}, default=str), flush=True)


def _child(what: str, code_or_argv, **env) -> None:
    argv = (
        [sys.executable, "-c", code_or_argv]
        if isinstance(code_or_argv, str)
        else [sys.executable, *code_or_argv]
    )
    t0 = time.perf_counter()
    try:
        proc = subprocess.run(
            argv, cwd=REPO, env={**os.environ, "PYTHONPATH": REPO, **env},
            capture_output=True, text=True, timeout=180,
        )
        rc, out, err = proc.returncode, proc.stdout, proc.stderr
    except subprocess.TimeoutExpired as exc:
        rc, out, err = "timeout (hung)", str(exc.stdout or ""), str(exc.stderr or "")
    emit(
        "held_chip", child=what, rc=rc, seconds=round(time.perf_counter() - t0, 1),
        stdout=out[-200:], stderr_tail=[l for l in err.splitlines() if l.strip()][-3:],
    )


def probe_held_chip() -> None:
    import jax

    emit("held_chip", parent=str(jax.devices()), jax_platforms=os.environ.get("JAX_PLATFORMS"))
    devices = "import jax; print(jax.devices())"
    _child("jax.devices() while the parent holds the chip", devices)
    _child("the same under JAX_PLATFORMS=cpu", devices, JAX_PLATFORMS="cpu")
    _child(
        "utils/platform.check_backend",
        "from hydragnn_tpu.utils.platform import check_backend; print(check_backend())",
    )
    _child(
        "pilot.tune's child",
        ["-m", "hydragnn_tpu.pilot.tune", "--log-dir", "x", "--serving-run", "r", "--candidate", "c"],
    )


def probe_reserialize() -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import serialize_executable as se

    from hydragnn_tpu.utils.platform import place_compile_cache

    cache_dir = place_compile_cache()
    # small programs are cached too, so the probe needs no long compile
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    hits = []
    jax.monitoring.register_event_listener(
        lambda event, **_: hits.append(event) if event == "/jax/compilation_cache/cache_hits" else None
    )
    # a program no earlier run compiled: the constant is this run's time
    salt = float(int(time.time()) % 100000)
    x = jnp.arange(1024.0).reshape(32, 32)

    def compile_once():
        fn = jax.jit(lambda a: jnp.tanh(a @ a.T) * 0.5 + salt)
        before = len(hits)
        return fn.lower(x).compile(), len(hits) > before

    fresh, fresh_hit = compile_once()
    want = np.asarray(fresh(x))
    jax.clear_caches()
    cached, cached_hit = compile_once()
    out = {
        "platform": jax.devices()[0].platform, "compile_cache_dir": cache_dir,
        "first_compile_from_jax_cache": fresh_hit, "second_compile_from_jax_cache": cached_hit,
    }
    for name, exe in (("fresh", fresh), ("from_jax_cache", cached)):
        try:
            payload, in_tree, out_tree = se.serialize(exe)
            # graftlint: disable=HG003 -- the probe asks what raw JAX does, below ExecCache's gates
            loaded = se.deserialize_and_load(
                payload, in_tree, out_tree, execution_devices=jax.devices()[:1]
            )
            got = np.asarray(loaded(x))
            out[f"{name}_roundtrip"] = "ok" if np.array_equal(got, want) else "wrong values"
        except Exception as exc:
            out[f"{name}_roundtrip"] = f"{type(exc).__name__}: {str(exc)[:300]}"
    emit("reserialize", **out)


def probe_exec_cache() -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import serialize_executable as se

    from hydragnn_tpu.utils.exec_cache import ExecCache, compat_manifest

    dev = jax.devices()[-1]
    x = jax.device_put(jnp.arange(4096.0).reshape(64, 64), dev)
    fn = jax.jit(lambda a: jnp.tanh(a @ a.T) + 1.0)
    # what raw JAX does: where does the executable land when it is
    # loaded with the device it was compiled for?
    payload, in_tree, out_tree = se.serialize(fn.lower(x).compile())
    # graftlint: disable=HG003 -- the probe asks what raw JAX does, below ExecCache's gates
    raw = se.deserialize_and_load(payload, in_tree, out_tree, execution_devices=[dev])
    raw_ids = [d.id for d in raw.runtime_executable().local_devices()]
    try:
        raw(x)
        raw_call = "ok"
    except Exception as exc:
        raw_call = f"{type(exc).__name__}: {str(exc)[:300]}"
    # what the executable cache makes of it
    with tempfile.TemporaryDirectory() as tmp:
        cache = ExecCache(tmp)
        compat = compat_manifest()
        fresh, hit0, _ = cache.get_or_compile("k", fn, (x,), compat)
        again, hit1, _ = cache.get_or_compile("k", fn, (x,), compat)
        try:
            got = again(x)
            result = {
                "equal": bool(np.array_equal(np.asarray(got), np.asarray(fresh(x)))),
                "output_device_ids": sorted(d.id for d in got.sharding.device_set),
            }
        except Exception as exc:
            result = {"error": f"{type(exc).__name__}: {str(exc)[:300]}"}
        reasons = dict(cache.stats["miss_reasons"])
    emit(
        "exec_cache", devices=len(jax.devices()), compiled_for_device_id=dev.id,
        raw_load_landed_on=raw_ids, raw_load_first_call=raw_call,
        first_was_hit=hit0, second_was_hit=hit1, miss_reasons=reasons, **result,
    )


PROBES = {
    "held_chip": probe_held_chip,
    "reserialize": probe_reserialize,
    "exec_cache": probe_exec_cache,
}


def main(argv) -> int:
    for name in argv or list(PROBES):
        PROBES[name]()
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
